"""``sgd_kernel_roofline``: percent of the HBM roofline reached by the SGD
kernel alone (``sgd_block`` or ``sgd_block_wide``): the least bytes of the
``train_glm`` calls in the traced window (features and label once per
epoch; ``bench/device_ops.kernel_least_bytes``) at 819 GB/s, over the
summed device time of the kernel's operations there
(``bench/device_ops.py``)."""
from bench import device_ops


def read(run):
    raw = device_ops.for_run(run)
    if raw is None or run.peaks is None:
        return None
    return device_ops.kernel_roofline(raw, device_ops.train_calls_bytes(run),
                                      run.peaks["hbm_bytes_per_s"])
