"""``stage_share``: percent of the device's busy time in the traced
window spent in the trainer's staging program (``jit_stage_morsel``:
slice, cast and stack the morsel's columns; ``bench/device_ops.py``)."""
from bench import device_ops


def read(run):
    raw = device_ops.for_run(run)
    if raw is None:
        return None
    return device_ops.stage_share(raw, run.trace["busy_s"])
