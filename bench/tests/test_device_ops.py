"""Check of ``sgd_kernel_roofline`` and ``stage_share``
(``bench/device_ops.py``) on hand-made operation intervals.  Nothing here
is a device reading.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_device_ops.py
"""
import types

import pytest

from bench import device_ops as dops

# the window 100..200 ns; kernel operations of both forms, staging spread
# over several operations, one of each straddling or outside the window
SPANS = [("bench.window", 100, 200), ("bench.drain", 100, 190)]
OPS = [("jit_stage_morsel:%concatenate.3", 100, 110),
       ("jit_stage_morsel:%concatenate.4", 110, 118),
       ("jit_epoch_step:%sgd_block_wide.1", 120, 150),
       ("jit_epoch_step:%sgd_block.1", 150, 160),
       ("jit_epoch_step:%broadcast_in_dim", 160, 162),
       ("jit_loss_step:%fusion.7", 165, 175),
       ("jit_stage_morsel:%concatenate.3", 95, 105),     # half inside
       ("jit_epoch_step:%sgd_block_wide.1", 205, 260)]   # after the window
RAW = {"spans": SPANS, "ops": {"/device:TPU:0": OPS}}


def test_op_seconds_sum_every_matching_op_inside_the_window():
    assert dops.op_seconds(RAW, dops.is_kernel) * 1e9 == pytest.approx(40)
    assert dops.op_seconds(RAW, dops.is_stage) * 1e9 == pytest.approx(
        10 + 8 + 5)


def test_shares_are_exact():
    # 4.0e-8 s of kernel at a peak of 1e9 B/s reads 40 B in full
    assert dops.kernel_roofline(RAW, 20, 1e9) == pytest.approx(50.0)
    busy_s = 65e-9
    assert dops.stage_share(RAW, busy_s) == pytest.approx(100 * 23 / 65)


def test_no_such_op_reads_none():
    none = {"spans": SPANS, "ops": {"/device:TPU:0": [
        ("jit_epoch_step:%while", 120, 150)]}}
    assert dops.op_seconds(none, dops.is_kernel) is None
    assert dops.kernel_roofline(none, 20, 1e9) is None
    assert dops.stage_share(none, 30e-9) is None
    assert dops.stage_share({"spans": SPANS, "ops": {}}, 30e-9) is None
    assert dops.for_run(types.SimpleNamespace(trace=None)) is None


def test_a_module_named_for_the_kernel_is_not_the_kernel():
    """Only the instruction's name counts: the copies of a program that
    wraps the kernel are not kernel time."""
    assert dops.is_kernel("jit_sgd_block:%sgd_block.1")
    assert not dops.is_kernel("jit_sgd_block:%copy.2")
    assert not dops.is_stage("jit_epoch_step:%concatenate.1")


def test_least_bytes_count_features_label_rows_and_epochs():
    config = {"tables": {"eps": {"columns": {
        "f": {"dist": "normal", "count": 2000},
        "y": {"dist": "planted_linear", "of": "f"}}}}}
    spec = {"kind": "train_glm", "table": "eps", "features": "f",
            "epochs": 10}
    got = dops.kernel_least_bytes(spec, config, {"eps": 400_000})
    assert got == 4 * 2001 * 400_000 * 10
    run = types.SimpleNamespace(
        counters={"program_calls": {"a": 3, "b": 2}}, config=config,
        sizes={"eps": 400_000},
        templates=[types.SimpleNamespace(name="a", spec=spec),
                   types.SimpleNamespace(name="b", spec=dict(
                       spec, kind="range_sum"))])
    assert dops.train_calls_bytes(run) == 3 * got
