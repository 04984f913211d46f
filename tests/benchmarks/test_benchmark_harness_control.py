"""The harness end to end on the CPU at a tiny size (see
``test_benchmark_harness.py``): the lower-precision control comes out as
not correct."""

from harness_tiny_cell import (SEED, harness, tiny_root,  # noqa: F401
                               verdicts)


def test_lower_precision_control_is_not_correct(tiny_root):
    result = harness.run_cell("tiny_cell", SEED + 1, 0.2, False,
                              root=tiny_root, control="bf16")
    assert not result["correct"]
    got = verdicts(result)
    assert not got["loss_gap"]["ok"] or not got["pseudo_norm_gap"]["ok"]
