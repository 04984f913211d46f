"""The harness end to end on the CPU at a tiny size, without its look for
a chip: cells, configurations and layer metrics are found as files; the
reference round agrees with the engine.  The lower-precision control and
the broken timed paths, which come out as not correct, are in files of
their own (``test_benchmark_harness_control.py``, ``..._fault_*.py``:
one run a file; ``harness_tiny_cell.py`` holds what they share)."""

import glob
import os

from harness_tiny_cell import (BENCH, SEED, harness,  # noqa: F401
                               tiny_root, verdicts)


def test_new_files_are_found_without_an_edit(tiny_root):
    cell = harness.load_cell(tiny_root, "tiny_cell")
    assert cell["config_doc"]["data"]["train_users"] == 6
    readers = harness.load_layer_metrics(tiny_root)
    assert list(readers) == ["toy_ms"]
    assert readers["toy_ms"].read({"toy": 3.0}) == 3.0
    assert readers["toy_ms"].read({}) is None
    shipped = harness.load_layer_metrics(BENCH)
    assert {os.path.splitext(os.path.basename(p))[0] for p in glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.py"))} == set(shipped)
    for module in shipped.values():
        assert isinstance(module.UNIT, str) and callable(module.read)


def test_sound_run_agrees_with_the_reference(tiny_root):
    result = harness.run_cell("tiny_cell", SEED, 0.2, False, root=tiny_root)
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 6
    # what every cell reports; the rest BENCHMARK.json keeps to cells
    assert set(result["metrics"]) >= {"clients_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    got = verdicts(result)
    # float32 against float32: the engine's round and the plain round
    # differ by rounding only
    assert got["loss_gap"]["value"] < 1e-5
    assert got["pseudo_norm_gap"]["value"] < 1e-5
    assert got["window_compiles"]["value"] == 0
