"""BENCHMARK.json and the files it names: the contract's shape, checked
without a chip."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["workloads"]) <= 24
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    for word in bench["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_names_and_units_hold_only_allowed_characters(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [entry["name"] for entry in bench[group]]
    for cell in bench["workloads"]:
        names += [cell["config"], cell["traffic"]]
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    for config in bench["configs"]:
        names += list(config["reduced"])
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(config["source"]) <= 200
    for name in names:
        assert NAME.match(name), name
    for group in ("configs", "workloads"):
        got = [entry["name"] for entry in bench[group]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for metric in bench["end_to_end"]:
        assert 0 < metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")


def test_every_entry_has_its_file(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for config in bench["configs"]:
        assert config["file"] == f"benchmarks/configs/{config['name']}.json"
        with open(os.path.join(REPO, config["file"])) as fh:
            doc = json.load(fh)
        assert doc["source"] == config["source"]
        assert doc["reduced"] == config["reduced"]
        assert os.path.exists(os.path.join(
            BENCH, "reference", f"{doc['reference']['model']}.py"))
    for cell in bench["workloads"]:
        with open(os.path.join(BENCH, "workloads",
                               f"{cell['name']}.json")) as fh:
            doc = json.load(fh)
        assert {k: doc[k] for k in ("config", "traffic", "chips")} == \
            {k: cell[k] for k in ("config", "traffic", "chips")}
        assert os.path.exists(os.path.join(
            BENCH, "traffic", f"{cell['traffic']}.json"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for metric in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", f"{metric['name']}.py")), metric["name"]
        assert metric["moves"] in e2e
        assert set(metric.get("workloads", cells)) <= cells
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def _cells_of(metric, cells):
    return set(metric.get("workloads", cells))


def test_every_cell_reports_what_the_contract_asks(bench):
    """``setup_s``, one more end-to-end metric and a per-layer metric in
    every cell; a per-layer metric only in cells that report the
    end-to-end metric it moves."""
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: _cells_of(m, cells) for m in bench["end_to_end"]}
    assert e2e["setup_s"] == cells
    for cell in cells:
        assert [n for n, where in e2e.items()
                if cell in where and n != "setup_s"], cell
        assert [m for m in bench["per_layer"]
                if cell in _cells_of(m, cells)], cell
    for metric in bench["per_layer"]:
        assert _cells_of(metric, cells) <= e2e[metric["moves"]], metric


def test_a_metric_kept_to_some_cells_is_reported_there_only(bench):
    sys.path.insert(0, REPO)
    from benchmarks import harness
    cells = {w["name"] for w in bench["workloads"]}
    for group in ("end_to_end", "per_layer"):
        for cell in cells:
            assert harness.kept_to_other_cells(group, cell) == {
                m["name"] for m in bench[group]
                if cell not in _cells_of(m, cells)}
    win = {"clients": 100, "window_s": 4.0, "setup_s": 1.0,
           "per_round_s": [0.8, 1.0, 0.8, 1.0, 2.0]}
    for cell in cells:
        got = set(harness.end_to_end(win, cell))
        assert got == {n for n in ("clients_per_s", "round_s_p50",
                                   "round_s_p90", "setup_s")
                       if n not in harness.kept_to_other_cells(
                           "end_to_end", cell)}
        assert {"clients_per_s", "setup_s"} <= got


def test_the_fence_median_reader_reads_the_window(bench):
    sys.path.insert(0, REPO)
    from benchmarks import harness
    reader = harness.load_layer_metrics(BENCH)["round_fence_p50_ms"]
    entry = [m for m in bench["per_layer"]
             if m["name"] == "round_fence_p50_ms"]
    assert len(entry) == 1 and entry[0]["unit"] == reader.UNIT
    assert entry[0]["source"] == "host_clock"
    # two groups of dispatches and one stalled fence: the middle one
    ctx = {"window": {"per_round_s": [0.16, 0.21, 0.16, 0.21, 0.2, 4.0]}}
    assert reader.read(ctx) == pytest.approx(205.0)
    assert reader.read({"window": {"per_round_s": []}}) is None
    assert reader.read({}) is None


def test_run_py_names_no_cell_configuration_or_metric(bench):
    with open(os.path.join(BENCH, "run.py")) as fh:
        source = fh.read()
    with open(os.path.join(BENCH, "harness.py")) as fh:
        source += fh.read()
    for group in ("configs", "workloads", "per_layer"):
        for entry in bench[group]:
            assert entry["name"] not in source, entry["name"]
    for cell in bench["workloads"]:
        assert cell["traffic"] not in source


def test_peaks_table_knows_the_chip_and_refuses_an_unknown_kind():
    sys.path.insert(0, REPO)
    from benchmarks import readers
    with open(os.path.join(BENCH, "peaks.json")) as fh:
        peaks = json.load(fh)
    assert peaks["TPU v5 lite"]["flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    ctx = {"peaks": peaks, "device": {"kind": "TPU v5 lite"}}
    assert readers.peak(ctx)["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        readers.peak({"peaks": peaks, "device": {"kind": "cpu"}})


def test_run_py_gives_no_result_without_a_tpu(bench):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = bench["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode not in (0, None)
    assert '"correct"' not in proc.stdout
    assert "TPU" in proc.stderr
