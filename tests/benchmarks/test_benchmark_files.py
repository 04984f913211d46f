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
