"""``benchmarks/scope_times.py`` and the readers on top of it, on a small
trace of the recorded form (``data/trace_small.json``'s): two programs
that share instruction names, a loop that spans its body's operations,
a backward and a rematerialised path, the 99% guard, the newest map
before the window, a stale map."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import harness, scope_times  # noqa: E402
from msrflute_tpu.telemetry import compiles  # noqa: E402

NEW = ("client_steps_ms", "aggregate_ms", "quant_select_ms", "moe_layer_ms",
       "attn_core_ms", "attn_proj_ms", "head_loss_ms", "scope_unattributed")


def _chain(path):
    return "/".join(compiles.scopes_in(path))


#: the round program's map, as the program would write it: chains from
#: the paths the compiled text gives the instructions
ROUND_MAP = {
    "while.4": _chain("jit(staged)/round_aggregate/client_steps/while"),
    "fusion.3": _chain("jit(staged)/round_aggregate/client_steps/while/body/"
                       "transpose(jvp(mla_attn_core))/pallas_call"),
    "fusion.5": _chain("jit(staged)/round_aggregate/client_steps/while/body/"
                       "checkpoint/rematted_computation/mla_proj/dot_general"),
    "fusion.7": _chain("jit(staged)/round_aggregate/client_steps/while/body/"
                       "jvp(routed_experts)/moe/expert_gmm_fwd"),
    "fusion.8": _chain("jit(staged)/round_aggregate/client_steps/while/body/"
                       "lm_head_loss/log_softmax"),
    "fusion.9": _chain("jit(staged)/round_aggregate/vmap(quant_select)/"
                       "while/body/reduce_sum"),
    "add.6": _chain("jit(staged)/round_aggregate/add"),
    "copy.2": "",
}


def _trace():
    """One chip; the evaluation program, then two runs of the round
    program.  ``fusion.3`` is an operation of both programs."""
    round_ops = lambda t0: [  # noqa: E731 - 1,000 ns a run
        ["copy.2", t0 + 0.0, 20.0],
        ["while.4", t0 + 20.0, 700.0],      # spans the four below
        ["fusion.3", t0 + 30.0, 300.0],
        ["fusion.5", t0 + 330.0, 150.0],
        ["fusion.7", t0 + 480.0, 200.0],
        ["fusion.8", t0 + 680.0, 30.0],
        ["fusion.9", t0 + 720.0, 180.0],
        ["add.6", t0 + 900.0, 90.0],
    ]
    return {"planes": [
        {"name": "/host:CPU", "lines": []},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_shard_body(11)", 0.0, 500.0],
                ["jit_staged(22)", 1000.0, 1000.0],
                ["jit_staged(22)", 2000.0, 1000.0]]},
            {"name": "XLA Ops", "events": (
                [["fusion.3", 10.0, 400.0]] + round_ops(1000.0) +
                round_ops(2000.0))}]}]}


def _map(scopes, ts, module="jit_staged", stale=False):
    return {"module": module, "scopes": scopes, "written_ts": ts,
            "stale": stale, "file": f"{module}-{ts}.json"}


def test_the_table_of_the_small_trace():
    table = scope_times.scope_table(_trace(), [_map(ROUND_MAP, 5.0)])
    assert table["modules"] == ["jit_staged"] and table["runs"] == 2
    assert table["module_s"] == pytest.approx(2000e-9)
    # the evaluation program's fusion.3 (400 ns) is not the round's
    got = {scope: row["s"] * 1e9 for scope, row in table["scopes"].items()}
    assert got == pytest.approx({
        "": 40.0,                       # copy.2, no path
        "client_steps": 2 * (700 - 680.0),   # the loop's own time
        "mla_attn_core": 600.0,         # a backward path
        "mla_proj": 300.0,              # a rematerialised one
        "routed_experts": 400.0, "lm_head_loss": 60.0,
        "quant_select": 360.0, "round_aggregate": 180.0})
    assert table["scopes"]["client_steps"]["inside_s"] * 1e9 == \
        pytest.approx(2 * 700.0)
    assert table["scopes"]["round_aggregate"]["inside_s"] * 1e9 == \
        pytest.approx(2 * 970.0)
    assert table["scopes"]["mla_attn_core"]["calls"] == 2
    assert table["scopes"]["mla_attn_core"]["top"] == [
        ["fusion.3", pytest.approx(600e-9), 2]]
    assert [row[0] for row in table["scopes"][""]["top"]] == ["copy.2"]
    # no scope: the operation without a path and the 10 ns a run that
    # are no operation's at all
    assert table["unattributed_s"] * 1e9 == pytest.approx(2 * 30.0)
    assert table["unknown_s"] == 0.0
    assert sum(row["share"] for row in table["scopes"].values()) == \
        pytest.approx(1980 / 2000)


def test_the_guard_refuses_a_map_of_another_compile():
    trace = _trace()
    total = 2 * 980.0   # own time of the operations, both runs

    def reads(*unknown):
        known = {k: v for k, v in ROUND_MAP.items() if k not in unknown}
        return scope_times.scope_table(trace, [_map(known, 5.0)]) is not None

    assert reads()
    # two unknown operations of 4 ns among 1,968 ns: under 1%, read
    slow = _trace()
    slow["planes"][1]["lines"][1]["events"] += [["extra.1", 1995.0, 4.0],
                                                ["extra.1", 2995.0, 4.0]]
    known = [_map(ROUND_MAP, 5.0)]
    assert 8.0 / (total + 8.0) < 0.01      # 0.4% unknown: read
    assert scope_times.scope_table(slow, known) is not None
    assert 40.0 / total > 0.01             # copy.2, 2.0% unknown: refused
    assert not reads("copy.2")
    assert not reads("add.6")
    # another compile's map: the same names but for one, renumbered
    other = {("fusion.30" if k == "fusion.3" else k): v
             for k, v in ROUND_MAP.items()}
    assert scope_times.scope_table(trace, [_map(other, 5.0)]) is None


def test_the_newest_sound_map_before_the_window_is_the_one():
    check = {k: "" for k in ROUND_MAP}   # the check program's, older
    maps = [_map(check, 1.0), _map(ROUND_MAP, 2.0),
            _map(check, 9.0),             # written after the window opened
            _map(check, 3.0, module="jit_shard_body")]
    table = scope_times.scope_table(_trace(), maps, before_ts=5.0)
    assert table["maps"] == ["jit_staged-2.0.json"]
    assert "mla_proj" in table["scopes"]
    # without a window the newest of all
    assert scope_times.scope_table(_trace(), maps)["maps"] == \
        ["jit_staged-9.0.json"]
    # a stale map is never read, and hides no older one
    maps = [_map(ROUND_MAP, 2.0), _map(check, 3.0, stale=True)]
    assert scope_times.scope_table(_trace(), maps, before_ts=5.0)["maps"] \
        == ["jit_staged-2.0.json"]
    assert scope_times.scope_table(
        _trace(), [_map(ROUND_MAP, 2.0, stale=True)]) is None
    # no program of that name in the trace, no map of it: nothing
    assert scope_times.scope_table(
        _trace(), [_map(ROUND_MAP, 2.0, module="jit_other")]) is None


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
@pytest.fixture
def run_dirs(tmp_path, monkeypatch):
    """A harness run's layout: ``<work>/out/models/telemetry/programs``
    beside ``<work>/profile``; the profile is the small trace."""
    programs = tmp_path / "out" / "models" / "telemetry" / "programs"
    programs.mkdir(parents=True)
    (tmp_path / "profile").mkdir()
    monkeypatch.setattr(scope_times.trace_reduce, "load_profile",
                        lambda folder: _trace())
    path = programs / "jit_staged-0.json"

    def write(scopes=ROUND_MAP, stale=False):
        path.write_text(json.dumps({
            "module": "jit_staged", "fun_name": "staged", "scopes": scopes,
            "ops": len(scopes), "scoped": 7, "stale": stale,
            "missing": [], "written_ts": 50.0}))
        return {"kind": "span", "name": "program_scopes", "ts": 49.0,
                "dur_s": 0.1, "file": str(path), "module": "jit_staged",
                "stale": stale}
    return write


def _ctx(spans):
    return {"spans": spans, "window": {"t_open": 100.0, "t_close": 130.0},
            "config": {"server_config": {"rounds_per_step": 2}}}


def test_every_new_reader_reads_a_map_and_nothing_without_one(run_dirs,
                                                              capfd):
    readers = harness.load_layer_metrics(harness.BENCH_DIR)
    assert set(NEW) <= set(readers)
    # no map (every tree before PR 39): nothing, and no error
    empty = _ctx([{"kind": "span", "name": "launch", "ts": 1.0,
                   "dur_s": 0.1}])
    assert [readers[name].read(empty) for name in NEW] == [None] * len(NEW)
    # a stale map: nothing
    stale = _ctx([run_dirs(stale=True)])
    assert [readers[name].read(stale) for name in NEW] == [None] * len(NEW)
    capfd.readouterr()
    # a sound one: two runs of two rounds, 1,000 ns a run
    ctx = _ctx([run_dirs()])
    got = {name: readers[name].read(ctx) for name in NEW}
    per_round = 1e3 * 1e-9 / 2   # ns a run -> ms a round
    assert got == pytest.approx({
        "client_steps_ms": 700 * per_round,
        "aggregate_ms": 90 * per_round,
        "quant_select_ms": 180 * per_round,
        "moe_layer_ms": 200 * per_round,
        "attn_core_ms": 300 * per_round,
        "attn_proj_ms": 150 * per_round,
        "head_loss_ms": 30 * per_round,
        "scope_unattributed": 3.0})
    # the parts are the whole: what round_program_ms would read
    assert got["client_steps_ms"] + got["aggregate_ms"] + \
        got["quant_select_ms"] + got["scope_unattributed"] / 100 * \
        1000 * per_round == pytest.approx(1000 * per_round)
    # parsed once, said once, whatever the number of readers
    err = capfd.readouterr().err.splitlines()
    said = [json.loads(line) for line in err if "scope_times" in line]
    assert len(said) == 1
    assert set(said[0]["scope_times"]["scopes"]) == {
        "", "client_steps", "mla_attn_core", "mla_proj", "routed_experts",
        "lm_head_loss", "quant_select", "round_aggregate"}
    # a scope the cell's program does not have: left out, not zero
    ctx = _ctx([run_dirs({k: v.replace("quant_select", "round_aggregate")
                          for k, v in ROUND_MAP.items()})])
    assert readers["quant_select_ms"].read(ctx) is None
    assert readers["aggregate_ms"].read(ctx) == \
        pytest.approx(270 * per_round)


def test_each_new_entry_is_appended_and_keeps_to_its_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        doc = json.load(fh)
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index(NEW[0])
    assert tuple(names[first:first + len(NEW)]) == NEW
    cells = [w["name"] for w in doc["workloads"]]
    for entry in doc["per_layer"][first:first + len(NEW)]:
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "clients_per_s"
        assert entry["better"] == "lower"
        assert set(entry["workloads"]) <= set(cells)
    by_name = {m["name"]: m for m in doc["per_layer"]}
    assert by_name["quant_select_ms"]["workloads"] == ["cnn_dga_dp_q8_k170"]
    assert by_name["client_steps_ms"]["workloads"] == cells
    assert by_name["moe_layer_ms"]["workloads"] == cells[2:]


def test_the_command_line_reads_an_operators_capture(run_dirs, tmp_path,
                                                     capsys):
    run_dirs()
    telemetry = str(tmp_path / "out" / "models" / "telemetry")
    assert scope_times.main([str(tmp_path / "profile"), telemetry]) == 0
    table = json.loads(capsys.readouterr().out)["scope_times"]
    assert table["runs"] == 2 and "mla_attn_core" in table["scopes"]
    assert scope_times.main([str(tmp_path / "profile"), telemetry,
                             "^jit_nothing"]) == 1
    assert scope_times.main([]) == 2
