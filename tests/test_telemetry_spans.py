"""What a span record carries and what a dispatched chunk leaves behind
(ISSUE 24): ``sid`` / ``parent`` / ``thread`` / ``chunk``, the children
of ``dispatch`` and ``stats_fetch``, the readiness probes, the set-up
spans of the CLI, the compile spans, and the one profiler window with
its clock mark.  Counts and structure only: nothing here is a time.
"""

import glob
import json
import os
import re
import sys
import threading

import numpy as np
import pytest
import yaml

from msrflute_tpu.telemetry.spans import Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_cli import _write_blob  # noqa: E402
from test_telemetry_contract import _cfg, _run  # noqa: E402

CHILDREN = {"dispatch": ("stage_host", "h2d", "launch"),
            "stats_fetch": ("fence_wait", "stats_d2h")}
SETUP = ("cli_config", "data_load", "server_build")
COMPILES = ("jit_trace", "jit_lower", "compile")


def _records(model_dir):
    path = os.path.join(str(model_dir), "telemetry", "events.jsonl")
    with open(path) as fh:
        return [json.loads(line) for line in fh]


def _spans(records, name=None):
    return [r for r in records if r["kind"] == "span" and
            (name is None or r["name"] == name)]


@pytest.fixture(scope="module")
def cli_run(tmp_path_factory):
    """The real CLI in-process, pipelined (depth 1), telemetry on, with a
    profiler window over rounds 3-4: what an operator's run leaves."""
    import e2e_trainer
    tmp = tmp_path_factory.mktemp("cli_spans")
    data_dir, out_dir = tmp / "data", tmp / "out"
    data_dir.mkdir()
    _write_blob(data_dir / "train.json", 12)
    _write_blob(data_dir / "val.json", 4, seed=1)
    _write_blob(data_dir / "test.json", 4, seed=2)
    cfg = {
        "model_config": {"model_type": "LR", "num_classes": 3,
                         "input_dim": 6},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": 6, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.3, "pipeline_depth": 1,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 3, "rec_freq": 3, "initial_val": True,
            "telemetry": {"enable": True, "profile_rounds": "3:5"},
            "data_config": {
                "val": {"batch_size": 8, "val_data": "val.json"},
                "test": {"batch_size": 8, "test_data": "test.json"}}},
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.3},
            "data_config": {"train": {"batch_size": 4,
                                      "list_of_train_data": "train.json"}}},
    }
    cfg_path = tmp / "cfg.yaml"
    cfg_path.write_text(yaml.safe_dump(cfg))
    argv, sys.argv = sys.argv, [
        "e2e_trainer.py", "-config", str(cfg_path), "-dataPath",
        str(data_dir), "-outputPath", str(out_dir), "-task", "cv_lr_mnist"]
    try:
        server = e2e_trainer.main()
    finally:
        sys.argv = argv
    server.scope.close()
    return {"records": _records(out_dir / "models"),
            "model_dir": out_dir / "models", "server": server}


@pytest.fixture(scope="module")
def serial_run(tmp_path_factory):
    """The server alone, ``pipeline_depth: 0``."""
    tmp = tmp_path_factory.mktemp("serial_spans")
    server, state = _run(_cfg(0, telemetry={"enable": True}), tmp)
    assert state.round == 6
    server.scope.close()
    return {"records": _records(tmp), "model_dir": tmp, "server": server}


@pytest.fixture
def run(request, cli_run, serial_run):
    return {"cli": cli_run, "serial": serial_run}[request.param]


# ======================================================================
# the record
# ======================================================================
@pytest.mark.parametrize("run", ["cli", "serial"], indirect=True)
def test_every_span_has_an_id_a_parent_and_a_thread(run):
    spans = _spans(run["records"])
    sids = [s["sid"] for s in spans]
    assert len(sids) == len(set(sids)) and all(
        isinstance(sid, int) for sid in sids)
    for span in spans:
        assert span["parent"] is None or span["parent"] in set(sids)
        assert isinstance(span["thread"], str) and span["thread"]
    # begin/end spans live on their virtual track, not on a thread
    assert {s["thread"].split(" (")[0] for s in
            _spans(run["records"], "round_device")} == {"rounds in flight"}


@pytest.mark.parametrize("parent", sorted(CHILDREN))
@pytest.mark.parametrize("run", ["cli", "serial"], indirect=True)
def test_children_lie_inside_their_parent(run, parent):
    spans = _spans(run["records"])
    parents = _spans(run["records"], parent)
    assert len(parents) == 6
    for p in parents:
        kids = [s for s in spans if s["parent"] == p["sid"]
                and s["name"] not in COMPILES]
        assert tuple(k["name"] for k in sorted(
            kids, key=lambda s: s["ts"])) == CHILDREN[parent]
        for kid in kids:
            assert kid["chunk"] == p["chunk"] == p["round0"]
            assert kid["thread"] == p["thread"] == "MainThread"
            assert kid["rounds"] == p["rounds"]
            assert kid["ts"] >= p["ts"] - 1e-6
        assert sum(k["dur_s"] for k in kids) <= p["dur_s"] + 1e-5
    if parent == "dispatch":
        for kid in _spans(run["records"], "h2d"):
            assert kid["bytes"] > 0 and kid["puts"] >= 2
        # every staging says whether its buffers were kept ones, and the
        # first of a run can only have allocated
        staged = _spans(run["records"], "stage_host")
        assert all(isinstance(kid["reused"], bool) and kid["bytes"] > 0
                   for kid in staged)
        assert staged[0]["reused"] is False
        # `compiled` is the engine's compile log speaking; jax's own
        # report (a backend compile under that launch) agrees
        launches = _spans(run["records"], "launch")
        assert launches[0]["compiled"] and not launches[-1]["compiled"]
        for launch in launches:
            assert launch["compiled"] == any(
                s["parent"] == launch["sid"]
                for s in _spans(run["records"], "compile"))
    else:
        assert all(isinstance(p["ready_at_start"], bool) for p in parents)


@pytest.mark.parametrize("run", ["cli", "serial"], indirect=True)
def test_spans_of_one_chunk_share_its_identifier(run):
    spans = _spans(run["records"])
    by_sid = {s["sid"]: s for s in spans}
    for chunk in range(6):
        mine = [s for s in spans if s.get("chunk") == chunk]
        names = [s["name"] for s in mine]
        for once in ("pack", "dispatch", "round_device", "stats_fetch",
                     "host_tail", "housekeeping", "ckpt_submit"):
            assert names.count(once) == 1, (chunk, once, names)
    # what runs under a host tail inherits the tail's chunk
    under_tail = [s for s in spans if s["name"] in
                  ("eval", "eval_pack", "eval_device", "ckpt_submit")
                  and s["parent"] is not None]
    assert under_tail
    for span in under_tail:
        top = span
        while top["parent"] is not None:
            top = by_sid[top["parent"]]
        if top["name"] == "host_tail":
            assert span["chunk"] == top["chunk"]
        else:
            # the initial evaluation belongs to no chunk
            assert top["name"] == "eval" and top["round"] == 0 and \
                "chunk" not in span
    assert any(s["name"] == "eval" and "chunk" in s for s in spans) == \
        any(s["name"] == "eval" and s["round"] > 0 for s in spans)


def test_async_writer_span_is_on_another_thread(cli_run):
    writes = _spans(cli_run["records"], "ckpt_async_write")
    assert writes
    for span in writes:
        assert span["thread"] != "MainThread" and span["parent"] is None
        # what the save wrote (benchmarks/layer_metrics/ckpt_write_ms.py
        # reads only spans that say so)
        assert isinstance(span["bytes"], int) and span["bytes"] > 0


def test_serial_dispatch_finds_nothing_in_flight(serial_run):
    for span in _spans(serial_run["records"], "dispatch"):
        assert span["ring"] == 0 and span["inflight"] == 0


def test_pipelined_dispatch_sees_the_ring(cli_run):
    spans = _spans(cli_run["records"], "dispatch")
    assert {s["ring"] for s in spans} == {0, 1}
    for span in spans:
        assert 0 <= span["inflight"] <= span["ring"]


def test_eval_pack_is_paid_once_a_split(cli_run):
    packs = _spans(cli_run["records"], "eval_pack")
    assert len(packs) == len(_spans(cli_run["records"], "eval")) == 5
    assert [p["cached"] for p in packs if p["split"] == "val"] == \
        [False, True, True]
    assert [p["cached"] for p in packs if p["split"] == "test"] == \
        [False, True]


# ======================================================================
# set-up: the CLI's phases and jax's compiles, from inside the program
# ======================================================================
@pytest.mark.parametrize("name", SETUP)
def test_setup_phase_ends_before_the_first_dispatch(cli_run, name):
    first = min(s["ts"] for s in _spans(cli_run["records"], "dispatch"))
    (span,) = _spans(cli_run["records"], name)
    assert span["dur_s"] >= 0 and span["ts"] + span["dur_s"] <= first
    phases = [_spans(cli_run["records"], n)[0] for n in SETUP]
    for a, b in zip(phases, phases[1:]):
        # ts and dur_s are each rounded to a microsecond in the record
        assert a["ts"] + a["dur_s"] <= b["ts"] + 2e-6


@pytest.mark.parametrize("name", COMPILES)
def test_compile_spans_cover_engine_construction(cli_run, name):
    build = _spans(cli_run["records"], "server_build")[0]
    spans = _spans(cli_run["records"], name)
    assert spans and all(s["fun_name"] for s in spans)
    # buffered before the scope existed: init_state's programs
    assert any(build["ts"] <= s["ts"] <= build["ts"] + build["dur_s"]
               for s in spans)
    # live ones hang under what caused them: the first launch compiles
    launch = _spans(cli_run["records"], "launch")[0]
    assert any(s["parent"] == launch["sid"] for s in spans)


def test_compile_spans_buffer_until_a_tracer_is_attached(tmp_path):
    from msrflute_tpu.telemetry.compiles import CompileSpans
    spans = CompileSpans()
    spans._on_duration("/jax/core/compile/backend_compile_duration", 0.5,
                       fun_name="dropped")  # nobody asked yet
    spans._buffering = True
    spans._on_event("/jax/compilation_cache/cache_hits")
    spans._on_duration("/jax/core/compile/backend_compile_duration", 0.25,
                       fun_name="jit_f")
    spans._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.125,
                       fun_name="f")
    spans._on_duration("/jax/some/other_duration", 1.0)
    tracer = Tracer(str(tmp_path))
    spans.attach(tracer)
    spans._on_event("/jax/compilation_cache/cache_misses")
    spans._on_duration("/jax/core/compile/backend_compile_duration", 0.5,
                       fun_name="jit_g")
    spans.detach(tracer)
    spans._on_duration("/jax/core/compile/backend_compile_duration", 0.5,
                       fun_name="after")
    tracer.close()
    got = [(r["name"], r["fun_name"], r.get("cache"), r["dur_s"])
           for r in _spans(_records_of(tracer))]
    assert got == [("compile", "jit_f", "hit", 0.25),
                   ("jit_trace", "f", None, 0.125),
                   ("compile", "jit_g", "miss", 0.5)]


def _records_of(tracer):
    with open(tracer.events_path) as fh:
        return [json.loads(line) for line in fh]


# ======================================================================
# the tracer itself
# ======================================================================
def test_tracer_links_parents_threads_and_chunks(tmp_path):
    tracer = Tracer(str(tmp_path))
    seen = {}

    def other():
        with tracer.span("elsewhere"):
            pass

    with tracer.span("outer", chunk=7) as outer_args:
        with tracer.span("inner") as inner_args:
            inner_args["late"] = 3
            token = tracer.begin("flight")
            worker = threading.Thread(target=other, name="side-thread")
            worker.start()
            worker.join(timeout=30)
            assert not worker.is_alive()
        tracer.emit_span("done_before", 10.0, 12.5, fun_name="f")
        outer_args["bytes"] = 11
    with tracer.span("own_chunk", chunk=9):
        with tracer.span("kept", chunk=1):
            pass
    tracer.end(token)
    tracer.emit_span("early", 1.0, 2.0, thread="gone-thread")
    tracer.close()
    for rec in _spans(_records_of(tracer)):
        seen[rec["name"]] = rec
    outer, inner = seen["outer"], seen["inner"]
    assert outer["parent"] is None and outer["bytes"] == 11
    assert inner["parent"] == outer["sid"] and inner["late"] == 3
    assert inner["chunk"] == 7  # handed down
    assert seen["flight"]["parent"] == inner["sid"]
    assert seen["flight"]["chunk"] == 7
    assert seen["flight"]["thread"].startswith("rounds in flight")
    assert seen["elsewhere"]["parent"] is None
    assert seen["elsewhere"]["thread"] == "side-thread"
    assert "chunk" not in seen["elsewhere"]
    done = seen["done_before"]
    assert (done["ts"], done["dur_s"]) == (10.0, 2.5)
    assert done["parent"] == outer["sid"] and done["chunk"] == 7
    assert seen["kept"]["chunk"] == 1 and \
        seen["kept"]["parent"] == seen["own_chunk"]["sid"]
    assert seen["early"]["thread"] == "gone-thread" and \
        seen["early"]["parent"] is None
    # the Perfetto side still loads: complete events with a duration
    with open(tracer.trace_path) as fh:
        events = json.load(fh)["traceEvents"]
    assert {e["name"] for e in events if e["ph"] == "X"} >= set(seen)


# ======================================================================
# one profiler window, on the program's clock
# ======================================================================
def test_one_start_trace_site_in_the_package():
    sites = []
    for path in glob.glob(os.path.join(REPO, "msrflute_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as fh:
            for line in fh:
                if re.search(r"\bstart_trace\(", line):
                    sites.append(os.path.relpath(path, REPO))
    assert sites == ["msrflute_tpu/telemetry/profiling.py"]


def _annotations(profile_dir):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(str(profile_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert len(paths) == 1, paths
    names = set()
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            names.update(event.name for event in line.events)
    return names


def test_profile_window_shares_a_clock_mark_with_the_events(cli_run):
    marks = [r for r in cli_run["records"]
             if r["kind"] == "event" and r["name"] == "flute_clock_sync"]
    assert len(marks) == 1
    # taken just before the annotation, after the capture began: inside
    # the run, on the epoch clock the spans use
    third = [s for s in _spans(cli_run["records"], "dispatch")
             if s["round0"] == 3][0]
    assert marks[0]["epoch_s"] <= marks[0]["ts"] <= third["ts"]
    names = _annotations(cli_run["model_dir"] / "telemetry" / "xla_profile")
    assert "flute_clock_sync" in names
    # the Python call tracer is off: no interpreter frames in the capture
    assert not any(name.startswith("$") for name in names)


@pytest.mark.parametrize("telemetry", [None, {"enable": True}],
                         ids=["telemetry_off", "telemetry_on"])
def test_do_profiling_drives_the_same_window(tmp_path, telemetry):
    cfg = _cfg(1, telemetry=telemetry, rounds=4)
    cfg.server_config.do_profiling = True
    server, state = _run(cfg, tmp_path)
    assert state.round == 4
    profiler = server._profiler
    # the second chunk (one round a chunk here), as it always was
    assert profiler.window == (1, 2) and profiler.captured
    where = (tmp_path / "profile" if telemetry is None
             else tmp_path / "telemetry" / "xla_profile")
    assert "flute_clock_sync" in _annotations(where)
    if telemetry is None:
        assert server.scope is None
        assert not os.path.isdir(tmp_path / "telemetry")
    else:
        assert profiler is server.scope.profiler
        server.scope.close()
        assert [r["name"] for r in _records(tmp_path)
                if r["kind"] == "event"].count("flute_clock_sync") == 1


# ======================================================================
# telemetry off: the CLI registers nothing and asks nothing
# ======================================================================
def test_cli_with_telemetry_off_registers_no_listener(tmp_path,
                                                      monkeypatch):
    import e2e_trainer
    import jax
    import msrflute_tpu.telemetry as tel
    import msrflute_tpu.telemetry.compiles as compiles
    from msrflute_tpu.engine import round as round_mod

    def bomb(*a, **k):
        raise AssertionError("telemetry touched with telemetry off")

    monkeypatch.setattr(tel, "Telemetry", bomb)
    monkeypatch.setattr(tel.spans, "Tracer", bomb)
    monkeypatch.setattr(compiles, "install", bomb)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener", bomb)
    monkeypatch.setattr(jax.monitoring, "register_event_listener", bomb)
    monkeypatch.setattr(round_mod.PackedStats, "is_ready", bomb)
    monkeypatch.setattr(round_mod.PackedStats, "wait", bomb)
    data_dir, out_dir = tmp_path / "data", tmp_path / "out"
    data_dir.mkdir()
    _write_blob(data_dir / "train.json", 12)
    cfg = {
        "model_config": {"model_type": "LR", "num_classes": 3,
                         "input_dim": 6},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": 3, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.3, "pipeline_depth": 1,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 100, "initial_val": False, "data_config": {}},
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.3},
            "data_config": {"train": {"batch_size": 4,
                                      "list_of_train_data": "train.json"}}},
    }
    cfg_path = tmp_path / "cfg.yaml"
    cfg_path.write_text(yaml.safe_dump(cfg))
    monkeypatch.setattr(sys, "argv", [
        "e2e_trainer.py", "-config", str(cfg_path), "-dataPath",
        str(data_dir), "-outputPath", str(out_dir), "-task", "cv_lr_mnist"])
    server = e2e_trainer.main()
    assert server.scope is None and server.engine.span_factory is None
    assert int(np.asarray(server.state.round)) == 3
    assert not os.path.isdir(out_dir / "models" / "telemetry")
