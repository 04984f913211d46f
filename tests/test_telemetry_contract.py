"""flutescope's zero-cost / zero-transfer contract (ISSUE 4 acceptance).

Three properties, each pinned end-to-end through the real round loop:

1. **Telemetry OFF is free**: no scope object, no tracer construction,
   no telemetry directory, a byte-identical devbus-free round program.
2. **Telemetry ON is transfer-neutral**: zero implicit host
   materializations (the ArrayImpl interception harness from
   ``tests/test_bench_contract.py``), the one-packed-fetch-per-round
   guard holds, and params are BIT-IDENTICAL to the telemetry-off run —
   serial and pipelined.
3. **The acceptance trace**: a pipelined chaos run with telemetry on
   (under ``MSRFLUTE_STRICT_TRANSFERS=1``) produces a Perfetto-loadable
   ``trace.json`` whose round-k host-tail span overlaps round-k+1's
   device span, with chaos + checkpoint events present.
"""

import json
import os
import threading

import jax
import numpy as np
import pytest

from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.data import ArraysDataset
from msrflute_tpu.engine import OptimizationServer
from msrflute_tpu.models import make_task

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(pipeline_depth, telemetry=None, chaos=None, rounds=6):
    raw = {
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": rounds, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.2, "rounds_per_step": 1,
            "pipeline_depth": pipeline_depth,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 100, "initial_val": False, "data_config": {}},
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.2},
            "data_config": {"train": {"batch_size": 4}}},
    }
    if telemetry is not None:
        raw["server_config"]["telemetry"] = telemetry
    if chaos is not None:
        raw["server_config"]["chaos"] = chaos
        raw["server_config"]["checkpoint_retry"] = {
            "retries": 3, "backoff_base_s": 0.0, "jitter": 0.0}
    return FLUTEConfig.from_dict(raw)


def _dataset():
    rng = np.random.default_rng(0)
    users, per = [], []
    for u in range(8):
        users.append(f"u{u}")
        per.append({"x": rng.normal(size=(8, 8)).astype(np.float32),
                    "y": rng.integers(0, 4, 8).astype(np.int32)})
    return ArraysDataset(users, per)


def _run(cfg, model_dir, seed=0):
    server = OptimizationServer(make_task(cfg.model_config), cfg,
                                _dataset(), model_dir=str(model_dir),
                                seed=seed)
    state = server.train()
    return server, state


# ======================================================================
# 1. telemetry off adds nothing
# ======================================================================
def test_telemetry_off_constructs_no_telemetry_state(tmp_path,
                                                     monkeypatch):
    """With no telemetry block the round loop must never touch the
    subsystem: Tracer/Watchdog/XlaIntrospector construction would blow
    up here (the device-truth layer included — telemetry off means NO
    xla-introspection objects, the plain jit dispatch path)."""
    import msrflute_tpu.telemetry as tel

    def bomb(*a, **k):
        raise AssertionError("telemetry constructed with telemetry off")

    monkeypatch.setattr(tel, "Telemetry", bomb)
    monkeypatch.setattr(tel.spans, "Tracer", bomb)
    monkeypatch.setattr(tel.xla, "XlaIntrospector", bomb)
    # the endurance layer (ISSUE 13) honours the same contract:
    # telemetry off constructs no rollup engine and no flight recorder
    monkeypatch.setattr(tel.rollup, "RollupEngine", bomb)
    monkeypatch.setattr(tel.rollup, "FlightRecorder", bomb)
    # ISSUE 24: no compile listener is registered, no readiness probe
    # is asked (is_ready / wait exist for the traced path alone), no
    # profiler object exists, and the engine holds no span factory
    import msrflute_tpu.telemetry.compiles as compiles
    import msrflute_tpu.telemetry.profiling as profiling
    from msrflute_tpu.engine import round as round_mod
    monkeypatch.setattr(compiles, "install", bomb)
    monkeypatch.setattr(compiles, "CompileSpans", bomb)
    monkeypatch.setattr(profiling, "RoundProfiler", bomb)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener", bomb)
    monkeypatch.setattr(jax.monitoring, "register_event_listener", bomb)
    monkeypatch.setattr(round_mod.PackedStats, "is_ready", bomb)
    monkeypatch.setattr(round_mod.PackedStats, "wait", bomb)
    import jax._src.array as jarray
    monkeypatch.setattr(jarray.ArrayImpl, "is_ready", bomb)
    server, state = _run(_cfg(pipeline_depth=1), tmp_path)
    assert state.round == 6
    assert server.scope is None
    assert server.engine.span_factory is None and server._profiler is None
    assert not server.engine.devbus.enabled
    assert server.engine.xla is None
    # no scorecard either — nothing to regress-gate without telemetry
    assert not os.path.exists(tmp_path / "telemetry" / "scorecard.json")
    assert not os.path.isdir(tmp_path / "telemetry")
    # the round program carries no devbus outputs: the stats slot table
    # has no devbus_* entries
    packer = next(iter(server.engine._stats_packers.values()))
    stats = packer.unpack_np({dt: np.zeros(n, dtype=dt)
                              for dt, n in packer.sizes.items()})
    assert not any(k.startswith("devbus_") for k in stats)


# ======================================================================
# 2. telemetry on: zero implicit syncs, one fetch per round,
#    bit-identical params — serial and pipelined
# ======================================================================
@pytest.mark.parametrize("depth", [0, 1])
def test_telemetry_on_zero_implicit_syncs_and_bit_identical(tmp_path,
                                                            monkeypatch,
                                                            depth):
    import jax._src.array as jarray

    # --- reference run: telemetry off -----------------------------
    _, ref_state = _run(_cfg(depth), tmp_path / f"ref{depth}")
    ref_params = jax.device_get(ref_state.params)

    # --- instrumented run under the interception harness ----------
    sanctioned = threading.local()
    real_get = jax.device_get

    def sanctioning_get(x):
        sanctioned.on = True
        try:
            return real_get(x)
        finally:
            sanctioned.on = False

    implicit = []
    train_thread = threading.current_thread()
    real_value = jarray.ArrayImpl._value
    real_array = jarray.ArrayImpl.__array__

    def spy_value(self):
        if not getattr(sanctioned, "on", False) and \
                threading.current_thread() is train_thread:
            implicit.append("_value")
        return real_value.fget(self)

    def spy_array(self, *args, **kwargs):
        if not getattr(sanctioned, "on", False) and \
                threading.current_thread() is train_thread:
            implicit.append("__array__")
        return real_array(self, *args, **kwargs)

    monkeypatch.setenv("MSRFLUTE_STRICT_TRANSFERS", "1")
    cfg = _cfg(depth, telemetry={"enable": True})
    server = OptimizationServer(make_task(cfg.model_config), cfg,
                                _dataset(),
                                model_dir=str(tmp_path / f"tel{depth}"),
                                seed=0)
    monkeypatch.setattr(jax, "device_get", sanctioning_get)
    monkeypatch.setattr(jarray.ArrayImpl, "_value", property(spy_value))
    monkeypatch.setattr(jarray.ArrayImpl, "__array__", spy_array)
    try:
        state = server.train()
    finally:
        monkeypatch.setattr(jarray.ArrayImpl, "_value", real_value)
        monkeypatch.setattr(jarray.ArrayImpl, "__array__", real_array)
        monkeypatch.setattr(jax, "device_get", real_get)

    assert state.round == 6
    assert implicit == [], (
        f"telemetry-on run performed implicit host syncs: {implicit}")
    if depth:
        assert server.pipelined_chunks > 0
    # bit-identical params vs the telemetry-off run
    tel_params = jax.device_get(state.params)
    for la, lb in zip(jax.tree.leaves(ref_params),
                      jax.tree.leaves(tel_params)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))
    # and the devbus scalars really rode along (no extra fetch needed)
    packer = next(iter(server.engine._stats_packers.values()))
    stats = packer.unpack_np({dt: np.zeros(n, dtype=dt)
                              for dt, n in packer.sizes.items()})
    assert "devbus_update_ratio" in stats
    # the device-truth layer ran THROUGH the interception harness: AOT
    # capture recorded the round program's cost with zero implicit
    # syncs, zero recompiles, and a scorecard on disk — telemetry-on is
    # transfer-neutral INCLUDING the xla layer
    assert server.engine.xla is not None
    assert server.engine.xla.entries and server.engine.xla.recompiles == 0
    assert os.path.exists(
        tmp_path / f"tel{depth}" / "telemetry" / "scorecard.json")
    # the rollup path ran through the same interception harness: the
    # endurance layer is transfer-neutral and bit-neutral too (its
    # default-on state is covered by the bit-identity assert above)
    assert server.scope.rollup is not None
    assert os.path.exists(
        tmp_path / f"tel{depth}" / "telemetry" / "rollups.jsonl")


def test_telemetry_on_keeps_one_packed_fetch_per_round(tmp_path,
                                                       monkeypatch):
    """The transfer-count regression guard from test_bench_contract,
    re-run with the full subsystem on: telemetry must add ZERO fetch
    events to the training thread.  Pipelined mode like the original
    guard (serial mode's SYNC checkpoint legitimately fetches the state
    payload per round — telemetry-independent)."""
    cfg = _cfg(1, telemetry={"enable": True}, rounds=3)
    server = OptimizationServer(make_task(cfg.model_config), cfg,
                                _dataset(), model_dir=str(tmp_path),
                                seed=0)
    fetches = []
    real = jax.device_get
    train_thread = threading.current_thread()

    def counting_get(x):
        if threading.current_thread() is train_thread:
            fetches.append(len(jax.tree.leaves(x)))
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    state = server.train()
    monkeypatch.setattr(jax, "device_get", real)
    assert state.round == 3
    assert fetches == [1, 1, 1], fetches


# ======================================================================
# 3. the acceptance trace: pipelined chaos run -> Perfetto overlap
# ======================================================================
def test_pipelined_chaos_trace_shows_overlap_and_events(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv("MSRFLUTE_STRICT_TRANSFERS", "1")
    cfg = _cfg(1, rounds=8,
               telemetry={"enable": True},
               chaos={"seed": 7, "dropout_rate": 0.3,
                      "straggler_rate": 0.3, "straggler_inflation": 2.0,
                      "ckpt_io_error_rate": 0.3})
    server, state = _run(cfg, tmp_path)
    assert state.round == 8
    assert server.pipelined_chunks > 0
    server.scope.close()

    with open(tmp_path / "telemetry" / "trace.json") as fh:
        trace = json.load(fh)
    events = trace["traceEvents"]
    assert isinstance(events, list) and events  # Perfetto-loadable shape
    for ev in events:
        assert {"name", "ph", "pid"} <= set(ev)

    device = {}   # round0 -> (ts, ts+dur)
    tails = {}
    names = set()
    for ev in events:
        names.add(ev["name"])
        if ev.get("ph") != "X":
            continue
        iv = (ev["ts"], ev["ts"] + ev["dur"])
        args = ev.get("args") or {}
        if ev["name"] == "round_device":
            device[args["round0"]] = iv
        elif ev["name"] == "host_tail":
            tails[args["round0"]] = iv
    # every round phase made it into the trace
    for expected in ("pack", "dispatch", "stats_fetch", "host_tail",
                     "housekeeping", "ckpt_submit", "round_device"):
        assert expected in names, sorted(names)
    # chaos + checkpoint fault events are pinned at their timestamps
    assert "chaos_faults" in names
    assert "ckpt_io_fault" in names
    # THE pipeline picture: round k's host tail ran while round k+1's
    # device window was open
    overlapped = 0
    for k, (t_lo, t_hi) in tails.items():
        nxt = device.get(k + 1)
        if nxt is not None:
            lo, hi = max(t_lo, nxt[0]), min(t_hi, nxt[1])
            if hi > lo:
                overlapped += 1
    assert overlapped > 0, (
        f"no host-tail span overlapped the next round's device span: "
        f"tails={tails} device={device}")
    # the reader CLI agrees: overlap efficiency is computed and > 0
    from msrflute_tpu.telemetry.scope_cli import summarize
    summary = summarize(str(tmp_path))
    assert summary["overlap"]["efficiency_pct"] > 0
    assert summary["events"]["chaos_faults"] > 0
