"""flutescope unit tests: spans/trace export, the device-metric bus,
watchdogs, profiling-window parsing, the metrics-stream move, the
telemetry config schema, and the preemption flush path."""

import json
import os
import threading

import numpy as np
import pytest

from msrflute_tpu.telemetry import (Telemetry, devbus_config_enabled,
                                    emit_event, make_telemetry,
                                    telemetry_config_enabled)
from msrflute_tpu.telemetry.devbus import DeviceMetricBus
from msrflute_tpu.telemetry.profiling import parse_profile_rounds
from msrflute_tpu.telemetry.spans import Tracer
from msrflute_tpu.telemetry.watchdog import Watchdog, WatchdogAbort


def _trace(tracer):
    tracer.flush()
    with open(tracer.trace_path) as fh:
        return json.load(fh)["traceEvents"]


def _jsonl(tracer):
    tracer.flush()
    with open(tracer.events_path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


# ======================================================================
# spans
# ======================================================================
def test_span_context_manager_emits_complete_event(tmp_path):
    tracer = Tracer(str(tmp_path))
    with tracer.span("pack", rounds=3):
        pass
    events = _trace(tracer)
    spans = [e for e in events if e.get("ph") == "X"]
    assert len(spans) == 1
    assert spans[0]["name"] == "pack"
    assert spans[0]["args"] == {"rounds": 3}
    assert spans[0]["dur"] >= 0.0
    # and the same span rode the JSONL stream
    lines = _jsonl(tracer)
    assert [(l["kind"], l["name"]) for l in lines] == [("span", "pack")]


def test_begin_end_spans_overlap_on_distinct_virtual_tracks(tmp_path):
    """The pipelined-overlap case: two begin/end spans open at once must
    land on different virtual tids with overlapping [ts, ts+dur)."""
    tracer = Tracer(str(tmp_path))
    a = tracer.begin("round_device", round0=0)
    b = tracer.begin("round_device", round0=1)
    tracer.end(a)
    tracer.end(b)
    spans = [e for e in _trace(tracer) if e.get("ph") == "X"]
    assert len(spans) == 2
    assert spans[0]["tid"] != spans[1]["tid"]
    lo = max(s["ts"] for s in spans)
    hi = min(s["ts"] + s["dur"] for s in spans)
    assert hi >= lo  # the intervals genuinely overlap
    # double-end is a no-op, and the freed slot is reused
    tracer.end(a)
    c = tracer.begin("round_device", round0=2)
    assert c.tid in (a.tid, b.tid)
    tracer.end(c)


def test_spans_are_thread_aware(tmp_path):
    tracer = Tracer(str(tmp_path))
    with tracer.span("main_work"):
        pass

    def worker():
        with tracer.span("writer_work"):
            pass

    t = threading.Thread(target=worker, name="ckpt-latest-writer")
    t.start()
    t.join()
    events = _trace(tracer)
    spans = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert spans["main_work"]["tid"] != spans["writer_work"]["tid"]
    names = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert "ckpt-latest-writer" in names


def test_instant_and_counter_events(tmp_path):
    tracer = Tracer(str(tmp_path))
    tracer.instant("chaos_faults", round=3, dropped=2.0)
    tracer.counter("devbus/update_ratio", 0.25)
    events = _trace(tracer)
    inst = [e for e in events if e.get("ph") == "i"]
    ctr = [e for e in events if e.get("ph") == "C"]
    assert inst[0]["name"] == "chaos_faults"
    assert inst[0]["args"]["dropped"] == 2.0
    assert ctr[0]["args"]["value"] == 0.25
    kinds = {(l["kind"], l["name"]) for l in _jsonl(tracer)}
    assert ("event", "chaos_faults") in kinds
    assert ("counter", "devbus/update_ratio") in kinds


def test_trace_json_is_valid_and_rewritten_per_flush(tmp_path):
    tracer = Tracer(str(tmp_path))
    with tracer.span("a"):
        pass
    tracer.flush()
    first = json.load(open(tracer.trace_path))
    with tracer.span("b"):
        pass
    tracer.close()
    second = json.load(open(tracer.trace_path))
    assert len(second["traceEvents"]) > len(first["traceEvents"])
    assert second["displayTimeUnit"] == "ms"


# ======================================================================
# devbus
# ======================================================================
def test_devbus_publish_drain_and_host_split():
    bus = DeviceMetricBus(enabled=True)
    bus.publish("update_ratio", 0.5)
    bus.publish("dp_clip", 1.25)
    drained = bus.drain()
    assert drained == {"devbus_update_ratio": 0.5, "devbus_dp_clip": 1.25}
    assert bus.drain() == {}  # drained is drained
    stats = {"train_loss_sum": np.ones(2), **{k: np.asarray([v, v])
                                             for k, v in drained.items()}}
    got = dict(DeviceMetricBus.split_fetched(stats))
    assert set(got) == {"update_ratio", "dp_clip"}
    assert got["dp_clip"].shape == (2,)


def test_devbus_disabled_is_a_noop():
    bus = DeviceMetricBus(enabled=False)
    bus.publish("x", 1.0)
    assert bus.drain() == {}


def test_devbus_config_gates():
    assert not devbus_config_enabled(None)
    assert not telemetry_config_enabled({"enable": False})
    assert devbus_config_enabled({"enable": True})
    assert not devbus_config_enabled({"enable": True, "devbus": False})


# ======================================================================
# watchdog
# ======================================================================
def test_watchdog_nan_loss_default_aborts():
    wd = Watchdog({})
    wd.observe_round(0, train_loss=1.0)
    with pytest.raises(WatchdogAbort):
        wd.observe_round(1, train_loss=float("nan"))
    assert wd.findings[0]["kind"] == "nan_loss"


def test_watchdog_nan_loss_mark_calls_mark_and_event():
    events, marks = [], []
    wd = Watchdog({"nan_loss": "mark"},
                  on_event=lambda kind, **f: events.append((kind, f)),
                  on_mark=lambda kind, f: marks.append(kind))
    wd.observe_round(2, train_loss=float("inf"))
    assert events[0][0] == "watchdog_nan_loss"
    assert marks == ["nan_loss"]


def test_watchdog_round_time_regression_fires_against_trailing_median():
    events = []
    wd = Watchdog({"nan_loss": "off", "round_time_action": "log",
                   "round_time_factor": 3.0, "round_time_window": 8},
                  on_event=lambda kind, **f: events.append((kind, f)))
    for r in range(6):
        wd.observe_round(r, round_secs=1.0)
    assert events == []
    wd.observe_round(6, round_secs=10.0)  # > 3x the 1.0 median
    assert events[0][0] == "watchdog_round_time_regression"
    assert events[0][1]["round"] == 6


def test_watchdog_ckpt_streak_fires_once_per_new_failure():
    events = []
    wd = Watchdog({"nan_loss": "off", "ckpt_failure_action": "log",
                   "ckpt_failure_streak": 2},
                  on_event=lambda kind, **f: events.append(kind))
    wd.observe_round(0, ckpt_failures=1)
    wd.observe_round(1, ckpt_failures=2)
    wd.observe_round(2, ckpt_failures=2)  # streak unchanged: no re-fire
    wd.observe_round(3, ckpt_failures=3)
    assert events == ["watchdog_ckpt_failure_streak",
                      "watchdog_ckpt_failure_streak"]
    wd.observe_round(4, ckpt_failures=0)  # success resets
    wd.observe_round(5, ckpt_failures=2)  # re-armed
    assert len(events) == 3


def test_watchdog_rejects_unknown_action():
    with pytest.raises(ValueError):
        Watchdog({"nan_loss": "explode"})


# ======================================================================
# profiling window parsing
# ======================================================================
def test_parse_profile_rounds_forms():
    assert parse_profile_rounds(None) is None
    assert parse_profile_rounds(5) == (5, 6)
    assert parse_profile_rounds("3:7") == (3, 7)
    assert parse_profile_rounds([2, 4]) == (2, 4)
    for bad in ("nope", "7:3", [-1, 2], True, {"lo": 1}):
        with pytest.raises((ValueError, TypeError)):
            parse_profile_rounds(bad)


def test_round_profiler_degrades_gracefully(monkeypatch, tmp_path):
    """A profiler that refuses to start (one trace per process, and the
    flag-gated ``profile_dir`` trace may hold it) must disable the
    window, not kill the run."""
    import jax

    from msrflute_tpu.telemetry.profiling import RoundProfiler

    def busy(log_dir, **kwargs):
        raise RuntimeError("Profile has already been started.")

    monkeypatch.setattr(jax.profiler, "start_trace", busy)
    prof = RoundProfiler("1:3", str(tmp_path))
    prof.observe(0)
    assert not prof.active
    prof.observe(1)  # start fails -> disabled
    assert prof.failed and not prof.active
    prof.observe(2)  # further observes are no-ops
    prof.finish()


def test_round_profiler_window_inside_fused_chunk_still_fires(
        monkeypatch, tmp_path):
    """profile_rounds: 5 with fused chunks of 4 (boundaries 0,4,8,...):
    the chunk [4,8) INTERSECTS the window, so the capture must start at
    boundary 4 and stop at 8 — not silently never fire."""
    import jax

    from msrflute_tpu.telemetry.profiling import RoundProfiler

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    prof = RoundProfiler(5, str(tmp_path))
    for r0 in range(0, 16, 4):
        prof.observe(r0, rounds=4)
    assert calls == ["start", "stop"]
    assert prof.captured


def test_round_profiler_window_drives_start_stop(monkeypatch, tmp_path):
    import jax

    from msrflute_tpu.telemetry.profiling import RoundProfiler

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    prof = RoundProfiler("2:4", str(tmp_path))
    for r in range(6):
        prof.observe(r)
    assert [c[0] for c in calls] == ["start", "stop"]
    assert prof.captured


# ======================================================================
# metrics stream + structured events + preemption flush
# ======================================================================
def _capture_metrics(monkeypatch, tmp_path):
    from msrflute_tpu.telemetry import metrics as tmetrics
    path = tmp_path / "metrics.jsonl"
    fh = open(path, "a")
    monkeypatch.setattr(tmetrics, "_METRICS_FH", fh)
    monkeypatch.setattr(tmetrics, "_LAST_FLUSH", 0.0)
    return path, fh


def test_utils_logging_reexports_telemetry_metrics():
    from msrflute_tpu.telemetry import metrics as tmetrics
    from msrflute_tpu.utils import logging as ulog
    assert ulog.log_metric is tmetrics.log_metric
    assert ulog.flush_metrics is tmetrics.flush_metrics
    assert ulog.log_event is tmetrics.log_event


def test_log_event_writes_structured_record(monkeypatch, tmp_path):
    from msrflute_tpu.telemetry import metrics as tmetrics
    path, fh = _capture_metrics(monkeypatch, tmp_path)
    tmetrics.log_event("checkpoint_recovery", detail="crc mismatch",
                       path="latest_model.msgpack")
    tmetrics.flush_metrics()
    records = [json.loads(l) for l in open(path)]
    assert records[0]["event"] == "checkpoint_recovery"
    assert records[0]["detail"] == "crc mismatch"
    fh.close()


def test_preemption_request_flushes_and_emits_event(monkeypatch, tmp_path):
    """The crash-safe contract: a preemption request makes the metrics
    stream durable and leaves a structured record BEFORE any drain work,
    and runs registered flush hooks (the trace writer)."""
    from msrflute_tpu.resilience.preemption import PreemptionHandler
    path, fh = _capture_metrics(monkeypatch, tmp_path)
    flushed = []
    handler = PreemptionHandler()
    handler.add_flush_hook(lambda: flushed.append(True))
    handler.request("test preempt")
    assert handler.requested
    assert flushed == [True]
    records = [json.loads(l) for l in open(path)]  # already flushed
    assert any(r.get("event") == "preemption" and
               r.get("reason") == "test preempt" for r in records)
    # a second request is idempotent (no duplicate record)
    handler.request("again")
    records = [json.loads(l) for l in open(path)]
    assert sum(r.get("event") == "preemption" for r in records) == 1
    fh.close()


def test_emit_event_without_scope_hits_metrics_stream(monkeypatch,
                                                      tmp_path):
    path, fh = _capture_metrics(monkeypatch, tmp_path)
    emit_event(None, "chaos_faults", round=2, dropped=1.0)
    from msrflute_tpu.telemetry import metrics as tmetrics
    tmetrics.flush_metrics()
    records = [json.loads(l) for l in open(path)]
    assert records[0]["event"] == "chaos_faults"
    fh.close()


# ======================================================================
# Telemetry facade + config schema
# ======================================================================
def test_make_telemetry_off_paths():
    assert make_telemetry(None, "/nonexistent") is None
    assert make_telemetry({"enable": False}, "/nonexistent") is None


def test_telemetry_facade_consume_devbus(tmp_path, monkeypatch):
    scope = make_telemetry({"enable": True}, str(tmp_path))
    assert isinstance(scope, Telemetry)
    logged = []
    from msrflute_tpu.telemetry import metrics as tmetrics
    monkeypatch.setattr(tmetrics, "log_metric",
                        lambda name, value, step=None, extra=None:
                        logged.append((name, value, step)))
    stats = {"devbus_update_ratio": np.asarray([0.1, 0.2]),
             "train_loss_sum": np.asarray([1.0, 2.0])}
    scope.consume_devbus(stats, round0=4, rounds=2)
    assert logged == [("devbus/update_ratio", 0.1, 4),
                      ("devbus/update_ratio", pytest.approx(0.2), 5)]
    scope.close()


def test_schema_accepts_full_telemetry_block():
    from msrflute_tpu import schema
    schema.validate({
        "model_config": {"model_type": "LR"},
        "server_config": {
            "telemetry": {
                "enable": True, "trace": True, "devbus": True,
                "profile_rounds": "3:5",
                "watchdog": {"nan_loss": "abort",
                             "round_time_action": "log",
                             "round_time_factor": 2.5,
                             "round_time_window": 8,
                             "ckpt_failure_action": "mark",
                             "ckpt_failure_streak": 3}}},
    })


@pytest.mark.parametrize("block, fragment", [
    ({"telemetry": {"enalbe": True}}, "enalbe"),
    ({"telemetry": {"watchdog": {"nan_loss": "explode"}}}, "explode"),
    ({"telemetry": {"profile_rounds": "7:3"}}, "profile_rounds"),
    ({"telemetry": {"watchdog": {"round_time_factor": 0.5}}},
     "round_time_factor"),
    # a bare string/bool block would die cryptically at server
    # construction — the schema must catch it at config load
    ({"telemetry": {"watchdog": "abort"}}, "must be a mapping"),
    ({"telemetry": True}, "must be a mapping"),
])
def test_schema_rejects_bad_telemetry_blocks(block, fragment):
    from msrflute_tpu import schema
    with pytest.raises(schema.SchemaError) as exc:
        schema.validate({"model_config": {"model_type": "LR"},
                         "server_config": block})
    assert fragment in str(exc.value)


def test_config_dataclass_carries_telemetry_block():
    from msrflute_tpu.config import FLUTEConfig
    cfg = FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR"},
        "server_config": {"telemetry": {"enable": True,
                                        "profile_rounds": 2}},
    })
    assert cfg.server_config.telemetry == {"enable": True,
                                           "profile_rounds": 2}
    assert cfg.server_config.get("telemetry")["profile_rounds"] == 2


# ======================================================================
# review-hardening regressions
# ======================================================================
def test_signal_context_request_defers_flush_to_the_poll(monkeypatch,
                                                         tmp_path):
    """A SIGTERM handler must do NO file IO / lock acquisition: the
    request only latches, and the round loop's poll runs flush_now()
    outside signal context."""
    import signal as _signal

    from msrflute_tpu.resilience.preemption import PreemptionHandler
    path, fh = _capture_metrics(monkeypatch, tmp_path)
    flushed = []
    handler = PreemptionHandler()
    handler.add_flush_hook(lambda: flushed.append(True))
    handler._on_signal(_signal.SIGTERM.value, None)
    assert handler.requested
    assert flushed == []  # deferred — nothing ran in handler context
    records = [json.loads(l) for l in open(path)]
    assert not any(r.get("event") == "preemption" for r in records)
    handler.flush_now()  # the loop's poll
    assert flushed == [True]
    records = [json.loads(l) for l in open(path)]
    assert any(r.get("event") == "preemption" and
               "SIGTERM" in r.get("reason", "") for r in records)
    handler.flush_now()  # idempotent
    assert flushed == [True]
    fh.close()


def test_consume_devbus_skips_nonscalar_with_event(tmp_path, monkeypatch):
    """A vmapped per-client publish (vector, not scalar) must not crash
    the host tail — it is skipped with a one-time structured event."""
    scope = make_telemetry({"enable": True}, str(tmp_path))
    logged, events = [], []
    from msrflute_tpu.telemetry import metrics as tmetrics
    monkeypatch.setattr(tmetrics, "log_metric",
                        lambda name, value, step=None, extra=None:
                        logged.append((name, value)))
    monkeypatch.setattr(tmetrics, "log_event",
                        lambda kind, **f: events.append(kind))
    stats = {"devbus_per_client": np.ones((2, 4)),   # [R, K] vector
             "devbus_ok": np.asarray([0.5, 0.6])}
    scope.consume_devbus(stats, round0=0, rounds=2)
    scope.consume_devbus(stats, round0=2, rounds=2)  # warn only once
    assert [n for n, _ in logged] == ["devbus/ok"] * 4
    assert events.count("devbus_nonscalar_skipped") == 1
    scope.close()


def test_tracer_event_cap_drops_visibly_not_silently(tmp_path,
                                                     monkeypatch):
    monkeypatch.setattr(Tracer, "MAX_EVENTS", 5)
    tracer = Tracer(str(tmp_path))
    for i in range(10):
        tracer.instant("e", i=i)
    tracer.flush()
    trace = json.load(open(tracer.trace_path))["traceEvents"]
    capped = [e for e in trace if e["name"] == "tracer_events_capped"]
    assert capped and capped[0]["args"]["dropped"] > 0
    # the JSONL stream is incremental and keeps everything
    lines = [json.loads(l) for l in open(tracer.events_path)]
    assert sum(1 for l in lines if l["name"] == "e") == 10
    tracer.close()


def test_tracer_flush_throttled_respects_interval(tmp_path, monkeypatch):
    tracer = Tracer(str(tmp_path))
    with tracer.span("a"):
        pass
    tracer.flush_throttled()  # _last_flush==0 -> flushes
    assert os.path.exists(tracer.trace_path)
    first = os.path.getmtime(tracer.trace_path)
    monkeypatch.setattr(Tracer, "FLUSH_INTERVAL_SECS", 3600.0)
    with tracer.span("b"):
        pass
    tracer.flush_throttled()  # inside the interval -> no rewrite
    assert os.path.getmtime(tracer.trace_path) == first
    tracer.close()  # close always flushes
    names = {e["name"] for e in
             json.load(open(tracer.trace_path))["traceEvents"]}
    assert "b" in names


def test_watchdog_abort_still_writes_trace_and_waits_checkpoints(
        tmp_path):
    """A WatchdogAbort out of the round loop must leave trace.json on
    disk (the aborted run's trace is the one you need) and the async
    checkpoint writer drained."""
    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.data import ArraysDataset
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task
    from msrflute_tpu.telemetry.watchdog import WatchdogAbort

    cfg = FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": 6, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.2, "rounds_per_step": 1,
            "pipeline_depth": 1,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "telemetry": {"enable": True},
            "val_freq": 100, "initial_val": False, "data_config": {}},
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.2},
            "data_config": {"train": {"batch_size": 4}}},
    })
    rng = np.random.default_rng(0)
    users, per = [], []
    for u in range(8):
        users.append(f"u{u}")
        per.append({"x": rng.normal(size=(8, 8)).astype(np.float32),
                    "y": rng.integers(0, 4, 8).astype(np.int32)})
    server = OptimizationServer(make_task(cfg.model_config), cfg,
                                ArraysDataset(users, per),
                                model_dir=str(tmp_path), seed=0)
    calls = []

    def aborting_observe(round_no, **kw):
        calls.append(round_no)
        if round_no >= 2:
            raise WatchdogAbort("synthetic abort")

    server.scope.watchdog.observe_round = aborting_observe
    with pytest.raises(WatchdogAbort):
        server.train()
    assert calls  # the abort really came from the watchdog path
    # trace.json materialized despite the abort, and the writer drained
    assert os.path.exists(tmp_path / "telemetry" / "trace.json")
    trace = json.load(open(tmp_path / "telemetry" / "trace.json"))
    assert any(e["name"] == "round_device"
               for e in trace["traceEvents"])
    assert server.ckpt._mp_mailbox is None and not server.ckpt._mp_busy
