"""flutescope — round-structured telemetry for the TPU round loop.

Four parts, one config block (``server_config.telemetry``, default OFF
with a measured-zero-overhead fast path — see docs/observability.md):

- :mod:`.spans` — thread-aware span tracer emitting Perfetto-loadable
  ``trace.json`` + a crash-safe ``events.jsonl`` stream;
- :mod:`.devbus` — the device-metric bus: per-round device scalars that
  ride the EXISTING flatpack packed-stats single transfer (zero new
  ``device_get``s);
- :mod:`.profiling` — opt-in ``jax.profiler`` capture for a configured
  round window, with a clock mark shared with ``events.jsonl``;
- :mod:`.compiles` — ``jax.monitoring``'s trace / lower / compile
  durations as ``jit_trace`` / ``jit_lower`` / ``compile`` spans;
- :mod:`.watchdog` — NaN-loss / round-time-regression /
  checkpoint-failure-streak detectors with log/mark/abort actions, plus
  the longitudinal tier (stall / rss_leak / throughput_drift);
- :mod:`.rollup` — ISSUE 13's endurance layer: incremental windowed
  rollups (``rollups.jsonl``, O(window) host memory) and the flight
  recorder (``flight.json`` persisted on abort/preemption/exception).

Plus :mod:`.metrics` (the always-on ``metrics.jsonl`` writer + structured
event records, re-exported by ``utils.logging``) and :mod:`.timing` (the
bench/tools stopwatch primitives).

This package imports no jax at import time (``bench.py`` must pick a
backend before jax loads); :mod:`.profiling` imports jax only when a
capture actually starts.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Dict, Optional

from . import metrics, rollup
from .devbus import DeviceMetricBus
from .spans import NULL_SPAN, SpanToken, Tracer
from .timing import Stopwatch, scalar_time
from .watchdog import Watchdog, WatchdogAbort

__all__ = [
    "DeviceMetricBus", "NULL_SPAN", "SpanToken",
    "Stopwatch", "Telemetry", "Tracer", "Watchdog", "WatchdogAbort",
    "devbus_config_enabled", "emit_event", "make_telemetry",
    "scalar_time", "telemetry_config_enabled", "trace_config_enabled",
    "xla_config_enabled",
]

#: subdirectory of the model dir holding trace.json/events.jsonl/profiles
TELEMETRY_DIRNAME = "telemetry"

#: the compact per-run regression surface (tools/scope diff reads it)
SCORECARD_FILENAME = "scorecard.json"


def telemetry_config_enabled(raw: Optional[Dict[str, Any]]) -> bool:
    """Whether a raw ``server_config.telemetry`` block turns the
    subsystem on (absent or ``enable: false`` => off)."""
    return bool(raw) and bool(dict(raw).get("enable", True))


def trace_config_enabled(raw: Optional[Dict[str, Any]]) -> bool:
    """Whether the span tracer is on for this config: what the CLI asks
    before it registers the compile listeners (``compiles.install``),
    so that a telemetry-off run registers none."""
    return telemetry_config_enabled(raw) and \
        bool(dict(raw).get("trace", True))


def devbus_config_enabled(raw: Optional[Dict[str, Any]]) -> bool:
    """Whether the device-metric bus is on for this config — the engine
    reads this at build time (a disabled bus leaves the compiled round
    program byte-identical to a telemetry-free build)."""
    return telemetry_config_enabled(raw) and \
        bool(dict(raw).get("devbus", True))


def xla_config_enabled(raw: Optional[Dict[str, Any]]) -> bool:
    """Whether the device-truth layer (``telemetry/xla.py``: compiled
    cost/memory capture + recompile sentinel) is on — the engine reads
    this at build time and constructs an :class:`~.xla.XlaIntrospector`
    only then (telemetry off => zero xla-introspection objects, the
    zero-cost contract)."""
    return telemetry_config_enabled(raw) and \
        bool(dict(raw).get("xla", True))


class Telemetry:
    """One run's telemetry scope: tracer + watchdog + profiler handles.

    Constructed only when ``server_config.telemetry`` enables the
    subsystem — the round loop holds ``None`` otherwise and pays a single
    is-None check per instrumentation point (the zero-cost contract,
    ``tests/test_telemetry_contract.py``).
    """

    def __init__(self, raw: Dict[str, Any], model_dir: str):
        self.raw = dict(raw)
        self.out_dir = os.path.join(model_dir, TELEMETRY_DIRNAME)
        self.tracer: Optional[Tracer] = (
            Tracer(self.out_dir) if self.raw.get("trace", True) else None)
        self.watchdog = Watchdog(self.raw.get("watchdog"),
                                 on_event=self.event)
        self._nonscalar_warned: set = set()
        # endurance layer (ISSUE 13): windowed rollups + flight recorder
        # — both default ON with telemetry (they are the days-long-run
        # observability; telemetry-off still constructs neither)
        self.rollup: Optional[rollup.RollupEngine] = None
        if self.raw.get("rollup", True):
            self.rollup = rollup.RollupEngine(
                self.out_dir,
                window=int(self.raw.get(
                    "rollup_window", rollup.RollupEngine.DEFAULT_WINDOW)))
        self.flight: Optional[rollup.FlightRecorder] = None
        if self.raw.get("flight", True):
            self.flight = rollup.FlightRecorder(
                self.out_dir,
                max_events=int(self.raw.get(
                    "flight_events", rollup.FlightRecorder.DEFAULT_EVENTS)))
            self.flight.rollup = self.rollup
        # the stall monitor persists the flight record BEFORE it
        # interrupts a hung main thread (watchdog.py) — wire it here so
        # the pairing exists whether or not the server adds context
        self.watchdog.on_flight = self.record_flight
        # bounded log growth (telemetry.max_log_mb): arms size-capped
        # rotation for metrics.jsonl AND events.jsonl at flush cadence.
        # Set UNCONDITIONALLY — the metrics cap is a process global, and
        # a later server constructed without the knob must get the
        # documented unbounded default back, not the previous run's cap
        max_log_mb = float(self.raw.get("max_log_mb", 0) or 0)
        metrics.set_max_log_mb(max_log_mb)
        if self.tracer is not None and max_log_mb > 0:
            self.tracer.max_log_bytes = int(max_log_mb * 2 ** 20)
        # lazy import: profiling reaches for jax only when a capture
        # window is configured and actually starts
        from .profiling import RoundProfiler
        self.profiler = RoundProfiler(
            self.raw.get("profile_rounds"),
            os.path.join(self.out_dir, "xla_profile"), tracer=self.tracer)
        # jax's trace / lower / compile durations become spans: what the
        # CLI's early install() buffered (engine construction,
        # init_state) is handed over now
        self.compiles = None
        if self.tracer is not None:
            from . import compiles
            self.compiles = compiles.install()
            self.compiles.attach(self.tracer)

    # -- spans ----------------------------------------------------------
    def span(self, name: str, **args: Any):
        """Context manager; yields the span's args (a dict the block may
        add to) or None when the trace is off."""
        inner = (self.tracer.span(name, **args)
                 if self.tracer is not None else NULL_SPAN)
        if self.rollup is None:
            return inner
        # rollup-fed spans: ONE extra perf_counter pair per phase — the
        # windowed per-phase quantiles come from here, so they exist
        # even when the trace itself is disabled (trace: false)
        return self._rollup_span(name, inner)

    @contextlib.contextmanager
    def _rollup_span(self, name: str, inner):
        t0 = time.perf_counter()
        try:
            with inner as span_args:
                yield span_args
        finally:
            self.rollup.observe_phase(name, time.perf_counter() - t0)

    def emit_spans(self, spans) -> None:
        """``(name, start, end)`` epoch times of phases that were over
        before this scope existed (the CLI's set-up)."""
        if self.tracer is not None:
            for name, t0, t1 in spans:
                self.tracer.emit_span(name, t0, t1)

    def begin(self, name: str, **args: Any) -> Optional[SpanToken]:
        if self.tracer is not None:
            return self.tracer.begin(name, **args)
        if self.rollup is not None:
            # trace:false still feeds the rollup's per-phase quantiles
            # (the documented contract): a plain timing token on the
            # same µs convention, no tracer track behind it (tid -1)
            return SpanToken(name, args, time.perf_counter() * 1e6, -1)
        return None

    def end(self, token: Optional[SpanToken]) -> None:
        if token is None or token.done:
            return
        if self.tracer is not None:
            if self.rollup is not None:
                self.rollup.observe_phase(
                    token.name,
                    (self.tracer._now_us() - token.t0_us) / 1e6)
            self.tracer.end(token)
            return
        token.done = True
        if self.rollup is not None:
            self.rollup.observe_phase(
                token.name,
                (time.perf_counter() * 1e6 - token.t0_us) / 1e6)

    # -- events / devbus ------------------------------------------------
    def event(self, kind: str, **fields: Any) -> None:
        """Structured record in BOTH streams: the always-on metrics
        stream and (when tracing) the trace's instant-event track —
        plus the rollup window's event counters and the flight ring."""
        metrics.log_event(kind, **fields)
        if self.tracer is not None:
            self.tracer.instant(kind, **fields)
        if self.rollup is not None:
            self.rollup.observe_event(kind)
        if self.flight is not None:
            self.flight.record_event(kind, fields)

    def devbus_host(self, name: str, value: float,
                    step: Optional[int] = None) -> None:
        """Host-side bus publish for values ALREADY fetched through a
        bundled ``device_get`` (scaffold ``c_norm``, the stashed
        ``dp_clip``): metric line + counter sample, no device access."""
        metrics.log_metric(f"devbus/{name}", float(value), step=step)
        if self.tracer is not None:
            self.tracer.counter(f"devbus/{name}", float(value))

    def consume_devbus(self, stats: Dict[str, Any], round0: int,
                       rounds: int) -> None:
        """Decode bus-published entries of one FETCHED stats dict (numpy,
        ``[R]``-leading) into per-round metric lines + counter samples.

        Non-scalar publishes (e.g. an un-reduced per-client vector from
        inside ``vmap``) are skipped with a one-time warning instead of
        crashing the host tail — the bus contract is per-round SCALARS;
        reduce (psum/mean) before publishing."""
        import numpy as np
        for name, arr in DeviceMetricBus.split_fetched(stats):
            for j in range(rounds):
                value = np.asarray(arr[j] if getattr(arr, "ndim", 0)
                                   else arr)
                if value.size != 1:
                    if name not in self._nonscalar_warned:
                        self._nonscalar_warned.add(name)
                        self.event("devbus_nonscalar_skipped",
                                   metric=name, shape=list(value.shape))
                    break
                value = float(value.reshape(()))
                metrics.log_metric(f"devbus/{name}", value, step=round0 + j)
                if self.tracer is not None:
                    self.tracer.counter(f"devbus/{name}", value)

    # -- scorecard ------------------------------------------------------
    def write_scorecard(self, card: Dict[str, Any]) -> Optional[str]:
        """Persist the run's compact regression surface
        (``telemetry/scorecard.json``) — the machine-readable summary
        ``tools/scope diff`` gates on.  Atomic (tmp + replace) so a
        concurrent reader never sees a torn card; returns the path, or
        None when the block disables it (``scorecard: false``)."""
        if not self.raw.get("scorecard", True):
            return None
        import json
        os.makedirs(self.out_dir, exist_ok=True)
        path = os.path.join(self.out_dir, SCORECARD_FILENAME)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(card, fh, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return path

    # -- endurance rollups + flight recorder (ISSUE 13) -----------------
    def rollup_observe(self, round_no: int, secs: float, clients: float,
                       mfu: Optional[float] = None,
                       rss_bytes: Optional[int] = None,
                       xla_snapshot: Optional[Dict[str, Any]] = None
                       ) -> None:
        """One completed round's longitudinal observations (all values
        the host tail already holds — the zero-transfer contract)."""
        if self.rollup is None:
            return
        gauges = dict(xla_snapshot or {})
        if self.tracer is not None:
            gauges["trace_events_dropped"] = self.tracer.dropped
        if gauges:
            self.rollup.update_gauges(gauges)
        self.rollup.observe_round(round_no, secs, clients, mfu=mfu,
                                  rss_bytes=rss_bytes)

    def rollup_housekeeping(self) -> None:
        """Round-housekeeping flush point: append the rollup record
        when the window completed (bounded work, no throttle needed —
        at most one record per ``rollup_window`` rounds)."""
        if self.rollup is not None:
            self.rollup.maybe_flush()

    def record_flight(self, reason: str,
                      detail: Optional[str] = None) -> Optional[str]:
        """Persist ``flight.json`` (no-op when the recorder is off) —
        the abort/preemption/exception paths' forensic snapshot."""
        if self.flight is None:
            return None
        return self.flight.persist(reason, detail=detail)

    def set_flight_context(self, card_fn) -> None:
        """Wire the server's scorecard builder into the flight record
        (called best-effort at persist time, never earlier)."""
        if self.flight is not None:
            self.flight.card_fn = card_fn

    # -- lifecycle ------------------------------------------------------
    def flush(self) -> None:
        if self.tracer is not None:
            self.tracer.flush()
        metrics.flush_metrics()

    def flush_throttled(self) -> None:
        """Round-housekeeping flush point: keeps the on-disk trace
        reasonably fresh (Tracer.FLUSH_INTERVAL_SECS throttle) without
        paying the full-rewrite cost every round.  Metrics flush
        separately at their own cadence."""
        if self.tracer is not None:
            self.tracer.flush_throttled()

    def close(self) -> None:
        self.profiler.finish()
        if self.compiles is not None:
            self.compiles.detach(self.tracer)
        self.watchdog.stop_stall_monitor()
        if self.rollup is not None:
            self.rollup.close()
        if self.tracer is not None:
            self.tracer.close()
        metrics.flush_metrics()


def make_telemetry(raw: Optional[Dict[str, Any]],
                   model_dir: str) -> Optional[Telemetry]:
    """Build the run's :class:`Telemetry` scope, or None when the config
    block is absent/disabled (the default — and the fast path: the round
    loop then contains no telemetry state at all)."""
    if not telemetry_config_enabled(raw):
        return None
    return Telemetry(dict(raw), model_dir)


def emit_event(scope: Optional[Telemetry], kind: str, **fields: Any) -> None:
    """Structured event that works with or without a telemetry scope:
    always a metrics-stream record; additionally a trace instant when
    tracing is on.  The chaos/checkpoint/preemption paths emit through
    here so their events are never log-lines-only again."""
    if scope is not None:
        scope.event(kind, **fields)
    else:
        metrics.log_event(kind, **fields)
