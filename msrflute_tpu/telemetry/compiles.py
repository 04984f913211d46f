"""The program's own record of what jax traces, lowers and compiles.

``jax.monitoring`` reports three durations per program, each with the
function's name: tracing to a jaxpr, lowering to an MLIR module, and the
backend compile (a persistent-cache hit is still a request, and much
shorter).  :func:`install` registers listeners for them ONCE a process
(jax keeps listeners for good) and hands back the one
:class:`CompileSpans`; it is called only when a telemetry block turns
tracing on, as early as the configuration is read, so that engine
construction and ``init_state`` are covered.  Until a tracer is attached
the records are buffered; attached, each becomes a ``jit_trace``,
``jit_lower`` or ``compile`` span (args ``fun_name``, and ``cache`` =
``hit``/``miss`` where jax says) through :meth:`Tracer.emit_span`;
detached again (the scope closed) they are dropped.

A duration is reported at its end, so a record's start is the report
time minus the duration.  A trace of an inner jitted function lies
inside its caller's: readers take the union of these spans' intervals,
not the sum of their durations.

This is observation only: nothing here changes how a program is
dispatched (``telemetry/xla.py``'s ahead-of-time wrapper does, and is a
separate switch).
"""

from __future__ import annotations

import threading
import time
from typing import Any, List, Optional, Tuple

SPAN_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
CACHE_OF_EVENT = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
#: records kept while no tracer is attached (a set-up makes a few
#: thousand: every inner jitted function is traced once)
MAX_BUFFERED = 65536


class CompileSpans:
    """Listener state: buffer, then forward to the attached tracer."""

    def __init__(self):
        self._lock = threading.Lock()
        self._tracer = None
        self._buffering = False
        self._buffer: List[Tuple[str, float, float, str, dict]] = []
        #: the cache's word on the compile now open on this thread (jax
        #: reports hit/miss inside the backend-compile duration)
        self._cache = threading.local()

    # -- what jax calls -------------------------------------------------
    def _on_event(self, event: str, **kwargs: Any) -> None:
        verdict = CACHE_OF_EVENT.get(event)
        if verdict is not None:
            self._cache.verdict = verdict

    def _on_duration(self, event: str, duration: float,
                     **kwargs: Any) -> None:
        name = SPAN_OF_EVENT.get(event)
        if name is None:
            return
        end = time.time()
        args = {"fun_name": str(kwargs.get("fun_name"))}
        if name == "compile":
            verdict = getattr(self._cache, "verdict", None)
            self._cache.verdict = None
            if verdict is not None:
                args["cache"] = verdict
        record = (name, end - float(duration), end,
                  threading.current_thread().name, args)
        with self._lock:
            tracer = self._tracer
            if tracer is None:
                if self._buffering and len(self._buffer) < MAX_BUFFERED:
                    self._buffer.append(record)
                return
        self._emit(tracer, record)

    @staticmethod
    def _emit(tracer, record) -> None:
        name, t0, t1, thread, args = record
        tracer.emit_span(name, t0, t1, thread=thread, **args)

    # -- what the scope calls -------------------------------------------
    def attach(self, tracer) -> None:
        """Hand over what was buffered and forward from now on."""
        with self._lock:
            self._tracer = tracer
            self._buffering = False
            buffered, self._buffer = self._buffer, []
        for record in buffered:
            self._emit(tracer, record)

    def detach(self, tracer) -> None:
        with self._lock:
            if self._tracer is tracer:
                self._tracer = None


_INSTALLED: Optional[CompileSpans] = None


def install() -> CompileSpans:
    """Register the listeners (once a process) and start buffering until
    a tracer is attached."""
    global _INSTALLED
    if _INSTALLED is None:
        from jax import monitoring
        _INSTALLED = CompileSpans()
        monitoring.register_event_duration_secs_listener(
            _INSTALLED._on_duration)
        monitoring.register_event_listener(_INSTALLED._on_event)
    with _INSTALLED._lock:
        if _INSTALLED._tracer is None:
            _INSTALLED._buffering = True
    return _INSTALLED
