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

**Which compiled operation belongs to which named scope.**  The
program marks its mechanisms with ``jax.named_scope`` (:data:`SCOPES`,
the catalogue of docs/observability.md): trace-time metadata that costs
the device nothing.  A device trace names operations by their compiled
instruction (``fusion.1576``), which says nothing of the mechanism and
changes with every compile; the compiled program's text carries each
instruction's ``op_name`` path.  So while a tracer is attached, the
first launch of an engine entry point (:func:`programs_before` before
the call, :func:`program_scopes` after it has been issued) is followed by
one read of the dispatched executable's text:
``jitted.lower(<the call's avals>).compile()`` comes from jax's
in-memory caches (trace, lowering and executable: under a millisecond,
no compile request), and the text is parsed into ``{instruction:
its path's catalogue scopes, innermost last}`` and written to
``<telemetry dir>/programs/<hlo module>-<k>.json``, announced by ONE
span ``program_scopes`` whose duration is what the map cost.  A reader
(``benchmarks/scope_times.py``) joins it with the device trace by
module and instruction.

The persistent compilation cache keys a program WITHOUT its metadata:
a scope put around unchanged arithmetic hits the entry compiled before
the scope existed, and that executable's text, and the profile's, still
say the old paths.  The map is therefore made from the executable and
checked against the lowering (which always carries this process's
scopes): a catalogue scope that the lowering has and the executable
lacks makes the map ``stale`` (``missing`` names them), logged once
with the cure (an empty ``JAX_COMPILATION_CACHE_DIR``), and no reader
gives a number from a stale map.  Nothing is recompiled, evicted or
salted: a second loaded copy of a large round program would reserve its
scratch memory again.

This is observation only: nothing here changes how a program is
dispatched (``telemetry/xla.py``'s ahead-of-time wrapper does, and is a
separate switch).
"""

from __future__ import annotations

import json
import logging
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Set, Tuple

_LOGGER = logging.getLogger("msrflute_tpu")

SPAN_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
CACHE_OF_EVENT = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
#: the catalogue of ``jax.named_scope`` names (docs/observability.md,
#: "Named scopes"; a test holds the two together).  Engine level:
#: ``round_aggregate`` (``engine/round.py``) around the whole round,
#: ``client_steps`` (``engine/client_update.py``) around the local steps
#: inside it; the rest are mechanisms of the models and of the payload
#: transform, each inside one of the two.
SCOPES = ("round_aggregate", "client_steps", "quant_select", "embed",
          "lm_head_loss", "mla_proj", "mla_attn_core", "gqa_proj",
          "gqa_attn_core", "short_conv", "dense_ffn", "shared_expert",
          "routed_experts")
#: subdirectory of the telemetry dir that holds the maps
PROGRAMS_DIRNAME = "programs"
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
        self._stale_warned = False

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

    # -- the scope maps (program_scopes) --------------------------------
    def warn_stale(self, module: str, missing: List[str]) -> None:
        with self._lock:
            first, self._stale_warned = not self._stale_warned, True
        if first:
            _LOGGER.warning(
                "program_scopes: the executable of %s carries no scope "
                "%s although this process traced it: the persistent "
                "compilation cache keys a program without its metadata "
                "and handed back an entry compiled before the scope "
                "existed.  No scope time is read from it; point "
                "JAX_COMPILATION_CACHE_DIR at an empty directory to "
                "compile it anew.", module, ", ".join(missing))


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


# ----------------------------------------------------------------------
# the map: compiled instruction -> innermost catalogue scope
# ----------------------------------------------------------------------
_SCOPE_SET = frozenset(SCOPES)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LOC_NAME = re.compile(r'loc\("([^"]*)"')
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
#: how an instruction names another computation
_CALLED = re.compile(r"\b(calls|to_apply|select|scatter|body|condition|"
                     r"true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
#: ``transpose(jvp(mla_proj))`` -> ``mla_proj``: jax wraps the scope a
#: transformation was applied under into the transformation's name
_WRAPPED = re.compile(r"^(?:\w+\()+([^()]*)\)+$")


def scopes_in(path: str) -> List[str]:
    """The catalogue scopes of an ``op_name`` path, outermost first.  A
    backward or rematerialised operation's path still holds its scope
    (``transpose(jvp(mla_attn_core))``,
    ``checkpoint/rematted_computation/mla_proj``); a function's own name
    (``jit(embed)``) is not a scope."""
    found = []
    for part in path.split("/"):
        if part.startswith(("jit(", "pjit(")):
            continue
        wrapped = _WRAPPED.match(part)
        name = wrapped.group(1) if wrapped else part
        # a rematerialised path names its scopes twice over
        if name in _SCOPE_SET and name not in found:
            found.append(name)
    return found


def parse_program(text: str) -> Dict[str, Any]:
    """A compiled program's text (``compiled.as_text()``) ->
    ``{"module", "scopes": {instruction: its path's catalogue scopes,
    outermost first and joined by "/", "" where it has none},
    "present": catalogue scopes anywhere in the text}``.  An operation
    counts under the LAST scope of its entry, the innermost; the ones
    before it say what that lies in.

    - The instructions of a fusion's own computation and of what a
      reduction, a scatter or a sort applies are left out of ``scopes``
      (the device runs the fusion, and a fusion carries the path the
      compiler gave it, as a rule its root's) but count for ``present``.
    - An instruction with no path of its own (a copy the compiler made)
      that lies in the computation a loop, a branch, a call or an async
      operation runs takes that instruction's scopes: the body of a
      loop traced under ``client_steps`` is the local steps', whatever
      the compiler put into it."""
    lines = text.splitlines()
    head = _MODULE.match(lines[0]) if lines else None
    present: Set[str] = set()
    chains: Dict[str, str] = {}  # op_name path -> its scopes
    rows = []      # (computation, instruction, its scopes or "")
    run_by = {}    # computation -> (computation, scopes) of what runs it
    inlined = set()
    computation = ""
    for line in lines:
        if not line.startswith(" "):
            header = _COMPUTATION.match(line)
            if header:
                computation = header.group(1)
            continue
        name = _INSTRUCTION.match(line)
        if not name:
            continue
        path = _OP_NAME.search(line)
        scope = ""
        if path:
            scope = chains.get(path.group(1))
            if scope is None:
                found = scopes_in(path.group(1))
                present.update(found)
                scope = chains[path.group(1)] = "/".join(found)
        rows.append((computation, name.group(1), scope))
        for key, callee in _CALLED.findall(line):
            if key in ("select", "scatter") or \
                    (key == "calls" and " fusion(" in line) or \
                    (key == "to_apply" and " call(" not in line):
                inlined.add(callee)
            else:
                run_by[callee] = (computation, scope)
        for group in _BRANCHES.findall(line):
            for callee in group.split(","):
                run_by[callee.strip().lstrip("%")] = (computation, scope)

    def inherited(computation: str) -> str:
        for _ in range(len(run_by) + 1):  # the call graph has no cycle
            above = run_by.get(computation)
            if above is None:
                return ""
            computation, scope = above
            if scope:
                return scope
        return ""

    around = {c: inherited(c) for c in {row[0] for row in rows}}
    scopes = {name: scope or around[computation]
              for computation, name, scope in rows
              if computation not in inlined}
    return {"module": head.group(1) if head else "", "scopes": scopes,
            "present": present}


def lowering_scopes(text: str) -> Set[str]:
    """The catalogue scopes a lowered module's locations carry
    (``lowered.as_text(debug_info=True)``): what THIS process traced,
    whatever the compile cache then handed back."""
    present: Set[str] = set()
    for path in set(_LOC_NAME.findall(text)):
        present.update(scopes_in(path))
    return present


def _write_program(out_dir: str, record: dict,
                   scopes: Dict[str, str]) -> str:
    """``<out_dir>/programs/<module>-<k>.json``, ``k`` counting this
    module's programs in the order they were first launched (a round
    program and the check of it under another precision share a module
    name); returns the absolute path."""
    folder = os.path.join(os.path.abspath(out_dir), PROGRAMS_DIRNAME)
    os.makedirs(folder, exist_ok=True)
    k = 0
    while os.path.exists(
            path := os.path.join(folder, f"{record['module']}-{k}.json")):
        k += 1
    with open(path + ".tmp", "w", encoding="utf-8") as fh:
        json.dump({**record, "written_ts": time.time(),
                   "scopes": scopes}, fh)
    os.replace(path + ".tmp", path)
    return path


def _variants(jitted) -> Optional[int]:
    """How many programs an entry point has compiled so far: the jit's
    own cache, or the ahead-of-time wrapper's (``telemetry/xla.py``)."""
    if hasattr(jitted, "cache_len"):
        return int(jitted.cache_len)
    if hasattr(jitted, "_cache_size"):
        return int(jitted._cache_size())
    return None


def programs_before(jitted) -> Optional[int]:
    """Before an engine entry point is called: None (nothing to do, and
    nothing done) unless a tracer is attached, else the entry point's
    count of compiled programs, for :func:`program_scopes` after the
    call to tell a first launch by."""
    spans = _INSTALLED
    if spans is None or spans._tracer is None or \
            not hasattr(jitted, "lower"):
        return None
    return _variants(jitted)


def _aval(x):
    """The struct an argument of the call lowers as.  A donated array is
    deleted by now and still says its shape, dtype and sharding; an
    uncommitted one lowers with no sharding of its own (a struct that
    named one would be another program)."""
    import jax
    if not isinstance(x, jax.Array):
        return x
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if x.committed else None,
        weak_type=bool(getattr(x, "weak_type", False)))


def program_scopes(jitted, before: Optional[int], args: tuple) -> None:
    """After the call has been issued (the device is already busy with
    it): if it was this program's first launch, write its map and say
    so in one ``program_scopes`` span.  Observation: a failure is
    logged and the run goes on."""
    spans = _INSTALLED
    if before is None or spans is None:
        return
    tracer = spans._tracer
    if tracer is None or _variants(jitted) == before:
        return
    import jax
    t0 = time.time()
    try:
        lowered = jitted.lower(*jax.tree.map(_aval, tuple(args)))
        # trace, lowering and executable come from jax's in-memory
        # caches: no compile request, no second loaded program
        program = parse_program(lowered.compile().as_text())
        traced = lowering_scopes(lowered.as_text(debug_info=True))
    except Exception as exc:  # noqa: BLE001 - telemetry must not abort
        _LOGGER.warning("program_scopes: no map for %s: %s",
                        getattr(jitted, "__name__", jitted), exc,
                        exc_info=True)
        return
    missing = sorted(traced - program["present"])
    record = {
        "module": program["module"],
        "fun_name": str(getattr(jitted, "__name__",
                                getattr(jitted, "name", ""))),
        "ops": len(program["scopes"]),
        "scoped": sum(1 for s in program["scopes"].values() if s),
        "stale": bool(missing), "missing": missing,
    }
    path = _write_program(tracer.out_dir, record, program["scopes"])
    if missing:
        spans.warn_stale(record["module"], missing)
    tracer.emit_span("program_scopes", t0, time.time(), file=path, **record)
