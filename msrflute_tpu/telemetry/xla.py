"""flutescope device-truth layer — compiled-program cost capture and the
recompile sentinel.

Everything flutescope reported before this module was *host-side* time:
spans, wall clocks, fetched scalars.  The compiled XLA executable knows
the other half — how many FLOPs and HBM bytes a round program actually
costs, and when a "steady-state" loop silently recompiled (forfeiting
the whole overlap win).  This module is the ONE place that knowledge is
extracted:

- :class:`XlaIntrospector` — the per-run registry.  The engine wraps
  each fused-round entry point (``round_step``, ``multi_round_r{R}``,
  ``staged_r{R}``, the payload/custom-agg programs, the eval step) in an
  :class:`_InstrumentedFn` that owns the signature->executable cache via
  the AOT path (``jitted.lower(*args).compile()``), so every compile is
  OBSERVED at the moment it happens, with ``cost_analysis()`` FLOPs +
  bytes-accessed and ``memory_analysis()`` temp/argument/output bytes
  recorded per entry point.  The AOT cache replaces jax's internal jit
  dispatch cache for the wrapped callable — same lowering, same
  executable, bit-identical outputs (pinned by the telemetry on/off
  equivalence tests) — which is exactly what makes the capture total:
  a compile cannot happen behind the sentinel's back.
- **recompile sentinel** — each call computes a cheap hashable
  structural key (C++ flatten + per-leaf shape/dtype/weak-type tuples;
  static config is baked into the entry-point name); the descriptive
  signature + per-leaf path map are built only when the key is NEW,
  i.e. at compile time.  A SECOND distinct signature for the same
  entry point is a ``recompile`` event carrying the leaf-level diff
  vs. the previous compile; the ``recompile_storm`` watchdog detector
  (telemetry/watchdog.py) counts these after warmup.
- MFU / HBM helpers — :func:`mfu` is the ONE place the
  ``flops / (secs x chip_peak_flops)`` math lives (bench.py and the
  server's live per-round MFU both call it, so the two can never
  drift); :func:`aot_cost` is the shared
  "compile this and tell me what it costs" used by the ad-hoc
  call sites the tools had grown.

Import discipline: NO jax import at module import time (the telemetry
package contract — bench.py must pick a backend first); jax is touched
lazily inside calls.  No device values are ever materialized here: cost
and memory analyses are host metadata of the executable, and the
wrapper returns the program's output arrays untouched.
"""

from __future__ import annotations

import hashlib
import json
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = [
    "XlaIntrospector", "aot_cost", "cost_analysis", "memory_analysis",
    "mfu", "operand_signature", "program_size_bytes", "signature_diff",
]


# ----------------------------------------------------------------------
# operand signatures (the recompile sentinel's identity)
# ----------------------------------------------------------------------
def _leaf_desc(leaf: Any) -> List[Any]:
    """``[shape, dtype, weak_type]`` of one operand leaf — exactly the
    structural facts jax's jit cache keys on for array arguments."""
    shape = getattr(leaf, "shape", None)
    if shape is None:
        # non-array leaf (python scalar riding the tree): its type is
        # its signature — a changed python type retraces too
        return [[], type(leaf).__name__, False]
    dtype = str(getattr(leaf, "dtype", ""))
    weak = bool(getattr(getattr(leaf, "aval", None), "weak_type", False))
    return [list(shape), dtype, weak]


def _leaf_key(leaf: Any) -> Any:
    """Hashable structural identity of one leaf — the dispatch-time
    cache key's element.  MUST distinguish exactly what
    :func:`_leaf_desc` does: the two are the fast and the descriptive
    spelling of the same identity."""
    shape = getattr(leaf, "shape", None)
    if shape is None:
        return type(leaf).__name__
    return (tuple(shape), str(getattr(leaf, "dtype", "")),
            bool(getattr(getattr(leaf, "aval", None), "weak_type", False)))


def structural_key(args: Any) -> Tuple[Any, ...]:
    """Hashable ``(treedef, per-leaf keys)`` of an operand tree — the
    per-dispatch cache key.  Built from the C++ flatten plus one tuple
    per leaf (no path strings, no json, no sha1), so the hot dispatch
    path stays cheap even for thousand-leaf param trees; the expensive
    descriptive :func:`operand_signature` runs only when this key is
    NEW (i.e. at compile time, when the diff payload is needed)."""
    from jax.tree_util import tree_flatten

    leaves, treedef = tree_flatten(args)
    return (treedef, tuple(_leaf_key(leaf) for leaf in leaves))


def operand_signature(args: Any) -> Tuple[str, Dict[str, List[Any]]]:
    """``(hash, desc)`` of an operand tree.

    ``desc`` maps each leaf's tree path to ``[shape, dtype, weak_type]``;
    ``hash`` additionally covers the treedef (a changed pytree structure
    — new dict key, dropped operand — is a retrace even when every
    surviving leaf matches).
    """
    from jax.tree_util import keystr, tree_flatten_with_path

    leaves, treedef = tree_flatten_with_path(args)
    desc = {keystr(path): _leaf_desc(leaf) for path, leaf in leaves}
    blob = json.dumps([str(treedef), desc], sort_keys=True)
    return hashlib.sha1(blob.encode("utf-8")).hexdigest()[:16], desc


def signature_diff(old: Dict[str, List[Any]],
                   new: Dict[str, List[Any]]) -> Dict[str, Any]:
    """Leaf-level difference between two operand signatures — the
    payload of a ``recompile`` event: WHICH operand changed shape/dtype,
    from what, to what."""
    changed = {path: {"was": old[path], "now": new[path]}
               for path in sorted(set(old) & set(new))
               if old[path] != new[path]}
    added = {path: new[path] for path in sorted(set(new) - set(old))}
    removed = {path: old[path] for path in sorted(set(old) - set(new))}
    out: Dict[str, Any] = {}
    if changed:
        out["changed"] = changed
    if added:
        out["added"] = added
    if removed:
        out["removed"] = removed
    return out


# ----------------------------------------------------------------------
# executable analyses (None-safe across backends)
# ----------------------------------------------------------------------
def cost_analysis(compiled: Any) -> Dict[str, float]:
    """``{"flops", "bytes_accessed"}`` of a compiled executable, or ``{}``
    when the backend cannot provide it (multihost partial executables).
    The key normalization lives HERE so bench/profiler/telemetry can
    never disagree about it."""
    try:
        cost = dict(compiled.cost_analysis() or {})
    except Exception:
        return {}
    out = {}
    if "flops" in cost:
        out["flops"] = float(cost["flops"])
    if "bytes accessed" in cost:
        out["bytes_accessed"] = float(cost["bytes accessed"])
    return out


def memory_analysis(compiled: Any) -> Dict[str, int]:
    """Temp/argument/output byte sizes of a compiled executable —
    ``temp`` is XLA's scratch high-watermark, and ``temp + argument +
    output`` is the program's resident HBM footprint (``hbm_bytes``).
    ``{}`` when unavailable."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    if mem is None:
        return {}
    out: Dict[str, int] = {}
    for field, attr in (("temp_bytes", "temp_size_in_bytes"),
                        ("argument_bytes", "argument_size_in_bytes"),
                        ("output_bytes", "output_size_in_bytes"),
                        ("generated_code_bytes",
                         "generated_code_size_in_bytes")):
        value = getattr(mem, attr, None)
        if value is not None:
            out[field] = int(value)
    if {"temp_bytes", "argument_bytes", "output_bytes"} <= set(out):
        out["hbm_bytes"] = (out["temp_bytes"] + out["argument_bytes"]
                            + out["output_bytes"])
    return out


def aot_cost(fn: Callable, *args: Any) -> Optional[Dict[str, Any]]:
    """Compile ``jit(fn)`` (or an already-jitted callable) for ``args``
    via the AOT path and return its merged cost + memory analysis, or
    None when analysis is unavailable.  The one helper behind the
    bench's ``grad_step_cost``, the profiler's cost section and the
    static reports — the ad-hoc ``.lower().compile().cost_analysis()``
    call sites they each used to carry."""
    import jax

    import time

    try:
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        tic = time.perf_counter()
        compiled = jitted.lower(*args).compile()
        secs = time.perf_counter() - tic
    except Exception:
        return None
    out: Dict[str, Any] = {}
    out.update(cost_analysis(compiled))
    out.update(memory_analysis(compiled))
    if out:
        # lower+compile wall seconds of THIS aot call (0.0 when the
        # persistent compilation cache already held the executable) —
        # the bench's per-protocol compile-cost observable
        out["compile_seconds"] = round(secs, 4)
    return out or None


def program_size_bytes(fn: Callable, *args: Any) -> Optional[int]:
    """Compiled-program SIZE proxy for one entry point at one signature:
    the executable's ``generated_code_bytes`` when the backend reports
    it (TPU), else the lowered StableHLO module's text size (CPU reports
    0 generated bytes).  Both scale with traced program TEXT — cloned
    scan bodies, unrolled epochs — not with executed FLOPs, which is
    exactly what the epoch-bloat regression guard must pin
    (tests/test_megakernel.py): the fused-epoch program at num_epochs=4
    sits in the same size class as num_epochs=1, where a scan body
    cloned per epoch would not."""
    import jax

    try:
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        lowered = jitted.lower(*args)
    except Exception:
        return None
    try:
        gen = memory_analysis(lowered.compile()).get("generated_code_bytes")
        if gen:
            return int(gen)
    except Exception:
        pass
    try:
        return len(lowered.as_text())
    except Exception:
        return None


def mfu(flops: float, secs: float,
        peak_flops: Optional[float] = None) -> Optional[float]:
    """Model FLOPs utilization: ``flops / (secs x peak)``.

    THE shared MFU formula (bench.py / the server's live per-round
    value).  ``peak_flops`` defaults to this
    process's chip via :func:`~msrflute_tpu.utils.compat.chip_peak_flops`
    — on CPU that is a documented NOMINAL peak, so CPU MFU values are
    comparable across CPU runs but never against a TPU's.  Returns None
    when any input is missing/non-positive.
    """
    if not flops or not secs or secs <= 0:
        return None
    if peak_flops is None:
        from ..utils.compat import chip_peak_flops
        _, peak_flops = chip_peak_flops()
    if not peak_flops or peak_flops <= 0:
        return None
    return float(flops) / float(secs) / float(peak_flops)


# ----------------------------------------------------------------------
# the instrumented entry point + per-run registry
# ----------------------------------------------------------------------
class _InstrumentedFn:
    """AOT-cached wrapper around one jitted entry point.

    Owns the signature -> compiled-executable mapping (so the registry
    sees every compile), dispatches through the cached executable, and
    passes outputs through untouched.  Donation, shardings and
    bit-identical math all ride the identical lowering the plain jit
    call would have used.
    """

    __slots__ = ("_registry", "name", "_jitted", "_cache", "_sig_by_key",
                 "rounds")

    def __init__(self, registry: "XlaIntrospector", name: str,
                 jitted: Callable, rounds: int = 1):
        self._registry = registry
        self.name = name
        self._jitted = jitted
        self._cache: Dict[Any, Any] = {}
        #: structural key -> the descriptive signature hash recorded at
        #: compile time (note_dispatch attributes cost to THIS variant)
        self._sig_by_key: Dict[Any, str] = {}
        #: rounds one call of this entry point executes (R for fused
        #: chunks) — the registry divides FLOPs by it for per-round MFU
        self.rounds = int(rounds)

    def __call__(self, *args: Any) -> Any:
        key = structural_key(args)
        compiled = self._cache.get(key)
        if compiled is None:
            # compile time (the cold path): the descriptive signature +
            # per-leaf desc are built HERE only — steady-state dispatch
            # pays just the tuple key above.  lower+compile wall seconds
            # ride the compile record (ISSUE 12: compile cost is a real
            # per-entry-point budget, surfaced in bench device_truth).
            import time

            sig, desc = operand_signature(args)
            tic = time.perf_counter()
            compiled = self._jitted.lower(*args).compile()
            secs = time.perf_counter() - tic
            self._cache[key] = compiled
            self._sig_by_key[key] = sig
            self._registry.record_compile(self.name, sig, desc, compiled,
                                          rounds=self.rounds,
                                          compile_seconds=secs)
        self._registry.note_dispatch(self.name, self._sig_by_key[key])
        return compiled(*args)

    def lower(self, *args: Any):
        """The wrapped jit's own lowering (in-memory after a call): what
        ``telemetry/compiles.py`` reads a program's scope map from."""
        return self._jitted.lower(*args)

    @property
    def cache_len(self) -> int:
        return len(self._cache)


class XlaIntrospector:
    """One run's compiled-entry-point registry (constructed ONLY when
    ``server_config.telemetry.xla`` enables the layer — the zero-cost
    contract pins that a telemetry-off run never builds one).

    Events are buffered in :attr:`pending_events` and drained by the
    server's host tail into the structured-event streams — compile
    observation itself performs no file IO and no device access.
    """

    def __init__(self) -> None:
        #: entry name -> record (signature, analyses, compile count)
        self.entries: Dict[str, Dict[str, Any]] = {}
        #: structured events awaiting the host tail's drain
        self.pending_events: List[Dict[str, Any]] = []
        #: all compiles / compiles beyond the first per entry point
        self.compiles = 0
        self.recompiles = 0
        #: ``{"entry", "flops", "hbm_bytes", "rounds"}`` of the most
        #: recent round-program dispatch (the server snapshots this per
        #: chunk for the live MFU computation)
        self.last_dispatch: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------------
    def wrap(self, name: str, jitted: Callable,
             rounds: int = 1) -> _InstrumentedFn:
        """Wrap one jitted entry point for observed AOT dispatch."""
        return _InstrumentedFn(self, name, jitted, rounds=rounds)

    # ------------------------------------------------------------------
    def record_compile(self, name: str, sig: str,
                       desc: Dict[str, List[Any]], compiled: Any,
                       rounds: int = 1,
                       compile_seconds: Optional[float] = None
                       ) -> Dict[str, Any]:
        """Register one observed compile; returns the entry record.
        First compile of an entry point is an ``xla_compile`` event
        (expected warmup); any later one is a ``recompile`` event
        carrying the operand diff — the sentinel's finding.
        ``compile_seconds`` (lower+compile wall time, when the caller
        measured it) accumulates per entry point across variants."""
        analysis: Dict[str, Any] = {}
        analysis.update(cost_analysis(compiled))
        analysis.update(memory_analysis(compiled))
        entry = self.entries.get(name)
        is_recompile = entry is not None
        event: Dict[str, Any] = {
            "kind": "recompile" if is_recompile else "xla_compile",
            "entry": name, "signature": sig, "rounds": int(rounds),
        }
        event.update(analysis)
        if compile_seconds is not None:
            event["compile_seconds"] = round(float(compile_seconds), 4)
        if is_recompile:
            self.recompiles += 1
            event["compile_index"] = entry["compiles"]
            event["diff"] = signature_diff(entry["desc"], desc)
            entry["compiles"] += 1
            entry["signature"] = sig
            entry["desc"] = desc
            entry.update(analysis)
            if compile_seconds is not None:
                entry["compile_seconds"] = round(
                    entry.get("compile_seconds", 0.0)
                    + float(compile_seconds), 4)
        else:
            entry = {"compiles": 1, "signature": sig, "desc": desc,
                     "rounds": int(rounds), "variants": {}}
            entry.update(analysis)
            if compile_seconds is not None:
                entry["compile_seconds"] = round(float(compile_seconds), 4)
            self.entries[name] = entry
        # per-variant analysis: when several compiled variants of one
        # entry point coexist (bucket churn — the case the sentinel
        # observes), dispatch attribution must come from the variant
        # actually dispatched, not whichever compiled last
        entry.setdefault("variants", {})[sig] = analysis
        self.compiles += 1
        self.pending_events.append(event)
        return entry

    def note_dispatch(self, name: str, sig: Optional[str] = None) -> None:
        """Mark ``name`` as the most recently dispatched entry point
        (round-program entries feed the live MFU; others are ignored by
        the server's snapshot).  ``sig`` selects the compiled VARIANT
        whose analysis is attributed — with several coexisting variants
        (bucket churn) the live MFU/HBM must describe the program that
        actually ran this chunk."""
        entry = self.entries.get(name)
        if entry is None:
            return
        analysis = entry.get("variants", {}).get(sig, entry)
        self.last_dispatch = {
            "entry": name,
            "rounds": int(entry.get("rounds", 1)),
            "flops": analysis.get("flops"),
            "bytes_accessed": analysis.get("bytes_accessed"),
            "hbm_bytes": analysis.get("hbm_bytes"),
        }

    # ------------------------------------------------------------------
    def drain_events(self) -> List[Dict[str, Any]]:
        """Hand the buffered compile/recompile events to the caller
        (the server's host tail, which owns emitting them)."""
        out, self.pending_events = self.pending_events, []
        return out

    def summary(self) -> Dict[str, Any]:
        """Per-entry-point table for the scorecard: FLOPs, bytes, HBM
        footprint, compile count — signatures/descs elided (they live
        in the event stream)."""
        out: Dict[str, Any] = {}
        for name, entry in sorted(self.entries.items()):
            out[name] = {k: entry[k] for k in
                         ("compiles", "rounds", "flops", "bytes_accessed",
                          "temp_bytes", "argument_bytes", "output_bytes",
                          "hbm_bytes", "compile_seconds") if k in entry}
        return out

    def snapshot(self) -> Dict[str, Any]:
        """The cumulative device-truth gauges one rollup window carries
        (ISSUE 13): compile/recompile counters + the HBM high-watermark.
        Host metadata only — reading it never touches a device."""
        return {"compiles": int(self.compiles),
                "recompiles": int(self.recompiles),
                "hbm_peak_bytes": self.hbm_peak_bytes()}

    def hbm_peak_bytes(self) -> Optional[int]:
        """High-watermark resident HBM footprint across every compiled
        entry point (the biggest single program the run dispatched)."""
        peaks = [entry["hbm_bytes"] for entry in self.entries.values()
                 if "hbm_bytes" in entry]
        return max(peaks) if peaks else None
