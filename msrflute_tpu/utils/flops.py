"""Static per-op-type FLOP decomposition of a jitted function.

Chip-independent profiling support (SURVEY §5.1): XLA's
``compiled.cost_analysis()`` reports one aggregate FLOP number, which
says nothing about WHERE the FLOPs are.  This walks the function's
jaxpr — recursing through pjit/custom-vjp sub-jaxprs and multiplying
through ``scan`` trip counts — and buckets exact FLOP counts by op
class:

- ``dot``: ``dot_general`` (2·batch·M·N·K from the dimension numbers)
- ``conv``: ``conv_general_dilated``
  (2·|out|·in_ch_per_group·prod(kernel_spatial))
- ``elementwise``: unary/binary/ternary VPU ops, |out| each
- ``other``: everything else with an array output, |out| each
  (gather/scatter/reduce bookkeeping — not MXU work)

``cond`` branches are counted optimistically (max over branches) and
``while`` bodies cannot be counted statically (trip count unknown) —
both are surfaced in the result so a consumer knows when the counts are
approximate.  Used by ``tools/static_flops_report.py`` to show the
headline round is MXU-bound (conv+dot share) without needing the chip.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

import jax
from jax.extend import core as jax_core

_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "pow", "max", "min", "rem",
    "neg", "abs", "sign", "floor", "ceil", "round",
    "exp", "log", "log1p", "expm1", "tanh", "logistic", "erf",
    "sqrt", "rsqrt", "cbrt", "sin", "cos", "tan",
    "integer_pow", "select_n", "clamp", "nextafter",
    "and", "or", "xor", "not",
    "eq", "ne", "lt", "le", "gt", "ge", "is_finite",
})

#: reduction primitives: roughly one op per INPUT element
_REDUCTIONS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin",
    "cumsum", "cumprod", "cummax", "cummin",
})


def _size(aval) -> float:
    shape = getattr(aval, "shape", ())
    return float(np.prod(shape)) if shape else 1.0


def _dot_flops(eqn) -> float:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    batch = float(np.prod([lhs.shape[i] for i in lb])) if lb else 1.0
    k = float(np.prod([lhs.shape[i] for i in lc])) if lc else 1.0
    m = float(np.prod([lhs.shape[i] for i in range(len(lhs.shape))
                       if i not in set(lc) | set(lb)]))
    n = float(np.prod([rhs.shape[i] for i in range(len(rhs.shape))
                       if i not in set(rc) | set(_rb)]))
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval  # kernel
    dn = eqn.params["dimension_numbers"]
    out_ch = float(rhs.shape[dn.rhs_spec[0]])
    kernel_elems = float(np.prod(rhs.shape))
    # per output element: one MAC per (in_ch/groups x kernel_spatial) tap
    return 2.0 * _size(out) * kernel_elems / max(out_ch, 1.0)


def _sub_jaxprs(value):
    if isinstance(value, jax_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def flops_by_op(fn, *args, **kwargs) -> Dict[str, Any]:
    """Trace ``fn(*args, **kwargs)`` and return FLOPs bucketed by op class
    plus ``total`` and share fractions.  Exact for dot/conv/elementwise
    under scans; ``approximate`` is True when cond/while made the count a
    bound rather than an identity."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    flags = {"approximate": False}

    def visit(jaxpr, mult: float, buckets) -> float:
        """Accumulate into ``buckets``; returns the subtree total (always
        equal to the sum of what this call added to ``buckets``, so
        shares stay consistent even through cond's max-branch rule)."""
        total = 0.0
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "scan":
                body = eqn.params["jaxpr"]
                total += visit(body.jaxpr,
                               mult * float(eqn.params["length"]), buckets)
                continue
            if prim == "cond":
                # count only the most expensive branch, in buckets AND in
                # total — each branch tallies into its own scratch dict
                # and only the max branch's is merged, or the shares'
                # denominator would drift from the bucket sum
                flags["approximate"] = True
                best_total, best_buckets = 0.0, None
                for b in eqn.params["branches"]:
                    scratch = {k: 0.0 for k in buckets}
                    t = visit(b.jaxpr, mult, scratch)
                    if best_buckets is None or t > best_total:
                        best_total, best_buckets = t, scratch
                for k, v in (best_buckets or {}).items():
                    buckets[k] += v
                total += best_total
                continue
            if prim == "while":
                flags["approximate"] = True  # trip count is dynamic
                for key in ("body_jaxpr", "cond_jaxpr"):
                    for sub in _sub_jaxprs(eqn.params.get(key)):
                        total += visit(sub, mult, buckets)
                continue
            sub_found = False
            for value in eqn.params.values():
                for sub in _sub_jaxprs(value):
                    total += visit(sub, mult, buckets)
                    sub_found = True
            if sub_found:
                continue  # pjit/remat/custom_vjp wrapper: body counted
            if prim == "dot_general":
                f = _dot_flops(eqn) * mult
                buckets["dot"] += f
            elif prim == "conv_general_dilated":
                f = _conv_flops(eqn) * mult
                buckets["conv"] += f
            elif prim in _ELEMENTWISE:
                f = _size(eqn.outvars[0].aval) * mult
                buckets["elementwise"] += f
            elif prim in _REDUCTIONS:
                f = _size(eqn.invars[0].aval) * mult
                buckets["other"] += f
            elif eqn.outvars and getattr(eqn.outvars[0].aval, "shape", None) \
                    is not None:
                # data movement (gather, transpose, pad, ...): count |out|
                # into "other" so the share denominators stay honest
                f = _size(eqn.outvars[0].aval) * mult
                buckets["other"] += f
            else:
                f = 0.0
            total += f
        return total

    buckets = {"dot": 0.0, "conv": 0.0, "elementwise": 0.0, "other": 0.0}
    total = visit(closed.jaxpr, 1.0, buckets)
    out: Dict[str, Any] = dict(buckets)
    out["total"] = total
    out["approximate"] = flags["approximate"]
    mxu = buckets["dot"] + buckets["conv"]
    out["mxu_share"] = round(mxu / total, 4) if total else 0.0
    for k in ("dot", "conv", "elementwise", "other"):
        out[f"{k}_share"] = round(buckets[k] / total, 4) if total else 0.0
    return out
