"""The benchmark's own count of required operations (the yardstick for
``train_mfu``): 2 x multiply-accumulates of every ``dot_general`` and
``conv_general_dilated`` in a function's jaxpr, through ``scan`` trip
counts and nested jaxprs.  Copied in substance from
``msrflute_tpu/utils/flops.py::flops_by_op`` (dot and conv only) so that
a later change to the program cannot move the yardstick; it is applied
to the plain reference model, so nothing the program recomputes counts.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.extend import core as jax_core


def _dot_flops(eqn) -> float:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = float(np.prod([lhs.shape[i] for i in lb])) if lb else 1.0
    k = float(np.prod([lhs.shape[i] for i in lc])) if lc else 1.0
    m = float(np.prod([lhs.shape[i] for i in range(len(lhs.shape))
                       if i not in set(lc) | set(lb)]))
    n = float(np.prod([rhs.shape[i] for i in range(len(rhs.shape))
                       if i not in set(rc) | set(rb)]))
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    out, rhs = eqn.outvars[0].aval, eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    out_ch = float(rhs.shape[dn.rhs_spec[0]])
    # per output element one multiply-accumulate per kernel tap of one
    # output channel (in_ch/groups x kernel spatial)
    return 2.0 * float(np.prod(out.shape)) * float(np.prod(rhs.shape)) / \
        max(out_ch, 1.0)


def _sub_jaxprs(value):
    if isinstance(value, jax_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def matmul_flops(fn, *args) -> float:
    """Dot + convolution FLOPs of ``fn(*args)``.  ``cond`` and ``while``
    are refused: their trip counts are not static."""
    def visit(jaxpr, mult: float) -> float:
        total = 0.0
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in ("cond", "while"):
                raise ValueError(f"cannot count operations under {prim!r}")
            inner = mult * float(eqn.params["length"]) \
                if prim == "scan" else mult
            for value in eqn.params.values():
                for sub in _sub_jaxprs(value):
                    total += visit(sub, inner)
            if prim == "dot_general":
                total += _dot_flops(eqn) * mult
            elif prim == "conv_general_dilated":
                total += _conv_flops(eqn) * mult
        return total

    return visit(jax.make_jaxpr(fn)(*args).jaxpr, 1.0)
