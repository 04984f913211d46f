"""The one timing source of truth for bench/tools phase timers.

Before flutescope, wall-clock timing lived in ad-hoc probes:
``bench.py``'s inline ``tic = time.time()`` pairs and
``tools/timing_probe.py``'s scalar-fetch fence.  They now all sit on the
primitives here, so the methodology (perf_counter clock; scalar-fetch
sync fence on remote backends) cannot drift between the harnesses that
compare numbers.  Bench JSON field names are unchanged — only the
stopwatch behind them moved.

No jax at module import time (bench.py must select a backend before
anything imports jax); :func:`grad_wall` imports it lazily.
"""

from __future__ import annotations

import time
from typing import Any


class Stopwatch:
    """``with Stopwatch() as sw: ... ; sw.secs`` — one timed region on
    the perf_counter clock (the same clock the span tracer runs on).
    In-process server phases that belong in trace.json go through the
    tracer's own ``span()`` API; this is the bare harness-side timer."""

    def __init__(self):
        self.secs = 0.0
        self._t0 = 0.0

    def __enter__(self) -> "Stopwatch":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.secs = time.perf_counter() - self._t0


def scalar_time(fn, *args: Any, iters: int = 20) -> float:
    """Mean wall seconds per call of ``fn`` (which must return a SCALAR),
    fetching the value to host each iteration as the sync fence.

    A host ``float()`` of a scalar result is a fence no backend can
    return from early: the 4-byte transfer completes only after the
    producing program does.  Cost: one dispatch floor per iteration,
    paid identically on both sides of any comparison built on this."""
    float(fn(*args))  # compile + first run
    tic = time.perf_counter()
    for _ in range(iters):
        float(fn(*args))
    return (time.perf_counter() - tic) / iters


def grad_wall(attn_fn, q, k, v, iters: int = 20) -> float:
    """Fwd+bwd wall time of ``sum(attn_fn(q,k,v)**2)`` w.r.t. all three
    inputs.  The jitted probe returns full-reduction sums of every grad —
    a scalar for :func:`scalar_time`'s fence that also keeps XLA from
    dead-code-eliminating any part of the backward pass."""
    import jax
    import jax.numpy as jnp

    def loss(q, k, v):
        return jnp.sum(attn_fn(q, k, v) ** 2)

    def probe(q, k, v):
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return (jnp.sum(dq.astype(jnp.float32)) +
                jnp.sum(dk.astype(jnp.float32)) +
                jnp.sum(dv.astype(jnp.float32)))

    return scalar_time(jax.jit(probe), q, k, v, iters=iters)
