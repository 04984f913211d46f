"""Per-dispatch overhead vs argument/result buffer count on the chip.

The faithful (fuse=1) fullrun measured ~88 ms per round DISPATCH for the
LR protocol (``.scratch/fullrun_out/lr_mnist_fuse1`` secsPerRound p50)
against a 0.14 ms trivial-op dispatch floor — suggesting the remote
runtime pays per-BUFFER, not per-call.  This probe times a no-op-ish jit
at varying output-buffer counts and input-tree sizes, with and without
donation, so the engine's stats-packing decision (one flat stats vector
vs a ~15-leaf dict) rests on a measurement.

Fence discipline: every case syncs by fetching ONE scalar from the FIRST
output leaf — a fence whose cost is constant in the buffer count, so the
case timings differ only by what the dispatch itself pays.

Writes one JSON line to stdout.
"""

from __future__ import annotations

import json
import sys
import time


def _sync(out) -> None:
    """Constant-cost fence: fetch one scalar from the first output leaf
    (block_until_ready is not a trustworthy fence on this backend)."""
    import jax
    jax.device_get(jax.tree.leaves(out)[0].ravel()[0])


def _fetch_time(fn, args, iters=30):
    _sync(fn(*args))  # compile + first run
    tic = time.perf_counter()
    for _ in range(iters):
        _sync(fn(*args))
    return (time.perf_counter() - tic) / iters


def main() -> int:
    import jax
    import jax.numpy as jnp

    assert jax.default_backend() == "tpu", jax.default_backend()
    res = {"backend": "tpu", "cases": {}}

    # output-buffer scaling: one [8,128] input, N small outputs
    x = jnp.ones((8, 128), jnp.float32)
    for n_out in (1, 4, 16, 64):
        fn = jax.jit(lambda x, n=n_out: [x[:1, :1] * (i + 1)
                                         for i in range(n)])
        res["cases"][f"outputs_{n_out}"] = round(
            1e3 * _fetch_time(fn, (x,)), 4)

    # input-tree scaling: N small inputs, one output
    for n_in in (1, 4, 16, 64):
        args = [jnp.full((8, 8), float(i)) for i in range(n_in)]
        fn = jax.jit(lambda *a: sum(x[0, 0] for x in a)[None])
        res["cases"][f"inputs_{n_in}"] = round(
            1e3 * _fetch_time(fn, args), 4)

    # host->device staging: the faithful round device_puts ~8-10 small
    # host arrays per round (masks/ids/lrs/rngs) — is each put an RPC?
    import numpy as np
    for n_put in (1, 4, 16):
        host = [np.full((8, 8), float(i), np.float32) for i in range(n_put)]
        # one put call per array (the engine's shape) vs one call on the list
        tic = time.perf_counter()
        for _ in range(30):
            staged = [jax.device_put(h) for h in host]
            _sync(staged)
        res["cases"][f"put_each_{n_put}"] = round(
            1e3 * (time.perf_counter() - tic) / 30, 4)
        tic = time.perf_counter()
        for _ in range(30):
            staged = jax.device_put(host)
            _sync(staged)
        res["cases"][f"put_tree_{n_put}"] = round(
            1e3 * (time.perf_counter() - tic) / 30, 4)

    # donation: does donating a 16-leaf tree change per-dispatch cost?
    # Identical single-leaf fence on both sides; the donated case threads
    # its output back in (the engine's own state-carry pattern).
    tree = [jnp.full((64, 64), float(i)) for i in range(16)]

    def roll(*a):
        return [t + 1.0 for t in a]

    res["cases"]["tree16_no_donate"] = round(
        1e3 * _fetch_time(jax.jit(roll), tuple(tree)), 4)
    fn_don = jax.jit(roll, donate_argnums=tuple(range(16)))
    out = fn_don(*tree)
    _sync(out)
    tic = time.perf_counter()
    iters = 30
    for _ in range(iters):
        out = fn_don(*out)
        _sync(out)
    res["cases"]["tree16_donated_threaded"] = round(
        1e3 * (time.perf_counter() - tic) / iters, 4)

    # input staging (PR 6, engine/round.py::_dispatch_staged): the faithful
    # round's REAL per-dispatch operand mix — [K,S,B,D] feature grid,
    # [K,S,B] sample mask, [K] client mask/ids, [K] chaos drop/
    # keep_steps/corrupt vectors, and the lr/round/threshold scalars —
    # staged per-leaf (the pre-PR shape the ~88 ms suspect came from) vs
    # packed one-buffer-per-dtype through the engine's own packers
    # (utils/flatpack.py AxisPacker/ScalarStager).  This is the number
    # that makes the staging win reproducible on the chip.
    import numpy as _np
    from msrflute_tpu.utils.flatpack import AxisPacker, ScalarStager
    rng = _np.random.default_rng(0)
    K, S, B, D = 10, 4, 20, 64
    axis_tree = {
        "grid": rng.normal(size=(K, S, B, D)).astype(_np.float32),
        "sample_mask": _np.ones((K, S, B), _np.float32),
        "client_mask": _np.ones((K,), _np.float32),
        "client_ids": _np.arange(K, dtype=_np.int32),
        "drop": _np.zeros((K,), _np.float32),
        "keep_steps": _np.full((K,), float(S), _np.float32),
        "corrupt": _np.zeros((K,), _np.int32),
    }
    sc_tree = {"client_lr": _np.float32(0.1),
               "server_lr": _np.float32(1.0),
               "round_idx": _np.int32(0),
               "leakage": _np.float32(_np.inf),
               "quant": _np.float32(-1.0)}
    iters = 30
    # legacy: one device_put per leaf (12 transfers)
    tic = time.perf_counter()
    for _ in range(iters):
        # flint would flag this shape in product code — it IS the probe
        staged = [jax.device_put(v) for v in axis_tree.values()]
        staged += [jax.device_put(v) for v in sc_tree.values()]
        _sync(staged)
    res["cases"]["dispatch_mix_per_leaf"] = round(
        1e3 * (time.perf_counter() - tic) / iters, 4)
    # staged: pack host-side, one put per dtype group (4 transfers)
    ax_packer = AxisPacker(axis_tree, lead_ndim=1)
    stager = ScalarStager(sc_tree)
    tic = time.perf_counter()
    for _ in range(iters):
        ax = jax.device_put(ax_packer.pack_np(axis_tree))
        sc = jax.device_put(stager.pack_np(sc_tree))
        _sync((ax, sc))
    res["cases"]["dispatch_mix_staged"] = round(
        1e3 * (time.perf_counter() - tic) / iters, 4)
    res["staging_speedup"] = round(
        res["cases"]["dispatch_mix_per_leaf"]
        / max(res["cases"]["dispatch_mix_staged"], 1e-9), 2)

    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
