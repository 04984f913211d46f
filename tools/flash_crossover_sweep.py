"""On-chip flash-vs-dense attention crossover sweep.

The committed longctx bench (`bench_tpu_longctx.json`) showed the Pallas
flash kernel SLOWER than XLA's dense softmax attention at L=2048
(flash_speedup 0.83-0.93): at that length the score matrix is small
enough that XLA's fused dense path is excellent.  Flash's O(L) memory is
the long-L story.  This tool measures, per sequence length and per
(block_q, block_k) tile choice, fwd+bwd wall time of both paths on the
bench's RingLM head geometry — the empirical basis for (a) the kernel's
default tiles and (b) the dense/flash auto-select crossover in
``models/ringlm.py``.

Writes one JSON object to stdout; stderr carries progress.  TPU-only by
assertion (a CPU "measurement" of interpret-mode kernels means nothing).
"""

from __future__ import annotations

import functools
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from tools.timing_probe import grad_wall as _grad_wall  # noqa: E402


def main() -> int:
    import jax
    import jax.numpy as jnp

    assert jax.default_backend() == "tpu", jax.default_backend()
    from msrflute_tpu.ops.pallas_attention import flash_attention
    from msrflute_tpu.utils.backend import enable_compilation_cache
    enable_compilation_cache()

    B, H, D = 4, 4, 64  # the longctx bench's RingLM head geometry
    rng = np.random.default_rng(0)
    res = {"backend": "tpu", "geometry": {"batch": B, "heads": H,
                                          "head_dim": D,
                                          "layout": "[B, L, H, D]",
                                          "dtype": "bfloat16"},
           "lengths": {}}

    def dense(q, k, v):
        # VERBATIM the ringlm local path (models/ringlm.py::_MHA else
        # branch) on [B, L, H, D] — same einsums, finfo-min mask, and the
        # bench's bf16 compute dtype (the TPU longctx protocol sets
        # dtype=bfloat16, so bf16 scores ARE the production dense path)
        L = q.shape[1]
        scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
        scores = jnp.einsum("blhd,bmhd->bhlm", q, k) * scale
        mask = jnp.tril(jnp.ones((L, L), bool))
        scores = jnp.where(mask[None, None], scores,
                           jnp.finfo(scores.dtype).min)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhlm,bmhd->blhd", p, v)

    grad_wall = _grad_wall

    for L in (1024, 2048, 4096, 8192, 16384):
        # flash_attention takes [B, L, H, D] (pallas_attention.py:427)
        q, k, v = (jnp.asarray(rng.normal(size=(B, L, H, D)),
                               jnp.bfloat16) for _ in range(3))
        row = {}
        if L <= 8192:  # dense bhlm scores at 16k: 4*4*16384^2 bf16 = 8.6 GB
            try:
                row["dense_fwd_bwd_ms"] = 1e3 * grad_wall(dense, q, k, v)
            except Exception as e:  # OOM is data, not failure
                row["dense_fwd_bwd_ms"] = None
                row["dense_error"] = type(e).__name__
        else:
            row["dense_fwd_bwd_ms"] = None
            row["dense_error"] = "skipped (score matrix ~8.6 GB bf16)"
        for bq, bk in ((128, 128), (128, 256), (256, 256), (128, 512),
                       (256, 512), (512, 512)):
            if bq > L or bk > L:
                continue
            fa = functools.partial(flash_attention, causal=True,
                                   block_q=bq, block_k=bk,
                                   force_flash=True)
            try:
                row[f"flash_{bq}x{bk}_fwd_bwd_ms"] = \
                    1e3 * grad_wall(fa, q, k, v)
            except Exception as e:
                row[f"flash_{bq}x{bk}_fwd_bwd_ms"] = None
                row[f"flash_{bq}x{bk}_error"] = repr(e)[:200]
        best = min((v for k2, v in row.items()
                    if k2.startswith("flash") and isinstance(v, float)),
                   default=None)
        if best and row.get("dense_fwd_bwd_ms"):
            row["flash_speedup_best"] = round(
                row["dense_fwd_bwd_ms"] / best, 3)
        res["lengths"][str(L)] = {k2: (round(v, 3)
                                       if isinstance(v, float) else v)
                                  for k2, v in row.items()}
        print(f"[flash_sweep] L={L}: {res['lengths'][str(L)]}",
              file=sys.stderr)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
