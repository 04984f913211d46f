"""Validate the ringlm dense/flash "auto" policy on both crossover sides.

Reads a ``flash_crossover.json`` sweep
(``tools/flash_crossover_sweep.py``), picks the measured length just BELOW
the dense→flash crossover and the first length AT/ABOVE it, re-times both
paths at those lengths with the production tile defaults, and checks that
``models/ringlm.py::_resolve_flash("auto", L)`` — i.e. the shipped
``FLASH_AUTO_MIN_LEN`` constant — selects the measured-faster branch on
each side.  Exit 0 only if the policy is right on both sides; the JSON on
stdout carries the measurements either way.

Usage (chip job)::

    python tools/validate_flash_auto.py [flash_crossover.json]
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.timing_probe import grad_wall  # noqa: E402


def main() -> int:
    import jax
    import jax.numpy as jnp

    assert jax.default_backend() == "tpu", jax.default_backend()

    path = sys.argv[1] if len(sys.argv) > 1 else "flash_crossover.json"
    from tools.calibrate_flash import analyze
    from msrflute_tpu.models.ringlm import FLASH_AUTO_MIN_LEN, _resolve_flash
    from msrflute_tpu.ops.pallas_attention import flash_attention

    try:
        cal = analyze(path)
        if not cal["lengths"]:
            raise ValueError("sweep artifact has no length rows")
    except Exception as exc:
        # unusable sweep (empty/truncated from a timed-out job 92): rc 2
        # so the queue job can distinguish "re-arm" from "policy wrong"
        print(json.dumps({"error": f"{type(exc).__name__}: {exc}",
                          "artifact": path}))
        return 2
    lengths = sorted(cal["lengths"])
    cross = cal.get("recommended_flash_auto_min_len") or cal.get("crossover")
    below = max((L for L in lengths if L < FLASH_AUTO_MIN_LEN), default=None)
    above = min((L for L in lengths if L >= FLASH_AUTO_MIN_LEN), default=None)

    B, H, D = 4, 4, 64  # the sweep's RingLM head geometry
    rng = np.random.default_rng(0)

    def dense(q, k, v):
        L = q.shape[1]
        scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
        scores = jnp.einsum("blhd,bmhd->bhlm", q, k) * scale
        mask = jnp.tril(jnp.ones((L, L), bool))
        scores = jnp.where(mask[None, None], scores,
                           jnp.finfo(scores.dtype).min)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhlm,bmhd->blhd", p, v)

    def flash(q, k, v):
        # force_flash: this arm must TIME THE KERNEL — the dispatch gate
        # substituting dense here would validate the crossover constant
        # against dense-vs-dense timings (vacuously)
        return flash_attention(q, k, v, causal=True, force_flash=True)

    out = {"backend": "tpu", "flash_auto_min_len": FLASH_AUTO_MIN_LEN,
           "sweep_crossover": cross, "sides": {}}
    ok = True
    for side, L in (("below", below), ("above", above)):
        if L is None:
            # constant sits outside the measured range on this side —
            # nothing to validate there (e.g. flash wins everywhere)
            out["sides"][side] = None
            continue
        q, k, v = (jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.bfloat16)
                   for _ in range(3))
        dms = grad_wall(dense, q, k, v) * 1e3
        fms = grad_wall(flash, q, k, v) * 1e3
        picked_flash = _resolve_flash("auto", L)
        # near the crossover the two paths are close BY CONSTRUCTION;
        # within a 5% band either pick is correct (timing jitter must
        # not fail the run over a sign flip)
        within_band = abs(dms - fms) <= 0.05 * max(dms, fms)
        correct = within_band or picked_flash == (fms < dms)
        ok &= correct
        out["sides"][side] = {
            "length": L, "dense_fwd_bwd_ms": round(dms, 3),
            "flash_fwd_bwd_ms": round(fms, 3),
            "auto_picks": "flash" if picked_flash else "dense",
            "measured_faster": "flash" if fms < dms else "dense",
            "within_5pct_band": within_band,
            "auto_correct": correct,
        }
    out["ok"] = ok
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
