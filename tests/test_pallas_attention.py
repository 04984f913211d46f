"""Flash-attention kernel parity vs dense softmax attention.

Runs the REAL kernel code path in Pallas interpret mode on CPU (same
kernels the TPU compiles); checks forward and all three input gradients,
causal and full, including shapes that exercise the padding/masking path
(L not a block multiple, D < 128) and bf16 inputs.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from msrflute_tpu.ops.pallas_attention import flash_attention


def dense_attention(q, k, v, causal):
    D = q.shape[-1]
    s = jnp.einsum("blhd,bmhd->bhlm", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(D)
    if causal:
        Lq, Lk = q.shape[1], k.shape[1]
        mask = jnp.arange(Lq)[:, None] >= jnp.arange(Lk)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhlm,bmhd->blhd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [
    (2, 64, 2, 32),    # block-aligned after D padding
    (1, 50, 3, 24),    # L and D both need padding
])
def test_forward_matches_dense(causal, shape):
    B, L, H, D = shape
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32)
               for _ in range(3))
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    want = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_dense(causal):
    B, L, H, D = 1, 40, 2, 16   # exercises padding in both L and D
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
               for _ in range(3))

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=16,
                              block_k=16, interpret=True)
        return jnp.sum(jnp.sin(out))  # non-trivial cotangent

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(dense_attention(q, k, v, causal)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5,
                                   err_msg=f"d{name} mismatch")


def test_bf16_inputs():
    B, L, H, D = 1, 32, 2, 32
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.bfloat16)
               for _ in range(3))
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    assert got.dtype == jnp.bfloat16
    want = dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2, rtol=3e-2)


def test_cross_attention_lengths():
    """Lq != Lk (non-causal cross attention) works and matches."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 24, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 56, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 56, 2, 16)), jnp.float32)
    got = flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    want = dense_attention(q, k, v, False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_shape_validation():
    x = jnp.zeros((2, 8, 2, 4))
    with pytest.raises(ValueError):
        flash_attention(jnp.zeros((8, 4)), x, x)
    with pytest.raises(ValueError):
        flash_attention(x, x, jnp.zeros((2, 8, 2, 5)))


def test_flash_lse_cotangent_kernel():
    """Kernel-path lse + a NONZERO lse cotangent vs the dense reference.

    The off-TPU default of :func:`flash_attention_lse` is the dense
    reference, so this is the one test that still drives the kernel
    backward's glse plumbing (``_dq_kernel``/``_dkv_kernel``) with
    ``interpret=True`` — with global-position offsets and Lq != Lk, the
    exact configuration ring attention runs on TPU."""
    from msrflute_tpu.ops.pallas_attention import (_dense_lse,
                                                   flash_attention_lse)
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 24, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 40, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 40, 2, 16)), jnp.float32)
    # q global positions start past the k chunk: every row sees some keys
    q_off, k_off = 40, 8

    def obj_kernel(q, k, v):
        out, lse = flash_attention_lse(q, k, v, causal=True,
                                       q_offset=q_off, k_offset=k_off,
                                       block_q=16, block_k=16,
                                       interpret=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def obj_dense(q, k, v):
        out, lse = _dense_lse(q, k, v, q_off, k_off, True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    np.testing.assert_allclose(float(obj_kernel(q, k, v)),
                               float(obj_dense(q, k, v)), rtol=1e-5)
    gk = jax.grad(obj_kernel, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(obj_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5,
                                   err_msg=f"d{name} (lse cotangent)")


# ======================================================================
# retiled stat streams (PR 12): the lse path at full (8, 128) tiles
# ======================================================================
def test_retiled_stat_lanes_are_full_tiles():
    """The PR-2 8-lane lse/delta/glse stat blocks are gone: the streams
    ride full 128-lane tiles (the pallas-shape rule now passes this
    module with ZERO suppressions — tests/test_flint_clean.py gates the
    tree)."""
    from msrflute_tpu.ops.pallas_attention import _LANES, _STAT_LANES
    assert _STAT_LANES == _LANES == 128


def test_lse_values_match_dense_after_retile():
    """flash_attention_lse's per-row logsumexp (the retiled stream's
    payload) matches the dense reference exactly-enough, including
    padded rows pinned at the -1e30 identity."""
    from msrflute_tpu.ops.pallas_attention import (_dense_lse,
                                                   flash_attention_lse)
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(2, 40, 2, 24)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 56, 2, 24)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 56, 2, 24)), jnp.float32)
    out_k, lse_k = flash_attention_lse(q, k, v, causal=True, block_q=16,
                                       block_k=16, interpret=True)
    out_d, lse_d = _dense_lse(q, k, v, 0, 0, True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse_k), np.asarray(lse_d),
                               atol=2e-5, rtol=2e-5)


# ======================================================================
# AOT-cost dispatch gate (PR 12): no silent-regression path
# ======================================================================
def _fake_probe(dense, flash_of):
    def probe(B, Lq, Lk, H, D, dtype, causal, candidates):
        return dense, {c: flash_of(c) for c in candidates}
    return probe


def test_gate_falls_back_to_dense_and_records_event():
    from msrflute_tpu.ops import pallas_attention as pa
    pa.reset_attention_plans()
    try:
        plan = pa.plan_attention(
            2, 2048, 2048, 8, 64, jnp.float32, True,
            cost_probe=_fake_probe(
                {"flops": 1e9, "bytes_accessed": 1e6},
                lambda c: {"flops": 5e9, "bytes_accessed": 5e6}))
        assert plan["impl"] == "dense"
        assert plan["dense_secs_est"] < plan["flash_secs_est"]
        events = pa.drain_attention_events()
        assert len(events) == 1
        ev = events[0]
        assert ev["kind"] == "attention_fallback_dense"
        assert ev["seq_q"] == 2048 and ev["causal"] is True
        # drained means drained; and the cached plan does not re-emit
        assert pa.drain_attention_events() == []
        again = pa.plan_attention(2, 2048, 2048, 8, 64, jnp.float32, True)
        assert again is plan and pa.drain_attention_events() == []
    finally:
        pa.reset_attention_plans()


def test_gate_picks_cheapest_flash_blocks_when_kernel_wins():
    from msrflute_tpu.ops import pallas_attention as pa
    pa.reset_attention_plans()
    try:
        def flash_cost(c):
            # (256, 256) is the planted winner
            penalty = 0.0 if c == (256, 256) else 1e9
            return {"flops": 1e9 + penalty, "bytes_accessed": 1e6}
        plan = pa.plan_attention(
            2, 2048, 2048, 8, 64, jnp.float32, False,
            cost_probe=_fake_probe(
                {"flops": 9e9, "bytes_accessed": 9e6}, flash_cost))
        assert plan["impl"] == "flash"
        assert (plan["block_q"], plan["block_k"]) == (256, 256)
        assert pa.drain_attention_events() == []
    finally:
        pa.reset_attention_plans()


def test_gate_prices_explicit_blocks_first():
    from msrflute_tpu.ops import pallas_attention as pa
    pa.reset_attention_plans()
    try:
        seen = []
        def probe(B, Lq, Lk, H, D, dtype, causal, candidates):
            seen.extend(candidates)
            return ({"flops": 9e9, "bytes_accessed": 1e6},
                    {c: {"flops": 1e9, "bytes_accessed": 1e6}
                     for c in candidates})
        plan = pa.plan_attention(1, 512, 512, 2, 64, jnp.float32, True,
                                 block_q=64, block_k=64, cost_probe=probe)
        assert seen[0] == (64, 64)
        # equal scores: sorted() keeps the cheapest-first winner stable
        assert plan["impl"] == "flash"
    finally:
        pa.reset_attention_plans()


def test_gate_real_probe_runs_on_cpu():
    """The real AOT prober end-to-end on a tiny shape (interpret-mode
    kernel + dense reference through telemetry.xla.aot_cost): whatever
    impl wins, the plan is complete and cached."""
    from msrflute_tpu.ops import pallas_attention as pa
    pa.reset_attention_plans()
    try:
        plan = pa.plan_attention(1, 64, 64, 2, 32, jnp.float32, True,
                                 block_q=32, block_k=32)
        assert plan["impl"] in ("flash", "dense")
        assert plan["block_q"] > 0 and plan["block_k"] > 0
        assert plan["flash_secs_est"] is not None
    finally:
        pa.reset_attention_plans()


def test_gate_tied_scores_honor_pinned_blocks():
    """cost_analysis often cannot see intra-kernel tiling, so candidate
    scores tie — a caller-pinned tiling must win the tie, not whichever
    tuple sorts first."""
    from msrflute_tpu.ops import pallas_attention as pa
    pa.reset_attention_plans()
    try:
        plan = pa.plan_attention(
            1, 2048, 2048, 4, 64, jnp.float32, True,
            block_q=512, block_k=512,
            cost_probe=_fake_probe(
                {"flops": 9e9, "bytes_accessed": 9e6},
                lambda c: {"flops": 1e9, "bytes_accessed": 1e6}))
        assert plan["impl"] == "flash"
        assert (plan["block_q"], plan["block_k"]) == (512, 512)
    finally:
        pa.reset_attention_plans()


def test_gate_treats_missing_flash_costs_as_probe_failure():
    """A backend whose cost_analysis omits the kernel programs (inf
    score) while pricing dense finitely must NOT fall back to dense —
    a telemetry gap is not a measured loss (the O(L^2) surprise the
    policy forbids)."""
    from msrflute_tpu.ops import pallas_attention as pa
    pa.reset_attention_plans()
    try:
        plan = pa.plan_attention(
            1, 2048, 2048, 4, 64, jnp.float32, True,
            block_q=256, block_k=256,
            cost_probe=_fake_probe({"flops": 1e9, "bytes_accessed": 1e6},
                                   lambda c: {}))
        assert plan["impl"] == "flash"
        assert (plan["block_q"], plan["block_k"]) == (256, 256)
        assert pa.drain_attention_events() == []
    finally:
        pa.reset_attention_plans()


# ======================================================================
# PR 37: a value width of its own, grouped key-value heads, the
# context's precision — the token models' path, held to the plain
# statement ``models/token_blocks._attention_rows`` over the whole row
# ======================================================================
#: name -> (L, key-value heads, group, D, Dv, q_offset, k_offset)
GEOMETRIES = {
    # Kanana-2's latent attention: 192-wide keys, 128-wide values
    "mla_d192_dv128_g1": (300, 2, 1, 192, 128, 0, 0),
    # LFM2's grouped queries: four query heads a key-value head
    "gqa_d64_g4": (300, 2, 4, 64, 64, 0, 0),
    # the kernel's own callers (ring attention): one width, one head a
    # key head, global offsets and an lse cotangent
    "own_offsets_lse": (200, 2, 1, 32, 32, 72, 8),
}


@functools.lru_cache(maxsize=None)
def _kernel_and_plain(geometry, precision):
    """(forward, dq, dk, dv) through the kernels (interpreted, tiles of
    128: three blocks, the last padded) and through the plain rows."""
    import contextlib
    from msrflute_tpu.models import token_blocks
    from msrflute_tpu.ops import pallas_attention as pa
    L, KV, G, D, Dv, q_off, k_off = GEOMETRIES[geometry]
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(1, L, KV, G, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, L, KV, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, L, KV, Dv)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(1, L, KV, G, Dv)), jnp.float32)
    own = geometry.startswith("own")

    def plain(q, k, v):
        out = token_blocks._attention_rows(q, k, v, q_off - k_off)
        if not own:
            return out, jnp.sum(out * w)
        # the row's logsumexp, written out: what the kernel also returns
        s = jnp.einsum("brkgd,bmkd->bkgrm", q, k) * D ** -0.5
        seen = (jnp.arange(L)[None, :] + k_off <=
                jnp.arange(L)[:, None] + q_off)
        lse = jax.nn.logsumexp(jnp.where(seen, s, -jnp.inf), axis=-1)
        return out, jnp.sum(out * w) + jnp.sum(jnp.sin(lse))

    def kernel(q, k, v):
        if own:
            out, lse = pa.flash_attention_lse(
                q[:, :, :, 0], k, v, causal=True, q_offset=q_off,
                k_offset=k_off, block_q=128, block_k=128, interpret=True)
            out = out[:, :, :, None]
            return out, jnp.sum(out * w) + jnp.sum(jnp.sin(lse))
        out = token_blocks.causal_attention(q, k, v, 128, interpret=True)
        return out, jnp.sum(out * w)

    results = []
    old = pa._CAUSAL_BLOCK
    pa._CAUSAL_BLOCK = 128
    try:
        with (jax.default_matmul_precision(precision) if precision
              else contextlib.nullcontext()):
            for fn in (kernel, plain):
                out = fn(q, k, v)[0]
                grads = jax.grad(lambda *a: fn(*a)[1], (0, 1, 2))(q, k, v)
                results.append((out,) + tuple(grads))
    finally:
        pa._CAUSAL_BLOCK = old
    return results


@pytest.mark.parametrize("quantity", ["forward", "dq", "dk", "dv"])
@pytest.mark.parametrize("precision", [None, "highest"],
                         ids=["default", "highest"])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_kernels_match_the_plain_rows(geometry, precision, quantity):
    """Forward and the three gradients, causal, ``L`` no multiple of the
    block.  Under ``highest`` the operands are float32 and the products
    full: float32 rounding.  Under the default the token models' path
    hands the MXU bfloat16 operands (one pass, float32 accumulation:
    what the chip's default does to the plain einsums), which the CPU's
    plain path does not: bfloat16's rounding, 2^-8 an operand."""
    got, want = (r[["forward", "dq", "dk", "dv"].index(quantity)]
                 for r in _kernel_and_plain(geometry, precision))
    assert got.shape == want.shape
    exact = precision == "highest" or geometry.startswith("own")
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < \
        (3e-5 if exact else 3e-2) * scale
    if not exact:
        # and it IS the lower precision it says: not float32's result
        assert float(jnp.max(jnp.abs(got - want))) > 1e-5 * scale
