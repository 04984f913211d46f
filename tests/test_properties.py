"""Property-based tests (hypothesis) for the data/ops invariants the whole
engine rests on — the masked-padding algebra must hold for ARBITRARY
shapes/values, not just the fixtures."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

SETTINGS = dict(max_examples=25, deadline=None)


def _sort_rows(a: np.ndarray) -> np.ndarray:
    """Lexicographic ROW sort (np.sort(axis=0) would sort columns
    independently and miss cross-feature scrambles)."""
    return a[np.lexsort(a.T[::-1])]


@st.composite
def _federated_shapes(draw):
    n_users = draw(st.integers(2, 6))
    dim = draw(st.integers(1, 5))
    counts = [draw(st.integers(1, 17)) for _ in range(n_users)]
    batch = draw(st.integers(1, 6))
    return n_users, dim, counts, batch


@given(_federated_shapes(), st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_pack_round_batches_masked_padding_algebra(shapes, seed):
    """Every real sample appears exactly once; the mask counts exactly the
    real samples; all padding rows are zero; client bookkeeping matches."""
    from msrflute_tpu.data import ArraysDataset
    from msrflute_tpu.data.batching import pack_round_batches, steps_for

    n_users, dim, counts, batch = shapes
    rng = np.random.default_rng(seed)
    per_user = [{"x": rng.normal(size=(n, dim)).astype(np.float32) + 1.0}
                for n in counts]  # +1: no accidental zero rows
    ds = ArraysDataset([f"u{i}" for i in range(n_users)], per_user)
    S = steps_for(max(counts), batch)
    rb = pack_round_batches(ds, list(range(n_users)), batch, S,
                            rng=np.random.default_rng(seed + 1))
    for j, n in enumerate(counts):
        flat = rb.arrays["x"][j].reshape(S * batch, dim)
        mask = rb.sample_mask[j].reshape(-1)
        assert mask.sum() == n == rb.num_samples[j]
        real = flat[mask > 0]
        # the real ROWS are a permutation of the source rows
        np.testing.assert_allclose(_sort_rows(real),
                                   _sort_rows(per_user[j]["x"]), rtol=1e-6)
        assert not flat[mask == 0].any()  # padding rows all-zero
        assert rb.client_mask[j] == 1.0

    # truncation path: a cap below some client sizes must bound the mask
    # and keep every surviving row a genuine source row
    cap = max(1, min(counts))
    rb2 = pack_round_batches(ds, list(range(n_users)), batch, S,
                             rng=np.random.default_rng(seed + 2),
                             desired_max_samples=cap)
    # batch-granular cap: the crossing batch trains in full (reference
    # core/trainer.py:363-364), bounded by S*B and the client's rows
    eff_cap = min(-(-cap // batch) * batch, S * batch)
    for j, n in enumerate(counts):
        t = min(n, eff_cap)
        mask = rb2.sample_mask[j].reshape(-1)
        assert mask.sum() == t == rb2.num_samples[j]
        real = rb2.arrays["x"][j].reshape(S * batch, dim)[mask > 0]
        src_rows = {tuple(np.round(r, 5)) for r in per_user[j]["x"]}
        assert all(tuple(np.round(r, 5)) in src_rows for r in real)


@given(st.integers(1, 2 ** 31 - 1), st.integers(1, 3000),
       st.floats(0.0, 1.0), st.floats(1e-30, 1e30), st.floats(0.0, 1.0))
@settings(**SETTINGS)
def test_quantile_selection_is_the_sorted_quantile(seed, n, q, scale, ties):
    """The exact threshold's two order statistics are the sort's, bit for
    bit, for any size, quantile, scale and share of exact zeros; the
    blend is jnp.quantile's to one ulp."""
    from msrflute_tpu.ops.quantization import (abs_order_stats,
                                               quantile_abs, quantile_ranks)
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=n) * scale).astype(np.float32)
    x[rng.random(n) < ties] = 0.0
    low, high, _, _ = quantile_ranks(n, np.float32(q))
    low_value, high_value, has_nan = abs_order_stats(jnp.asarray(x), low,
                                                     high)
    ordered = np.sort(np.abs(x))
    assert np.float32(low_value).view(np.int32) == \
        ordered[int(low)].view(np.int32)
    assert np.float32(high_value).view(np.int32) == \
        ordered[int(high)].view(np.int32)
    assert not bool(has_nan)
    want = np.float32(jnp.quantile(jnp.abs(jnp.asarray(x)), np.float32(q)))
    got = np.float32(quantile_abs(jnp.asarray(x), np.float32(q)))
    if np.isfinite(want):
        assert abs(float(got) - float(want)) <= np.spacing(np.abs(want))


@given(st.integers(1, 2 ** 31 - 1), st.integers(2, 6), st.integers(1, 8))
@settings(**SETTINGS)
def test_moe_dispatch_indices_invariants(seed, n_experts, capacity):
    """Kept tokens get unique slots per expert, all below capacity."""
    from msrflute_tpu.ops.moe import _dispatch_indices
    rng = np.random.default_rng(seed)
    eid = jnp.asarray(rng.integers(0, n_experts, size=(40,)), jnp.int32)
    pos, keep = _dispatch_indices(eid, n_experts, capacity)
    pos, keep = np.asarray(pos), np.asarray(keep)
    assert (pos[keep] < capacity).all()
    for e in range(n_experts):
        sel = keep & (np.asarray(eid) == e)
        slots = pos[sel]
        assert len(np.unique(slots)) == len(slots)  # no collisions
    # overflow tokens are exactly those beyond capacity per expert
    for e in range(n_experts):
        total = int((np.asarray(eid) == e).sum())
        kept = int((keep & (np.asarray(eid) == e)).sum())
        assert kept == min(total, capacity)


@given(st.integers(1, 2 ** 31 - 1), st.integers(2, 5), st.integers(2, 20),
       st.floats(0.05, 5.0))
@settings(**SETTINGS)
def test_dirichlet_partition_property(seed, classes, clients, alpha):
    from msrflute_tpu.data.partition import dirichlet_partition
    rng = np.random.default_rng(seed)
    y = rng.integers(0, classes, size=600)
    parts = dirichlet_partition(y, clients, alpha, rng)
    allidx = np.concatenate(parts)
    assert len(allidx) == 600
    assert len(np.unique(allidx)) == 600


@given(st.integers(1, 2 ** 31 - 1), st.integers(1, 6), st.integers(1, 6))
@settings(**SETTINGS)
def test_masked_mean_ignores_padding(seed, real, pad):
    """masked_mean of [real ++ padding] == plain mean of the real rows."""
    from msrflute_tpu.models.base import masked_mean
    rng = np.random.default_rng(seed)
    vals = rng.normal(size=(real + pad,)).astype(np.float32)
    mask = np.concatenate([np.ones(real), np.zeros(pad)]).astype(np.float32)
    got = float(masked_mean(jnp.asarray(vals), jnp.asarray(mask)))
    np.testing.assert_allclose(got, vals[:real].mean(), rtol=1e-5)
