"""LFM2-MoE (``models/lfm2.py``, ``ops/moe.py``) against the plain
reference (``benchmarks/reference/lfm2_moe.py``) at a tiny size on the
CPU: two layers of each kind (conv and attention operators, dense and
expert MLPs), hidden 64, 8 experts of which 2 are held, 2 a token.

Tolerances: both sides are float32 under ``highest``, so what separates
them is summation order (the program sums a token's experts from a
sorted pair buffer, the reference from a dense masked product; blocked
against whole softmax rows): 1e-5 of a leaf's largest gradient covers
it, and every planted fault below reads 1e-2 or more.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.reference import lfm2_moe as ref  # noqa: E402
from msrflute_tpu.models import lfm2, make_task  # noqa: E402
from msrflute_tpu.ops import moe  # noqa: E402

TINY = dict(
    model_type="LFM2_MOE", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, conv_L_cache=3, norm_eps=1e-5, rope_theta=1e6,
    num_experts=8, num_experts_per_tok=2, routed_scaling_factor=1,
    experts_held=2, expert_offset=0, vocab_size=64, num_dense_layers=2,
    layer_types="conv,full_attention,conv,full_attention", seq_len=16,
    attention_block=8)


def _weights(seed=3, **over):
    return ref.init(np.random.default_rng(seed), {**TINY, **over})


def _batch(seed=4, rows=2, length=17):
    ids = np.random.default_rng(seed).integers(1, 64, size=(rows, length))
    return {"x": jnp.asarray(ids, jnp.int32),
            "sample_mask": jnp.ones((rows,), jnp.float32)}


def _program_loss(task):
    return lambda params, batch: task.loss(params, batch, None, True)[0]


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("over", [{}, {"remat": True},
                                  {"expert_offset": 4}],
                         ids=["plain", "remat", "offset4"])
def test_loss_and_every_gradient_leaf_match_the_reference(over):
    config = {**TINY, **over}
    task, weights, batch = make_task(config), _weights(**over), _batch()
    assert jax.tree.structure(task.init_params(jax.random.PRNGKey(0))) == \
        jax.tree.structure(weights)
    # one program a side: run eagerly, every operation compiles alone
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: task.loss(p, batch, None, True)[:2], has_aux=True))(weights)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, batch, config)))(weights)
    assert abs(float(loss) - float(want)) < 1e-6 * abs(float(want))
    for (path, got), exp in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want_grads)):
        scale = max(float(jnp.max(jnp.abs(exp))), 1e-4)
        assert float(jnp.max(jnp.abs(got - exp))) < 1e-5 * scale, \
            jax.tree_util.keystr(path)
    # the selection bias enters the choice only
    for name, layer in grads.items():
        if "moe" in getattr(layer, "keys", lambda: ())():
            assert not np.any(np.asarray(layer["moe"]["select_bias"])), name
    # what the expert layers counted: two expert layers, one step
    counters = aux["counters"]
    assert float(counters["moe_layer_steps"]) == 2.0
    assert float(counters["moe_pairs_dropped"]) == 0.0
    pairs = sum(float(jnp.sum(c)) for c in jax.jit(
        lambda p: ref.held_pairs(p, batch["x"][:, :-1], config))(weights))
    assert float(counters["moe_pairs_held"]) == pairs


def test_bf16_path_is_the_lower_precision_it_says():
    """``model_config.dtype: bfloat16`` (the benchmark's control) moves
    the loss by rounding of that size, and by no more."""
    batch, weights = _batch(), _weights()
    exact = float(jax.jit(lambda p: ref.loss(p, batch, TINY))(weights))
    low = float(jax.jit(_program_loss(make_task(
        {**TINY, "dtype": "bfloat16"})))(weights, batch))
    assert 1e-5 < abs(low - exact) / exact < 5e-2


# ----------------------------------------------------------------------
# the expert layer
# ----------------------------------------------------------------------
def _layer_inputs(tokens=48, seed=0):
    rng = np.random.default_rng(seed)
    sizes = ref._sizes({**TINY, "experts_held": 8})
    p = ref.init(rng, {**TINY, "experts_held": 8,
                       "num_dense_layers": 0,
                       "layer_types": "conv"})["layer_0"]
    x = jnp.asarray(rng.standard_normal((1, tokens, 64)), jnp.float32)
    return x, p, sizes


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold two experts each.  What every chip computes alike
    (the operator and the residual stream, ``h``) counted once, plus the
    four shares' parts of the expert MLP, is the layer the uncut
    reference computes with all eight experts."""
    x, p, sizes = _layer_inputs()
    uncut = ref._layer(x, p, "conv", "moe", sizes, 1e-5, 1e6, 1.0)
    h = ref.x_mid(x, p, "conv", sizes, 1e-5, 1e6)
    z = ref._rms_norm(h, p["norm_ffn"], 1e-5)[0]
    m = p["moe"]
    total = h[0]
    pairs = 0.0
    for share in range(4):
        held = slice(2 * share, 2 * share + 2)
        part, counters = moe.held_experts_ffn(
            z, m["router"], m["select_bias"], m["w1"][held], m["w3"][held],
            m["w2"][held], experts_per_token=2, expert_offset=2 * share)
        # the reference's own share is the same part
        cut = {**sizes, "experts_held": 2, "expert_offset": 2 * share}
        want = ref._expert_mlp(z[None], {**m, "w1": m["w1"][held],
                                         "w3": m["w3"][held],
                                         "w2": m["w2"][held]}, cut, 1.0)[0]
        assert float(jnp.max(jnp.abs(part - want))) < 1e-6
        total = total + part
        pairs += float(counters["moe_pairs_held"])
    assert pairs == z.shape[0] * 2  # every pair is held by one share
    assert float(jnp.max(jnp.abs(total - uncut[0]))) < 1e-5 * float(
        jnp.max(jnp.abs(uncut)))


def test_no_token_is_dropped_when_one_expert_takes_most_tokens():
    """Every token routed to held expert 0 (and most to expert 1): 300
    tokens are three tiles of one expert's rows, nothing is dropped and
    the result is the reference's."""
    x, p, sizes = _layer_inputs(tokens=300, seed=1)
    m = dict(p["moe"])
    bias = np.zeros((8,), np.float32)
    bias[0], bias[1] = 10.0, 5.0
    m["select_bias"] = jnp.asarray(bias)
    z = x[0]
    held = slice(0, 2)
    got, counters = moe.held_experts_ffn(
        z, m["router"], m["select_bias"], m["w1"][held], m["w3"][held],
        m["w2"][held], experts_per_token=2)
    assert float(counters["moe_pairs_held"]) == 600.0
    assert float(counters["moe_max_load"]) == 300.0
    assert float(counters["moe_pairs_dropped"]) == 0.0
    cut = {**sizes, "experts_held": 2}
    want = ref._expert_mlp(z[None], {**m, "w1": m["w1"][held],
                                     "w3": m["w3"][held],
                                     "w2": m["w2"][held]}, cut, 1.0)[0]
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("seed", range(4))
def test_every_held_pair_owns_one_row_of_its_experts_tiles(seed):
    rng = np.random.default_rng(seed)
    tokens, k, held_n, offset = 200, 4, 3, 2
    chosen = np.stack([rng.permutation(16)[:k] for _ in range(tokens)])
    if seed == 0:
        chosen[:, 0] = offset  # one expert takes every token
    row_of_pair, held, pair_of_row, tile_expert, n_active, counts = (
        np.asarray(a) for a in moe.plan_pairs(
            jnp.asarray(chosen, jnp.int32), held_n, offset))
    local = chosen - offset
    want_held = (local >= 0) & (local < held_n)
    assert np.array_equal(held, want_held)
    assert np.array_equal(counts, [(local == e).sum()
                                   for e in range(held_n)])
    rows = row_of_pair[held]
    assert len(set(rows.tolist())) == len(rows)  # a row per held pair
    assert np.array_equal(pair_of_row[rows], np.flatnonzero(held.ravel()))
    assert (pair_of_row < tokens * k).sum() == held.sum()  # none dropped
    # a pair's row lies in a tile of its own expert, among the active
    assert np.array_equal(tile_expert[rows // moe.TILE_ROWS], local[held])
    assert rows.max() < n_active * moe.TILE_ROWS
    assert n_active == sum(max(-(-c // moe.TILE_ROWS), 1) for c in counts)
    assert len(pair_of_row) >= tokens * k + held_n * moe.TILE_ROWS - 1


def test_grouped_matmul_transposes_are_the_dense_ones():
    """The three kernels against ``einsum`` on a sorted buffer."""
    rng = np.random.default_rng(0)
    tokens, k, held_n = 160, 2, 2
    chosen = jnp.asarray(rng.integers(0, 3, size=(tokens, k)), jnp.int32)
    row_of_pair, held, pair_of_row, tile_expert, n_active, _ = \
        moe.plan_pairs(chosen, held_n, 0)
    z = jnp.asarray(rng.standard_normal((tokens, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((held_n, 64, 32)), jnp.float32)
    owned = (pair_of_row < tokens * k)[:, None]
    expert_of_row = jnp.repeat(tile_expert, moe.TILE_ROWS)

    def kernels(z, w):
        rows = moe.rows_of_tokens(z, pair_of_row, row_of_pair, held,
                                  n_active)
        out = moe.grouped_matmul(rows, w, tile_expert, n_active)
        return jnp.sum(jnp.where(owned, out, 0.0) ** 2)

    def dense(z, w):
        rows = jnp.where(owned, z.at[pair_of_row // k].get(
            mode="fill", fill_value=0), 0.0)
        out = jnp.einsum("md,mdh->mh", rows, w[expert_of_row])
        return jnp.sum(jnp.where(owned, out, 0.0) ** 2)

    got = jax.value_and_grad(kernels, argnums=(0, 1))(z, w)
    want = jax.value_and_grad(dense, argnums=(0, 1))(z, w)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * float(
            jnp.max(jnp.abs(b)))


def test_config_errors_name_the_key():
    with pytest.raises(ValueError, match="layer_types"):
        make_task({**TINY, "layer_types": "conv,mamba"})
    with pytest.raises(ValueError, match="experts_held"):
        make_task({**TINY, "experts_held": 6, "expert_offset": 4})
    # no expert layer, no counters
    assert make_task({**TINY, "num_dense_layers": 4}).counter_names == ()
    assert make_task(TINY).counter_names == lfm2.COUNTERS


def test_a_local_step_through_the_attention_kernels_is_the_plain_paths(
        monkeypatch):
    """The cell's local step with the grouped-query core forced through
    the tiled kernels (``interpret=True``; ``remat`` on, as the cell has
    it): query head ``h`` reads key-value head ``h // 2`` by the block
    index and ``dk``/``dv`` are summed over the group inside the kernel;
    the loss and every gradient leaf are the plain path's to float32
    rounding, and the trace says which path each took."""
    import functools
    from msrflute_tpu.models import token_blocks
    from msrflute_tpu.ops import pallas_attention as pa
    config = {**TINY, "remat": True}
    task, weights, batch = make_task(config), _weights(), _batch()

    def step(p):
        return jax.value_and_grad(_program_loss(task))(p, batch)

    pa.drain_attention_events()
    want, want_grads = jax.jit(step)(weights)
    assert [e["impl"] for e in pa.drain_attention_events()] == ["plain"]
    monkeypatch.setattr(token_blocks, "causal_attention", functools.partial(
        token_blocks.causal_attention, interpret=True))
    # another function object: jit would hand back ``step``'s program
    loss, grads = jax.jit(lambda p: step(p))(weights)
    said = pa.drain_attention_events()
    assert [(e["kind"], e["impl"]) for e in said] == \
        [("attention_path", "flash")], said
    # 16 tokens, 4 query heads over 2 key-value heads of 16
    assert said[0]["q_shape"] == [2, 16, 4, 16]
    assert said[0]["k_shape"] == [2, 16, 2, 16]
    assert abs(float(loss) - float(want)) < 1e-6 * abs(float(want))
    for (path, got), exp in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want_grads)):
        scale = max(float(jnp.max(jnp.abs(exp))), 1e-4)
        assert float(jnp.max(jnp.abs(got - exp))) < 1e-5 * scale, \
            jax.tree_util.keystr(path)
