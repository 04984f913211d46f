"""SDAR-MoE under the block-diffusion objective (``models/sdar_moe.py``,
``models/token_blocks.py::BlockDiffusionLMTask``, ``ops/moe.py``,
``ops/pallas_attention.py``) against the plain reference
(``benchmarks/reference/sdar_moe.py``) at a tiny size on the CPU: hidden
64, 4 query heads over 2 key-value heads of 16, 8 experts of 32 of
which 4 are held, 2 a token, rows of 64 and 96 ids in blocks of 4.

Tolerances: both sides are float32 under ``highest``, so what separates
them is summation order (the program sums a position's experts from a
sorted pair buffer, the reference from a dense masked product; the
blocks of attention rows differ): 1e-5 of a leaf's largest gradient
covers it, and every planted fault of
``tests/benchmarks/test_benchmark_sdar.py`` reads 1e-2 or more.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.reference import fedround, sdar_moe as ref  # noqa: E402
from msrflute_tpu.models import make_task, token_blocks  # noqa: E402
from msrflute_tpu.ops import moe, pallas_attention as pa  # noqa: E402

TINY = dict(
    model_type="SDAR_MOE", hidden_size=64, moe_intermediate_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, experts_held=4, expert_offset=0,
    rms_norm_eps=1e-6, rope_theta=1e6, num_hidden_layers=2, vocab_size=96,
    seq_len=64, attention_block=32, block_length=4, noise_seed=5)


def _weights(seed=3, **over):
    return ref.init(np.random.default_rng(seed), {**TINY, **over})


def _batch(seed=4, rows=2, length=64, real=None, span=4):
    ids = np.random.default_rng(seed).integers(1, 95, size=(rows, length))
    tok = np.ones((rows, length), np.float32)
    if real is not None:
        tok[-1, real:] = 0.0
        ids[-1, real:] = 0
    draws = [token_blocks.bd_draws(5, "train", 0, r, length, span)
             for r in range(rows)]
    return {"x": jnp.asarray(ids, jnp.int32), "tok_mask": jnp.asarray(tok),
            "bd_mask": jnp.asarray(tok * np.stack([m for m, _ in draws])),
            "bd_weight": jnp.asarray(tok * np.stack([w for _, w in draws])),
            "sample_mask": jnp.ones((rows,), jnp.float32)}


@pytest.fixture(autouse=True)
def _highest(monkeypatch):
    # the reference in blocks of other rows than the program's
    monkeypatch.setattr(ref, "ATTENTION_ROWS", 24)
    with jax.default_matmul_precision("highest"):
        yield


def _kernels(monkeypatch):
    """The core through the tiled kernels, in the interpreter."""
    monkeypatch.setattr(
        token_blocks, "block_diffusion_attention", functools.partial(
            token_blocks.block_diffusion_attention, interpret=True))


def _close(got_tree, want_tree, rel=1e-5):
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(got_tree),
            jax.tree.leaves(want_tree)):
        scale = max(float(jnp.max(jnp.abs(want))), 1e-4)
        assert float(jnp.max(jnp.abs(got - want))) < rel * scale, \
            jax.tree_util.keystr(path)


# ----------------------------------------------------------------------
# the model and the objective against the plain reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("path", ["plain", "kernels"])
@pytest.mark.parametrize("over, batch", [
    ({}, {}), ({"remat": True}, {"length": 96}),
    ({"expert_offset": 4}, {"real": 40}),
    ({"block_length": 8}, {"span": 8, "length": 96})],
    ids=["L64", "remat_L96", "offset4_short_row", "B8_L96"])
def test_loss_and_every_gradient_leaf_match_the_reference(
        monkeypatch, path, over, batch):
    if path == "kernels":
        _kernels(monkeypatch)
    config = {**TINY, **over}
    task, weights, batch = make_task(config), _weights(**over), \
        _batch(**batch)
    assert jax.tree.structure(task.init_params(jax.random.PRNGKey(0))) == \
        jax.tree.structure(weights)
    pa.drain_attention_events()
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: task.loss(p, batch, None, True)[:2], has_aux=True))(weights)
    said = pa.drain_attention_events()
    assert {e["impl"] for e in said if e["kind"] == "attention_path"} == \
        {"flash" if path == "kernels" else "plain"}
    assert [e["kind"] for e in said].count("attn_tiles") == \
        (path == "kernels")
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, batch, config)))(weights)
    assert float(want) > 1.0
    assert abs(float(loss) - float(want)) < 1e-6 * abs(float(want))
    _close(grads, want_grads)
    # the head is its own leaf
    assert float(jnp.max(jnp.abs(grads["head"] - grads["embedding"]))) > 0
    # what the layers and the objective counted: two layers, one step
    counters = aux["counters"]
    assert set(counters) == set(token_blocks.COUNTERS +
                                token_blocks.BD_COUNTERS)
    assert task.counter_names == token_blocks.COUNTERS + \
        token_blocks.BD_COUNTERS
    assert float(counters["moe_layer_steps"]) == 2.0
    assert float(counters["moe_pairs_dropped"]) == 0.0
    real = batch["tok_mask"]
    assert float(counters["bd_positions_real"]) == float(jnp.sum(real))
    assert float(counters["bd_positions_masked"]) == \
        float(jnp.sum(batch["bd_mask"]))
    assert float(aux["sample_count"]) == float(ref.sample_count(batch)) == 2
    # the padded half of a padded row is real to the expert layer's
    # counter only where the module padded nothing
    if not -real.shape[1] % config["attention_block"]:
        ids = ref._fields(batch, config)[0]
        held = jax.jit(lambda p: ref.held_pairs(p, ids, config))(weights)
        assert float(counters["moe_pairs_held"]) == sum(
            float(jnp.sum(c)) for c in held)


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_one_whole_client_update_is_the_plain_rounds(monkeypatch, path):
    """Two local SGD steps of one client (the cell's protocol: batch 1,
    one epoch over its two rows, learning rate 0.1) by the program's
    task loss inside ``fedround``'s plain round against the same round
    by the reference's loss: the pseudo-gradient, leaf by leaf."""
    if path == "kernels":
        _kernels(monkeypatch)
    task, weights, batch = make_task(TINY), _weights(), _batch()
    steps = {key: np.asarray(value)[None, :, None]
             for key, value in batch.items()}        # [K=1, S=2, B=1, ...]
    rounds = [{**steps, "client_mask": np.ones((1,), np.float32),
               "client_lr": 0.1, "server_lr": 1.0, "quant_quantile": None}]

    def run(loss):
        return fedround.run_rounds(
            forward=ref.forward, loss=loss, sample_count=ref.sample_count,
            model_config=TINY, params=weights, rounds=rounds,
            strategy={"name": "fedavg"}, block=1, precision="highest")[0]

    got = run(lambda p, b, mc: task.loss(p, b, None, True)[0])
    want = run(ref.loss)
    assert got["train_loss"] == pytest.approx(want["train_loss"], rel=1e-6)
    assert float(want["pseudo_norm"][0]) > 0
    # the router's gradient is what is left of eight gates that sum to
    # one: small beside its terms, so their rounding shows (1e-4 of it)
    _close(got["aggregate"], want["aggregate"], rel=1e-4)
    _close(jax.tree.map(lambda a, b: a - b, weights, got["new_params"]),
           jax.tree.map(lambda a, b: a - b, weights, want["new_params"]),
           rel=1e-4)


def test_evaluation_scores_the_masked_positions():
    task, weights, batch = make_task(TINY), _weights(), _batch(real=40)
    stats = jax.jit(lambda p: task.eval_stats(p, batch))(weights)
    loss = float(jax.jit(lambda p: ref.loss(p, batch, TINY))(weights))
    assert float(stats["loss_sum"]) / float(stats["sample_count"]) == \
        pytest.approx(loss, rel=1e-6)
    assert float(stats["sample_count"]) == 64 + 40
    assert float(stats["correct_count"]) == float(jnp.sum(batch["bd_mask"]))
    ids, x0, _, _ = ref._fields(batch, TINY)
    hit = jnp.argmax(ref.forward(weights, ids, TINY), axis=-1) == x0
    assert float(stats["correct_sum"]) == float(
        jnp.sum(hit * batch["bd_mask"]))
    metrics = task.finalize_metrics(
        {**stats, "correct_sum": 3.0, "correct_count": 12.0})
    assert metrics["acc"].value == 0.25 and metrics["acc"].higher_is_better
    assert metrics["loss"].value == pytest.approx(loss, rel=1e-6)


def test_bf16_path_is_the_lower_precision_it_says():
    batch, weights = _batch(), _weights()
    exact = float(jax.jit(lambda p: ref.loss(p, batch, TINY))(weights))
    task = make_task({**TINY, "dtype": "bfloat16"})
    low = float(jax.jit(
        lambda p: task.loss(p, batch, None, True)[0])(weights))
    assert 1e-5 < abs(low - exact) / exact < 5e-2


# ----------------------------------------------------------------------
# the leak: what a block's logits may depend on
# ----------------------------------------------------------------------
@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_a_blocks_logits_see_the_clean_past_and_nothing_else(monkeypatch,
                                                             path):
    """Block ``b``'s logits read the noised ids of block ``b`` and the
    clean ids of blocks before ``b``: changing ``x0`` in block ``b`` or
    later (at positions that are masked, so ``xt`` stays) leaves them
    unchanged TO THE BIT; changing block ``b - 1`` does not."""
    if path == "kernels":
        _kernels(monkeypatch)
    task, weights = make_task(TINY), _weights()
    batch = _batch(rows=1)
    batch["bd_mask"] = jnp.ones_like(batch["bd_mask"])   # xt = all mask
    span, b = 4, 9
    logits = jax.jit(lambda p, bt: task._scored(p, bt)[1])

    def changed(lo, hi):
        ids = np.array(batch["x"])
        ids[0, lo:hi] = 1 + (ids[0, lo:hi] + 7) % 94
        return {**batch, "x": jnp.asarray(ids)}

    base = np.asarray(logits(weights, batch))
    block = slice(b * span, (b + 1) * span)
    own_or_later = np.asarray(logits(weights, changed(b * span, 64)))
    assert np.array_equal(own_or_later[0, block], base[0, block])
    assert np.array_equal(own_or_later[0, :b * span], base[0, :b * span])
    before = np.asarray(logits(weights, changed((b - 1) * span, b * span)))
    assert np.abs(before[0, block] - base[0, block]).max() > 1e-4
    assert np.array_equal(before[0, :(b - 1) * span],
                          base[0, :(b - 1) * span])


# ----------------------------------------------------------------------
# the kernels against a dense masked statement
# ----------------------------------------------------------------------
def _dense_core(q, k, v, span):
    group = q.shape[2] // k.shape[2]
    seen = jnp.asarray(pa.bd_seen(q.shape[1] // 2, span))
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("blhd,bmhd->bhlm", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("tile", [(128, 128), (64, 128)],
                         ids=["128x128", "64x128"])
@pytest.mark.parametrize("length, span", [(64, 4), (96, 8), (200, 4),
                                          (200, 8)])
def test_kernels_are_the_dense_masked_statement(length, span, tile):
    """Forward and the three gradients, grouped heads (4 over 2), a
    value width of its own, a padded last tile (200), two tile shapes."""
    rng = np.random.default_rng(length + span)
    rows = 2 * length
    q, k, v, w = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                  for shape in ((1, rows, 4, 16), (1, rows, 2, 16),
                                (1, rows, 2, 24), (1, rows, 4, 24)))

    def kernels(q, k, v):
        return pa.block_diffusion_flash_attention(
            q, k, v, span, block_q=tile[0], block_k=tile[1], interpret=True)

    want = _dense_core(q, k, v, span)
    assert float(jnp.max(jnp.abs(kernels(q, k, v) - want))) < 2e-6
    got_grads = jax.grad(lambda *a: jnp.sum(kernels(*a) * w), (0, 1, 2))(
        q, k, v)
    want_grads = jax.grad(lambda *a: jnp.sum(_dense_core(*a, span) * w),
                          (0, 1, 2))(q, k, v)
    _close(got_grads, want_grads)
    # the plain path says the same
    plain = token_blocks._blocked_bd_attention(
        q.reshape(1, 2 * length, 2, 2, 16), k, v, span,
        8 if length % 32 else 32)
    assert float(jnp.max(jnp.abs(
        plain.reshape(want.shape) - want))) < 2e-6


def test_seen_is_the_three_part_mask():
    seen = pa.bd_seen(12, 4)
    assert np.array_equal(seen, ref.seen(12, 4))
    assert seen.sum() == 12 * (12 + 4)
    xt, x0 = slice(0, 12), slice(12, 24)
    blk = np.arange(12) // 4
    assert np.array_equal(seen[xt, xt], blk[:, None] == blk[None, :])
    assert np.array_equal(seen[xt, x0], blk[None, :] < blk[:, None])
    assert np.array_equal(seen[x0, x0], blk[None, :] <= blk[:, None])
    assert not seen[x0, xt].any()


@pytest.mark.parametrize("length, span, tile", [
    (4096, 4, (512, 512)), (1024, 4, (512, 512)), (200, 8, (128, 128)),
    (96, 4, (32, 64)), (256, 4, (128, 256)), (64, 4, (128, 128))])
def test_the_tile_map_runs_the_tiles_that_hold_a_seen_pair(length, span,
                                                           tile):
    tiles = pa.bd_tile_map(length, span, *tile)
    lp, nq, nk = tiles["lp"], tiles["nq"], tiles["nk"]
    assert lp % tile[0] == lp % tile[1] == 0 and 0 <= lp - length < max(
        np.lcm(*tile), 1)
    run = {(i, j): m for i, row in enumerate(tiles["rows"]) for j, m in row}
    assert tiles["tiles_run"] == len(run)
    assert tiles["tiles_masked"] == sum(run.values())
    assert tiles["tiles_total"] == 4 * nq * nk
    assert tiles["pairs_seen"] == length * (length + span)
    if tile[0] == tile[1]:
        # square tiles, n a half: the noised tile i runs its own noised
        # tile and the clean tiles 0..i, the clean tile i the clean tiles
        # 0..i; three a row cross a block boundary
        n = nq
        assert tiles["tiles_run"] == n * n + 2 * n
        assert tiles["tiles_masked"] == 3 * n
        assert tiles["tiles_total"] - tiles["tiles_run"] == \
            3 * n * n - 2 * n
    if lp <= 1024:
        # against the statement on the padded row: a tile runs if and
        # only if it holds a seen pair, without a mask only if all are
        seen = pa.bd_seen(lp, span)
        for i in range(2 * nq):
            for j in range(2 * nk):
                part = seen[i * tile[0]:(i + 1) * tile[0],
                            j * tile[1]:(j + 1) * tile[1]]
                assert part.any() == ((i, j) in run), (i, j)
                if (i, j) in run:
                    assert run[(i, j)] == (not part.all()), (i, j)
    # the flat tables: one step a tile, every accumulator opened and
    # closed once, the group's heads in turn for a key tile
    by_row, by_column = pa._bd_tables(tiles, 2)
    qt, kt, flags = (np.asarray(a) for a in by_row)
    assert len(qt) == tiles["tiles_run"]
    assert ((flags & 1) > 0).sum() == ((flags & 2) > 0).sum() == 2 * nq
    assert [(i, j) for i, j in zip(qt, kt)] == sorted(run)
    kt, head, qt, flags = (np.asarray(a) for a in by_column)
    assert len(kt) == 2 * tiles["tiles_run"]
    assert ((flags & 1) > 0).sum() == ((flags & 2) > 0).sum() == \
        len(set(kt))
    assert sorted(zip(qt, kt, head)) == sorted(
        (i, j, g) for i, j in run for g in range(2))
    with pytest.raises(ValueError, match="whole blocks"):
        pa.bd_tile_map(length + 1, span, *tile)


def test_the_cells_map_and_its_event():
    pa.drain_attention_events()
    said = pa.record_attention_tiles(4096, 4, *pa.causal_blocks(4096))
    assert pa.drain_attention_events() == [said]
    assert said == {"kind": "attn_tiles", "L": 4096, "B": 4,
                    "block_q": 512, "block_k": 512, "tiles_run": 80,
                    "tiles_masked": 24, "tiles_total": 256,
                    "pairs_seen": 4096 * 4100}
    # 80.1% of the pairs the tiles run are seen; of the square, a quarter
    assert said["pairs_seen"] / (80 * 512 * 512) == pytest.approx(0.8008,
                                                                  abs=1e-4)


# ----------------------------------------------------------------------
# routing: the two laws
# ----------------------------------------------------------------------
def _route_tokens_before(z, router_w, select_bias, experts_per_token,
                         scaling=1.0, eps=1e-6):
    """``ops.moe.route_tokens`` as it stood before it took a law."""
    from jax import lax
    logits = jnp.matmul(z.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(
        scores + lax.stop_gradient(select_bias.astype(jnp.float32)),
        experts_per_token)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gate = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps) * scaling
    return chosen, gate


def test_the_sigmoid_law_is_bit_equal_to_what_it_was():
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.standard_normal((300, 64)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((64, 16)) * 0.125, jnp.float32)
    bias = jnp.asarray(rng.standard_normal((16,)) * 0.1, jnp.float32)
    for args, kwargs in (((3,), {}), ((3, 2.448), {"eps": 1e-20})):
        got = moe.route_tokens(z, router, bias, *args, **kwargs)
        want = _route_tokens_before(z, router, bias, *args, **kwargs)
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])
        assert str(jax.make_jaxpr(lambda z: moe.route_tokens(
            z, router, bias, *args, **kwargs))(z)) == str(jax.make_jaxpr(
                lambda z: _route_tokens_before(
                    z, router, bias, *args, **kwargs))(z))
    with pytest.raises(ValueError, match="scoring"):
        moe.route_tokens(z, router, bias, 3, scoring="tanh")


def test_the_softmax_law_is_its_definition():
    rng = np.random.default_rng(1)
    z = rng.standard_normal((200, 64)).astype(np.float32)
    router = (rng.standard_normal((64, 16)) * 0.125).astype(np.float32)
    chosen, gate = moe.route_tokens(jnp.asarray(z), jnp.asarray(router),
                                    None, 4, scoring="softmax")
    logits = z.astype(np.float64) @ router.astype(np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.argsort(-p, axis=-1)[:, :4]
    assert np.array_equal(np.sort(chosen, -1), np.sort(want, -1))
    picked = np.take_along_axis(p, np.asarray(chosen), -1)
    assert np.allclose(gate, picked / picked.sum(-1, keepdims=True),
                       rtol=2e-6)
    assert np.allclose(np.sum(gate, -1), 1.0, atol=1e-6)
    # and the reference's routing is the same
    sizes = {"num_experts_per_tok": 4}
    ref_chosen, ref_gate = ref.routing(jnp.asarray(z),
                                       {"router": jnp.asarray(router)}, sizes)
    assert np.array_equal(chosen, ref_chosen)
    assert np.array_equal(gate, ref_gate)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One layer, all 16 experts held, by the reference, against the sum
    of its eight shares of 2 by ``held_experts_ffn`` (each chip's part of
    an expert-parallel layer; the sum is the exchange's result)."""
    config = {**TINY, "num_experts": 16, "experts_held": 16,
              "num_hidden_layers": 1}
    sizes = ref._sizes(config)
    p = jax.tree.map(jnp.asarray, ref.init(np.random.default_rng(2),
                                           config)["layer_0"])
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 128, 64)),
                    jnp.float32)
    uncut = ref._layer(x, p, sizes, 1e-6, 1e6)
    h = ref.x_mid(x, p, sizes, 1e-6, 1e6)
    z = ref._rms_norm(h, p["norm_ffn"], 1e-6)[0]
    m = p["moe"]
    total, pairs = h[0], 0.0
    for share in range(8):
        held = slice(2 * share, 2 * share + 2)
        part, counters = moe.held_experts_ffn(
            z, m["router"], None, m["w1"][held], m["w3"][held],
            m["w2"][held], experts_per_token=2, expert_offset=2 * share,
            scoring="softmax")
        cut = {**sizes, "experts_held": 2, "expert_offset": 2 * share}
        want = ref.routed_mlp(z[None], {**m, "w1": m["w1"][held],
                                        "w3": m["w3"][held],
                                        "w2": m["w2"][held]}, cut)[0]
        assert float(jnp.max(jnp.abs(part - want))) < 1e-6
        total = total + part
        pairs += float(counters["moe_pairs_held"])
    assert pairs == z.shape[0] * 2  # every pair is held by one share
    assert float(jnp.max(jnp.abs(total - uncut[0]))) < 1e-5 * float(
        jnp.max(jnp.abs(uncut)))


# ----------------------------------------------------------------------
# the noise: an input of the loss, drawn when the dataset is built
# ----------------------------------------------------------------------
def test_the_draws_are_a_pure_function_of_seed_split_user_row():
    draw = token_blocks.bd_draws
    mask, weight = draw(5, "train", 3, 1, 4096, 4)
    again = draw(5, "train", 3, 1, 4096, 4)
    assert np.array_equal(mask, again[0]) and np.array_equal(weight,
                                                              again[1])
    for other in ((6, "train", 3, 1), (5, "val", 3, 1), (5, "train", 4, 1),
                  (5, "train", 3, 0)):
        assert not np.array_equal(mask, draw(*other, 4096, 4)[0]), other
    rate = 1.0 / weight
    assert rate.min() >= token_blocks.BD_RATE_MIN and rate.max() <= 1.0
    # one rate a block of four
    assert np.array_equal(rate.reshape(-1, 4), np.repeat(
        rate.reshape(-1, 4)[:, :1], 4, axis=1))
    assert len(np.unique(rate)) == 1024
    # E[t] = 0.525 on [0.05, 1]: the masked share of 4,096 positions
    assert abs(mask.mean() - 0.525) < 0.03
    assert abs(rate.mean() - 0.525) < 0.03
    assert set(np.unique(mask)) == {0.0, 1.0}


def test_the_dataset_carries_the_draws_beside_the_rows(tmp_path):
    from benchmarks.generators import tokens
    from msrflute_tpu.data.user_blob import load_user_blob
    spec = {"vocab": 95, "len_min": 40, "len_max": 64, "noise": 0.2,
            "samples_per_user": 2, "train_users": 3, "val_users": 2,
            "test_users": 2}
    tokens.write_splits(str(tmp_path), 7, spec)
    task = make_task(TINY)
    assert task.seq_pad_keys == ("x", "tok_mask", "bd_mask", "bd_weight")
    sets = {split: task.make_dataset(
        load_user_blob(str(tmp_path / f"{split}.hdf5")), TINY, split)
        for split in ("train", "val")}
    for split, dataset in sets.items():
        for user in range(len(dataset.user_list)):
            arrays = dataset[user] if not hasattr(dataset, "user_arrays") \
                else dataset.user_arrays(user)
            real = arrays["tok_mask"]
            assert arrays["x"].max() < TINY["vocab_size"] - 1  # never the mask
            for row in range(2):
                mask, weight = token_blocks.bd_draws(5, split, user, row, 64,
                                                     4)
                assert np.array_equal(arrays["bd_mask"][row],
                                      mask * real[row])
                assert np.array_equal(arrays["bd_weight"][row],
                                      weight * real[row])
            # a short row: nothing beyond its real positions
            assert real.sum(axis=1).min() == 40
            assert not arrays["bd_weight"][real == 0].any()


# ----------------------------------------------------------------------
# the configuration, the yaml, the scopes
# ----------------------------------------------------------------------
def test_the_built_tree_has_the_parameters_the_configuration_counts():
    import yaml
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sdar_30b_a3b_ep8share.json")) as fh:
        doc = json.load(fh)
    with open(os.path.join(REPO, doc["base_yaml"])) as fh:
        mc = yaml.safe_load(fh)["model_config"]
    shapes = jax.eval_shape(make_task(mc).init_params,
                            jax.random.PRNGKey(0))
    count = doc["parameters"]
    total = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    assert total == count["total"] == 456_346_624
    assert count["bytes_float32"] == 4 * total
    assert count["layers"] * count["layer"] + count["embedding_and_head"] + \
        count["final_norm"] == total
    layer = shapes["layer_1"]
    assert sum(leaf.size for leaf in jax.tree.leaves(layer)) == \
        count["layer"] == 94_638_336
    assert sum(leaf.size for leaf in jax.tree.leaves(layer["attn"])) == \
        count["attention"] == 18_874_624
    assert shapes["head"].shape == shapes["embedding"].shape == (18992, 2048)
    assert "select_bias" not in layer["moe"]
    assert layer["moe"]["w1"].shape == (16, 2048, 768)
    # and the reference's init gives the same tree
    ref_shapes = jax.eval_shape(
        lambda: ref.init(np.random.default_rng(0),
                         {**TINY, "hidden_size": 64}))
    assert jax.tree.structure(ref_shapes) == jax.tree.structure(
        jax.eval_shape(make_task(TINY).init_params, jax.random.PRNGKey(0)))


def test_config_errors_name_the_key():
    for key, value in (("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
                       ("norm_topk_prob", False),
                       ("use_sliding_window", True),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            make_task({**TINY, key: value})
    with pytest.raises(ValueError, match="experts_held"):
        make_task({**TINY, "experts_held": 6, "expert_offset": 4})
    with pytest.raises(ValueError, match="attention_block"):
        make_task({**TINY, "attention_block": 30})


def test_the_scopes_name_the_mechanisms_in_the_compiled_program():
    task, weights, batch = make_task(TINY), _weights(), _batch()
    text = jax.jit(jax.grad(
        lambda p: task.loss(p, batch, None, True)[0])).lower(
        weights).compile().as_text()
    for scope in ("embed", "gqa_proj", "gqa_attn_core", "routed_experts",
                  "lm_head_loss"):
        assert f"/{scope}/" in text, scope


def test_the_cli_trains_evaluates_saves_and_resumes_and_the_counters_ride(
        tmp_path):
    """Two rounds of the real CLI on the benchmark's tiny root with its
    telemetry on, then two more from the saved state: the same engine,
    scan, writer and telemetry as the other token tasks.  Every
    ``host_tail`` span carries the objective's two counters beside the
    expert layers', and the benchmark's reader makes the masked share of
    them."""
    from benchmarks import harness
    root = os.path.join(REPO, "tests", "benchmarks", "data", "sdar_root")
    loaded = harness.load_cell(root, "tiny_sdar_cell")
    doc = loaded["config_doc"]
    cfg = harness.build_config(loaded, True, None)
    cfg["server_config"]["max_iteration"] = 2
    data_dir, out_dir = str(tmp_path / "data"), str(tmp_path / "out")
    harness.load_generator(root, doc["data"]).write_splits(
        data_dir, 7, doc["data"])
    assert harness.run_cli(cfg, doc["task"], data_dir, out_dir) == 0
    models = os.path.join(out_dir, "models")
    with open(os.path.join(models, "status_log.json")) as fh:
        first = json.load(fh)
    assert first["i"] == 2 and os.path.exists(
        os.path.join(models, "latest_model.msgpack"))
    assert any(name.startswith("best_val") for name in os.listdir(models))
    spans = harness.read_spans(out_dir)
    tails = [s for s in spans if s["name"] == "host_tail"]
    assert tails
    for span in tails:
        assert set(token_blocks.COUNTERS + token_blocks.BD_COUNTERS) <= \
            set(span)
        assert span["moe_pairs_dropped"] == 0.0
        # 2 clients x 2 rows of 64 real ids a round
        assert span["bd_positions_real"] == 256.0 * span["rounds"]
        assert 0 < span["bd_positions_masked"] < span["bd_positions_real"]
    share = harness.load_layer_metrics(harness.BENCH_DIR)[
        "bd_masked_share"].read(
        {"spans": spans, "window": {"t_open": 0.0, "t_close": 1e12}})
    assert share == pytest.approx(
        100.0 * sum(s["bd_positions_masked"] for s in tails) /
        sum(s["bd_positions_real"] for s in tails))
    # the evaluation's accuracy is over masked positions: a number in
    # [0, 1] beside the loss, for validation and test
    with open(os.path.join(out_dir, "log", "metrics.jsonl")) as fh:
        names = {json.loads(line).get("name") for line in fh}
    assert {"Val acc", "Val loss", "Test acc", "Test loss"} <= names, names
    # resume: two more rounds from the saved state
    cfg["server_config"]["max_iteration"] = 4
    cfg["server_config"]["resume_from_checkpoint"] = True
    assert harness.run_cli(cfg, doc["task"], data_dir, out_dir) == 0
    with open(os.path.join(models, "status_log.json")) as fh:
        assert json.load(fh)["i"] == 4
    with open(os.path.join(out_dir, "log", "log.out")) as fh:
        assert "resumed from checkpoint at round 2" in fh.read()
