"""Laguna-MoE (``models/laguna.py``, ``models/token_blocks.py::
_GQAttention``, ``ops/pallas_attention.py``'s window law) against the
plain reference (``benchmarks/reference/laguna_moe.py``) at a tiny size
on the CPU: hidden 64, three layers (full + dense, sliding + routed,
full + routed: a period of two, so that a test's compile stays short),
6 (full) / 8 (sliding) query heads over 2 key-value heads of 16, a
window of 8 keys, 16 experts of 32 of which 4 are held, 3 a token, a
shared expert of 32, rows of 32 and 40 ids.

Tolerances: both sides are float32 under ``highest``, so what separates
them is summation order (the program sums a token's experts from a
sorted pair buffer, the reference from a dense masked product; the
blocks of attention rows differ): 1e-5 of a leaf's largest gradient
covers it, and every planted fault of
``tests/benchmarks/test_benchmark_laguna.py`` reads 3e-3 or more.
"""

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.reference import laguna_moe as ref  # noqa: E402
from msrflute_tpu.models import laguna, make_task, token_blocks  # noqa: E402
from msrflute_tpu.ops import moe, pallas_attention as pa  # noqa: E402

TINY = dict(
    model_type="LAGUNA_MOE", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    num_attention_heads=6, num_attention_heads_sliding=8,
    num_key_value_heads=2, head_dim=16, sliding_window=8,
    full_attention_period=2, num_dense_layers=1, gating=True,
    rope_theta=500000, rope_factor=64,
    rope_original_max_position_embeddings=16, rope_beta_fast=64,
    rope_beta_slow=1, rope_attention_factor=1.4158883083359672,
    partial_rotary_factor=0.5, rope_theta_sliding=10000, rms_norm_eps=1e-6,
    num_experts=16, num_experts_per_tok=3, moe_routed_scaling_factor=2.5,
    experts_held=4, expert_offset=0, num_hidden_layers=3, vocab_size=96,
    seq_len=32, attention_block=8)


def _weights(seed=3, **over):
    return ref.init(np.random.default_rng(seed), {**TINY, **over})


def _batch(seed=4, rows=2, length=33, real=None):
    ids = np.random.default_rng(seed).integers(1, 96, size=(rows, length))
    batch = {"x": jnp.asarray(ids, jnp.int32),
             "sample_mask": jnp.ones((rows,), jnp.float32)}
    if real is not None:
        tok = np.ones((rows, length), np.float32)
        tok[-1, real:] = 0.0
        batch["tok_mask"] = jnp.asarray(tok)
    return batch


@pytest.fixture(autouse=True)
def _highest(monkeypatch):
    # the reference in blocks of other rows than the program's
    monkeypatch.setattr(ref, "ATTENTION_ROWS", 12)
    with jax.default_matmul_precision("highest"):
        yield


def _kernels(monkeypatch):
    """Both cores through the tiled kernels, in the interpreter."""
    monkeypatch.setattr(token_blocks, "causal_attention", functools.partial(
        token_blocks.causal_attention, interpret=True))


def _close(got_tree, want_tree, rel=1e-5):
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(got_tree),
            jax.tree.leaves(want_tree)):
        scale = max(float(jnp.max(jnp.abs(want))), 1e-4)
        assert float(jnp.max(jnp.abs(got - want))) < rel * scale, \
            jax.tree_util.keystr(path)


# ----------------------------------------------------------------------
# the model against the plain reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("path, over, batch", [
    ("plain", {}, {}),
    ("kernels", {"remat": True, "sliding_window": 5, "num_hidden_layers": 4},
     {"length": 41}),
    ("plain", {"expert_offset": 4}, {"real": 20})],
    ids=["L32-plain", "remat_W5_four_layers_L40-kernels",
         "offset4_short_row-plain"])
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
    # both head counts took the path; the window law's map said itself
    assert {e["q_shape"][2] for e in said
            if e["kind"] == "attention_path"} == {6, 8}
    assert [e["kind"] for e in said].count("attn_window_tiles") == \
        (path == "kernels")
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, batch, config)))(weights)
    assert float(want) > 1.0
    assert abs(float(loss) - float(want)) < 1e-6 * abs(float(want))
    _close(grads, want_grads)
    # the head is its own leaf, the gate has a gradient, the bias none
    assert float(jnp.max(jnp.abs(grads["head"] - grads["embedding"]))) > 0
    assert float(jnp.max(jnp.abs(grads["layer_1"]["attn"]["wg"]))) > 0
    assert float(jnp.max(jnp.abs(
        grads["layer_1"]["moe"]["select_bias"]))) == 0
    counters = aux["counters"]
    assert set(counters) == set(token_blocks.COUNTERS) == \
        set(task.counter_names)
    assert float(counters["moe_layer_steps"]) == \
        config["num_hidden_layers"] - 1      # the routed layers
    assert float(counters["moe_pairs_dropped"]) == 0.0
    if "tok_mask" not in batch:
        held = jax.jit(lambda p: ref.held_pairs(
            p, batch["x"][:, :-1], config))(weights)
        assert float(counters["moe_pairs_held"]) == sum(
            float(jnp.sum(c)) for c in held)


def test_bf16_path_is_the_lower_precision_it_says():
    weights, batch = _weights(), _batch()

    def loss(**over):
        task = make_task({**TINY, **over})
        return jax.jit(lambda p: task.loss(p, batch, None, True)[0])(weights)

    assert 1e-5 < abs(float(loss()) - float(loss(dtype="bfloat16"))) < 5e-2


def test_a_sliding_layers_logits_see_the_window_and_nothing_else(
        monkeypatch):
    """Three SLIDING layers and nothing else that moves anything between
    positions (period 8: layer 0 alone is full, and its attention is
    blinded): a change of id ``j`` moves the logits at ``i`` only where
    ``j <= i`` and, through three layers of a window of 8, ``i - j <= 3
    x 7``.  Through the kernels."""
    _kernels(monkeypatch)
    config = {**TINY, "num_hidden_layers": 4, "full_attention_period": 8}
    task, weights = make_task(config), _weights(
        num_hidden_layers=4, full_attention_period=8)
    # layer 0 is full: blind it (a zero output projection), so that only
    # the three sliding layers move anything between positions
    weights["layer_0"]["attn"]["wo"] = np.zeros_like(
        weights["layer_0"]["attn"]["wo"])
    ids = np.random.default_rng(9).integers(1, 96, size=(1, 32))
    apply = jax.jit(task._apply)
    base = apply(weights, jnp.asarray(ids, jnp.int32))
    reach = 3 * (config["sliding_window"] - 1)
    for j in (0, 5, 13):
        other = ids.copy()
        other[0, j] = (ids[0, j] % 95) + 1
        moved = np.asarray(jnp.max(jnp.abs(
            apply(weights, jnp.asarray(other, jnp.int32)) - base),
            axis=-1))[0]
        assert not moved[:j].any()
        assert moved[j] > 0 and moved[min(j + reach, 31)] > 0
        assert not moved[j + reach + 1:].any()


# ----------------------------------------------------------------------
# the rotary laws
# ----------------------------------------------------------------------
def test_yarn_table_at_the_published_numbers():
    """``low`` = 5 and ``high`` = 16 of 32 frequencies: pairs 0-5 keep
    their plain frequency, pairs 16-31 take a 64th of it, a linear blend
    between; the program's table and the reference's are the same
    float32 numbers, made by two pieces of code."""
    table = np.asarray(laguna.yarn_inv_freq(500000, 64, 64, 4096, 64, 1))
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)

    def dim(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) / \
            (2 * math.log(500000))

    assert (math.floor(dim(64)), math.ceil(dim(1))) == (5, 16)
    assert np.allclose(table[:6], plain[:6], rtol=1e-12)
    assert np.allclose(table[16:], plain[16:] / 64, rtol=1e-12)
    ramp = (np.arange(6, 16) - 5) / 11
    assert np.allclose(table[6:16],
                       plain[6:16] / 64 * ramp + plain[6:16] * (1 - ramp),
                       rtol=1e-12)
    assert 0.1 * math.log(64) + 1 == pytest.approx(1.4158883083359672)
    import yaml
    with open(os.path.join(REPO, "experiments", "laguna_moe",
                           "config.yaml")) as fh:
        mc = yaml.safe_load(fh)["model_config"]
    theirs = ref.rotary_tables(mc)
    assert np.array_equal(theirs["full"][0], table.astype(np.float32))
    assert theirs["full"][1] == 1.4158883083359672
    assert np.array_equal(
        theirs["sliding"][0],
        np.asarray(laguna.plain_inv_freq(10000, 128), np.float32))
    assert theirs["sliding"][1] == 1.0


def test_rope_law_turns_the_first_elements_and_passes_the_rest():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 9, 2, 16)),
                    jnp.float32)
    law = token_blocks.RotaryLaw(laguna.plain_inv_freq(10000, 8), 1.5)
    out = token_blocks.rope_law(x, law)
    assert np.array_equal(out[..., 8:], x[..., 8:])
    want = ref.rope(x, (np.asarray(law.inv_freq, np.float32), 1.5))
    assert float(jnp.max(jnp.abs(out - want))) < 1e-6
    # position 0 is turned by no angle and scaled by the factor
    assert np.allclose(out[:, 0, :, :8], 1.5 * x[:, 0, :, :8], rtol=1e-6)
    # the whole head under the plain law is rope_half's result
    whole = token_blocks.rope_law(x, token_blocks.RotaryLaw(
        laguna.plain_inv_freq(10000, 16)))
    assert float(jnp.max(jnp.abs(
        whole - token_blocks.rope_half(x, 10000.0)))) < 1e-5


# ----------------------------------------------------------------------
# the window law's kernels against the dense statement (interpret mode)
# ----------------------------------------------------------------------
def _dense_core(q, k, v, window):
    group = q.shape[2] // k.shape[2]
    seen = jnp.asarray(pa.window_seen(q.shape[1], window))
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("blhd,bmhd->bhlm", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("bhlm,bmhd->blhd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("length, window, tile, heads, precision", [
    # the band's edges on tile boundaries
    (256, 64, (64, 64), 6, "highest"),
    # a row that is no multiple of the tile
    (200, 48, (64, 64), 8, "highest"),
    # a window that is no multiple of the tile
    (192, 100, (64, 128), 6, "default"),
    # a window longer than the row: the causal law
    (96, 200, (64, 64), 8, "highest")],
    ids=["L256_W64-group_of_6-highest", "L200_W48-group_of_8-highest",
         "L192_W100_64x128-group_of_6-default", "L96_W200-group_of_8-highest"])
def test_window_kernels_are_the_dense_banded_statement(length, window, tile,
                                                       heads, precision):
    """Forward and the three gradients, grouped heads (6 and 8 over ONE
    key-value head: Laguna's groups), a value width of its own,
    ``highest`` and default operands."""
    rng = np.random.default_rng(length + window + heads)
    q, k, v, w = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                  for shape in ((1, length, heads, 16), (1, length, 1, 16),
                                (1, length, 1, 24), (1, length, heads, 24)))

    def kernels(q, k, v):
        return pa.window_flash_attention(
            q, k, v, window, block_q=tile[0], block_k=tile[1],
            interpret=True)

    with jax.default_matmul_precision(precision):
        got = kernels(q, k, v)
        got_grads = jax.grad(lambda *a: jnp.sum(kernels(*a) * w),
                             (0, 1, 2))(q, k, v)
    want = _dense_core(q, k, v, window)
    want_grads = jax.grad(lambda *a: jnp.sum(_dense_core(*a, window) * w),
                          (0, 1, 2))(q, k, v)
    if precision == "highest":
        assert float(jnp.max(jnp.abs(got - want))) < 2e-6
        _close(got_grads, want_grads)
    else:
        # bfloat16 operands, float32 accumulation
        assert float(jnp.max(jnp.abs(got - want))) < 3e-2
        _close(got_grads, want_grads, rel=5e-2)
    if window >= length:
        # the causal law: the causal kernels' result at the same tile
        with jax.default_matmul_precision(precision):
            causal = pa._flash_lse(q, k, v, 0, 0, True, tile[0], tile[1],
                                   True, pa.context_mxu_dtype(q.dtype))[0]
        assert float(jnp.max(jnp.abs(got - causal))) < 1e-6
    if precision == "highest":
        # the plain path says the same
        plain = token_blocks._blocked_attention(
            q.reshape(1, length, 1, heads, 16), k, v, 8,
            0 if window >= length else window)
        assert float(jnp.max(jnp.abs(
            plain.reshape(want.shape) - want))) < 2e-6


@pytest.mark.parametrize("length, window, tile", [
    (4096, 512, (512, 512)), (4096, 512, (256, 256)), (200, 48, (64, 64)),
    (192, 100, (64, 128)), (96, 200, (64, 64)), (300, 1, (128, 128))])
def test_the_window_map_runs_the_tiles_that_hold_a_seen_pair(length, window,
                                                             tile):
    tiles = pa.win_tile_map(length, window, *tile)
    lp, nq, nk = tiles["lp"], tiles["nq"], tiles["nk"]
    assert lp % tile[0] == lp % tile[1] == 0 and 0 <= lp - length < max(
        np.lcm(*tile), 1)
    run = {(i, j): m for i, row in enumerate(tiles["rows"]) for j, m in row}
    assert tiles["tiles_run"] == len(run)
    assert tiles["tiles_masked"] == sum(run.values())
    assert tiles["tiles_whole"] == tiles["tiles_run"] - tiles["tiles_masked"]
    assert tiles["tiles_total"] == nq * nk and tiles["key_tiles"] == nk
    assert tiles["pairs_seen"] == pa.window_seen(length, window).sum() == \
        pa.window_pairs_seen(length, window)
    # the kernels compute every tile that holds a seen pair, whole: the
    # roofline share cannot pass 100
    assert tiles["tiles_run"] * tile[0] * tile[1] >= tiles["pairs_seen"]
    # against the statement on the padded row: a tile runs if and only
    # if it holds a seen pair, without a mask only if all are seen
    seen = pa.window_seen(lp, window)
    for i in range(nq):
        for j in range(nk):
            part = seen[i * tile[0]:(i + 1) * tile[0],
                        j * tile[1]:(j + 1) * tile[1]]
            assert part.any() == ((i, j) in run), (i, j)
            if (i, j) in run:
                assert run[(i, j)] == (not part.all()), (i, j)
    # the flat tables: one step a tile, every accumulator opened and
    # closed once, the group's heads in turn for a key tile
    by_row, by_column = pa._bd_tables(tiles, 8)
    qt, kt, flags = (np.asarray(a) for a in by_row)
    assert len(qt) == tiles["tiles_run"]
    assert ((flags & 1) > 0).sum() == ((flags & 2) > 0).sum() == nq
    assert [(i, j) for i, j in zip(qt, kt)] == sorted(run)
    kt, head, qt, flags = (np.asarray(a) for a in by_column)
    assert len(kt) == 8 * tiles["tiles_run"]
    assert ((flags & 1) > 0).sum() == ((flags & 2) > 0).sum() == nk
    assert sorted(zip(qt, kt, head)) == sorted(
        (i, j, g) for i, j in run for g in range(8))


def test_the_cells_window_map_and_its_event():
    """4,096 / 512: at tiles of 512 x 512 15 tiles a head, every one
    masked, half of what they compute is seen; at 256 x 256 45 tiles, 15
    whole, two thirds."""
    pa.drain_attention_events()
    said = pa.record_window_tiles(4096, 512, *pa.causal_blocks(4096))
    assert pa.drain_attention_events() == [said]
    assert said == {"kind": "attn_window_tiles", "L": 4096, "window": 512,
                    "block_q": 512, "block_k": 512, "tiles_run": 15,
                    "tiles_whole": 0, "tiles_masked": 15, "tiles_total": 64,
                    "pairs_seen": 1_966_336}
    assert said["pairs_seen"] == 512 * 4096 - 512 * 511 // 2
    assert said["pairs_seen"] / (15 * 512 * 512) == pytest.approx(
        0.500, abs=1e-3)
    small = pa.win_tile_map(4096, 512, 256, 256)
    assert (small["tiles_run"], small["tiles_whole"],
            small["tiles_masked"]) == (45, 15, 30)
    assert small["pairs_seen"] / (45 * 256 * 256) == pytest.approx(
        0.667, abs=1e-3)
    # the causal half: 23% of it is seen through the window
    assert said["pairs_seen"] / (4096 * 4097 // 2) == pytest.approx(
        0.234, abs=1e-3)
    with pytest.raises(ValueError, match="window"):
        pa.win_tile_map(64, 0, 64, 64)


# ----------------------------------------------------------------------
# what the new options leave as it was
# ----------------------------------------------------------------------
class _GQAttentionBefore(token_blocks.nn.Module):
    """``_GQAttention`` as PR 41 left it, copied: one head count, an
    RMSNorm on query and key, ``rope_half`` at ``theta``, no window, no
    gate."""
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    block: int
    dtype: object
    diffusion_block: int = 0

    @token_blocks.nn.compact
    def __call__(self, z):
        batch, length, hidden = z.shape
        heads, kv, dim = self.heads, self.kv_heads, self.head_dim
        copies = 2 if self.diffusion_block else 1
        normal = token_blocks._normal
        wq = self.param("wq", normal(0.02), (hidden, heads * dim))
        wk = self.param("wk", normal(0.02), (hidden, kv * dim))
        wv = self.param("wv", normal(0.02), (hidden, kv * dim))
        wo = self.param("wo", normal(0.02), (heads * dim, hidden))
        with jax.named_scope("gqa_proj"):
            q = token_blocks._RMSNorm(self.eps, name="norm_q")(
                (z @ wq.astype(self.dtype)).reshape(batch, length, heads,
                                                    dim))
            k = token_blocks._RMSNorm(self.eps, name="norm_k")(
                (z @ wk.astype(self.dtype)).reshape(batch, length, kv, dim))
            v = (z @ wv.astype(self.dtype)).reshape(batch, length, kv, dim)
            q = token_blocks.rope_half(q, self.theta, copies)
            k = token_blocks.rope_half(k, self.theta, copies)
            q = q.reshape(batch, length, kv, heads // kv, dim)
        with jax.named_scope("gqa_attn_core"):
            if self.diffusion_block:
                out = token_blocks.block_diffusion_attention(
                    q, k, v, self.diffusion_block, self.block)
            else:
                out = token_blocks.causal_attention(q, k, v, self.block)
        with jax.named_scope("gqa_proj"):
            return out.reshape(batch, length, heads * dim) @ \
                wo.astype(self.dtype)


@pytest.mark.parametrize("path, diffusion_block", [("kernels", 0),
                                                   ("plain", 4)],
                         ids=["causal-kernels", "bd-plain"])
def test_the_old_form_of_gqattention_traces_as_it_did(monkeypatch, path,
                                                      diffusion_block):
    """LFM2's and SDAR's attention (the defaults of the new fields):
    the jaxpr of forward and gradient is the copied old module's,
    letter for letter, on the plain path and through the kernels."""
    if path == "kernels":
        for name in ("causal_attention", "block_diffusion_attention"):
            monkeypatch.setattr(token_blocks, name, functools.partial(
                getattr(token_blocks, name), interpret=True))
    args = (4, 2, 16, 1e-6, 1e6, 8, jnp.float32)
    z = jnp.asarray(np.random.default_rng(0).standard_normal((1, 32, 64)),
                    jnp.float32)

    def text(cls):
        module = cls(*args, diffusion_block=diffusion_block)
        params = module.init(jax.random.PRNGKey(0), z)
        import re
        return re.sub(r" at 0x[0-9a-f]+|test_laguna_moe|token_blocks", "",
                      str(jax.make_jaxpr(jax.value_and_grad(
                          lambda p: jnp.sum(module.apply(p, z))))(params)))

    assert text(token_blocks._GQAttention) == text(_GQAttentionBefore)


def test_the_plain_causal_path_traces_as_it_did():
    """``_blocked_attention`` without a window: a block's mask is ``cols
    <= rows`` and nothing more (no ``0 +``, no second compare)."""
    q, k, v = (jnp.zeros(shape, jnp.float32) for shape in (
        (1, 16, 2, 2, 8), (1, 16, 2, 8), (1, 16, 2, 8)))
    text = str(jax.make_jaxpr(
        lambda *a: token_blocks._blocked_attention(*a, 8))(q, k, v))
    assert text.count(" le ") == 2 and " lt " not in text and \
        " and " not in text
    banded = str(jax.make_jaxpr(
        lambda *a: token_blocks._blocked_attention(*a, 8, 4))(q, k, v))
    assert " lt " in banded and " and " in banded
    # a block of a banded row multiplies the keys its rows can see only
    assert "f32[1,2,2,8,11]" in banded and "f32[1,2,2,8,16]" not in banded


# ----------------------------------------------------------------------
# the share and the model
# ----------------------------------------------------------------------
def test_the_shares_routed_parts_and_the_shared_expert_once_add_up():
    """One routed layer with all 16 experts held, by the reference,
    against the sum of its four shares of 4 by ``held_experts_ffn``
    (each chip's part of an expert-parallel layer; the sum is the
    exchange's result) plus the shared expert counted ONCE (every chip
    computes it whole; it is no part of the exchange)."""
    config = {**TINY, "experts_held": 16, "num_hidden_layers": 2}
    sizes = ref._sizes(config)
    tables = ref.rotary_tables(config)
    p = jax.tree.map(jnp.asarray, ref.init(np.random.default_rng(2),
                                           config)["layer_1"])
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 128, 64)),
                    jnp.float32)
    kind = ("sliding", "moe")

    @jax.jit
    def both(x, p):
        uncut = ref._layer(x, p, kind, sizes, 1e-6, tables, 2.5)
        h = ref.x_mid(x, p, sizes, 1e-6, "sliding", tables["sliding"])
        z = ref._rms_norm(h, p["norm_ffn"], 1e-6)[0]
        m = p["moe"]
        total, pairs, worst = h[0] + ref._swiglu(z, p["shared"]), 0.0, 0.0
        for share in range(4):
            held = slice(4 * share, 4 * share + 4)
            part, counters = moe.held_experts_ffn(
                z, m["router"], m["select_bias"], m["w1"][held],
                m["w3"][held], m["w2"][held], experts_per_token=3,
                expert_offset=4 * share, scaling=2.5)
            cut = {**sizes, "experts_held": 4, "expert_offset": 4 * share}
            want = ref.routed_mlp(z[None], {**m, "w1": m["w1"][held],
                                            "w3": m["w3"][held],
                                            "w2": m["w2"][held]}, cut, 2.5)[0]
            worst = jnp.maximum(worst, jnp.max(jnp.abs(part - want)))
            total = total + part
            pairs += counters["moe_pairs_held"]
        return uncut, total, pairs, worst

    uncut, total, pairs, worst = both(x, p)
    assert float(worst) < 1e-6       # each share is the reference's share
    assert float(pairs) == x.shape[1] * 3  # every pair is held by one share
    assert float(jnp.max(jnp.abs(total - uncut[0]))) < 1e-5 * float(
        jnp.max(jnp.abs(uncut)))


def test_the_built_tree_has_the_parameters_the_configuration_counts():
    import yaml
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna_xs2_33b_a3b_ep32share.json")) as fh:
        doc = json.load(fh)
    with open(os.path.join(REPO, doc["base_yaml"])) as fh:
        mc = yaml.safe_load(fh)["model_config"]
    shapes = jax.eval_shape(make_task(mc).init_params,
                            jax.random.PRNGKey(0))
    count = doc["parameters"]

    def size(tree):
        return sum(leaf.size for leaf in jax.tree.leaves(tree))

    bias = sum(shapes[f"layer_{i}"]["moe"]["select_bias"].size
               for i in range(1, 5))
    # the trained parameters, and beside them the fixed selection bias
    assert size(shapes) - bias == count["total"] == 464_541_696
    assert bias == count["select_bias_entries"] == 4 * 256
    assert size(shapes) == count["tree_entries"]
    assert count["bytes_float32"] == 4 * count["total"]
    assert size(shapes["layer_0"]) == count["layer_0"] == 92_278_784
    for i in (1, 2, 3):
        assert size(shapes[f"layer_{i}"]) - 256 == \
            count["layers_1_to_3_each"] == 83_365_888
    assert size(shapes["layer_4"]) - 256 == count["layer_4"] == 70_782_976
    assert size(shapes["layer_0"]["attn"]) == size(
        shapes["layer_4"]["attn"]) == count["full_attention"] == 41_943_040
    assert size(shapes["layer_2"]["attn"]) == count["sliding_attention"] == \
        54_525_952
    assert size(shapes["layer_0"]["mlp"]) == count["dense_mlp"]
    assert size(shapes["layer_1"]["shared"]) + size(
        shapes["layer_1"]["moe"]) - 256 == count["routed_ffn"] == 28_835_840
    assert shapes["head"].shape == shapes["embedding"].shape == (12544, 2048)
    assert shapes["layer_0"]["attn"]["wq"].shape == (2048, 48 * 128)
    assert shapes["layer_1"]["attn"]["wg"].shape == (2048, 64 * 128)
    assert shapes["layer_1"]["attn"]["wk"].shape == (2048, 8 * 128)
    assert set(shapes["layer_1"]["attn"]) == {"wq", "wk", "wv", "wo", "wg"}
    assert shapes["layer_1"]["moe"]["w1"].shape == (8, 2048, 512)
    assert shapes["layer_1"]["moe"]["router"].shape == (2048, 256)
    assert laguna.layer_types(mc) == [
        ("full", "dense"), ("sliding", "moe"), ("sliding", "moe"),
        ("sliding", "moe"), ("full", "moe")]
    # and the reference's init gives the same tree
    assert jax.tree.structure(jax.eval_shape(
        lambda: ref.init(np.random.default_rng(0), TINY))) == \
        jax.tree.structure(jax.eval_shape(
            make_task(TINY).init_params, jax.random.PRNGKey(0)))


def test_config_errors_name_the_key():
    for key, value in (("attention_bias", True),
                       ("tie_word_embeddings", True),
                       ("moe_apply_router_weight_on_input", True),
                       ("gating", False)):
        with pytest.raises(ValueError, match=key):
            make_task({**TINY, key: value})
    with pytest.raises(ValueError, match="experts_held"):
        make_task({**TINY, "experts_held": 14, "expert_offset": 4})
    with pytest.raises(ValueError, match="num_attention_heads_sliding"):
        make_task({**TINY, "num_attention_heads_sliding": 7})
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        make_task({**TINY, "partial_rotary_factor": 0.45})


def test_the_scopes_name_the_mechanisms_in_the_compiled_program():
    task, weights, batch = make_task(TINY), _weights(), _batch()
    text = jax.jit(jax.grad(
        lambda p: task.loss(p, batch, None, True)[0])).lower(
        weights).compile().as_text()
    for scope in ("embed", "gqa_proj", "gqa_attn_core", "dense_ffn",
                  "shared_expert", "routed_experts", "lm_head_loss"):
        assert f"/{scope}/" in text, scope


def test_the_cli_trains_evaluates_saves_and_resumes_and_the_counters_ride(
        tmp_path):
    """Two rounds of the real CLI on the benchmark's tiny root with its
    telemetry on, then two more from the saved state: the same engine,
    scan, writer and telemetry as the other token tasks."""
    from benchmarks import harness
    pa.drain_attention_events()  # what earlier tests' traces left behind
    root = os.path.join(REPO, "tests", "benchmarks", "data", "laguna_root")
    loaded = harness.load_cell(root, "tiny_laguna_cell")
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
        assert set(token_blocks.COUNTERS) <= set(span)
        assert span["moe_pairs_dropped"] == 0.0
        # 2 clients x 2 steps x 2 routed layers a round
        assert span["moe_layer_steps"] == 8.0 * span["rounds"]
    with open(os.path.join(models, "telemetry", "events.jsonl")) as fh:
        events = [json.loads(line) for line in fh]
    paths = [e for e in events if e.get("name") == "attention_path"]
    assert paths and all(e["impl"] == "plain" for e in paths)
    with open(os.path.join(out_dir, "log", "metrics.jsonl")) as fh:
        names = {json.loads(line).get("name") for line in fh}
    assert {"Val acc", "Val loss", "Test acc", "Test loss"} <= names, names
    cfg["server_config"]["max_iteration"] = 4
    cfg["server_config"]["resume_from_checkpoint"] = True
    assert harness.run_cli(cfg, doc["task"], data_dir, out_dir) == 0
    with open(os.path.join(models, "status_log.json")) as fh:
        assert json.load(fh)["i"] == 4
    with open(os.path.join(out_dir, "log", "log.out")) as fh:
        assert "resumed from checkpoint at round 2" in fh.read()
