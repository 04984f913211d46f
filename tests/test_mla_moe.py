"""MLA-MoE (``models/mla_moe.py``, ``ops/moe.py``) against the plain
reference (``benchmarks/reference/mla_moe.py``) at a tiny size on the
CPU: hidden 64, 4 heads of 16 + 8 (keys) / 16 (values), latent 32, one
dense layer and four routed ones, 16 experts of which 4 are held, 3 a
token, 2 shared.

Tolerances: both sides are float32 under ``highest``, so what separates
them is summation order (the program sums a token's experts from a
sorted pair buffer, the reference from a dense masked product; the
blocks of attention rows differ): 1e-5 of a leaf's largest gradient
covers it (the readings are 7e-7), and every planted fault of
``tests/benchmarks/test_benchmark_mla.py`` reads 1e-2 or more.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.reference import mla_moe as ref  # noqa: E402
from msrflute_tpu.models import make_task, mla_moe, token_blocks  # noqa: E402
from msrflute_tpu.ops import moe  # noqa: E402

TINY = dict(
    model_type="MLA_MOE", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, q_lora_rank=None,
    n_routed_experts=16, n_shared_experts=2, num_experts_per_tok=3,
    experts_held=4, expert_offset=0, routed_scaling_factor=2.448,
    rms_norm_eps=1e-6, rope_theta=1e6, first_k_dense_replace=1,
    num_hidden_layers=5, vocab_size=96, seq_len=16, attention_block=8)


def _weights(seed=3, **over):
    return ref.init(np.random.default_rng(seed), {**TINY, **over})


def _batch(seed=4, rows=2, length=17):
    ids = np.random.default_rng(seed).integers(1, 96, size=(rows, length))
    return {"x": jnp.asarray(ids, jnp.int32),
            "sample_mask": jnp.ones((rows,), jnp.float32)}


@pytest.fixture(autouse=True)
def _highest(monkeypatch):
    # the reference in blocks of 8 rows too, but on other boundaries
    # than the program's would be at 2,048: here both have two blocks
    monkeypatch.setattr(ref, "ATTENTION_ROWS", 12)
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("over", [{}, {"remat": True},
                                  {"expert_offset": 8}],
                         ids=["plain", "remat", "offset8"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(over):
    config = {**TINY, **over}
    task, weights, batch = make_task(config), _weights(**over), _batch()
    assert jax.tree.structure(task.init_params(jax.random.PRNGKey(0))) == \
        jax.tree.structure(weights)
    inputs = batch["x"][:, :-1]
    logits = jax.jit(lambda p: task._apply(p, inputs))(weights)
    want_logits = jax.jit(lambda p: ref.forward(p, inputs, config))(weights)
    assert float(jnp.max(jnp.abs(logits - want_logits))) < 1e-5 * float(
        jnp.max(jnp.abs(want_logits)))
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
    # the head is its own leaf, and the embedding's gradient is the
    # gather's alone
    assert float(jnp.max(jnp.abs(grads["head"] - grads["embedding"]))) > 0
    # the selection bias enters the choice only
    for name, layer in grads.items():
        if "moe" in getattr(layer, "keys", lambda: ())():
            assert not np.any(np.asarray(layer["moe"]["select_bias"])), name
    # what the routed layers counted: four of them, one step
    counters = aux["counters"]
    assert set(counters) == set(token_blocks.COUNTERS)
    assert float(counters["moe_layer_steps"]) == 4.0
    assert float(counters["moe_pairs_dropped"]) == 0.0
    held = jax.jit(lambda p: ref.held_pairs(p, inputs, config))(weights)
    assert float(counters["moe_pairs_held"]) == sum(
        float(jnp.sum(c)) for c in held)
    # 32 tokens x 3 choices never fill a tile: one tile a held expert
    assert float(counters["moe_tiles_active"]) == 4.0 * TINY["experts_held"]


def test_bf16_path_is_the_lower_precision_it_says():
    batch, weights = _batch(), _weights()
    exact = float(jax.jit(lambda p: ref.loss(p, batch, TINY))(weights))
    task = make_task({**TINY, "dtype": "bfloat16"})
    low = float(jax.jit(
        lambda p: task.loss(p, batch, None, True)[0])(weights))
    assert 1e-5 < abs(low - exact) / exact < 5e-2


# ----------------------------------------------------------------------
# RoPE: interleaved pairs, against the form the source implements
# ----------------------------------------------------------------------
def _rotate_half_rope(x, theta):
    """The published ``apply_rotary_pos_emb_interleave``: de-interleave
    (even elements first), then the rotate-half rotation."""
    length, dim = x.shape[1], x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    half = dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


@pytest.mark.parametrize("rope", [mla_moe.rope_interleaved, ref.rope],
                         ids=["program", "reference"])
def test_interleaved_rope_is_the_sources_deinterleave_then_rotate_half(rope):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 9, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 9, 1, 8)), jnp.float32)
    ours_q, ours_k = rope(q, 1e6), rope(k, 1e6)
    theirs_q, theirs_k = _rotate_half_rope(q, 1e6), _rotate_half_rope(k, 1e6)
    # the source's result is ours with the even elements first ...
    order = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    assert float(jnp.max(jnp.abs(ours_q[..., order] - theirs_q))) < 1e-6
    # ... and so every query-key product is the same
    ours = jnp.einsum("bqhd,bkgd->bhqk", ours_q, ours_k)
    theirs = jnp.einsum("bqhd,bkgd->bhqk", theirs_q, theirs_k)
    assert float(jnp.max(jnp.abs(ours - theirs))) < 1e-5
    # position 0 is not turned, and pair i of position 1 by theta^(-2i/8)
    assert float(jnp.max(jnp.abs(ours_q[:, 0] - q[:, 0]))) == 0.0
    angle = 1e6 ** (-2.0 / 8)
    want = q[:, 1, :, 2] * np.cos(angle) - q[:, 1, :, 3] * np.sin(angle)
    assert float(jnp.max(jnp.abs(ours_q[:, 1, :, 2] - want))) < 1e-6


# ----------------------------------------------------------------------
# the share
# ----------------------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold four of the 16 experts each.  What every chip
    computes alike (attention, the residual stream and the SHARED expert)
    counted once, plus the four shares' parts of the routed experts, is
    the layer the uncut reference computes with all sixteen held."""
    rng = np.random.default_rng(0)
    uncut_cfg = {**TINY, "experts_held": 16, "first_k_dense_replace": 0,
                 "num_hidden_layers": 1}
    sizes = ref._sizes(uncut_cfg)
    p = ref.init(rng, uncut_cfg)["layer_0"]
    x = jnp.asarray(rng.standard_normal((1, 48, 64)), jnp.float32)
    uncut = ref._layer(x, p, "moe", sizes, 1e-6, 1e6, 2.448)
    h = ref.x_mid(x, p, sizes, 1e-6, 1e6)
    z = ref._rms_norm(h, p["norm_ffn"], 1e-6)
    m = p["moe"]
    total = (h + ref._swiglu(z, p["shared"]))[0]
    pairs = 0.0
    for share in range(4):
        held = slice(4 * share, 4 * share + 4)
        part, counters = moe.held_experts_ffn(
            z[0], m["router"], m["select_bias"], m["w1"][held],
            m["w3"][held], m["w2"][held], experts_per_token=3,
            expert_offset=4 * share, scaling=2.448,
            route_eps=mla_moe.ROUTE_EPS)
        # the reference's own share is the same part
        cut = {**sizes, "experts_held": 4, "expert_offset": 4 * share}
        want = ref.routed_mlp(z, {**m, "w1": m["w1"][held],
                                  "w3": m["w3"][held],
                                  "w2": m["w2"][held]}, cut, 2.448)[0]
        assert float(jnp.max(jnp.abs(part - want))) < 1e-6
        total = total + part
        pairs += float(counters["moe_pairs_held"])
    assert pairs == 48 * 3  # every pair is held by one share
    assert float(jnp.max(jnp.abs(total - uncut[0]))) < 1e-5 * float(
        jnp.max(jnp.abs(uncut)))


def test_the_gates_epsilon_is_the_callers():
    """``route_tokens`` divides by the chosen scores' sum + the caller's
    epsilon: LFM2's 1e-6 where none is given, this model's 1e-20."""
    rng = np.random.default_rng(1)
    z = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((64, 16)) - 3.0, jnp.float32)
    bias = jnp.zeros((16,), jnp.float32)
    scores = jax.nn.sigmoid(jnp.matmul(z, router))
    top = jnp.sort(scores, axis=-1)[:, -3:]
    _, ours = moe.route_tokens(z, router, bias, 3, 2.448, eps=1e-20)
    _, lfm2s = moe.route_tokens(z, router, bias, 3, 2.448)
    assert moe.ROUTE_EPS == 1e-6 and mla_moe.ROUTE_EPS == 1e-20
    np.testing.assert_allclose(np.sort(ours, axis=-1),
                               2.448 * top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.sort(lfm2s, axis=-1),
        2.448 * top / (top.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert float(jnp.sum(ours, -1).min()) > float(jnp.sum(lfm2s, -1).max())


# ----------------------------------------------------------------------
# the configuration's count, the errors, the counter's way to host_tail
# ----------------------------------------------------------------------
def test_the_built_tree_has_the_parameters_the_configuration_counts():
    import yaml
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kanana2_30b_a3b_ep16share.json")) as fh:
        doc = json.load(fh)
    with open(os.path.join(REPO, doc["base_yaml"])) as fh:
        mc = yaml.safe_load(fh)["model_config"]
    shapes = jax.eval_shape(make_task(mc).init_params, jax.random.PRNGKey(0))
    total = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    count = doc["parameters"]
    assert total == count["total"] == 424_961_024
    assert count["bytes_float32"] == 4 * total
    assert count["dense_layer"] + count["routed_layers"] * \
        count["routed_layer"] + count["embedding_and_head"] + \
        count["final_norm"] == total
    layer = shapes["layer_1"]
    assert sum(leaf.size for leaf in jax.tree.leaves(layer)) == \
        count["routed_layer"]
    assert sum(leaf.size for leaf in jax.tree.leaves(layer["attn"])) == \
        count["attention"]
    assert shapes["head"].shape == shapes["embedding"].shape == (16032, 2048)


def test_config_errors_name_the_key():
    for key, value in (("q_lora_rank", 1536), ("rope_interleave", False),
                       ("n_group", 8), ("tie_word_embeddings", True),
                       ("scoring_func", "softmax")):
        with pytest.raises(ValueError, match=key):
            make_task({**TINY, key: value})
    with pytest.raises(ValueError, match="experts_held"):
        make_task({**TINY, "experts_held": 12, "expert_offset": 8})
    # no routed layer, no counters
    assert make_task({**TINY, "first_k_dense_replace": 5}).counter_names == ()
    assert make_task(TINY).counter_names == token_blocks.COUNTERS


def test_the_scopes_name_the_mechanisms_in_the_compiled_program():
    task, weights, batch = make_task(TINY), _weights(), _batch()
    text = jax.jit(jax.grad(
        lambda p: task.loss(p, batch, None, True)[0])).lower(
        weights).compile().as_text()
    for scope in ("mla_proj", "mla_attn_core", "shared_expert",
                  "routed_experts"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("root, cell", [("mla_root", "tiny_mla_cell"),
                                        ("lfm2_root", "tiny_lfm2_cell")])
def test_the_tile_counter_rides_onto_host_tail_for_both_token_models(
        tmp_path, root, cell):
    """Two rounds of the real CLI with the benchmark's telemetry on:
    every ``host_tail`` span says how many tiles the grouped products
    ran, and the benchmark's reader makes the fill of it."""
    from benchmarks import harness
    root = os.path.join(REPO, "tests", "benchmarks", "data", root)
    loaded = harness.load_cell(root, cell)
    doc = loaded["config_doc"]
    cfg = harness.build_config(loaded, True, None)
    cfg["server_config"]["max_iteration"] = 2
    data_dir, out_dir = str(tmp_path / "data"), str(tmp_path / "out")
    harness.load_generator(root, doc["data"]).write_splits(
        data_dir, 7, doc["data"])
    assert harness.run_cli(cfg, doc["task"], data_dir, out_dir) == 0
    spans = harness.read_spans(out_dir)
    tails = [s for s in spans if s["name"] == "host_tail"]
    assert tails
    held = cfg["model_config"]["experts_held"]
    for span in tails:
        assert set(token_blocks.COUNTERS) <= set(span)
        # at these sizes no expert fills a tile: one tile an expert
        assert span["moe_tiles_active"] == held * span["moe_layer_steps"]
        assert span["moe_pairs_dropped"] == 0.0
    fill = harness.load_layer_metrics(harness.BENCH_DIR)[
        "expert_tile_fill"].read(
        {"spans": spans, "window": {"t_open": 0.0, "t_close": 1e12}})
    assert fill == pytest.approx(
        100.0 * sum(s["moe_pairs_held"] for s in tails) /
        (moe.TILE_ROWS * sum(s["moe_tiles_active"] for s in tails)))
    assert 0.0 < fill < 100.0


def test_a_local_step_through_the_attention_kernels_is_the_plain_paths(
        monkeypatch):
    """The cell's local step with the causal core forced through the tiled
    kernels (``interpret=True``; ``remat`` on, as the cell has it): the
    loss and every gradient leaf are the plain path's to float32
    rounding (the fixture's ``highest``: float32 operands, contracted in
    full), and the trace says which path each took."""
    import functools
    from msrflute_tpu.ops import pallas_attention as pa
    config = {**TINY, "remat": True}
    task, weights, batch = make_task(config), _weights(), _batch()

    def step(p):
        return jax.value_and_grad(
            lambda q: task.loss(q, batch, None, True)[0])(p)

    pa.drain_attention_events()
    want, want_grads = jax.jit(step)(weights)
    said = pa.drain_attention_events()
    assert [e["impl"] for e in said] == ["plain"], said
    monkeypatch.setattr(mla_moe, "causal_attention", functools.partial(
        token_blocks.causal_attention, interpret=True))
    # another function object: jit would hand back ``step``'s program
    loss, grads = jax.jit(lambda p: step(p))(weights)
    said = pa.drain_attention_events()
    assert [e["kind"] for e in said] == ["attention_path"], said
    assert said[0]["impl"] == "flash"
    # 16 tokens (padded to two plain blocks of 8), 4 heads of 16 + 8 / 16
    assert said[0]["q_shape"] == [2, 16, 4, 24]
    assert said[0]["v_shape"] == [2, 16, 4, 16]
    assert abs(float(loss) - float(want)) < 1e-6 * abs(float(want))
    for (path, got), exp in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want_grads)):
        scale = max(float(jnp.max(jnp.abs(exp))), 1e-4)
        assert float(jnp.max(jnp.abs(got - exp))) < 1e-5 * scale, \
            jax.tree_util.keystr(path)
