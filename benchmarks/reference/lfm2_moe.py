"""Plain reference: LFM2-MoE (LiquidAI LFM2-24B-A2B) in ``jax.numpy``,
float32, one chip's share of an expert-parallel deployment.  Imports
nothing of ``msrflute_tpu``; the tree's names are those the program's
module (``models/lfm2.py``) gives its parameters, so that the harness can
hand the program these weights.

Layer (``norm`` = RMSNorm with weight, eps ``norm_eps``; no projection
has a bias; ``*`` elementwise)::

    h = x + op(norm_op(x));  y = h + ffn(norm_ffn(h))

after the last layer ``norm_emb``, then logits against the TIED
embedding.

- gated short convolution: ``[B, C, u] = split3(W_in z)``;
  ``v_t = sum_{j<3} w_j * (B * u)_{t-j}`` (depthwise, causal, zeros before
  the row's start); ``op(z) = W_out (C * v)``;
- attention: ``q = norm_q(W_q z)``, ``k = norm_k(W_k z)`` (RMSNorm per
  head over the head size), RoPE (rotate-half) on both, ``v = W_v z``;
  each group of ``heads / kv_heads`` query heads shares one key-value
  head; causal softmax, scale ``head_dim ** -0.5``; ``op(z) = W_o
  concat(heads)``.  Written over blocks of query rows against the keys
  up to the block's end, so that the scores of a 4,096-token row never
  stand whole (32 x 4096 x 4096 floats are 2.1 GB, and the backward
  pass keeps several);
- dense MLP (the leading layers): ``W_2 (silu(W_1 z) * W_3 z)``;
- expert MLP: ``s = sigmoid(W_r z)`` over ALL ``num_experts`` (float32 at
  ``highest`` whatever the context: a choice that flips on rounding is a
  discrete event); ``I = top_k(s + b)`` (``b`` the selection bias: it
  enters the choice only, gets no gradient and stays as it is);
  ``g_i = s_i / (sum_{j in I} s_j + 1e-6) * routed_scaling_factor``, the
  sum over all chosen experts, held or not;
  ``ffn(z) = sum_{i in I and held} g_i E_i(z)``, ``E_i`` a SwiGLU.  The
  held experts are ``expert_offset .. expert_offset + experts_held - 1``;
  what the absent experts would add is left out.  Written densely over
  the held experts with a mask: every held expert on every token, which
  is why ``required_flops`` is this file's own.

Each layer, and each block of attention rows, is a ``jax.checkpoint``:
the backward pass computes it again instead of keeping what it made.
That changes no value and is not counted by ``required_flops``; it is
what lets the reference's round (weights, one client's weights, its
gradient, the aggregate) fit the chip beside a 4,096-token row.

Init (``assumed``, the family publishes no scheme in its config):
normal(0, 0.02) embedding and projections, conv taps normal(0, 0.5),
router columns normal(0, hidden ** -0.5) (logits of order 1 on normed
inputs, so the choice depends on the token), selection bias
normal(0, 0.1), norm weights 1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import fedround

ATTENTION_ROWS = 512  # query rows a block


def layer_kinds(model_config: dict) -> list:
    """``[(operator, ffn)]`` per layer: operator ``conv`` or
    ``full_attention``, ffn ``dense`` (the leading ``num_dense_layers``)
    or ``moe``."""
    ops = [t.strip() for t in str(model_config["layer_types"]).split(",")]
    dense = int(model_config["num_dense_layers"])
    return [(op, "dense" if i < dense else "moe")
            for i, op in enumerate(ops)]


def _sizes(mc: dict) -> dict:
    out = {k: int(mc[k]) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "conv_L_cache", "num_experts", "num_experts_per_tok",
        "experts_held", "vocab_size")}
    out["expert_offset"] = int(mc.get("expert_offset", 0))
    return out


def init(rng: np.random.Generator, model_config: dict) -> dict:
    s = _sizes(model_config)
    hidden = s["hidden_size"]

    def normal(shape, std):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(std)

    def norm(width):
        return {"weight": np.ones((width,), np.float32)}

    params = {"embedding": normal((s["vocab_size"], hidden), 0.02),
              "norm_emb": norm(hidden)}
    q_width = s["num_attention_heads"] * s["head_dim"]
    kv_width = s["num_key_value_heads"] * s["head_dim"]

    def one_layer(op, ffn):
        layer = {"norm_op": norm(hidden), "norm_ffn": norm(hidden)}
        if op == "conv":
            layer["conv"] = {
                "w_in": normal((hidden, 3 * hidden), 0.02),
                "w_conv": normal((hidden, s["conv_L_cache"]), 0.5),
                "w_out": normal((hidden, hidden), 0.02)}
        else:
            layer["attn"] = {
                "wq": normal((hidden, q_width), 0.02),
                "wk": normal((hidden, kv_width), 0.02),
                "wv": normal((hidden, kv_width), 0.02),
                "wo": normal((q_width, hidden), 0.02),
                "norm_q": norm(s["head_dim"]),
                "norm_k": norm(s["head_dim"])}
        if ffn == "dense":
            width = s["intermediate_size"]
            layer["mlp"] = {"w1": normal((hidden, width), 0.02),
                            "w3": normal((hidden, width), 0.02),
                            "w2": normal((width, hidden), 0.02)}
        else:
            held, width = s["experts_held"], s["moe_intermediate_size"]
            layer["moe"] = {
                "router": normal((hidden, s["num_experts"]), hidden ** -0.5),
                "select_bias": normal((s["num_experts"],), 0.1),
                "w1": normal((held, hidden, width), 0.02),
                "w3": normal((held, hidden, width), 0.02),
                "w2": normal((held, width, hidden), 0.02)}
        return layer

    for i, (op, ffn) in enumerate(layer_kinds(model_config)):
        params[f"layer_{i}"] = one_layer(op, ffn)
    return params


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["weight"]


def _conv_op(z, p, taps):
    hidden = z.shape[-1]
    gate_b, gate_c, u = jnp.split(z @ p["w_in"], 3, axis=-1)
    bu = gate_b * u
    padded = jnp.pad(bu, ((0, 0), (taps - 1, 0), (0, 0)))
    length = z.shape[1]
    v = sum(p["w_conv"][:, j] * padded[:, taps - 1 - j:taps - 1 - j + length]
            for j in range(taps))
    assert v.shape[-1] == hidden
    return (gate_c * v) @ p["w_out"]


def _rope(x, theta):
    """``x``: ``[B, L, heads, D]``; rotate-half RoPE at positions 0..L-1."""
    length, dim = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    half = dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def _attention_rows(q_rows, k, v, row0):
    """Softmax attention of one block of query rows ``[B, R, KV, G, D]``
    (starting at position ``row0``) over keys ``[B, M, KV, D]`` with
    ``M`` = the block's end."""
    scale = q_rows.shape[-1] ** -0.5
    scores = jnp.einsum("brkgd,bmkd->bkgrm", q_rows, k) * scale
    rows = row0 + jnp.arange(q_rows.shape[1])[:, None]
    cols = jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(cols <= rows, scores, -jnp.inf)
    return jnp.einsum("bkgrm,bmkd->brkgd",
                      jax.nn.softmax(scores, axis=-1), v)


def _attention_op(z, p, s, eps, theta):
    batch, length, _ = z.shape
    heads, kv, dim = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
    q = _rms_norm((z @ p["wq"]).reshape(batch, length, heads, dim),
                  p["norm_q"], eps)
    k = _rms_norm((z @ p["wk"]).reshape(batch, length, kv, dim),
                  p["norm_k"], eps)
    v = (z @ p["wv"]).reshape(batch, length, kv, dim)
    q, k = _rope(q, theta), _rope(k, theta)
    # query head h reads key-value head h // (heads / kv)
    q = q.reshape(batch, length, kv, heads // kv, dim)
    out = []
    for row0 in range(0, length, ATTENTION_ROWS):
        end = min(row0 + ATTENTION_ROWS, length)
        out.append(jax.checkpoint(_attention_rows, static_argnums=(3,))(
            q[:, row0:end], k[:, :end], v[:, :end], row0))
    out = jnp.concatenate(out, axis=1).reshape(batch, length, heads * dim)
    return out @ p["wo"]


def _dense_mlp(z, p):
    return (jax.nn.silu(z @ p["w1"]) * (z @ p["w3"])) @ p["w2"]


def routing(z, p, s, scale):
    """``(chosen [.., k] int32, gate [.., k])`` of every token: the top
    ``k`` of ``sigmoid(W_r z) + b`` and their renormalised scores."""
    logits = jnp.matmul(z.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["select_bias"]),
        s["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gate = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6) * scale
    return chosen, gate


def _expert_mlp(z, p, s, scale):
    chosen, gate = routing(z, p, s, scale)
    held = s["experts_held"]
    # [.., held]: a held expert's gate where a token chose it, else 0
    local = chosen - s["expert_offset"]
    dense_gate = jnp.sum(
        jax.nn.one_hot(local, held, dtype=gate.dtype) * gate[..., None],
        axis=-2)
    hidden = jax.nn.silu(jnp.einsum("bld,edh->bleh", z, p["w1"])) * \
        jnp.einsum("bld,edh->bleh", z, p["w3"])
    per_expert = jnp.einsum("bleh,ehd->bled", hidden, p["w2"])
    return jnp.einsum("bled,ble->bld", per_expert, dense_gate)


def _layer(x, p, op, ffn, s, eps, theta, scale):
    h = x_mid(x, p, op, s, eps, theta)
    z = _rms_norm(h, p["norm_ffn"], eps)
    if ffn == "dense":
        return h + _dense_mlp(z, p["mlp"])
    return h + _expert_mlp(z, p["moe"], s, scale)


def forward(params: dict, x, model_config: dict):
    """Logits ``[B, L, vocab]`` for ids ``[B, L]``, float32 throughout."""
    s = _sizes(model_config)
    eps = float(model_config["norm_eps"])
    theta = float(model_config["rope_theta"])
    scale = float(model_config.get("routed_scaling_factor", 1.0))
    h = params["embedding"][x]
    for i, (op, ffn) in enumerate(layer_kinds(model_config)):
        h = jax.checkpoint(
            lambda h, p, op=op, ffn=ffn: _layer(h, p, op, ffn, s, eps,
                                                theta, scale))(
            h, params[f"layer_{i}"])
    h = _rms_norm(h, params["norm_emb"], eps)
    return h @ params["embedding"].T


def loss(params: dict, batch: dict, model_config: dict):
    return fedround.next_token_loss(forward, params, batch, model_config)


def sample_count(batch: dict):
    """The strategy's weight: the client's real rows."""
    return jnp.sum(batch["sample_mask"])


def held_pairs(params: dict, x, model_config: dict) -> list:
    """Per expert layer, the number of (token, chosen expert) pairs of
    ``x`` that fall on a held expert, by the reference's own routing of
    its own forward pass."""
    s = _sizes(model_config)
    eps = float(model_config["norm_eps"])
    theta = float(model_config["rope_theta"])
    scale = float(model_config.get("routed_scaling_factor", 1.0))
    counts = []
    h = params["embedding"][x]
    for i, (op, ffn) in enumerate(layer_kinds(model_config)):
        p = params[f"layer_{i}"]
        if ffn == "moe":
            z = _rms_norm(x_mid(h, p, op, s, eps, theta), p["norm_ffn"], eps)
            local = routing(z, p["moe"], s, scale)[0] - s["expert_offset"]
            counts.append(jnp.sum((local >= 0) & (local < s["experts_held"]),
                                  axis=-1))
        h = _layer(h, p, op, ffn, s, eps, theta, scale)
    return counts


def x_mid(x, p, op, s, eps, theta):
    """A layer's residual stream after its operator."""
    z = _rms_norm(x, p["norm_op"], eps)
    if op == "conv":
        return x + _conv_op(z, p["conv"], s["conv_L_cache"])
    return x + _attention_op(z, p["attn"], s, eps, theta)


def required_flops(params: dict, batch: dict, model_config: dict) -> float:
    """Matmul operations ONE forward + backward of the step's loss needs
    (3 x the forward's: each product once forward, twice backward): every
    projection on every real input position, the experts' three products
    on the token-expert pairs that fall on HELD experts only (counted
    from this batch's own routing), causal attention's two products at
    half the square (position t reads t + 1 keys), the tied head.  The
    router counts; the convolution's three taps and the gather of the
    embedding are no matmuls.  Nothing for recomputation."""
    s = _sizes(model_config)
    hidden, dim = s["hidden_size"], s["head_dim"]
    x = batch["x"][:, :-1]
    real = batch.get("tok_mask")
    real = (x != 0) if real is None else real[:, :-1] > 0
    real = real & (batch["sample_mask"][:, None] > 0)
    tokens = float(jnp.sum(real))
    lengths = np.asarray(jnp.sum(real, axis=1), np.float64)
    # one program, not an operation at a time: the forward pass of a
    # 4,096-token row run eagerly compiles every operation on its own
    pairs = iter(jax.jit(lambda p, ids: held_pairs(p, ids, model_config))(
        params, x))
    macs = tokens * hidden * s["vocab_size"]
    for op, ffn in layer_kinds(model_config):
        if op == "conv":
            macs += tokens * (3 * hidden * hidden + hidden * hidden)
        else:
            q_width = s["num_attention_heads"] * dim
            kv_width = s["num_key_value_heads"] * dim
            macs += tokens * (2 * hidden * q_width + 2 * hidden * kv_width)
            # scores and values: row t of a real row of length n reads
            # t + 1 keys, n (n + 1) / 2 in all, per query head
            macs += 2.0 * s["num_attention_heads"] * dim * float(
                np.sum(lengths * (lengths + 1) / 2))
        if ffn == "dense":
            macs += tokens * 3 * hidden * s["intermediate_size"]
        else:
            macs += tokens * hidden * s["num_experts"]
            on_held = float(jnp.sum(jnp.where(real, next(pairs), 0)))
            macs += on_held * 3 * hidden * s["moe_intermediate_size"]
    return 3.0 * 2.0 * macs
