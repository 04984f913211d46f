"""Plain reference: MLA-MoE (the DeepSeek-V3 block as kakaocorp's
Kanana-2-30B-A3B publishes it, ``model_type: deepseek_v3``) in
``jax.numpy``, float32, one chip's share of an expert-parallel
deployment.  Imports nothing of ``msrflute_tpu``; the tree's names are
those the program's module (``models/mla_moe.py``) gives its parameters,
so that the harness can hand the program these weights.  Keys are the
published config's.

Layer (``norm`` = RMSNorm with weight, eps ``rms_norm_eps``; no
projection has a bias; ``*`` elementwise; ``T`` positions of width
``hidden_size``; the leading ``first_k_dense_replace`` layers are dense,
every later one routed)::

    h = x + attn(norm_op(x));  y = h + ffn(norm_ffn(h))

after the last layer ``norm_emb``, then logits against ``head``, which is
NOT the embedding (``tie_word_embeddings: false``).

- latent attention: ``q = z W_q`` (no query latent: ``q_lora_rank``
  null), per head ``q = [q_nope(qk_nope_head_dim), q_pe(qk_rope_head_dim)]``;
  ``[c(kv_lora_rank), k_pe(qk_rope_head_dim)] = z W_kv_a``;
  ``c = norm_kv(c)``; per head ``[k_nope, v(v_head_dim)] = c W_kv_b``;
  ``q_pe`` and ``k_pe`` turned by RoPE on interleaved pairs
  ``(2i, 2i+1)`` at angle ``pos * rope_theta ** (-2i / qk_rope_head_dim)``
  (``rope_interleave: true``, no scaling); ``k_pe`` is ONE head,
  broadcast to all; ``k = [k_nope, k_pe]``;
  ``o = softmax_causal(q k^T / sqrt(nope + rope)) v``;
  ``attn = concat_heads(o) W_o``.  Written over blocks of query rows
  against the keys up to the block's end, so that the scores of a
  4,096-token row never stand whole;
- dense ``ffn(z) = W_2 (silu(W_1 z) * W_3 z)``, width ``intermediate_size``;
- routed ``ffn(z) = shared(z) + sum over chosen AND held i of g_i E_i(z)``:
  ``s = sigmoid(W_r z)`` over ALL ``n_routed_experts`` (float32 at
  ``highest`` whatever the context: a choice that flips on rounding is a
  discrete event); chosen = ``top_k(s + b)`` (``noaux_tc``; ``n_group =
  topk_group = 1``: no group limit); ``g_i = routed_scaling_factor * s_i
  / (sum of chosen s + 1e-20)``, the sum over all chosen experts, held or
  not; ``E_i`` a SwiGLU of width ``moe_intermediate_size``; ``shared`` a
  SwiGLU of width ``n_shared_experts x moe_intermediate_size`` on every
  token.  Written densely over the held experts with a mask: every held
  expert on every token, which is why ``required_flops`` is this file's
  own.

Departures from the published form, each also in the configuration's
file: (1) the HELD SHARE: experts ``expert_offset .. expert_offset +
experts_held - 1`` are here, what the absent experts would add is left
out and that partial result goes on to the next layer; (2) the
vocabulary is a slice (a smaller ``vocab_size``); (3) the selection bias
``b`` gets no gradient and stays as it is (upstream moves it by a
load-balancing rule outside the optimizer); (4) the init scales below
(``assumed``: the config publishes none): normal(0, 0.02) embedding,
head and projections, router columns normal(0, hidden ** -0.5) (logits
of order 1 on normed inputs, so the choice depends on the token),
selection bias normal(0, 0.1), norm weights 1.

Each layer, and each block of attention rows, is a ``jax.checkpoint``:
the backward pass computes it again instead of keeping what it made.
That changes no value and is not counted by ``required_flops``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import fedround

ATTENTION_ROWS = 2048  # query rows a block (two blocks a 4,096-token row:
# every block is two large products of its own for the compiler, PERF.md
# section 6, PR 28)
ROUTE_EPS = 1e-20


def _sizes(mc: dict) -> dict:
    out = {k: int(mc[k]) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok", "experts_held", "vocab_size",
        "num_hidden_layers", "first_k_dense_replace")}
    out["expert_offset"] = int(mc.get("expert_offset", 0))
    return out


def layer_kinds(model_config: dict) -> list:
    """``dense`` for the leading ``first_k_dense_replace`` layers,
    ``moe`` after them."""
    dense = int(model_config["first_k_dense_replace"])
    return ["dense" if i < dense else "moe"
            for i in range(int(model_config["num_hidden_layers"]))]


def init(rng: np.random.Generator, model_config: dict) -> dict:
    s = _sizes(model_config)
    hidden, heads = s["hidden_size"], s["num_attention_heads"]

    def normal(shape, std):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(std)

    def norm(width):
        return {"weight": np.ones((width,), np.float32)}

    def swiglu(width, lead=()):
        return {"w1": normal((*lead, hidden, width), 0.02),
                "w3": normal((*lead, hidden, width), 0.02),
                "w2": normal((*lead, width, hidden), 0.02)}

    params = {"embedding": normal((s["vocab_size"], hidden), 0.02),
              "head": normal((s["vocab_size"], hidden), 0.02),
              "norm_emb": norm(hidden)}
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    for i, ffn in enumerate(layer_kinds(model_config)):
        layer = {"norm_op": norm(hidden), "norm_ffn": norm(hidden), "attn": {
            "wq": normal((hidden, heads * qk), 0.02),
            "wkv_a": normal((hidden, s["kv_lora_rank"] +
                             s["qk_rope_head_dim"]), 0.02),
            "norm_kv": norm(s["kv_lora_rank"]),
            "wkv_b": normal((s["kv_lora_rank"], heads * (
                s["qk_nope_head_dim"] + s["v_head_dim"])), 0.02),
            "wo": normal((heads * s["v_head_dim"], hidden), 0.02)}}
        if ffn == "dense":
            layer["mlp"] = swiglu(s["intermediate_size"])
        else:
            layer["shared"] = swiglu(
                s["n_shared_experts"] * s["moe_intermediate_size"])
            layer["moe"] = {
                "router": normal((hidden, s["n_routed_experts"]),
                                 hidden ** -0.5),
                "select_bias": normal((s["n_routed_experts"],), 0.1),
                **swiglu(s["moe_intermediate_size"], (s["experts_held"],))}
        params[f"layer_{i}"] = layer
    return params


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["weight"]


def rope(x, theta):
    """``x``: ``[B, L, heads, D]``; RoPE at positions 0..L-1 on the
    interleaved pairs ``(2i, 2i+1)``."""
    length, dim = x.shape[1], x.shape[-1]
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * \
        theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)[None]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                       axis=-1)  # [.., D / 2, 2]: pair i at (2i, 2i+1)
    return turned.reshape(x.shape)


def _attention_rows(q_rows, k, v, row0):
    """Softmax attention of one block of query rows ``[B, R, H, D]``
    (starting at position ``row0``) over keys ``[B, M, H, D]`` and values
    ``[B, M, H, Dv]`` with ``M`` = the block's end."""
    scale = q_rows.shape[-1] ** -0.5
    scores = jnp.einsum("brhd,bmhd->bhrm", q_rows, k) * scale
    rows = row0 + jnp.arange(q_rows.shape[1])[:, None]
    cols = jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(cols <= rows, scores, -jnp.inf)
    return jnp.einsum("bhrm,bmhd->brhd", jax.nn.softmax(scores, axis=-1), v)


def _attention(z, p, s, eps, theta):
    batch, length, _ = z.shape
    heads, nope, pe = (s["num_attention_heads"], s["qk_nope_head_dim"],
                       s["qk_rope_head_dim"])
    latent = s["kv_lora_rank"]
    q = (z @ p["wq"]).reshape(batch, length, heads, nope + pe)
    down = z @ p["wkv_a"]
    c = _rms_norm(down[..., :latent], p["norm_kv"], eps)
    k_pe = rope(down[..., latent:].reshape(batch, length, 1, pe), theta)
    up = (c @ p["wkv_b"]).reshape(batch, length, heads,
                                  nope + s["v_head_dim"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([up[..., :nope], jnp.tile(k_pe, (1, 1, heads, 1))],
                        axis=-1)
    v = up[..., nope:]
    out = []
    for row0 in range(0, length, ATTENTION_ROWS):
        end = min(row0 + ATTENTION_ROWS, length)
        out.append(jax.checkpoint(_attention_rows, static_argnums=(3,))(
            q[:, row0:end], k[:, :end], v[:, :end], row0))
    return jnp.concatenate(out, axis=1).reshape(
        batch, length, heads * s["v_head_dim"]) @ p["wo"]


def _swiglu(z, p):
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
    gate = scale * picked / (jnp.sum(picked, axis=-1, keepdims=True) +
                             ROUTE_EPS)
    return chosen, gate


def routed_mlp(z, p, s, scale):
    """The held experts' part: every held expert on every token, times
    its gate where the token chose it, else 0."""
    chosen, gate = routing(z, p, s, scale)
    local = chosen - s["expert_offset"]
    dense_gate = jnp.sum(
        jax.nn.one_hot(local, s["experts_held"], dtype=gate.dtype) *
        gate[..., None], axis=-2)
    hidden = jax.nn.silu(jnp.einsum("bld,edh->bleh", z, p["w1"])) * \
        jnp.einsum("bld,edh->bleh", z, p["w3"])
    per_expert = jnp.einsum("bleh,ehd->bled", hidden, p["w2"])
    return jnp.einsum("bled,ble->bld", per_expert, dense_gate)


def x_mid(x, p, s, eps, theta):
    """A layer's residual stream after its attention."""
    return x + _attention(_rms_norm(x, p["norm_op"], eps), p["attn"], s, eps,
                          theta)


def _layer(x, p, ffn, s, eps, theta, scale):
    h = x_mid(x, p, s, eps, theta)
    z = _rms_norm(h, p["norm_ffn"], eps)
    if ffn == "dense":
        return h + _swiglu(z, p["mlp"])
    return h + _swiglu(z, p["shared"]) + routed_mlp(z, p["moe"], s, scale)


def _numbers(model_config: dict) -> tuple:
    return (_sizes(model_config), float(model_config["rms_norm_eps"]),
            float(model_config["rope_theta"]),
            float(model_config["routed_scaling_factor"]))


def forward(params: dict, x, model_config: dict):
    """Logits ``[B, L, vocab]`` for ids ``[B, L]``, float32 throughout."""
    s, eps, theta, scale = _numbers(model_config)
    h = params["embedding"][x]
    for i, ffn in enumerate(layer_kinds(model_config)):
        h = jax.checkpoint(
            lambda h, p, ffn=ffn: _layer(h, p, ffn, s, eps, theta, scale))(
            h, params[f"layer_{i}"])
    return _rms_norm(h, params["norm_emb"], eps) @ params["head"].T


def loss(params: dict, batch: dict, model_config: dict):
    return fedround.next_token_loss(forward, params, batch, model_config)


def sample_count(batch: dict):
    """The strategy's weight: the client's real rows."""
    return jnp.sum(batch["sample_mask"])


def held_pairs(params: dict, x, model_config: dict) -> list:
    """Per routed layer, the number of (token, chosen expert) pairs of
    ``x`` that fall on a held expert, by the reference's own routing of
    its own forward pass."""
    s, eps, theta, scale = _numbers(model_config)
    counts = []
    h = params["embedding"][x]
    for i, ffn in enumerate(layer_kinds(model_config)):
        p = params[f"layer_{i}"]
        if ffn == "moe":
            z = _rms_norm(x_mid(h, p, s, eps, theta), p["norm_ffn"], eps)
            local = routing(z, p["moe"], s, scale)[0] - s["expert_offset"]
            counts.append(jnp.sum((local >= 0) & (local < s["experts_held"]),
                                  axis=-1))
        h = _layer(h, p, ffn, s, eps, theta, scale)
    return counts


def required_flops(params: dict, batch: dict, model_config: dict) -> float:
    """Matmul operations ONE forward + backward of the step's loss needs
    (3 x the forward's: each product once forward, twice backward): every
    projection on every real input position (``W_q``, ``W_kv_a``,
    ``W_kv_b``, ``W_o``, the dense or the shared SwiGLU, the router), the
    routed experts' three products on the token-expert pairs that fall on
    HELD experts only (counted from this batch's own routing), causal
    attention's two products at half the square (position t reads t + 1
    keys: scores over ``nope + rope``, values over ``v_head_dim``), the
    untied head.  The gather of the embedding is no matmul.  Nothing for
    recomputation, nothing for the masked half of the square that a
    blocked plain path multiplies."""
    s = _sizes(model_config)
    hidden, heads = s["hidden_size"], s["num_attention_heads"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
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
    attention = tokens * (
        hidden * heads * qk + hidden * (s["kv_lora_rank"] +
                                        s["qk_rope_head_dim"]) +
        s["kv_lora_rank"] * heads * (s["qk_nope_head_dim"] +
                                     s["v_head_dim"]) +
        heads * s["v_head_dim"] * hidden)
    # scores and values: row t of a real row of length n reads t + 1
    # keys, n (n + 1) / 2 in all, per head
    attention += heads * (qk + s["v_head_dim"]) * float(
        np.sum(lengths * (lengths + 1) / 2))
    macs = tokens * hidden * s["vocab_size"]
    for ffn in layer_kinds(model_config):
        macs += attention
        if ffn == "dense":
            macs += tokens * 3 * hidden * s["intermediate_size"]
        else:
            macs += tokens * 3 * hidden * (
                s["n_shared_experts"] * s["moe_intermediate_size"])
            macs += tokens * hidden * s["n_routed_experts"]
            on_held = float(jnp.sum(jnp.where(real, next(pairs), 0)))
            macs += on_held * 3 * hidden * s["moe_intermediate_size"]
    return 3.0 * 2.0 * macs
