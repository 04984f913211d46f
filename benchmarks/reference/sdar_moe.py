"""Plain reference: SDAR-MoE (JetLM's SDAR-30B-A3B-Chat, ``model_type:
sdar_moe``: the ``qwen3_moe`` block) trained under the block-diffusion
objective, in ``jax.numpy``, float32, one chip's share of an
expert-parallel deployment.  Imports nothing of ``msrflute_tpu``; the
tree's names are those the program's module (``models/sdar_moe.py``)
gives its parameters, so that the harness can hand the program these
weights.  Keys are the published config's.

Layer, EVERY layer routed (``decoder_sparse_step`` 1, ``mlp_only_layers``
[]), for hidden states ``x [T, hidden_size]`` at positions ``pos [T]``
(``norm`` = RMSNorm with weight, eps ``rms_norm_eps``; no bias anywhere)::

    h = x + attn(norm_op(x));  out = h + moe(norm_ffn(h))

- ``attn``: ``q = z W_q`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k = z W_k`` and ``v = z W_v`` as ``num_key_value_heads``
  heads; ``q``, ``k`` each through an RMSNorm over a head's elements (one
  weight of ``head_dim`` each, shared by the heads); RoPE on all elements
  in the rotate-half layout (element ``i`` with ``i + head_dim / 2``,
  angle ``pos * rope_theta ** (-2i / head_dim)``, no scaling); query head
  ``h`` reads key-value head ``h // (heads / kv heads)``; scores
  ``q k^T * head_dim ** -0.5``, masked by ``seen`` below, softmax, times
  ``v``; ``attn = concat(heads) W_o``.  Written over blocks of query rows
  against the keys they can see, the mask a boolean array built from
  ``seen``, so that the scores of a row never stand whole;
- ``moe``: ``p = softmax(u W_r)`` over ALL ``num_experts`` (logits
  float32 at ``highest`` whatever the context: a choice that flips on
  rounding is a discrete event); chosen = top ``num_experts_per_tok`` of
  ``p``; ``g_i = p_i / sum of the chosen p`` (``norm_topk_prob``);
  ``y = sum over chosen AND held i of g_i E_i(u)``, ``E_i(u) = (silu(u
  W1_i) * (u W3_i)) W2_i``.  No shared expert, no selection bias, no
  factor.  Written densely over the held experts with a mask: every held
  expert on every token, which is why ``required_flops`` is this file's
  own;
- after the last layer ``norm_emb`` and logits against ``head`` (NOT the
  embedding), on the ``xt`` half only.

Objective (block diffusion: the vectorised training form of BD3-LM,
arXiv:2503.09573, which SDAR, arXiv:2510.06303, adopts).  For a clean
row ``x0`` of ``L`` ids in blocks of ``B = block_length``: ``xt_i`` = the
mask id (the vocabulary's last id) where the batch's ``bd_mask_i`` is
set, else ``x0_i``; the model reads ``[xt ; x0]``, ``2 L`` positions,
``pos = (0..L-1, 0..L-1)``; with ``blk(i) = (i mod L) // B``::

    seen(q, k):  xt query, xt key: blk(k) == blk(q)
                 xt query, x0 key: blk(k) <  blk(q)
                 x0 query, x0 key: blk(k) <= blk(q)
                 x0 query, xt key: never

``loss = sum over rows and masked real positions i of bd_weight_i *
(-log softmax(logits_i)[x0_i]) / sum over rows of real positions``: no
shift between a position's logits and its target; the step's sample
count is its real rows.

Departures from the published form, each also in the configuration's
file: (1) the HELD SHARE: experts ``expert_offset .. expert_offset +
experts_held - 1`` are here, what the absent experts would add is left
out and that partial result goes on to the next layer; (2) the
vocabulary is a slice (a smaller ``vocab_size``), its last id the mask;
(3) the NOISE is an input: ``bd_mask`` and ``bd_weight`` (``1 / t`` of
the position's block, ``t`` uniform on ``[0.05, 1]``) come with the
packed batch, drawn once a row when the dataset is built, where
upstream draws afresh at every step; the row gives neither block length
nor schedule, so both are assumed; (4) a row shorter than ``L`` is
padded with id 0 and its last block sees that padding beside it (the
benchmark's rows are full); (5) the init scales below (``assumed``):
normal(0, 0.02) embedding, head and projections, router columns
normal(0, hidden ** -0.5), norm weights 1.

Each layer, and each block of attention rows, is a ``jax.checkpoint``:
the backward pass computes it again instead of keeping what it made.
That changes no value and is not counted by ``required_flops``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ATTENTION_ROWS = 2048  # query rows a block: four blocks a doubled row of
# 8,192 positions, each two large products of its own for the compiler
# (eight blocks of 1,024 cost a cold run 77 s of the reference's compiles:
# PERF.md section 6, PR 41); the noised block of rows 2,048..4,095 sees
# 6,140 keys: 1.6 GB of float32 scores for 32 heads, after the program's
# state is freed


def _sizes(mc: dict) -> dict:
    out = {k: int(mc[k]) for k in (
        "hidden_size", "moe_intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_experts",
        "num_experts_per_tok", "experts_held", "vocab_size",
        "num_hidden_layers")}
    out["expert_offset"] = int(mc.get("expert_offset", 0))
    out["block_length"] = int(mc.get("block_length", 4))
    out["mask_token_id"] = out["vocab_size"] - 1
    return out


def init(rng: np.random.Generator, model_config: dict) -> dict:
    s = _sizes(model_config)
    hidden, dim = s["hidden_size"], s["head_dim"]

    def normal(shape, std):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(std)

    def norm(width):
        return {"weight": np.ones((width,), np.float32)}

    params = {"embedding": normal((s["vocab_size"], hidden), 0.02),
              "head": normal((s["vocab_size"], hidden), 0.02),
              "norm_emb": norm(hidden)}
    held, width = s["experts_held"], s["moe_intermediate_size"]
    for i in range(s["num_hidden_layers"]):
        params[f"layer_{i}"] = {
            "norm_op": norm(hidden), "norm_ffn": norm(hidden),
            "attn": {
                "wq": normal((hidden, s["num_attention_heads"] * dim), 0.02),
                "wk": normal((hidden, s["num_key_value_heads"] * dim), 0.02),
                "wv": normal((hidden, s["num_key_value_heads"] * dim), 0.02),
                "wo": normal((s["num_attention_heads"] * dim, hidden), 0.02),
                "norm_q": norm(dim), "norm_k": norm(dim)},
            "moe": {
                "router": normal((hidden, s["num_experts"]), hidden ** -0.5),
                "w1": normal((held, hidden, width), 0.02),
                "w3": normal((held, hidden, width), 0.02),
                "w2": normal((held, width, hidden), 0.02)}}
    return params


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["weight"]


def rope(x, pos, theta):
    """``x``: ``[B, T, heads, D]`` at positions ``pos [T]``; element
    ``i`` turns with ``i + D / 2``."""
    dim = x.shape[-1]
    angles = pos.astype(jnp.float32)[:, None] * \
        theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)[None]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def seen(length: int, block: int) -> np.ndarray:
    """The mask as a boolean array ``[2 length, 2 length]``."""
    idx = np.arange(2 * length)
    clean, blk = idx >= length, (idx % length) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return ((~q_clean & ~k_clean & (k_blk == q_blk)) |
            (~q_clean & k_clean & (k_blk < q_blk)) |
            (q_clean & k_clean & (k_blk <= q_blk)))


def _attention_rows(q_rows, k, v, mask):
    """Softmax attention of one block of query rows ``[B, R, H, D]``
    over keys and values ``[B, M, KV, D]`` under ``mask [R, M]``."""
    group = q_rows.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("brhd,bmhd->bhrm", q_rows, k) * \
        q_rows.shape[-1] ** -0.5
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhrm,bmhd->brhd", jax.nn.softmax(scores, axis=-1), v)


def _attention(z, p, s, eps, theta):
    """``z``: ``[B, 2 L, hidden]``, the doubled row."""
    batch, rows, _ = z.shape
    length = rows // 2
    heads, kv, dim = (s["num_attention_heads"], s["num_key_value_heads"],
                      s["head_dim"])
    pos = jnp.tile(jnp.arange(length), 2)
    q = rope(_rms_norm((z @ p["wq"]).reshape(batch, rows, heads, dim),
                       p["norm_q"], eps), pos, theta)
    k = rope(_rms_norm((z @ p["wk"]).reshape(batch, rows, kv, dim),
                       p["norm_k"], eps), pos, theta)
    v = (z @ p["wv"]).reshape(batch, rows, kv, dim)
    mask = seen(length, s["block_length"])
    out = []
    for row0 in range(0, rows, ATTENTION_ROWS):
        end = min(row0 + ATTENTION_ROWS, rows)
        # the key columns any of these rows sees (the others would be
        # multiplied for nothing)
        cols = np.flatnonzero(mask[row0:end].any(axis=0))
        out.append(jax.checkpoint(_attention_rows)(
            q[:, row0:end], k[:, cols], v[:, cols],
            jnp.asarray(mask[row0:end][:, cols])))
    return jnp.concatenate(out, axis=1).reshape(
        batch, rows, heads * dim) @ p["wo"]


def routing(z, p, s):
    """``(chosen [.., k] int32, gate [.., k])`` of every token: the top
    ``k`` of ``softmax(W_r z)`` over all experts, renormalised."""
    logits = jnp.matmul(z.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    picked, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                   s["num_experts_per_tok"])
    return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


def routed_mlp(z, p, s):
    """The held experts' part: every held expert on every token, times
    its gate where the token chose it, else 0."""
    chosen, gate = routing(z, p, s)
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


def _layer(x, p, s, eps, theta):
    h = x_mid(x, p, s, eps, theta)
    return h + routed_mlp(_rms_norm(h, p["norm_ffn"], eps), p["moe"], s)


def _numbers(model_config: dict) -> tuple:
    return (_sizes(model_config), float(model_config["rms_norm_eps"]),
            float(model_config["rope_theta"]))


def forward(params: dict, x, model_config: dict):
    """Logits ``[B, L, vocab]`` of the ``xt`` half for the doubled row's
    ids ``[B, 2 L]`` (``[xt ; x0]``), float32 throughout."""
    s, eps, theta = _numbers(model_config)
    h = params["embedding"][x]
    for i in range(s["num_hidden_layers"]):
        h = jax.checkpoint(lambda h, p: _layer(h, p, s, eps, theta))(
            h, params[f"layer_{i}"])
    half = x.shape[1] // 2
    return _rms_norm(h[:, :half], params["norm_emb"], eps) @ params["head"].T


def _fields(batch: dict, model_config: dict) -> tuple:
    """``(doubled ids, x0, scored weight, real)`` of one step's batch."""
    x0 = batch["x"]
    real = batch["tok_mask"] * batch["sample_mask"][:, None]
    masked = batch["bd_mask"] * real
    xt = jnp.where(masked > 0, _sizes(model_config)["mask_token_id"], x0)
    return (jnp.concatenate([xt, x0], axis=1), x0,
            masked * batch["bd_weight"], real)


def loss(params: dict, batch: dict, model_config: dict):
    ids, x0, weight, real = _fields(batch, model_config)
    logp = jax.nn.log_softmax(forward(params, ids, model_config), axis=-1)
    per_token = -jnp.take_along_axis(logp, x0[..., None], axis=-1)[..., 0]
    return jnp.sum(per_token * weight) / jnp.maximum(jnp.sum(real), 1.0)


def sample_count(batch: dict):
    """The strategy's weight: the client's real rows."""
    return jnp.sum(batch["sample_mask"])


def held_pairs(params: dict, x, model_config: dict) -> list:
    """Per layer, for every position of the doubled row ``x``, the number
    of its chosen experts that are held, by the reference's own routing
    of its own forward pass."""
    s, eps, theta = _numbers(model_config)
    counts = []
    h = params["embedding"][x]
    for i in range(s["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        z = _rms_norm(x_mid(h, p, s, eps, theta), p["norm_ffn"], eps)
        local = routing(z, p["moe"], s)[0] - s["expert_offset"]
        counts.append(jnp.sum((local >= 0) & (local < s["experts_held"]),
                              axis=-1))
        h = _layer(h, p, s, eps, theta)
    return counts


def required_flops(params: dict, batch: dict, model_config: dict) -> float:
    """Matmul operations ONE forward + backward of the step's loss needs
    (3 x the forward's): the four projections and the router on every
    real position of BOTH halves, attention's two products over the SEEN
    pairs only (a full row: ``L (L + B)`` a head, not the ``4 L^2``
    square; a shorter row: its own count by ``seen``), the held experts'
    three products on the (position, expert) pairs that fall on HELD
    experts (counted from this batch's own routing, both halves), the
    head over the ``L`` noised rows.  The gather of the embedding is no
    matmul.  Nothing for recomputation."""
    s = _sizes(model_config)
    hidden, heads, dim = (s["hidden_size"], s["num_attention_heads"],
                          s["head_dim"])
    ids, _, _, real = _fields(batch, model_config)
    real = np.asarray(real) > 0
    lengths = real.sum(axis=1).astype(np.float64)
    positions = 2.0 * float(lengths.sum())
    span = s["block_length"]
    # a real query sees real keys only where its row ends on a block
    # boundary; a row's seen pairs by the mask's own closed form over
    # whole blocks, plus the part block's
    whole, part = lengths // span, lengths % span
    seen_pairs = (whole * span) * (whole * span + span) + \
        part * (2 * whole * span + 2 * part)
    pairs = jax.jit(lambda p, x: held_pairs(p, x, model_config))(params, ids)
    both = np.concatenate([real, real], axis=1)
    per_layer = positions * (
        2 * hidden * heads * dim +
        2 * hidden * s["num_key_value_heads"] * dim +
        hidden * s["num_experts"]) + \
        heads * 2 * dim * float(seen_pairs.sum())
    macs = float(lengths.sum()) * hidden * s["vocab_size"]
    for layer_pairs in pairs:
        on_held = float(np.sum(np.where(both, np.asarray(layer_pairs), 0)))
        macs += per_layer + on_held * 3 * hidden * s["moe_intermediate_size"]
    return 3.0 * 2.0 * macs
