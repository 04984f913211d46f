"""Plain reference: Laguna-MoE (poolside's Laguna-XS.2, ``model_type:
laguna``) in ``jax.numpy``, float32, one chip's share of an
expert-parallel deployment.  Imports nothing of ``msrflute_tpu``; the
tree's names are those the program's module (``models/laguna.py``) gives
its parameters, so that the harness can hand the program these weights.
Scalar keys are the published config's; the published lists
(``layer_types``, ``mlp_layer_types``, ``num_attention_heads_per_layer``)
and the ``rope_parameters`` dict come as the scalars the experiment's
yaml carries (``full_attention_period``, ``num_dense_layers``,
``num_attention_heads_sliding``, the eight rotary numbers).

Layer ``l`` (``norm`` = RMSNorm with weight, eps ``rms_norm_eps``; no
projection has a bias; ``*`` elementwise; ``T`` positions of width
``hidden_size``)::

    z = norm_op(x);  h = x + attn_l(z);  y = h + ffn_l(norm_ffn(h))

after the last layer ``norm_emb``, then logits against ``head``, which is
NOT the embedding (``tie_word_embeddings: false``).

- ``attn_l``: layer ``l`` is FULL where ``l % full_attention_period ==
  0``, else SLIDING.  ``H`` = ``num_attention_heads`` (full) or
  ``num_attention_heads_sliding`` (sliding) query heads over ``KV`` =
  ``num_key_value_heads`` key-value heads of ``D`` = ``head_dim``; query
  head ``h`` reads key-value head ``h // (H / KV)``; no norm on query or
  key.  ``q = rope_l(z W_q)``, ``k = rope_l(z W_k)``, ``v = z W_v``;
  ``s_ij = q_i . k_j / sqrt(D)``; a full query ``i`` sees ``j <= i``, a
  sliding one ``j <= i`` and ``i - j < sliding_window`` (that many keys,
  its own among them); ``o = softmax over seen (s) v``;
  ``attn = (o * sigmoid(z W_g)) W_o``: the OUTPUT GATE, elementwise,
  from the layer's normed input (``gating: true``).
- ``rope_l``: rotate-half (element ``i`` with ``i + R / 2``) on the
  first ``R`` elements of a head at positions 0..T-1, the rest passed
  through, cos and sin times a factor.  Sliding: ``R = D``,
  ``inv_freq_i = rope_theta_sliding ** (-2i / D)``, factor 1.  Full:
  ``R = partial_rotary_factor x D``; YaRN as transformers'
  ``_compute_yarn_parameters``: with ``f_i = rope_theta ** (2i / R)``
  and ``dim(n) = R ln(original_max / (2 pi n)) / (2 ln rope_theta)``,
  ``low = max(floor(dim(beta_fast)), 0)``, ``high = min(ceil(dim(
  beta_slow)), R - 1)``, ``ramp_i = clip((i - low) / (high - low), 0,
  1)``, ``inv_freq_i = ramp_i / (rope_factor f_i) + (1 - ramp_i) /
  f_i``; factor ``rope_attention_factor``.  (At the published numbers
  ``low`` = 5 and ``high`` = 16 of 32 frequencies.)  The tables are made
  on the host in float64 and rounded to float32 once.
- dense ``ffn(z) = W_2 (silu(W_1 z) * W_3 z)``, width
  ``intermediate_size``, in the leading ``num_dense_layers`` layers;
- routed ``ffn(z) = shared(z) + sum over chosen AND held e of g_e
  E_e(z)``: ``s = sigmoid(W_r z)`` over ALL ``num_experts`` (float32 at
  ``highest`` whatever the context: a choice that flips on rounding is a
  discrete event); chosen = ``top_k(s + b)``; ``g_e =
  moe_routed_scaling_factor * s_e / (sum of chosen s + 1e-6)``, the sum
  over all chosen experts, held or not; ``E_e`` a SwiGLU of width
  ``moe_intermediate_size``; ``shared`` a SwiGLU of width
  ``shared_expert_intermediate_size`` on every token.  Written densely
  over the held experts with a mask: every held expert on every token,
  which is why ``required_flops`` is this file's own.

Departures from the published form, each also in the configuration's
file: (1) the HELD SHARE: experts ``expert_offset .. expert_offset +
experts_held - 1`` are here, what the absent experts would add is left
out and that partial result goes on to the next layer; (2) the
vocabulary is a slice; (3) the selection bias ``b`` gets no gradient and
stays as it is; (4) the init scales below (``assumed``): normal(0, 0.02)
embedding, head and projections, router columns normal(0, hidden **
-0.5), selection bias normal(0, 0.1), norm weights 1.

Attention is written over blocks of ``ATTENTION_ROWS`` query rows, each
against the keys its rows can see and no others: up to the block's end
in a full layer, and in a sliding layer from the first key that the
block's first row sees (a SLICE of the keys: the others would be
multiplied for nothing and masked), so that round 0 at ``highest`` fits
the clock and the scores of a 4,096-token row never stand whole.  Each
layer, and each block of rows, is a ``jax.checkpoint``: the backward
pass computes it again instead of keeping what it made.  That changes
no value and is not counted by ``required_flops``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import fedround

ATTENTION_ROWS = 2048  # query rows a block: two blocks a 4,096-token row,
# each two large products of its own for the compiler (PERF.md section 6,
# PRs 28 and 41); a full layer's second block holds 48 x 2,048 x 4,096
# float32 scores, 1.6 GB, after the program's state is freed
ROUTE_EPS = 1e-6


def _sizes(mc: dict) -> dict:
    out = {k: int(mc[k]) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "shared_expert_intermediate_size", "num_attention_heads",
        "num_attention_heads_sliding", "num_key_value_heads", "head_dim",
        "sliding_window", "num_experts", "num_experts_per_tok",
        "experts_held", "vocab_size", "num_hidden_layers")}
    out["expert_offset"] = int(mc.get("expert_offset", 0))
    return out


def layer_kinds(model_config: dict) -> list:
    """``(attention, ffn)`` a layer: ``full`` where ``l %
    full_attention_period == 0`` else ``sliding``; ``dense`` for the
    leading ``num_dense_layers`` else ``moe``."""
    period = int(model_config["full_attention_period"])
    dense = int(model_config["num_dense_layers"])
    return [("full" if i % period == 0 else "sliding",
             "dense" if i < dense else "moe")
            for i in range(int(model_config["num_hidden_layers"]))]


def heads_of(s: dict, attn: str) -> int:
    return s["num_attention_heads_sliding" if attn == "sliding"
             else "num_attention_heads"]


def rotary_tables(model_config: dict) -> dict:
    """``{layer type: (inv_freq float32 [R / 2], factor)}``."""
    dim = int(model_config["head_dim"])
    sliding = float(model_config["rope_theta_sliding"]) ** (
        -np.arange(0, dim, 2, dtype=np.float64) / dim)
    base = float(model_config["rope_theta"])
    rotated = int(dim * float(model_config["partial_rotary_factor"]))
    scale = float(model_config["rope_factor"])
    span = int(model_config["rope_original_max_position_embeddings"])
    freqs = base ** (np.arange(0, rotated, 2, dtype=np.float64) / rotated)

    def correction(rotations):
        return rotated * math.log(span / (rotations * 2 * math.pi)) / \
            (2 * math.log(base))

    low = max(math.floor(correction(float(model_config["rope_beta_fast"]))),
              0)
    high = min(math.ceil(correction(float(model_config["rope_beta_slow"]))),
               rotated - 1)
    ramp = np.clip((np.arange(rotated // 2) - low) / (high - low), 0.0, 1.0)
    full = ramp / (scale * freqs) + (1.0 - ramp) / freqs
    return {"sliding": (sliding.astype(np.float32), 1.0),
            "full": (full.astype(np.float32),
                     float(model_config["rope_attention_factor"]))}


def init(rng: np.random.Generator, model_config: dict) -> dict:
    s = _sizes(model_config)
    hidden, dim, kv = s["hidden_size"], s["head_dim"], \
        s["num_key_value_heads"]

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
    for i, (attn, ffn) in enumerate(layer_kinds(model_config)):
        heads = heads_of(s, attn)
        layer = {"norm_op": norm(hidden), "norm_ffn": norm(hidden), "attn": {
            "wq": normal((hidden, heads * dim), 0.02),
            "wk": normal((hidden, kv * dim), 0.02),
            "wv": normal((hidden, kv * dim), 0.02),
            "wo": normal((heads * dim, hidden), 0.02),
            "wg": normal((hidden, heads * dim), 0.02)}}
        if ffn == "dense":
            layer["mlp"] = swiglu(s["intermediate_size"])
        else:
            layer["shared"] = swiglu(s["shared_expert_intermediate_size"])
            layer["moe"] = {
                "router": normal((hidden, s["num_experts"]), hidden ** -0.5),
                "select_bias": normal((s["num_experts"],), 0.1),
                **swiglu(s["moe_intermediate_size"], (s["experts_held"],))}
        params[f"layer_{i}"] = layer
    return params


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["weight"]


def rope(x, table):
    """``x``: ``[B, T, heads, D]`` at positions 0..T-1; the first ``2
    len(inv_freq)`` elements turn (``i`` with ``i + len(inv_freq)``), the
    rest pass through; cos and sin times the table's factor."""
    inv_freq, factor = table
    half = inv_freq.shape[0]
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * \
        jnp.asarray(inv_freq)[None]
    cos = (jnp.cos(angles) * factor)[None, :, None, :]
    sin = (jnp.sin(angles) * factor)[None, :, None, :]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def _attention_rows(q_rows, k, v, row0, col0, window):
    """Softmax attention of one block of query rows ``[B, R, H, D]``
    (from position ``row0``) over keys and values ``[B, M, KV, D]`` (from
    position ``col0``); ``window`` 0: every key up to the query's own."""
    group = q_rows.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("brhd,bmhd->bhrm", q_rows, k) * \
        q_rows.shape[-1] ** -0.5
    rows = row0 + jnp.arange(q_rows.shape[1])[:, None]
    cols = col0 + jnp.arange(k.shape[1])[None, :]
    seen = cols <= rows
    if window:
        seen = seen & (rows - cols < window)
    scores = jnp.where(seen, scores, -jnp.inf)
    return jnp.einsum("bhrm,bmhd->brhd", jax.nn.softmax(scores, axis=-1), v)


def _attention(z, p, s, attn, table):
    batch, length, _ = z.shape
    heads, kv, dim = heads_of(s, attn), s["num_key_value_heads"], \
        s["head_dim"]
    window = s["sliding_window"] if attn == "sliding" else 0
    q = rope((z @ p["wq"]).reshape(batch, length, heads, dim), table)
    k = rope((z @ p["wk"]).reshape(batch, length, kv, dim), table)
    v = (z @ p["wv"]).reshape(batch, length, kv, dim)
    out = []
    for row0 in range(0, length, ATTENTION_ROWS):
        end = min(row0 + ATTENTION_ROWS, length)
        # the first key any of these rows sees
        col0 = max(0, row0 - window + 1) if window else 0
        out.append(jax.checkpoint(_attention_rows, static_argnums=(3, 4, 5))(
            q[:, row0:end], k[:, col0:end], v[:, col0:end], row0, col0,
            window))
    out = jnp.concatenate(out, axis=1).reshape(batch, length, heads * dim)
    return (out * jax.nn.sigmoid(z @ p["wg"])) @ p["wo"]


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


def x_mid(x, p, s, eps, attn, table):
    """A layer's residual stream after its attention."""
    return x + _attention(_rms_norm(x, p["norm_op"], eps), p["attn"], s,
                          attn, table)


def _layer(x, p, kind, s, eps, tables, scale):
    attn, ffn = kind
    h = x_mid(x, p, s, eps, attn, tables[attn])
    z = _rms_norm(h, p["norm_ffn"], eps)
    if ffn == "dense":
        return h + _swiglu(z, p["mlp"])
    return h + _swiglu(z, p["shared"]) + routed_mlp(z, p["moe"], s, scale)


def _numbers(model_config: dict) -> tuple:
    return (_sizes(model_config), float(model_config["rms_norm_eps"]),
            rotary_tables(model_config),
            float(model_config["moe_routed_scaling_factor"]))


def forward(params: dict, x, model_config: dict):
    """Logits ``[B, L, vocab]`` for ids ``[B, L]``, float32 throughout."""
    s, eps, tables, scale = _numbers(model_config)
    h = params["embedding"][x]
    for i, kind in enumerate(layer_kinds(model_config)):
        h = jax.checkpoint(
            lambda h, p, kind=kind: _layer(h, p, kind, s, eps, tables,
                                           scale))(
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
    s, eps, tables, scale = _numbers(model_config)
    counts = []
    h = params["embedding"][x]
    for i, kind in enumerate(layer_kinds(model_config)):
        p = params[f"layer_{i}"]
        if kind[1] == "moe":
            z = _rms_norm(x_mid(h, p, s, eps, kind[0], tables[kind[0]]),
                          p["norm_ffn"], eps)
            local = routing(z, p["moe"], s, scale)[0] - s["expert_offset"]
            counts.append(jnp.sum((local >= 0) & (local < s["experts_held"]),
                                  axis=-1))
        h = _layer(h, p, kind, s, eps, tables, scale)
    return counts


def pairs_seen(lengths, window: int):
    """Seen (query, key) pairs a head of real rows of ``lengths``: query
    ``t`` reads ``t + 1`` keys, or ``min(t + 1, window)`` under a
    window."""
    lengths = np.asarray(lengths, np.float64)
    if not window:
        return lengths * (lengths + 1) / 2
    w = np.minimum(lengths, window)
    return w * lengths - w * (w - 1) / 2


def required_flops(params: dict, batch: dict, model_config: dict) -> float:
    """Matmul operations ONE forward + backward of the step's loss needs
    (3 x the forward's: each product once forward, twice backward): every
    projection on every real input position (``W_q``, ``W_k``, ``W_v``,
    the gate's ``W_g``, ``W_o`` at the layer type's head count, the dense
    or the shared SwiGLU, the router), the routed experts' three products
    on the token-expert pairs that fall on HELD experts only (counted
    from this batch's own routing), attention's two products over the
    SEEN pairs only (a full layer: half the square, position t reads t +
    1 keys; a sliding layer: the BAND, ``min(t + 1, sliding_window)``
    keys, not the square a plain masked product multiplies and not the
    causal half), the untied head.  The gather of the embedding is no
    matmul.  Nothing for recomputation."""
    s = _sizes(model_config)
    hidden, dim, kv = s["hidden_size"], s["head_dim"], \
        s["num_key_value_heads"]
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
    for attn, ffn in layer_kinds(model_config):
        heads = heads_of(s, attn)
        window = s["sliding_window"] if attn == "sliding" else 0
        macs += tokens * hidden * dim * (3 * heads + 2 * kv)
        macs += heads * 2 * dim * float(np.sum(pairs_seen(lengths, window)))
        if ffn == "dense":
            macs += tokens * 3 * hidden * s["intermediate_size"]
        else:
            macs += tokens * 3 * hidden * s["shared_expert_intermediate_size"]
            macs += tokens * hidden * s["num_experts"]
            on_held = float(jnp.sum(jnp.where(real, next(pairs), 0)))
            macs += on_held * 3 * hidden * s["moe_intermediate_size"]
    return 3.0 * 2.0 * macs
