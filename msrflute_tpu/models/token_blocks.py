"""What the token models with held experts have in common
(``models/lfm2.py``, ``models/mla_moe.py``, ``models/sdar_moe.py``,
``models/laguna.py``): RMSNorm, the dense SwiGLU, the held share of a
routed expert layer, RoPE's angles and rotary laws, grouped-query
attention (head counts, QK-norm, rotary law, window and output gate an
instance's own), the attention cores (causal, with or without a sliding
window; block diffusion over a doubled row), each through the tiled
kernels of ``ops/pallas_attention.py`` or through blocks of plain
attention rows where no compiled kernel applies, and the two tasks that
carry the expert layers' counters: the causal LM and the block-diffusion
LM.

The modules' parameter names (``weight``; ``w1``/``w3``/``w2``;
``router``/``select_bias``/``w1``/``w3``/``w2``; ``wq``/``wk``/``wv``/
``wo``/``wg``/``norm_q``/``norm_k``) are part of the models' checkpoint
contracts and of their plain references (``benchmarks/reference/``).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.moe import held_experts_ffn
from ..ops.pallas_attention import (block_diffusion_flash_attention,
                                    causal_flash_attention,
                                    record_attention_path,
                                    window_flash_attention)
from ..ops.pallas_kernels import compiled_kernels_apply
from ..utils.metrics import Metric
from .base import softmax_xent
from .nlp import SequenceLMTask, _TokenDatasetMixin

#: what the expert layers count, summed over layers and local steps
#: (``ops.moe.held_experts_ffn``); the engine carries them to the packed
#: round stats as ``ctr_<name>``
COUNTERS = ("moe_pairs_held", "moe_max_load", "moe_pairs_dropped",
            "moe_layer_steps", "moe_tiles_active")


def _normal(std: float):
    return nn.initializers.normal(std)


class _RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps) * weight
        return y.astype(x.dtype)


def rope_angles(length: int, dim: int, theta: float):
    """RoPE's angles ``[length, dim / 2]`` at positions 0..length-1:
    ``pos * theta ** (-2i / dim)``, float32.  How the pairs lie in a head
    (halves, interleaved) is the model's."""
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]


def _masked_rows(q_rows, k, v, seen_of):
    """Softmax attention of one block of query rows ``[B, R, KV, G, D]``
    over keys ``[B, M, KV, D]`` and values ``[B, M, KV, Dv]`` (``Dv``
    need not be ``D``) under ``seen_of() -> bool [R, M]``; scale ``D **
    -0.5``, softmax in float32."""
    scale = q_rows.shape[-1] ** -0.5
    scores = jnp.einsum("brkgd,bmkd->bkgrm", q_rows, k).astype(
        jnp.float32) * scale
    scores = jnp.where(seen_of(), scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgrm,bmkd->brkgd", probs, v)


def _attention_rows(q_rows, k, v, row0: int, col0: int = 0,
                    window: int = 0):
    """One block of causal query rows from position ``row0`` over the
    keys and values from position ``col0`` up to the block's end; under
    ``window > 0`` a query sees the ``window`` keys up to its own."""
    def seen():
        rows = row0 + jnp.arange(q_rows.shape[1])[:, None]
        cols = jnp.arange(k.shape[1])[None, :]
        if not window:
            return cols <= rows
        cols = col0 + cols
        return (cols <= rows) & (rows - cols < window)
    return _masked_rows(q_rows, k, v, seen)


def _blocked_attention(q, k, v, block: int, window: int = 0):
    """Causal attention of ``q [B, L, KV, G, D]`` over ``k [B, L, KV, D]``
    and ``v [B, L, KV, Dv]``, ``block`` query rows at a time against the
    keys up to the block's end (under ``window > 0``: from the first key
    the block's first row sees), each block a ``jax.checkpoint`` (the
    scores of a long row never stand whole); ``L`` a multiple of
    ``block``.  Returns ``[B, L, KV, G, Dv]``.  The plain path: what
    runs where the kernels do not, and the statement they are tested
    against."""
    rows = jax.checkpoint(_attention_rows, static_argnums=(3, 4, 5))
    out = []
    for r0 in range(0, q.shape[1], block):
        c0 = max(0, r0 - window + 1) if window else 0
        out.append(rows(q[:, r0:r0 + block], k[:, c0:r0 + block],
                        v[:, c0:r0 + block], r0, c0, window))
    return jnp.concatenate(out, axis=1)


def causal_attention(q, k, v, block: int, interpret=None, window: int = 0):
    """The token models' causal core, ``q [B, L, KV, G, D]`` over
    ``k [B, L, KV, D]`` and ``v [B, L, KV, Dv]`` -> ``[B, L, KV, G, Dv]``:
    the tiled kernels of ``ops/pallas_attention.py`` wherever a compiled
    kernel applies (scores, mask and softmax stay in VMEM, forward and
    backward; no ``jax.checkpoint``: the kernels' ``custom_vjp`` saves
    ``q``, ``k``, ``v``, ``out``, ``lse``), else :func:`_blocked_attention`
    at ``block`` rows (the CPU; GSPMD outside ``shard_map``).  Head
    widths and the group size are shapes: one path for all models.
    ``window > 0``: a query sees the ``window`` keys up to its own (the
    window law's kernels; a window no shorter than the row is the causal
    law and takes the causal kernels).  ``interpret=True`` forces the
    kernels through the interpreter (tests).  The trace says which path
    it took (``attention_path``)."""
    batch, length, kv, group, dim = q.shape
    if window >= length:
        window = 0
    if interpret is None and not compiled_kernels_apply():
        record_attention_path("plain", (batch, length, kv * group, dim),
                              k.shape, v.shape, block, length)
        return _blocked_attention(q, k, v, block, window)
    flat = q.reshape(batch, length, kv * group, dim)
    out = (window_flash_attention(flat, k, v, window, interpret=interpret)
           if window else
           causal_flash_attention(flat, k, v, interpret=interpret))
    return out.reshape(batch, length, kv, group, v.shape[-1])


def _bd_attention_rows(q_rows, k, v, row0: int, own: int, span: int):
    """One block of query rows ``[B, R, KV, G, D]`` of a block-diffusion
    row, in-half positions from ``row0``, over the keys it can see: the
    first ``own`` are the noised half's rows from ``row0`` (a noised
    query's own rows; it sees its own block of ``span`` there), the rest
    the clean half's rows from 0 (a noised query sees the earlier
    blocks, a clean one, ``own == 0``, its own block too)."""
    def seen():
        q_blk = (row0 + jnp.arange(q_rows.shape[1]))[:, None] // span
        cols = jnp.arange(k.shape[1])[None, :]
        k_blk = jnp.where(cols < own, row0 + cols, cols - own) // span
        return jnp.where(cols < own, k_blk == q_blk,
                         k_blk < q_blk if own else k_blk <= q_blk)
    return _masked_rows(q_rows, k, v, seen)


def _blocked_bd_attention(q, k, v, span: int, block: int):
    """Block-diffusion attention of a doubled row (``[xt ; x0]``, ``L``
    positions each, ``L`` a multiple of ``block`` and ``block`` of
    ``span``), ``block`` query rows at a time over the keys they can
    see, each a ``jax.checkpoint``: the plain path, and the statement
    the kernels are tested against."""
    length = q.shape[1] // 2
    rows = jax.checkpoint(_bd_attention_rows, static_argnums=(3, 4, 5))
    out = []
    for clean in (0, 1):
        for r0 in range(0, length, block):
            own = slice(r0, r0 + block)
            before = slice(length, length + r0 + block)
            keys, values = (
                (k[:, before], v[:, before]) if clean else
                (jnp.concatenate([k[:, own], k[:, before]], axis=1),
                 jnp.concatenate([v[:, own], v[:, before]], axis=1)))
            out.append(rows(
                q[:, clean * length + r0:clean * length + r0 + block],
                keys, values, r0, 0 if clean else block, span))
    return jnp.concatenate(out, axis=1)


def block_diffusion_attention(q, k, v, span: int, block: int,
                              interpret=None):
    """The block-diffusion core over a doubled row: ``q [B, 2 L, KV, G,
    D]`` over ``k [B, 2 L, KV, D]`` and ``v [B, 2 L, KV, Dv]``, rows
    ``[xt ; x0]``, blocks of ``span`` positions.  With ``blk(i) = (i mod
    L) // span``: an ``xt`` query sees the ``xt`` keys of its own block
    and the ``x0`` keys of earlier blocks; an ``x0`` query the ``x0``
    keys of its own and earlier blocks, and no ``xt`` key.  The kernels
    of ``ops/pallas_attention.py`` (a static tile map: unseen tiles are
    no grid step) wherever a compiled kernel applies, else
    :func:`_blocked_bd_attention`, as :func:`causal_attention`."""
    batch, rows, kv, group, dim = q.shape
    if interpret is None and not compiled_kernels_apply():
        record_attention_path("plain", (batch, rows, kv * group, dim),
                              k.shape, v.shape, block, rows // 2 + block)
        return _blocked_bd_attention(q, k, v, span, block)
    out = block_diffusion_flash_attention(
        q.reshape(batch, rows, kv * group, dim), k, v, span,
        interpret=interpret)
    return out.reshape(batch, rows, kv, group, v.shape[-1])


def rope_half(x, theta: float, copies: int = 1):
    """Rotate-half RoPE on ``[B, L, heads, D]`` (element ``i`` with ``i +
    D / 2``), angles in float32, at positions 0..L-1, or, a row of
    ``copies`` copies side by side, at 0..L/copies-1 in each."""
    angles = rope_angles(x.shape[1] // copies, x.shape[-1], theta)
    if copies > 1:
        angles = jnp.tile(angles, (copies, 1))
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * jnp.cos(angles).astype(x.dtype) +
            rotated * jnp.sin(angles).astype(x.dtype))


class RotaryLaw(NamedTuple):
    """A layer type's rotary law as numbers: the inverse frequencies of
    the rotated pairs (the first ``2 len(inv_freq)`` elements of a head
    turn, element ``i`` with ``i + len(inv_freq)``; the rest pass
    through) and the factor on cos and sin (YaRN's attention factor).
    The table is made on the host by the model that owns the law
    (``models/laguna.py``); hashable, so a module can carry it."""
    inv_freq: tuple
    factor: float = 1.0


def rope_law(x, law: RotaryLaw):
    """Rotate-half RoPE by ``law`` on ``[B, L, heads, D]`` at positions
    0..L-1, angles in float32."""
    half = len(law.inv_freq)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * \
        jnp.asarray(law.inv_freq, jnp.float32)[None]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    cos = (jnp.cos(angles) * law.factor).astype(x.dtype)
    sin = (jnp.sin(angles) * law.factor).astype(x.dtype)
    turn, rest = x[..., :2 * half], x[..., 2 * half:]
    rotated = jnp.concatenate([-turn[..., half:], turn[..., :half]], axis=-1)
    turned = turn * cos + rotated * sin
    return jnp.concatenate([turned, rest], axis=-1) if rest.shape[-1] \
        else turned


class _GQAttention(nn.Module):
    """Grouped-query attention, rotate-half RoPE.  Four families use it;
    what differs between them, and between one model's layer types, is
    the instance's: the head counts, ``qk_norm`` (an RMSNorm on every
    head's query and key: LFM2, SDAR; Laguna has none), the rotary law
    (``rotary`` None: every element turned at ``theta``; else a
    :class:`RotaryLaw`), ``window`` (0: causal over the row; ``> 0``: a
    query sees the ``window`` keys up to its own), ``gate`` (the output
    times ``sigmoid(z wg)`` elementwise before ``wo``: Laguna) and
    ``diffusion_block`` (``> 0``: the row is ``[xt ; x0]``, both halves
    at positions 0..L-1, under the block-diffusion mask at that block
    length)."""
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    block: int
    dtype: Any
    diffusion_block: int = 0
    qk_norm: bool = True
    rotary: Any = None
    window: int = 0
    gate: bool = False

    @nn.compact
    def __call__(self, z):  # [B, L, D], L a multiple of block
        batch, length, hidden = z.shape
        heads, kv, dim = self.heads, self.kv_heads, self.head_dim
        copies = 2 if self.diffusion_block else 1
        wq = self.param("wq", _normal(0.02), (hidden, heads * dim))
        wk = self.param("wk", _normal(0.02), (hidden, kv * dim))
        wv = self.param("wv", _normal(0.02), (hidden, kv * dim))
        wo = self.param("wo", _normal(0.02), (heads * dim, hidden))
        wg = (self.param("wg", _normal(0.02), (hidden, heads * dim))
              if self.gate else None)
        with jax.named_scope("gqa_proj"):
            q = (z @ wq.astype(self.dtype)).reshape(batch, length, heads, dim)
            if self.qk_norm:
                q = _RMSNorm(self.eps, name="norm_q")(q)
            k = (z @ wk.astype(self.dtype)).reshape(batch, length, kv, dim)
            if self.qk_norm:
                k = _RMSNorm(self.eps, name="norm_k")(k)
            v = (z @ wv.astype(self.dtype)).reshape(batch, length, kv, dim)
            if self.rotary is None:
                q = rope_half(q, self.theta, copies)
                k = rope_half(k, self.theta, copies)
            else:
                q, k = rope_law(q, self.rotary), rope_law(k, self.rotary)
            # query head h reads key-value head h // (heads / kv_heads)
            q = q.reshape(batch, length, kv, heads // kv, dim)
        with jax.named_scope("gqa_attn_core"):
            if self.diffusion_block:
                out = block_diffusion_attention(
                    q, k, v, self.diffusion_block, self.block)
            else:
                out = causal_attention(q, k, v, self.block,
                                       window=self.window)
        with jax.named_scope("gqa_proj"):
            out = out.reshape(batch, length, heads * dim)
            if self.gate:
                out = out * jax.nn.sigmoid(z @ wg.astype(self.dtype))
            return out @ wo.astype(self.dtype)


class _DenseMLP(nn.Module):
    width: int
    dtype: Any

    @nn.compact
    def __call__(self, z):
        hidden = z.shape[-1]
        w1 = self.param("w1", _normal(0.02), (hidden, self.width))
        w3 = self.param("w3", _normal(0.02), (hidden, self.width))
        w2 = self.param("w2", _normal(0.02), (self.width, hidden))
        return (jax.nn.silu(z @ w1.astype(self.dtype)) *
                (z @ w3.astype(self.dtype))) @ w2.astype(self.dtype)


class _HeldExperts(nn.Module):
    """``experts_held`` of ``num_experts`` routed SwiGLU experts, from
    ``expert_offset``; returns ``(y, counters)``.  ``route_eps``: the
    epsilon in the gate's denominator, None = ``route_tokens``' own.
    ``scoring``: ``route_tokens``' law; ``softmax`` has no selection
    bias, so no such leaf."""
    num_experts: int
    experts_held: int
    expert_offset: int
    per_token: int
    width: int
    scaling: float
    dtype: Any
    route_eps: Any = None
    scoring: str = "sigmoid"

    @nn.compact
    def __call__(self, z):
        hidden = z.shape[-1]
        held = self.experts_held
        router = self.param("router", _normal(hidden ** -0.5),
                            (hidden, self.num_experts))
        bias = (self.param("select_bias", _normal(0.1), (self.num_experts,))
                if self.scoring == "sigmoid" else None)
        w1 = self.param("w1", _normal(0.02), (held, hidden, self.width))
        w3 = self.param("w3", _normal(0.02), (held, hidden, self.width))
        w2 = self.param("w2", _normal(0.02), (held, self.width, hidden))
        y, counters = held_experts_ffn(
            z.reshape(-1, hidden), router, bias, w1.astype(self.dtype),
            w3.astype(self.dtype), w2.astype(self.dtype),
            experts_per_token=self.per_token,
            expert_offset=self.expert_offset, scaling=self.scaling,
            route_eps=self.route_eps, scoring=self.scoring)
        return y.reshape(z.shape), counters


def check_held(model_config, num_experts: int) -> tuple:
    """``(experts_held, expert_offset)`` of a model with expert layers."""
    held = int(model_config.get("experts_held", num_experts) or 0)
    offset = int(model_config.get("expert_offset", 0) or 0)
    if not 0 < held <= num_experts - offset:
        raise ValueError(
            f"model_config: experts_held={held} from expert_offset={offset} "
            f"does not lie within num_experts={num_experts}")
    return held, offset


class ExpertLMTask(_TokenDatasetMixin, SequenceLMTask):
    """Causal-LM task over a module that returns ``(logits, counters)``;
    int token rows pass through the dataset as they are.  The expert
    layers' counters leave through the loss's aux (``aux["counters"]``)
    and the engine sums them into the packed round stats."""

    counter_names = COUNTERS

    def init_params(self, rng: jax.Array):
        # nothing of the tree depends on the length (RoPE, no position
        # table): a short dummy keeps the init program small.  ONE
        # program: run eagerly, the module's init compiles every
        # primitive of the forward pass on its own (24 s at the
        # published widths, none of it kept by the persistent cache)
        dummy = jnp.zeros((1, 8), jnp.int32)
        return jax.jit(self.module.init)(rng, dummy)["params"]

    def _apply(self, params, inputs):
        return self.module.apply({"params": params}, inputs)[0]

    def loss(self, params, batch, rng=None, train=True):
        inputs, targets, tok_mask = self._inputs_targets(batch)
        logits, counters = self.module.apply({"params": params}, inputs)
        with jax.named_scope("lm_head_loss"):
            value, aux = self._masked_xent(logits.astype(jnp.float32),
                                           targets, tok_mask, batch)
        if counters:
            aux["counters"] = counters
        return value, aux


# ----------------------------------------------------------------------
# the block-diffusion objective (the vectorised training form of BD3-LM,
# arXiv:2503.09573, as SDAR, arXiv:2510.06303, adopts it)
# ----------------------------------------------------------------------
#: what the block-diffusion task counts beside the expert layers'
#: counters, summed over rows and local steps: the real positions that
#: were masked (and so scored), and the real positions
BD_COUNTERS = ("bd_positions_masked", "bd_positions_real")
#: the least masking rate of a block (the greatest weight is its inverse)
BD_RATE_MIN = 0.05
_SPLITS = {"train": 0, "val": 1, "test": 2}


def bd_draws(noise_seed: int, split: str, user: int, row: int, length: int,
             span: int) -> tuple:
    """The noise of one row, a pure function of ``(noise_seed, split,
    user, row)``: per block of ``span`` positions a rate ``t`` uniform on
    ``[BD_RATE_MIN, 1]``, per position a uniform ``u``; returns ``(masked
    [length] float32: 1.0 where u < t of the position's block, weight
    [length] float32: 1 / t of the position's block)``.  numpy, on the
    host, when the dataset is built: never inside the compiled step."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(noise_seed), _SPLITS[split], int(user), int(row)]))
    blocks = -(-length // span)
    rate = np.repeat(rng.uniform(BD_RATE_MIN, 1.0, size=blocks),
                     span)[:length]
    masked = rng.uniform(size=length) < rate
    return masked.astype(np.float32), (1.0 / rate).astype(np.float32)


class BlockDiffusionLMTask(ExpertLMTask):
    """Block-diffusion LM over a module that takes the doubled row
    ``[xt ; x0]`` (``[B, 2 L]`` ids) and returns ``(logits of the xt half
    [B, L, V], counters)``.

    The noise is an INPUT: ``make_dataset`` draws, once a row, ``bd_mask``
    (1.0 where the position is masked) and ``bd_weight`` (``1 / t`` of
    its block) from ``(model_config.noise_seed, split, user, row)``
    (:func:`bd_draws`), zero beyond the row's real positions, and the
    batch carries them beside ``x`` and ``tok_mask``.  ``xt`` is ``x``
    with the mask id (the vocabulary's last id) at the masked positions.

    Loss of a batch: ``sum over rows and masked real positions i of
    bd_weight_i * (-log softmax(logits_i)[x_i]) / sum over rows of real
    positions``: no shift between a position's logits and its target.
    The step's sample count is the rows.  Evaluation: the same loss, and
    accuracy over the masked real positions."""

    counter_names = COUNTERS + BD_COUNTERS
    seq_pad_keys = ("x", "tok_mask", "bd_mask", "bd_weight")

    def __init__(self, module, seq_len: int, name: str, span: int,
                 mask_id: int, noise_seed: int):
        super().__init__(module, seq_len=seq_len, name=name)
        self.span, self.mask_id, self.noise_seed = span, mask_id, noise_seed

    def init_params(self, rng: jax.Array):
        # one block of each half (see ExpertLMTask.init_params)
        dummy = jnp.zeros((1, 2 * self.span), jnp.int32)
        return jax.jit(self.module.init)(rng, dummy)["params"]

    def row_fields(self, entry, split: str, user: int) -> dict:
        real = entry["tok_mask"]
        draws = [bd_draws(self.noise_seed, split, user, row, real.shape[1],
                          self.span) for row in range(real.shape[0])]
        return {"bd_mask": real * np.stack([m for m, _ in draws]),
                "bd_weight": real * np.stack([w for _, w in draws])}

    def _scored(self, params, batch):
        """``(weighted loss sum, logits [B, L, V], masked, real,
        counters)``: the cross entropy of the masked real positions
        (``masked [B, L]``), each times its ``bd_weight``, summed;
        ``real [B, L]`` the real positions."""
        x0 = batch["x"].astype(jnp.int32)
        real = batch["tok_mask"].astype(jnp.float32) * \
            batch["sample_mask"][:, None]
        masked = batch["bd_mask"].astype(jnp.float32) * real
        xt = jnp.where(masked > 0, self.mask_id, x0)
        logits, counters = self.module.apply(
            {"params": params}, jnp.concatenate([xt, x0], axis=1))
        with jax.named_scope("lm_head_loss"):
            logits = logits.astype(jnp.float32)
            loss_sum = jnp.sum(softmax_xent(logits, x0) * masked *
                               batch["bd_weight"].astype(jnp.float32))
        counters = {**counters, "bd_positions_masked": jnp.sum(masked),
                    "bd_positions_real": jnp.sum(real)}
        return loss_sum, logits, masked, real, counters

    def _logits_targets(self, params, batch):
        # the causal tasks' helpers (top-k predictions, token log-probs)
        # score shifted targets of a row read once
        raise NotImplementedError(
            "a block-diffusion model's logits are those of a noised copy "
            "beside the clean one: BlockDiffusionLMTask._scored")

    def loss(self, params, batch, rng=None, train=True):
        loss_sum, _, _, real, counters = self._scored(params, batch)
        return loss_sum / jnp.maximum(jnp.sum(real), 1.0), {
            "sample_count": jnp.sum(batch["sample_mask"]),
            "counters": counters}

    def eval_stats(self, params, batch):
        loss_sum, logits, masked, real, _ = self._scored(params, batch)
        hit = (jnp.argmax(logits, axis=-1) ==
               batch["x"].astype(jnp.int32)).astype(jnp.float32)
        return {
            # the evaluation's loss is loss_sum / sample_count: the
            # training loss's normalisation, the real positions
            "loss_sum": loss_sum,
            "sample_count": jnp.sum(real),
            # accuracy over the masked real positions
            "correct_sum": jnp.sum(hit * masked),
            "correct_count": jnp.sum(masked),
            "seq_count": jnp.sum(batch["sample_mask"]),
        }

    def finalize_metrics(self, sums):
        metrics = super().finalize_metrics(sums)
        metrics["acc"] = Metric(
            float(sums["correct_sum"]) /
            max(float(sums["correct_count"]), 1.0), higher_is_better=True)
        return metrics
