"""What the token models with held experts have in common
(``models/lfm2.py``, ``models/mla_moe.py``): RMSNorm, the dense SwiGLU,
the held share of a routed expert layer, RoPE's angles, the causal
attention core (the tiled kernels of ``ops/pallas_attention.py``, or
blocks of plain attention rows where no compiled kernel applies), and
the causal-LM task that carries the expert layers' counters.

The modules' parameter names (``weight``; ``w1``/``w3``/``w2``;
``router``/``select_bias``/``w1``/``w3``/``w2``) are part of the two
models' checkpoint contracts and of their plain references
(``benchmarks/reference/``).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.moe import held_experts_ffn
from ..ops.pallas_attention import (causal_flash_attention,
                                    record_attention_path)
from ..ops.pallas_kernels import compiled_kernels_apply
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


def _attention_rows(q_rows, k, v, row0: int):
    """One block of query rows ``[B, R, KV, G, D]`` from position
    ``row0`` over the keys ``[B, M, KV, D]`` up to the block's end and
    the values ``[B, M, KV, Dv]`` (``Dv`` need not be ``D``); scale
    ``D ** -0.5``, softmax in float32."""
    scale = q_rows.shape[-1] ** -0.5
    scores = jnp.einsum("brkgd,bmkd->bkgrm", q_rows, k).astype(
        jnp.float32) * scale
    rows = row0 + jnp.arange(q_rows.shape[1])[:, None]
    cols = jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(cols <= rows, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgrm,bmkd->brkgd", probs, v)


def _blocked_attention(q, k, v, block: int):
    """Causal attention of ``q [B, L, KV, G, D]`` over ``k [B, L, KV, D]``
    and ``v [B, L, KV, Dv]``, ``block`` query rows at a time against the
    keys up to the block's end, each block a ``jax.checkpoint`` (the
    scores of a long row never stand whole); ``L`` a multiple of
    ``block``.  Returns ``[B, L, KV, G, Dv]``.  The plain path: what
    runs where the kernels do not, and the statement they are tested
    against."""
    rows = jax.checkpoint(_attention_rows, static_argnums=(3,))
    out = [rows(q[:, r0:r0 + block], k[:, :r0 + block], v[:, :r0 + block],
                r0)
           for r0 in range(0, q.shape[1], block)]
    return jnp.concatenate(out, axis=1)


def causal_attention(q, k, v, block: int, interpret=None):
    """The token models' causal core, ``q [B, L, KV, G, D]`` over
    ``k [B, L, KV, D]`` and ``v [B, L, KV, Dv]`` -> ``[B, L, KV, G, Dv]``:
    the tiled kernels of ``ops/pallas_attention.py`` wherever a compiled
    kernel applies (scores, mask and softmax stay in VMEM, forward and
    backward; no ``jax.checkpoint``: the kernels' ``custom_vjp`` saves
    ``q``, ``k``, ``v``, ``out``, ``lse``), else :func:`_blocked_attention`
    at ``block`` rows (the CPU; GSPMD outside ``shard_map``).  Head
    widths and the group size are shapes: one path for both models.
    ``interpret=True`` forces the kernels through the interpreter
    (tests).  The trace says which path it took (``attention_path``)."""
    batch, length, kv, group, dim = q.shape
    if interpret is None and not compiled_kernels_apply():
        record_attention_path("plain", (batch, length, kv * group, dim),
                              k.shape, v.shape, block, length)
        return _blocked_attention(q, k, v, block)
    out = causal_flash_attention(q.reshape(batch, length, kv * group, dim),
                                 k, v, interpret=interpret)
    return out.reshape(batch, length, kv, group, v.shape[-1])


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
    epsilon in the gate's denominator, None = ``route_tokens``' own."""
    num_experts: int
    experts_held: int
    expert_offset: int
    per_token: int
    width: int
    scaling: float
    dtype: Any
    route_eps: Any = None

    @nn.compact
    def __call__(self, z):
        hidden = z.shape[-1]
        held = self.experts_held
        router = self.param("router", _normal(hidden ** -0.5),
                            (hidden, self.num_experts))
        bias = self.param("select_bias", _normal(0.1), (self.num_experts,))
        w1 = self.param("w1", _normal(0.02), (held, hidden, self.width))
        w3 = self.param("w3", _normal(0.02), (held, hidden, self.width))
        w2 = self.param("w2", _normal(0.02), (held, self.width, hidden))
        y, counters = held_experts_ffn(
            z.reshape(-1, hidden), router, bias, w1.astype(self.dtype),
            w3.astype(self.dtype), w2.astype(self.dtype),
            experts_per_token=self.per_token,
            expert_offset=self.expert_offset, scaling=self.scaling,
            route_eps=self.route_eps)
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
