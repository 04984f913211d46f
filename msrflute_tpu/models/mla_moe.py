"""MLA-MoE (the DeepSeek-V3 block as kakaocorp's Kanana-2-30B-A3B
publishes it, ``model_type: deepseek_v3``) — one chip's share of an
expert-parallel deployment, as a causal-LM task for the federated round.

Net-new vs the reference (FLUTE ships no such model).  The layer
equations are written out in ``benchmarks/reference/mla_moe.py`` (the
plain float32 form the benchmark compares this module with); in short,
``h = x + attn(norm_op(x)); y = h + ffn(norm_ffn(h))`` with

- ``attn`` LATENT attention (MLA) in every layer: queries straight from
  the hidden state (``q_lora_rank: null``), ``num_attention_heads``
  heads of ``qk_nope_head_dim + qk_rope_head_dim``; keys and values from
  ONE normalised latent of ``kv_lora_rank`` (``W_kv_a`` down, RMSNorm,
  ``W_kv_b`` up to every head's ``qk_nope_head_dim`` key part and
  ``v_head_dim`` value) and ONE rotary key of ``qk_rope_head_dim`` that
  all heads share; RoPE on interleaved pairs ``(2i, 2i+1)``; causal
  softmax at scale ``(nope + rope) ** -0.5``;
- ``ffn`` a dense SwiGLU in the leading ``first_k_dense_replace`` layers
  and after them a SHARED expert (one SwiGLU of ``n_shared_experts x
  moe_intermediate_size`` on every token) beside the held share of
  ``n_routed_experts`` sigmoid-routed SwiGLU experts
  (:func:`msrflute_tpu.ops.moe.held_experts_ffn`: ``experts_held``
  experts from ``expert_offset``, ``num_experts_per_tok`` a token over
  all experts, the gate's epsilon 1e-20 as published, nothing dropped,
  no exchange on one chip);
- a final RMSNorm and an UNTIED head (``head [vocab, hidden]``).

The parameter tree's names are a checkpoint contract and are the plain
reference's (``layer_<i>/{norm_op, norm_ffn, attn/{wq, wkv_a, norm_kv,
wkv_b, wo}, mlp | moe + shared}``, ``embedding``, ``norm_emb``,
``head``).  The layers are unrolled, not one scanned body over stacked
leaves: the cold run fits its limit so (PERF.md section 6, PR 36).

The attention core (a group of one query head a key head, the shared
rotary key broadcast to every head, value heads narrower than key
heads) is ``models/token_blocks.causal_attention``: the tiled Pallas
kernels of ``ops/pallas_attention.py`` (a value width of its own; scores,
mask and softmax stay in VMEM, forward and backward) wherever a compiled
kernel applies, the blocked plain path at ``attention_block`` rows
elsewhere (the CPU; GSPMD outside ``shard_map``).  ``jax.named_scope``s
``embed``, ``mla_proj``, ``mla_attn_core``, ``dense_ffn``,
``shared_expert``, ``routed_experts`` and ``lm_head_loss`` (the final
norm and the head's product here, the log-softmax in the task's loss)
mark the mechanisms in the compiled program's metadata: the catalogue
of docs/observability.md, "Named scopes".

``model_config.dtype: bfloat16`` computes activations and matmul
operands in bfloat16 over float32 master weights (norms, the router and
the loss stay float32); ``remat: true`` recomputes each layer in the
backward pass.
"""

from __future__ import annotations

from typing import Any, Dict

import flax.linen as nn
import jax
import jax.numpy as jnp

from .base import parse_dtype
from .token_blocks import (ExpertLMTask, _DenseMLP, _HeldExperts, _normal,
                           _RMSNorm, causal_attention, check_held,
                           rope_angles)

#: the gate's denominator as the model's published form has it
ROUTE_EPS = 1e-20


def rope_interleaved(x, theta: float):
    """RoPE on ``[B, L, heads, D]`` at positions 0..L-1, pair ``i`` =
    elements ``(2i, 2i+1)`` turned by ``pos * theta ** (-2i / D)``,
    angles in float32.  The published implementation de-interleaves and
    then rotates halves: the same pairs by the same angles, its result
    this one's with the even elements first (scores do not see the
    order: queries and keys are permuted alike)."""
    angles = rope_angles(x.shape[1], x.shape[-1], theta)
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


class _LatentAttention(nn.Module):
    heads: int
    nope: int
    rope: int
    v_dim: int
    latent: int
    eps: float
    theta: float
    block: int
    dtype: Any

    @nn.compact
    def __call__(self, z):  # [B, L, D], L a multiple of block
        batch, length, hidden = z.shape
        heads, nope, rope, v_dim = self.heads, self.nope, self.rope, self.v_dim
        wq = self.param("wq", _normal(0.02), (hidden, heads * (nope + rope)))
        wkv_a = self.param("wkv_a", _normal(0.02),
                           (hidden, self.latent + rope))
        wkv_b = self.param("wkv_b", _normal(0.02),
                           (self.latent, heads * (nope + v_dim)))
        wo = self.param("wo", _normal(0.02), (heads * v_dim, hidden))
        with jax.named_scope("mla_proj"):
            q = (z @ wq.astype(self.dtype)).reshape(batch, length, heads,
                                                    nope + rope)
            down = z @ wkv_a.astype(self.dtype)
            latent = _RMSNorm(self.eps, name="norm_kv")(
                down[..., :self.latent])
            k_pe = rope_interleaved(down[..., None, self.latent:], self.theta)
            up = (latent @ wkv_b.astype(self.dtype)).reshape(
                batch, length, heads, nope + v_dim)
            q = jnp.concatenate(
                [q[..., :nope], rope_interleaved(q[..., nope:], self.theta)],
                axis=-1)
            k = jnp.concatenate(
                [up[..., :nope],
                 jnp.broadcast_to(k_pe, (batch, length, heads, rope))],
                axis=-1)
            v = up[..., nope:]
        with jax.named_scope("mla_attn_core"):
            # a group of one: every query head has a key head of its own
            out = causal_attention(q[:, :, :, None, :], k, v, self.block)
        with jax.named_scope("mla_proj"):
            return out.reshape(batch, length, heads * v_dim) @ \
                wo.astype(self.dtype)


class _Layer(nn.Module):
    ffn: str
    cfg: Any  # hashable tuple of (key, value) sizes: make_mla_moe_task

    @nn.compact
    def __call__(self, x):
        c = dict(self.cfg)
        eps, dtype = c["rms_norm_eps"], c["dtype"]
        z = _RMSNorm(eps, name="norm_op")(x)
        h = x + _LatentAttention(
            c["num_attention_heads"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"], eps,
            c["rope_theta"], c["attention_block"], dtype, name="attn")(z)
        z = _RMSNorm(eps, name="norm_ffn")(h)
        if self.ffn == "dense":
            with jax.named_scope("dense_ffn"):
                return h + _DenseMLP(c["intermediate_size"], dtype,
                                     name="mlp")(z), {}
        # n_shared_experts SwiGLUs of the experts' width on every token
        # are one SwiGLU of their summed width
        with jax.named_scope("shared_expert"):
            shared = _DenseMLP(
                c["n_shared_experts"] * c["moe_intermediate_size"], dtype,
                name="shared")(z)
        with jax.named_scope("routed_experts"):
            routed, counters = _HeldExperts(
                c["n_routed_experts"], c["experts_held"], c["expert_offset"],
                c["num_experts_per_tok"], c["moe_intermediate_size"],
                c["routed_scaling_factor"], dtype, ROUTE_EPS, name="moe")(z)
        return h + shared + routed, counters


class _MLAMoE(nn.Module):
    vocab_size: int
    hidden_size: int
    num_layers: int
    dense_layers: int
    cfg: Any
    remat: bool = False

    @nn.compact
    def __call__(self, x):  # [B, L] int32 -> logits [B, L, V], counters
        c = dict(self.cfg)
        dtype, block = c["dtype"], c["attention_block"]
        length = x.shape[1]
        x = jnp.pad(x, ((0, 0), (0, -length % block)))
        table = self.param("embedding", _normal(0.02),
                           (self.vocab_size, self.hidden_size))
        head = self.param("head", _normal(0.02),
                          (self.vocab_size, self.hidden_size))
        with jax.named_scope("embed"):
            h = jnp.take(table, x, axis=0).astype(dtype)
        layer_cls = nn.remat(_Layer) if self.remat else _Layer
        counters: Dict[str, jnp.ndarray] = {}
        for i in range(self.num_layers):
            # explicit names: the tree is the same with remat on or off
            h, counted = layer_cls(
                "dense" if i < self.dense_layers else "moe", self.cfg,
                name=f"layer_{i}")(h)
            for key, value in counted.items():
                counters[key] = counters.get(key, 0.0) + value
        with jax.named_scope("lm_head_loss"):
            h = _RMSNorm(c["rms_norm_eps"], name="norm_emb")(h)
            logits = h @ head.T.astype(dtype)
        return logits[:, :length], counters


#: what this module computes one way only; another value is an error
#: that names the key, not a silent other model
_ONLY = {"q_lora_rank": None, "rope_interleave": True, "rope_scaling": None,
         "n_group": 1, "topk_group": 1, "tie_word_embeddings": False,
         "scoring_func": "sigmoid", "norm_topk_prob": True,
         "attention_bias": False, "moe_layer_freq": 1}


def make_mla_moe_task(model_config) -> ExpertLMTask:
    for key, only in _ONLY.items():
        if model_config.get(key, only) != only:
            raise ValueError(
                f"model_config.{key}={model_config.get(key)!r}: "
                f"models/mla_moe.py computes {only!r} only")
    hidden = int(model_config["hidden_size"])
    layers = int(model_config["num_hidden_layers"])
    dense = int(model_config.get("first_k_dense_replace", 0))
    moe = dense < layers
    num_experts = int(model_config.get("n_routed_experts", 0) or 0)
    held, offset = check_held(model_config, num_experts) if moe else (0, 0)
    cfg = tuple(sorted({
        "dtype": parse_dtype(model_config),
        "rms_norm_eps": float(model_config.get("rms_norm_eps", 1e-6)),
        "rope_theta": float(model_config.get("rope_theta", 1e6)),
        "num_attention_heads": int(model_config["num_attention_heads"]),
        "qk_nope_head_dim": int(model_config["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(model_config["qk_rope_head_dim"]),
        "v_head_dim": int(model_config["v_head_dim"]),
        "kv_lora_rank": int(model_config["kv_lora_rank"]),
        "attention_block": int(model_config.get("attention_block", 512)),
        "intermediate_size": int(model_config.get("intermediate_size",
                                                  4 * hidden)),
        "moe_intermediate_size": int(
            model_config.get("moe_intermediate_size", hidden)),
        "n_shared_experts": int(model_config["n_shared_experts"]),
        "n_routed_experts": num_experts,
        "experts_held": held,
        "expert_offset": offset,
        "num_experts_per_tok": int(model_config.get("num_experts_per_tok",
                                                    1)),
        "routed_scaling_factor": float(
            model_config.get("routed_scaling_factor", 1.0)),
    }.items()))
    module = _MLAMoE(vocab_size=int(model_config["vocab_size"]),
                     hidden_size=hidden, num_layers=layers,
                     dense_layers=min(dense, layers), cfg=cfg,
                     remat=bool(model_config.get("remat", False)))
    task = ExpertLMTask(module, seq_len=int(model_config.get("seq_len", 4096)),
                        name="mla_moe")
    if not moe:
        task.counter_names = ()
    return task
