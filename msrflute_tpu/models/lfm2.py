"""LFM2-MoE (LiquidAI LFM2-24B-A2B) — one chip's share of an
expert-parallel deployment, as a causal-LM task for the federated round.

Net-new vs the reference (FLUTE ships no such model).  The layer
equations are written out in ``benchmarks/reference/lfm2_moe.py`` (the
plain float32 form the benchmark compares this module with); in short,
``h = x + op(norm(x)); y = h + ffn(norm(h))`` with

- ``op`` a gated short convolution (``W_in`` to three gates, a causal
  depthwise convolution of ``conv_L_cache`` taps, ``W_out``) or
  grouped-query attention (RMSNorm on every head's query and key,
  rotate-half RoPE, ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads, causal softmax), by
  ``layer_types``;
- ``ffn`` a dense SwiGLU in the leading ``num_dense_layers`` and after
  them the held share of ``num_experts`` sigmoid-routed SwiGLU experts
  (:func:`msrflute_tpu.ops.moe.held_experts_ffn`: ``experts_held``
  experts from ``expert_offset``, ``num_experts_per_tok`` a token over
  all experts, nothing dropped, no exchange on one chip);
- a final RMSNorm and logits against the TIED embedding.

The parameter tree's names are a checkpoint contract and are the plain
reference's (``layer_<i>/{norm_op, norm_ffn, conv | attn, mlp | moe}``).
RMSNorm, the dense SwiGLU, the held experts, grouped-query attention
with QK-norm and rotate-half RoPE (shared with ``models/sdar_moe.py``),
the causal attention core and the task are ``models/token_blocks.py``'s,
shared with ``models/mla_moe.py``; this file holds what is LFM2's own:
the gated short convolution, the layer pattern and the tied head.

``jax.named_scope``s ``embed``, ``short_conv``, ``gqa_proj``,
``gqa_attn_core``, ``dense_ffn``, ``routed_experts`` and ``lm_head_loss``
(the final norm and the tied head's product here, the log-softmax in the
task's loss) mark the mechanisms in the compiled program's metadata: the
catalogue of docs/observability.md, "Named scopes".

The attention core is ``token_blocks.causal_attention`` (scope
``gqa_attn_core``): the tiled Pallas kernels of
``ops/pallas_attention.py`` wherever a compiled kernel applies — query
head ``h`` reads key-value head ``h // 4`` through the block index,
``dk``/``dv`` are summed over the group inside the kernel, and the
scores of a 4,096-token row, 32 x 4096 x 4096 floats, never leave VMEM —
and elsewhere (the CPU; GSPMD outside ``shard_map``) the blocked plain
path: ``attention_block`` query rows at a time against the keys up to
the block's end, each block a ``jax.checkpoint``.  The sequence is
padded to a whole number of blocks inside the module and the padding's
logits are cut off again (causal: padding at the end changes nothing
before it).

``model_config.dtype: bfloat16`` computes activations and matmul
operands in bfloat16 over float32 master weights (norms, the router and
the loss stay float32); ``remat: true`` recomputes each layer in the
backward pass.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .base import parse_dtype
from .token_blocks import (COUNTERS, ExpertLMTask, _DenseMLP, _GQAttention,
                           _HeldExperts, _normal, _RMSNorm, check_held)


class _GatedShortConv(nn.Module):
    taps: int
    dtype: Any

    @nn.compact
    def __call__(self, z):  # [B, L, D]
        hidden = z.shape[-1]
        w_in = self.param("w_in", _normal(0.02), (hidden, 3 * hidden))
        w_conv = self.param("w_conv", _normal(0.5), (hidden, self.taps))
        w_out = self.param("w_out", _normal(0.02), (hidden, hidden))
        with jax.named_scope("short_conv"):
            gate_b, gate_c, u = jnp.split(z @ w_in.astype(self.dtype), 3,
                                          axis=-1)
            bu = gate_b * u
            padded = jnp.pad(bu, ((0, 0), (self.taps - 1, 0), (0, 0)))
            length = z.shape[1]
            taps = w_conv.astype(self.dtype)
            v = sum(taps[:, j] * padded[:, self.taps - 1 - j:
                                        self.taps - 1 - j + length]
                    for j in range(self.taps))
            return (gate_c * v) @ w_out.astype(self.dtype)


class _Layer(nn.Module):
    op: str
    ffn: str
    cfg: Any  # hashable tuple of (key, value): the sizes, see _LFM2

    @nn.compact
    def __call__(self, x):
        c = dict(self.cfg)
        eps, dtype = c["norm_eps"], c["dtype"]
        z = _RMSNorm(eps, name="norm_op")(x)
        if self.op == "conv":
            h = x + _GatedShortConv(c["conv_L_cache"], dtype, name="conv")(z)
        else:
            h = x + _GQAttention(
                c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"], eps, c["rope_theta"], c["attention_block"],
                dtype, name="attn")(z)
        z = _RMSNorm(eps, name="norm_ffn")(h)
        if self.ffn == "dense":
            with jax.named_scope("dense_ffn"):
                return h + _DenseMLP(c["intermediate_size"], dtype,
                                     name="mlp")(z), {}
        with jax.named_scope("routed_experts"):
            y, counters = _HeldExperts(
                c["num_experts"], c["experts_held"], c["expert_offset"],
                c["num_experts_per_tok"], c["moe_intermediate_size"],
                c["routed_scaling_factor"], dtype, name="moe")(z)
        return h + y, counters


class _LFM2(nn.Module):
    vocab_size: int
    hidden_size: int
    layers: Tuple[Tuple[str, str], ...]  # (operator, ffn) per layer
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
        with jax.named_scope("embed"):
            h = jnp.take(table, x, axis=0).astype(dtype)
        layer_cls = nn.remat(_Layer) if self.remat else _Layer
        counters: Dict[str, jnp.ndarray] = {}
        for i, (op, ffn) in enumerate(self.layers):
            # explicit names: the tree is the same with remat on or off
            h, counted = layer_cls(op, ffn, self.cfg, name=f"layer_{i}")(h)
            for key, value in counted.items():
                counters[key] = counters.get(key, 0.0) + value
        with jax.named_scope("lm_head_loss"):
            h = _RMSNorm(c["norm_eps"], name="norm_emb")(h)
            logits = h @ table.T.astype(dtype)
        return logits[:, :length], counters


def layer_kinds(model_config) -> Tuple[Tuple[str, str], ...]:
    """``layer_types`` (comma-separated ``conv`` / ``full_attention``)
    and ``num_dense_layers`` -> ``((operator, ffn), ...)``."""
    ops = [t.strip() for t in str(model_config["layer_types"]).split(",")]
    unknown = sorted(set(ops) - {"conv", "full_attention"})
    if unknown:
        raise ValueError(f"model_config.layer_types: unknown {unknown}; "
                         "expected conv or full_attention")
    dense = int(model_config.get("num_dense_layers", 0))
    return tuple((op, "dense" if i < dense else "moe")
                 for i, op in enumerate(ops))


def make_lfm2_task(model_config) -> ExpertLMTask:
    layers = layer_kinds(model_config)
    hidden = int(model_config["hidden_size"])
    heads = int(model_config["num_attention_heads"])
    moe = any(ffn == "moe" for _, ffn in layers)
    num_experts = int(model_config.get("num_experts", 0) or 0)
    held, offset = check_held(model_config, num_experts) if moe else (0, 0)
    cfg = tuple(sorted({
        "dtype": parse_dtype(model_config),
        "norm_eps": float(model_config.get("norm_eps", 1e-5)),
        "rope_theta": float(model_config.get("rope_theta", 1e6)),
        "conv_L_cache": int(model_config.get("conv_L_cache", 3)),
        "num_attention_heads": heads,
        "num_key_value_heads": int(model_config.get("num_key_value_heads",
                                                    heads)),
        "head_dim": int(model_config.get("head_dim", hidden // heads)),
        "attention_block": int(model_config.get("attention_block", 512)),
        "intermediate_size": int(model_config.get("intermediate_size",
                                                  4 * hidden)),
        "moe_intermediate_size": int(
            model_config.get("moe_intermediate_size", hidden)),
        "num_experts": num_experts,
        "experts_held": held,
        "expert_offset": offset,
        "num_experts_per_tok": int(model_config.get("num_experts_per_tok",
                                                    1)),
        "routed_scaling_factor": float(
            model_config.get("routed_scaling_factor", 1.0)),
    }.items()))
    module = _LFM2(vocab_size=int(model_config["vocab_size"]),
                   hidden_size=hidden, layers=layers, cfg=cfg,
                   remat=bool(model_config.get("remat", False)))
    task = ExpertLMTask(module,
                        seq_len=int(model_config.get("seq_len", 4096)),
                        name="lfm2_moe")
    if not moe:
        task.counter_names = ()
    return task
