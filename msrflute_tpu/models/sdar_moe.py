"""SDAR-MoE (JetLM's SDAR-30B-A3B-Chat, ``model_type: sdar_moe``: the
``qwen3_moe`` block trained to generate by diffusion over blocks) — one
chip's share of an expert-parallel deployment, as a block-diffusion LM
task for the federated round.

Net-new vs the reference (FLUTE ships no such model).  The layer and the
objective are written out in ``benchmarks/reference/sdar_moe.py`` (the
plain float32 form the benchmark compares this module with); in short,
``h = x + attn(norm_op(x)); y = h + moe(norm_ffn(h))`` in EVERY layer
(``decoder_sparse_step`` 1, ``mlp_only_layers`` []) with

- ``attn`` grouped-query attention with an RMSNorm on every head's query
  and key and rotate-half RoPE (``token_blocks._GQAttention``, LFM2's
  too) over a DOUBLED row: the noised copy ``xt`` and the clean copy
  ``x0`` of the same ``L`` positions side by side, both at rotary
  positions 0..L-1, under the block-diffusion mask (blocks of
  ``block_length``: ``xt`` sees its own block of ``xt`` and the earlier
  blocks of ``x0``; ``x0`` sees its own and the earlier blocks of
  ``x0``): ``token_blocks.block_diffusion_attention``, the kernels of
  ``ops/pallas_attention.py`` on a static tile map wherever a compiled
  kernel applies, blocks of plain rows elsewhere;
- ``moe`` the held share of ``num_experts`` SOFTMAX-routed SwiGLU experts
  (``ops.moe.held_experts_ffn`` with ``scoring="softmax"``: softmax over
  all experts, top ``num_experts_per_tok``, renormalised over the chosen
  (``norm_topk_prob``), no selection bias, no factor, no shared expert);
- a final RMSNorm on the ``xt`` half only and an UNTIED head.

The parameter tree's names are a checkpoint contract and are the plain
reference's (``layer_<i>/{norm_op, norm_ffn, attn/{wq, wk, wv, wo,
norm_q, norm_k}, moe/{router, w1, w3, w2}}``, ``embedding``,
``norm_emb``, ``head``).  ``jax.named_scope``s ``embed``, ``gqa_proj``,
``gqa_attn_core``, ``routed_experts`` and ``lm_head_loss`` as in the
other token models (docs/observability.md, "Named scopes").

Each half is padded to a whole number of ``attention_block`` rows inside
the module and the padding's logits are cut off again; a real position
sees no padded one as long as its row ends on a block boundary (a
shorter row's last block sees the padding ids beside it, in the program
and in the reference alike).  ``dtype`` and ``remat`` as in the other
token models.
"""

from __future__ import annotations

from typing import Any, Dict

import flax.linen as nn
import jax
import jax.numpy as jnp

from .base import parse_dtype
from .token_blocks import (BlockDiffusionLMTask, _GQAttention, _HeldExperts,
                           _normal, _RMSNorm, check_held)


class _Layer(nn.Module):
    cfg: Any  # hashable tuple of (key, value) sizes: make_sdar_moe_task

    @nn.compact
    def __call__(self, x):
        c = dict(self.cfg)
        eps, dtype = c["rms_norm_eps"], c["dtype"]
        z = _RMSNorm(eps, name="norm_op")(x)
        h = x + _GQAttention(
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], eps, c["rope_theta"], c["attention_block"], dtype,
            diffusion_block=c["block_length"], name="attn")(z)
        z = _RMSNorm(eps, name="norm_ffn")(h)
        with jax.named_scope("routed_experts"):
            y, counters = _HeldExperts(
                c["num_experts"], c["experts_held"], c["expert_offset"],
                c["num_experts_per_tok"], c["moe_intermediate_size"], 1.0,
                dtype, scoring="softmax", name="moe")(z)
        return h + y, counters


class _SDARMoE(nn.Module):
    vocab_size: int
    hidden_size: int
    num_layers: int
    cfg: Any
    remat: bool = False

    @nn.compact
    def __call__(self, x):  # [B, 2 L] ids ([xt ; x0]) -> [B, L, V], counters
        c = dict(self.cfg)
        dtype, block = c["dtype"], c["attention_block"]
        batch, length = x.shape[0], x.shape[1] // 2
        padded = length + -length % block
        x = jnp.pad(x.reshape(batch, 2, length),
                    ((0, 0), (0, 0), (0, padded - length))
                    ).reshape(batch, 2 * padded)
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
            h, counted = layer_cls(self.cfg, name=f"layer_{i}")(h)
            for key, value in counted.items():
                counters[key] = counters.get(key, 0.0) + value
        with jax.named_scope("lm_head_loss"):
            # the noised half alone goes through the norm and the head
            h = _RMSNorm(c["rms_norm_eps"], name="norm_emb")(h[:, :length])
            logits = h @ head.T.astype(dtype)
        return logits, counters


#: what this module computes one way only; another value is an error
#: that names the key, not a silent other model
_ONLY = {"decoder_sparse_step": 1, "mlp_only_layers": [],
         "norm_topk_prob": True, "rope_scaling": None,
         "use_sliding_window": False, "tie_word_embeddings": False,
         "attention_bias": False, "hidden_act": "silu"}


def make_sdar_moe_task(model_config) -> BlockDiffusionLMTask:
    for key, only in _ONLY.items():
        if model_config.get(key, only) != only:
            raise ValueError(
                f"model_config.{key}={model_config.get(key)!r}: "
                f"models/sdar_moe.py computes {only!r} only")
    hidden = int(model_config["hidden_size"])
    heads = int(model_config["num_attention_heads"])
    num_experts = int(model_config["num_experts"])
    held, offset = check_held(model_config, num_experts)
    span = int(model_config.get("block_length", 4))
    block = int(model_config.get("attention_block", 512))
    if block % span:
        raise ValueError(f"model_config.attention_block={block} is not a "
                         f"whole number of blocks of block_length={span}")
    cfg = tuple(sorted({
        "dtype": parse_dtype(model_config),
        "rms_norm_eps": float(model_config.get("rms_norm_eps", 1e-6)),
        "rope_theta": float(model_config.get("rope_theta", 1e6)),
        "num_attention_heads": heads,
        "num_key_value_heads": int(model_config.get("num_key_value_heads",
                                                    heads)),
        "head_dim": int(model_config.get("head_dim", hidden // heads)),
        "attention_block": block,
        "block_length": span,
        "moe_intermediate_size": int(
            model_config.get("moe_intermediate_size", hidden)),
        "num_experts": num_experts,
        "experts_held": held,
        "expert_offset": offset,
        "num_experts_per_tok": int(model_config.get("num_experts_per_tok",
                                                    1)),
    }.items()))
    vocab = int(model_config["vocab_size"])
    module = _SDARMoE(vocab_size=vocab, hidden_size=hidden,
                      num_layers=int(model_config["num_hidden_layers"]),
                      cfg=cfg, remat=bool(model_config.get("remat", False)))
    return BlockDiffusionLMTask(
        module, seq_len=int(model_config.get("seq_len", 4096)),
        name="sdar_moe", span=span, mask_id=vocab - 1,
        noise_seed=int(model_config.get("noise_seed", 0)))
