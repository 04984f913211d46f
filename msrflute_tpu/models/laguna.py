"""Laguna-MoE (poolside's Laguna-XS.2, ``model_type: laguna``) — one
chip's share of an expert-parallel deployment, as a causal-LM task for
the federated round.

Net-new vs the reference (FLUTE ships no such model).  The layer
equations are written out in ``benchmarks/reference/laguna_moe.py`` (the
plain float32 form the benchmark compares this module with); in short,
``h = x + attn(norm_op(x)); y = h + ffn(norm_ffn(h))`` with

- ``attn`` grouped-query attention of TWO layer types
  (``token_blocks._GQAttention``, no norm on query or key): layer ``l``
  is FULL where ``l % full_attention_period == 0`` (published
  ``layer_types``: full, sliding, sliding, sliding, ...) and SLIDING
  elsewhere.  The types differ in their query heads
  (``num_attention_heads`` full, ``num_attention_heads_sliding``
  sliding, both over ``num_key_value_heads`` key-value heads of
  ``head_dim``), in what a query sees (full: every key up to its own;
  sliding: the ``sliding_window`` keys up to its own) and in their
  rotary law (full: YaRN over the first ``partial_rotary_factor`` of a
  head, cos and sin times ``rope_attention_factor``; sliding: plain RoPE
  at ``rope_theta_sliding`` over the whole head); both multiply the
  core's output by ``sigmoid(z wg)`` elementwise before ``wo``
  (``gating``);
- ``ffn`` a dense SwiGLU in the leading ``num_dense_layers`` layers and
  after them a SHARED expert (one SwiGLU of
  ``shared_expert_intermediate_size`` on every token) beside the held
  share of ``num_experts`` sigmoid-routed SwiGLU experts
  (:func:`msrflute_tpu.ops.moe.held_experts_ffn`: ``experts_held``
  experts from ``expert_offset``, ``num_experts_per_tok`` a token over
  all experts, renormalised, times ``moe_routed_scaling_factor``;
  nothing dropped, no exchange on one chip);
- a final RMSNorm and an UNTIED head (``head [vocab, hidden]``).

The published config gives the layer pattern, the dense layers, the
heads a layer and the rotary laws as lists and a dict; ``model_config``
carries their contents as scalars (the benchmark's harness hashes
``model_config``'s items): the period, the count, the two head counts
and the eight rotary numbers.

The parameter tree's names are a checkpoint contract and are the plain
reference's (``layer_<i>/{norm_op, norm_ffn, attn/{wq, wk, wv, wo, wg},
mlp | moe + shared}``, ``embedding``, ``norm_emb``, ``head``).  The
attention core is ``models/token_blocks.causal_attention``: the tiled
Pallas kernels of ``ops/pallas_attention.py`` wherever a compiled kernel
applies (the causal kernels in a full layer, the window law's in a
sliding one), the blocked plain path at ``attention_block`` rows
elsewhere.  ``jax.named_scope``s ``embed``, ``gqa_proj``,
``gqa_attn_core``, ``dense_ffn``, ``shared_expert``, ``routed_experts``
and ``lm_head_loss`` as in the other token models
(docs/observability.md, "Named scopes").  ``dtype`` and ``remat`` as in
the other token models.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .base import parse_dtype
from .token_blocks import (ExpertLMTask, RotaryLaw, _DenseMLP, _GQAttention,
                           _HeldExperts, _normal, _RMSNorm, check_held)


def plain_inv_freq(theta: float, rotated: int) -> tuple:
    """``theta ** (-2i / rotated)``, ``i = 0 .. rotated / 2 - 1``."""
    return tuple(float(f) for f in
                 theta ** (-np.arange(0, rotated, 2, dtype=np.float64) /
                           rotated))


def yarn_inv_freq(theta: float, rotated: int, factor: float,
                  original_max: int, beta_fast: float,
                  beta_slow: float) -> tuple:
    """YaRN's inverse frequencies over ``rotated`` elements, as
    transformers' ``_compute_yarn_parameters`` makes them: pair ``i``
    keeps its plain frequency below the correction range, takes the
    interpolated one (``/ factor``) above it and a linear blend inside
    (``low`` / ``high``: the pairs that turn ``beta_fast`` / ``beta_slow``
    times over ``original_max`` positions, floored / ceiled)."""
    def correction_dim(rotations):
        return rotated * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rotated - 1)
    if low == high:
        high += 0.001
    plain = np.asarray(plain_inv_freq(theta, rotated), np.float64)
    ramp = np.clip((np.arange(rotated // 2, dtype=np.float64) - low) /
                   (high - low), 0.0, 1.0)
    return tuple(float(f) for f in
                 plain / factor * ramp + plain * (1.0 - ramp))


def layer_types(model_config) -> list:
    """``(attention, ffn)`` of every layer: ``full`` where ``l %
    full_attention_period == 0`` else ``sliding``; ``dense`` in the
    leading ``num_dense_layers`` else ``moe``."""
    period = int(model_config["full_attention_period"])
    dense = int(model_config["num_dense_layers"])
    return [("full" if i % period == 0 else "sliding",
             "dense" if i < dense else "moe")
            for i in range(int(model_config["num_hidden_layers"]))]


class _Layer(nn.Module):
    attn: str  # full | sliding
    ffn: str   # dense | moe
    cfg: Any   # hashable tuple of (key, value) sizes: make_laguna_moe_task

    @nn.compact
    def __call__(self, x):
        c = dict(self.cfg)
        eps, dtype = c["rms_norm_eps"], c["dtype"]
        sliding = self.attn == "sliding"
        z = _RMSNorm(eps, name="norm_op")(x)
        h = x + _GQAttention(
            c["num_attention_heads_sliding" if sliding
              else "num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], eps, 0.0, c["attention_block"], dtype,
            qk_norm=False,
            rotary=c["rotary_sliding" if sliding else "rotary_full"],
            window=c["sliding_window"] if sliding else 0,
            gate=True, name="attn")(z)
        z = _RMSNorm(eps, name="norm_ffn")(h)
        if self.ffn == "dense":
            with jax.named_scope("dense_ffn"):
                return h + _DenseMLP(c["intermediate_size"], dtype,
                                     name="mlp")(z), {}
        with jax.named_scope("shared_expert"):
            shared = _DenseMLP(c["shared_expert_intermediate_size"], dtype,
                               name="shared")(z)
        with jax.named_scope("routed_experts"):
            routed, counters = _HeldExperts(
                c["num_experts"], c["experts_held"], c["expert_offset"],
                c["num_experts_per_tok"], c["moe_intermediate_size"],
                c["moe_routed_scaling_factor"], dtype, name="moe")(z)
        return h + shared + routed, counters


class _LagunaMoE(nn.Module):
    vocab_size: int
    hidden_size: int
    layers: Any  # tuple of (attention, ffn) a layer
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
        for i, (attn, ffn) in enumerate(self.layers):
            # explicit names: the tree is the same with remat on or off
            h, counted = layer_cls(attn, ffn, self.cfg,
                                   name=f"layer_{i}")(h)
            for key, value in counted.items():
                counters[key] = counters.get(key, 0.0) + value
        with jax.named_scope("lm_head_loss"):
            h = _RMSNorm(c["rms_norm_eps"], name="norm_emb")(h)
            logits = h @ head.T.astype(dtype)
        return logits[:, :length], counters


#: what this module computes one way only; another value is an error
#: that names the key, not a silent other model
_ONLY = {"attention_bias": False, "tie_word_embeddings": False,
         "moe_apply_router_weight_on_input": False, "gating": True}


def make_laguna_moe_task(model_config) -> ExpertLMTask:
    for key, only in _ONLY.items():
        if model_config.get(key, only) != only:
            raise ValueError(
                f"model_config.{key}={model_config.get(key)!r}: "
                f"models/laguna.py computes {only!r} only")
    hidden = int(model_config["hidden_size"])
    heads = int(model_config["num_attention_heads"])
    kv = int(model_config["num_key_value_heads"])
    sliding_heads = int(model_config["num_attention_heads_sliding"])
    dim = int(model_config["head_dim"])
    for key, count in (("num_attention_heads", heads),
                       ("num_attention_heads_sliding", sliding_heads)):
        if count % kv:
            raise ValueError(
                f"model_config.{key}={count} is not a whole number of "
                f"groups over num_key_value_heads={kv}")
    rotated = int(dim * float(model_config["partial_rotary_factor"]))
    if rotated % 2 or not 0 < rotated <= dim:
        raise ValueError(
            f"model_config.partial_rotary_factor="
            f"{model_config['partial_rotary_factor']!r} does not leave "
            f"an even number of head_dim={dim} elements to rotate")
    layers = tuple(layer_types(model_config))
    moe = any(ffn == "moe" for _, ffn in layers)
    num_experts = int(model_config["num_experts"])
    held, offset = check_held(model_config, num_experts) if moe else (0, 0)
    # a factor of 1 is the plain law: both ends of the blend are equal
    rotary_full = RotaryLaw(
        yarn_inv_freq(
            float(model_config["rope_theta"]), rotated,
            float(model_config["rope_factor"]),
            int(model_config["rope_original_max_position_embeddings"]),
            float(model_config["rope_beta_fast"]),
            float(model_config["rope_beta_slow"])),
        float(model_config["rope_attention_factor"]))
    rotary_sliding = RotaryLaw(plain_inv_freq(
        float(model_config["rope_theta_sliding"]), dim))
    cfg = tuple(sorted({
        "dtype": parse_dtype(model_config),
        "rms_norm_eps": float(model_config["rms_norm_eps"]),
        "num_attention_heads": heads,
        "num_attention_heads_sliding": sliding_heads,
        "num_key_value_heads": kv,
        "head_dim": dim,
        "sliding_window": int(model_config["sliding_window"]),
        "rotary_full": rotary_full,
        "rotary_sliding": rotary_sliding,
        "attention_block": int(model_config["attention_block"]),
        "intermediate_size": int(model_config["intermediate_size"]),
        "moe_intermediate_size": int(model_config["moe_intermediate_size"]),
        "shared_expert_intermediate_size": int(
            model_config["shared_expert_intermediate_size"]),
        "num_experts": num_experts,
        "experts_held": held,
        "expert_offset": offset,
        "num_experts_per_tok": int(model_config["num_experts_per_tok"]),
        "moe_routed_scaling_factor": float(
            model_config["moe_routed_scaling_factor"]),
    }.items()))
    module = _LagunaMoE(vocab_size=int(model_config["vocab_size"]),
                        hidden_size=hidden, layers=layers, cfg=cfg,
                        remat=bool(model_config.get("remat", False)))
    task = ExpertLMTask(module, seq_len=int(model_config["seq_len"]),
                        name="laguna_moe")
    if not moe:
        task.counter_names = ()
    return task
