"""DGA — Dynamic Gradient Aggregation (arXiv:2106.07578).

Parity target: reference ``core/strategies/dga.py``:

- client softmax weight ``exp(-beta * metric)`` where metric is
  ``train_loss/num_samples`` or a gradient sufficient stat
  (``mag``/``var``/``mean``) per ``weight_train_loss``
  (``dga.py:110-129``), filtered through ``filter_weight``;
- local DP noising of payload + weight (``dga.py:131-134``);
- gradient quantization (``dga.py:148-149``);
- server-side **staleness simulation**: with probability ``stale_prob`` a
  client's weighted gradient is deferred to the next round
  (``dga.py:260-284``) — here the deferred sum is an explicit pytree state
  threaded through the jitted round step instead of host-side lists;
- global DP after aggregation (``dga.py:222-226``);
- optional RL weight re-estimation stays a host-side hook
  (``dga.py:286-406``, see :mod:`msrflute_tpu.rl`).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .base import BaseStrategy, filter_weight


class DGA(BaseStrategy):

    stateful = True

    def __init__(self, config, dp_config=None):
        super().__init__(config, dp_config)
        sc = config.server_config
        self.aggregate_median = sc.get("aggregate_median", "softmax")
        self.softmax_beta = float(sc.get("softmax_beta", 1.0))
        self.weight_metric = sc.get("weight_train_loss", "train_loss")
        self.stale_prob = float(sc.get("stale_prob", 0.0))
        cc = config.client_config
        mc = config.model_config
        self.quant_threshold = cc.get("quant_thresh")
        if self.quant_threshold is None and mc is not None:
            self.quant_threshold = mc.get("quant_threshold")
        bits = cc.get("quant_bits")
        if bits is None and mc is not None:
            bits = mc.get("quant_bits")
        self.quant_bits = int(bits) if bits is not None else 10

    def client_weight(self, *, num_samples, train_loss, stats, rng):
        if self.aggregate_median == "softmax":
            if self.weight_metric == "train_loss":
                metric = train_loss / jnp.maximum(num_samples, 1.0)
            elif self.weight_metric == "mag_var_loss":
                metric = stats["var"]
            elif self.weight_metric == "mag_mean_loss":
                metric = stats["mean"]
            else:
                metric = stats["mag"]
            weight = jnp.exp(-self.softmax_beta * metric)
        else:
            weight = jnp.ones_like(train_loss)
        return filter_weight(weight)

    def transform_payload(self, pseudo_grad: Any, weight: jnp.ndarray,
                          rng: jax.Array, quant_threshold=None,
                          strategy_state=None,
                          stats=None) -> Tuple[Any, jnp.ndarray]:
        dp_rng, _ = jax.random.split(rng)
        if self.dp_config is not None and self.dp_config.get("enable_local_dp", False):
            from ..privacy import apply_local_dp
            pseudo_grad, weight = apply_local_dp(
                pseudo_grad, weight, self.dp_config,
                add_weight_noise=(self.aggregate_median == "softmax"), rng=dp_rng)
        if self.quant_threshold is not None:
            from ..ops.quantization import quantize_pytree
            # the threshold may be annealed per round (reference
            # core/server.py:294-298): a dynamic scalar overrides the
            # static config value when >= 0
            thr = (quant_threshold if quant_threshold is not None
                   else float(self.quant_threshold))
            thr = jnp.where(jnp.asarray(thr) >= 0, thr,
                            float(self.quant_threshold))
            pseudo_grad = quantize_pytree(
                pseudo_grad, quant_threshold=thr, quant_bits=self.quant_bits)
        return pseudo_grad, weight

    # ---- staleness buffer (replaces dga.py:260-284 host lists) --------
    def init_state(self, params_like: Any) -> Any:
        if self.stale_prob <= 0.0:
            return ()
        zeros = jax.tree.map(jnp.zeros_like, params_like)
        return {"stale_grad_sum": zeros, "stale_weight_sum": jnp.zeros(())}

    def combine(self, weighted_grad_sum, weight_sum, deferred, state, rng,
                num_clients=None):
        new_state = state
        if self.stale_prob > 0.0 and deferred is not None:
            # fold in LAST round's deferred contributions; bank this round's
            # deferred sums for next round (dga.py:260-284 semantics).
            weighted_grad_sum = jax.tree.map(
                lambda tot, s: tot + s, weighted_grad_sum, state["stale_grad_sum"])
            weight_sum = weight_sum + state["stale_weight_sum"]
            new_state = {"stale_grad_sum": deferred["grad_sum"],
                         "stale_weight_sum": deferred["weight_sum"]}
        denom = jnp.maximum(weight_sum, 1e-12)
        agg = jax.tree.map(lambda g: g / denom, weighted_grad_sum)
        if self.dp_config is not None and self.dp_config.get("enable_global_dp", False):
            from ..privacy import apply_global_dp
            n = num_clients if num_clients is not None else jnp.ones(())
            agg = apply_global_dp(agg, self.dp_config,
                                  rng=jax.random.fold_in(rng, 1), num_clients=n)
        return agg, new_state
