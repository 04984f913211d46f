"""Task/model contract.

Parity target: reference ``core/model.py:7-51`` — ``BaseModel`` with
``loss(input)``, ``inference(input)`` -> ``{'output', 'acc', 'batch_size'}``
(plus custom metrics as ``{'value', 'higher_is_better'}``), and
``set_train``/``set_eval`` mode toggles.

TPU-native redesign: a task is a bundle of *pure functions* over explicit
params (no mutable module state, no train/eval mode flags — train-ness is an
argument so everything jits):

- ``init_params(rng)``                        -> params pytree
- ``loss(params, batch, rng, train)``         -> (scalar, aux)  masked mean
- ``eval_stats(params, batch)``               -> dict of scalar SUMS
- ``finalize_metrics(sums)``                  -> {name: Metric}

``batch`` is a dict of arrays with leading batch axis plus ``sample_mask``;
every reduction must be mask-weighted so padded samples are invisible.
``eval_stats`` returns *sums* (not means) so the engine can ``psum`` them
across devices and finalize once — this reproduces the reference's
sample-weighted metric merge (``core/evaluation.py:160-183``) exactly while
staying associative.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..utils.metrics import Metric, MetricsDict

Params = Any
Batch = Dict[str, jnp.ndarray]


class BaseTask:
    """Abstract task: model + loss + metrics, all pure."""

    name: str = "base"
    #: feature keys holding 0-padded ``[..., L]`` token sequences whose tail
    #: padding may be cropped per round (``data.batching.seq_length_bucket``);
    #: the model must derive its position mask from the ids, never from L
    seq_pad_keys: Tuple[str, ...] = ()
    #: scalars the model counts inside its forward pass (an expert layer's
    #: load), returned by ``loss`` as ``aux["counters"][name]``; the client
    #: update sums them over the local steps and the round over its
    #: clients, and they leave in the packed stats as ``ctr_<name>``
    counter_names: Tuple[str, ...] = ()

    def init_params(self, rng: jax.Array) -> Params:
        raise NotImplementedError

    def loss(self, params: Params, batch: Batch, rng: Optional[jax.Array] = None,
             train: bool = True) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """Masked mean loss over the batch + aux stats (e.g. sample count)."""
        raise NotImplementedError

    def kernel_windows(self, params: Params, batch: Batch
                       ) -> Dict[Tuple[str, ...], Tuple[tuple, tuple]]:
        """``{parameter path: (start, limit)}`` of every leaf that, at
        ``batch``'s static shapes, the forward pass reads only through
        ``lax.slice(leaf, start, limit)`` AND that the model also takes
        already cut to that slice (``ops/conv.py::Conv``: the kernel taps
        that can meet an input).  A path is the tuple of the leaf's dict
        keys; ``params`` and ``batch`` may be abstract.  The local-steps
        loop carries such a leaf as its window
        (``engine/client_update.py``).  No leaf by default."""
        return {}

    def eval_stats(self, params: Params, batch: Batch) -> Dict[str, jnp.ndarray]:
        """Scalar *sums* for evaluation; must include ``loss_sum`` and
        ``sample_count``."""
        raise NotImplementedError

    def finalize_metrics(self, sums: Dict[str, jnp.ndarray]) -> MetricsDict:
        """Turn psum'd eval sums into the reference metric dict
        (``{'value','higher_is_better'}``, ``core/metrics.py:35-56``)."""
        n = max(float(sums["sample_count"]), 1.0)
        metrics = {"loss": Metric(float(sums["loss_sum"]) / n, higher_is_better=False)}
        if "correct_sum" in sums:
            metrics["acc"] = Metric(float(sums["correct_sum"]) / n, higher_is_better=True)
        return metrics


def to_float_image(x: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """Cast image batches to float; uint8 pixels normalize to [0, 1] so hosts
    can ship raw bytes (4x less transfer) and normalization fuses on-device."""
    if x.dtype == jnp.uint8:
        return x.astype(dtype) * (1.0 / 255.0)
    return x.astype(dtype)


def parse_dtype(model_config):
    """``model_config.dtype`` -> jnp dtype for activations/compute.

    TPU-native knob with no reference equivalent: ``bfloat16`` runs the
    matmuls/convs on the MXU at full rate while parameters (and the
    loss/metric math, which tasks upcast) stay float32 — the standard
    mixed-precision recipe.
    """
    name = str(model_config.get("dtype", "float32") or "float32").lower()
    table = {"float32": jnp.float32, "f32": jnp.float32,
             "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
             "float16": jnp.float16, "f16": jnp.float16}
    if name not in table:
        raise ValueError(f"model_config.dtype={name!r}; "
                         f"expected one of {sorted(table)}")
    return table[name]


def masked_mean(values: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Mean over real samples only; padded entries contribute nothing."""
    total = jnp.sum(values * mask)
    count = jnp.maximum(jnp.sum(mask), 1.0)
    return total / count


def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Per-sample cross entropy with integer labels."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
