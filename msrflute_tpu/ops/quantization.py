"""Gradient quantization — histogram binning + quantile sparsification.

Parity target: reference ``extensions/quantization/quant.py:9-100``:
per-layer (or global) min/max histogram binning of the gradient into
``2**quant_bits`` levels, with components whose magnitude falls below the
``quant_threshold`` quantile set to zero.  Semantics preserved:

- bin labels = ``linspace(min, max, n_bins)``; each value maps to the
  nearest label (the reference shifts by half a bin width before
  ``bucketize`` to turn ceil into round — here we use rounding directly);
- threshold = quantile of ``|grad|`` at ``quant_threshold``; strictly
  greater survives (``quant.py:50-51``).

TPU-native: pure jnp, runs inside the jitted round under vmap over clients.
This is the designated Pallas-fusion candidate (SURVEY.md §7): a fused
clip->noise->bin pass over the flat update; see
:mod:`msrflute_tpu.ops.pallas_kernels`.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp


def approx_quantile_abs(x: jnp.ndarray, q, n_bins: int = 2048) -> jnp.ndarray:
    """Histogram-CDF approximation of ``quantile(|x|, q)``.

    ``jnp.quantile`` sorts — O(n log n) *per leaf per client* under the
    round's vmap, which profiling flagged as the dominant cost of a
    DGA+quant round.  A fixed-width histogram of ``|x|`` is one O(n)
    scatter-add; the threshold is linearly interpolated inside the bin
    where the CDF crosses ``q``.  Max error is one bin width
    (``max|x| / n_bins``) — far below the annealed-threshold granularity
    the reference runs with (``extensions/quantization/quant.py:50-51``).
    """
    a = jnp.abs(x.reshape(-1).astype(jnp.float32))
    hi = jnp.maximum(jnp.max(a), 1e-30)
    idx = jnp.clip((a / hi * n_bins).astype(jnp.int32), 0, n_bins - 1)
    # integer accumulators: float32 counts saturate at 2^24 (x+1 == x),
    # silently breaking the one-bin-width error bound for >16M-element leaves
    counts = jnp.zeros((n_bins,), jnp.int32).at[idx].add(1)
    cdf = jnp.cumsum(counts).astype(jnp.float32) / a.size
    # first bin whose cdf >= q, then interpolate within it
    bin_i = jnp.argmax(cdf >= q)
    prev = jnp.where(bin_i > 0, cdf[jnp.maximum(bin_i - 1, 0)], 0.0)
    frac = (q - prev) / jnp.maximum(cdf[bin_i] - prev, 1e-12)
    return (bin_i + jnp.clip(frac, 0.0, 1.0)) * hi / n_bins


def bin_sparsify(g: jnp.ndarray, lo, hi, thresh, n_bins: int) -> jnp.ndarray:
    """The elementwise core in plain jnp: nearest of ``n_bins`` labels on
    ``linspace(lo, hi)`` (== the reference's half-bin-shifted bucketize),
    zero where ``|g| <= thresh``.  The non-TPU path of
    :func:`quantize_array` and the reference the Pallas kernel
    (:func:`~msrflute_tpu.ops.pallas_kernels.quant_bin_sparsify`) is
    checked against."""
    width = (hi - lo) / jnp.maximum(n_bins - 1, 1)
    idx = jnp.clip(jnp.round((g - lo) / jnp.maximum(width, 1e-30)),
                   0, n_bins - 1)
    return jnp.where(jnp.abs(g) > thresh, lo + idx * width, 0.0)


def quantize_array(grad: jnp.ndarray, n_bins: int,
                   quant_threshold: float,
                   min_grad: Optional[jnp.ndarray] = None,
                   max_grad: Optional[jnp.ndarray] = None,
                   approx: bool = False) -> jnp.ndarray:
    """Quantize one tensor to ``n_bins`` levels, zeroing sub-threshold
    components (reference ``quant_bins`` + thresholding).

    Stats (min/max/quantile) run in XLA; on TPU the elementwise
    bin+sparsify pass runs as the fused Pallas kernel where a compiled
    kernel can apply (``pallas_kernels.compiled_kernels_apply``)."""
    g = grad.astype(jnp.float32)
    lo = jnp.min(g) if min_grad is None else min_grad
    hi = jnp.max(g) if max_grad is None else max_grad
    thresh = (approx_quantile_abs(g, quant_threshold) if approx
              else jnp.quantile(jnp.abs(g), quant_threshold))
    from .pallas_kernels import compiled_kernels_apply, quant_bin_sparsify
    if compiled_kernels_apply():
        out = quant_bin_sparsify(g.reshape(-1), lo, hi, thresh, n_bins)
        return out.reshape(grad.shape).astype(grad.dtype)
    return bin_sparsify(g, lo, hi, thresh, n_bins).astype(grad.dtype)


def quantize_pytree(tree: Any, quant_threshold: Optional[float],
                    quant_bits: int = 8, global_stats: bool = False,
                    approx: bool = False) -> Any:
    """Quantize every leaf (reference ``quant_model``).  ``global_stats``
    computes one min/max/threshold across all leaves (``quant.py:36-39``).
    ``approx`` swaps the exact sort-based quantile for the O(n)
    histogram-CDF estimate (config ``client_config.quant_approx``)."""
    if quant_threshold is None:
        return tree
    n_bins = 2 ** int(quant_bits)
    if not global_stats:
        return jax.tree.map(
            lambda g: quantize_array(g, n_bins, quant_threshold,
                                     approx=approx), tree)
    from jax.flatten_util import ravel_pytree
    flat, unravel = ravel_pytree(tree)
    lo, hi = jnp.min(flat), jnp.max(flat)
    thresh = (approx_quantile_abs(flat, quant_threshold) if approx
              else jnp.quantile(jnp.abs(flat), quant_threshold))
    return unravel(bin_sparsify(flat, lo, hi, thresh, n_bins))
