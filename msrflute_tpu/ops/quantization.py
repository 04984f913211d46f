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
The threshold is an exact rank selection (:func:`quantile_abs`: a fixed
number of fused compare-and-count passes over the leaf, no sort); the
elementwise bin+sparsify pass is the Pallas kernel of
:mod:`msrflute_tpu.ops.pallas_kernels` on TPU.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

#: ``|x|`` of a float32 as its bit pattern: non-negative floats order as
#: their patterns do as int32, NaN above inf
_ABS_BITS = 0x7FFFFFFF
_INF_BITS = 0x7F800000


def abs_order_stats(x: jnp.ndarray, low, high):
    """``jnp.sort(jnp.abs(x).ravel())[low]`` and ``[high]`` without the
    sort, bit for bit, plus whether ``x`` holds a NaN (which then orders
    above inf, as in the sort).  ``low`` and ``high`` are int32 ranks,
    traced or not, with ``low <= high <= low + 1`` inside ``[0, n)``.

    The rank-``low`` pattern is the largest ``v`` with
    ``count(|x| < v) <= low``; that predicate is monotone in ``v``, so
    ``v`` is built bit by bit from the top: 31 passes, each one fused
    compare-and-count that only reads ``x``.  One more pass gives rank
    ``high``: the same value if more than ``high`` elements are ``<=`` it,
    else the least element above it.  The loop has a fixed length, so it
    batches under ``vmap`` with no masking."""
    def bits(leaf):
        # rebuilt where it is used, so that it fuses into each pass's
        # reduction and no |x| copy of the leaf is kept
        return lax.bitcast_convert_type(
            leaf.astype(jnp.float32), jnp.int32) & _ABS_BITS

    def keep_or_clear(i, prefix):
        # the leaf tied to the loop counter: the TPU compiler otherwise
        # hoists its bitcast out of the loop as an int32 copy of the leaf
        leaf, _ = lax.optimization_barrier((x, i))
        cand = prefix | jnp.left_shift(jnp.int32(1), 30 - i)
        below = jnp.sum(bits(leaf) < cand, dtype=jnp.int32)
        return jnp.where(below <= low, cand, prefix)

    with jax.named_scope("quant_select"):
        low_bits = lax.fori_loop(0, 31, keep_or_clear, jnp.int32(0))
        b = bits(x)
        at_most = jnp.sum(b <= low_bits, dtype=jnp.int32)
        above = jnp.min(jnp.where(b > low_bits, b, _ABS_BITS))
        has_nan = jnp.max(b) > _INF_BITS
        high_bits = jnp.where(at_most > high, low_bits, above)
    return (lax.bitcast_convert_type(low_bits, jnp.float32),
            lax.bitcast_convert_type(high_bits, jnp.float32), has_nan)


def quantile_ranks(n: int, q):
    """Ranks ``low``/``high`` (int32) and their weights for the linear
    quantile ``q`` of ``n`` sorted values, computed as
    ``jax._src.numpy.reductions._quantile`` computes them: float32
    ``q * (n - 1)``, floor and ceil, clamped to ``[0, n - 1]``.  Above
    2**24 elements float32 ``n - 1`` can round up to ``n``; the ranks
    are held to the last element once more as integers, where
    ``jnp.quantile``'s gather clips."""
    last = jnp.float32(n) - 1
    pos = jnp.asarray(q, jnp.float32) * last
    low, high = jnp.floor(pos), jnp.ceil(pos)
    high_weight = pos - low
    low_weight = 1 - high_weight
    low = jnp.minimum(lax.clamp(0.0, low, last).astype(jnp.int32), n - 1)
    high = jnp.minimum(lax.clamp(0.0, high, last).astype(jnp.int32), n - 1)
    return low, high, low_weight, high_weight


def quantile_abs(x: jnp.ndarray, q) -> jnp.ndarray:
    """``jnp.quantile(jnp.abs(x), q)`` (float32, method ``linear``) by
    selection instead of a sort: the two order statistics of
    :func:`quantile_ranks` from :func:`abs_order_stats`, blended as
    ``jnp.quantile`` blends them; NaN if ``x`` holds one.  ``q`` may be
    traced."""
    low, high, low_weight, high_weight = quantile_ranks(x.size, q)
    low_value, high_value, has_nan = abs_order_stats(x, low, high)
    return jnp.where(has_nan, jnp.nan,
                     low_value * low_weight + high_value * high_weight)


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
                   max_grad: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Quantize one tensor to ``n_bins`` levels, zeroing sub-threshold
    components (reference ``quant_bins`` + thresholding).

    Stats (min/max and the threshold's rank selection) run in XLA; on TPU
    the elementwise bin+sparsify pass runs as the fused Pallas kernel
    where a compiled kernel can apply
    (``pallas_kernels.compiled_kernels_apply``)."""
    g = grad.astype(jnp.float32)
    lo = jnp.min(g) if min_grad is None else min_grad
    hi = jnp.max(g) if max_grad is None else max_grad
    thresh = quantile_abs(g, quant_threshold)
    from .pallas_kernels import compiled_kernels_apply, quant_bin_sparsify
    if compiled_kernels_apply():
        out = quant_bin_sparsify(g.reshape(-1), lo, hi, thresh, n_bins)
        return out.reshape(grad.shape).astype(grad.dtype)
    return bin_sparsify(g, lo, hi, thresh, n_bins).astype(grad.dtype)


def quantize_pytree(tree: Any, quant_threshold: Optional[float],
                    quant_bits: int = 8, global_stats: bool = False) -> Any:
    """Quantize every leaf (reference ``quant_model``).  ``global_stats``
    computes one min/max/threshold across all leaves (``quant.py:36-39``)."""
    if quant_threshold is None:
        return tree
    n_bins = 2 ** int(quant_bits)
    if not global_stats:
        return jax.tree.map(
            lambda g: quantize_array(g, n_bins, quant_threshold), tree)
    from jax.flatten_util import ravel_pytree
    flat, unravel = ravel_pytree(tree)
    lo, hi = jnp.min(flat), jnp.max(flat)
    # the exact threshold in the type jnp.quantile gave it: the leaves'
    # for a static quantile, float32 for a traced one
    thresh = quantile_abs(flat, quant_threshold).astype(
        jnp.result_type(flat, quant_threshold))
    return unravel(bin_sparsify(flat, lo, hi, thresh, n_bins))
