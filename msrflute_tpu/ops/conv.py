"""A convolution that reads only the kernel taps that can meet an input.

A tap of a convolution kernel that, at every output position, lands on
padding multiplies zeros: its products add nothing to the output, its
gradient is exactly zero, and yet the plain call reads it forward,
reverses and copies it backward and writes its zero gradient.  At small
feature maps that is most of a kernel: a 3x3 kernel with padding 1 over
a 1x1 map has one live tap of nine (ResNet-18 on 32x32 inputs: the whole
last stage, ``models/resnet.py``).

:func:`live_tap_conv` has the signature of ``lax.conv_general_dilated``
(flax's ``nn.Conv`` takes it through its ``conv_general_dilated=``
field).  From static shapes alone, at trace time, it slices the kernel to
the window of taps that can meet a real input position and shrinks the
padding by what it cut; autodiff of the slice pads the live gradient with
zeros.  Where no tap is dead it makes the plain call with the caller's
arguments, so every other shape compiles to the program it had.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

from jax import lax

#: ``conv_taps`` records by geometry, and the convolutions seen (cut or
#: not) since the last drain — buffered at trace time, drained by the
#: server's host tail with the attention and compile events
_PENDING: dict = {}
_SEEN = 0
_PENDING_CAP = 64


def drain_conv_events() -> list:
    """Hand the buffered ``conv_taps`` records to the caller (the
    server's host tail, which owns emitting them)."""
    global _SEEN
    out = [dict(rec, convs_traced=_SEEN) for rec in _PENDING.values()]
    _PENDING.clear()
    _SEEN = 0
    return out


def live_taps(n: int, k: int, stride: int, dilation: int,
              lo: int, hi: int) -> Optional[Tuple[int, int]]:
    """First and last live tap of one spatial axis: tap ``t`` is live
    iff some output position ``o`` has ``0 <= o*stride + t*dilation - lo
    < n``.  ``None`` where no tap is (the output is all padding)."""
    out = (n + lo + hi - ((k - 1) * dilation + 1)) // stride + 1
    live = [t for t in range(k)
            if any(0 <= o * stride + t * dilation - lo < n
                   for o in range(max(out, 0)))]
    return (live[0], live[-1]) if live else None


def _record(lhs_shape, rhs_shape, kernel_shape,
            window: List[Tuple[int, int]]) -> None:
    key = (tuple(lhs_shape), tuple(rhs_shape), tuple(window))
    if key in _PENDING:
        _PENDING[key]["convs"] += 1
    elif len(_PENDING) < _PENDING_CAP:
        _PENDING[key] = {
            "kind": "conv_taps",
            "lhs_shape": list(lhs_shape),
            "kernel_shape": list(rhs_shape),
            "live_window": [list(taps) for taps in window],
            "weights_total": math.prod(rhs_shape),
            "weights_live": math.prod(kernel_shape),
            "convs": 1,
        }


def live_tap_conv(lhs, rhs, window_strides: Sequence[int], padding,
                  lhs_dilation: Optional[Sequence[int]] = None,
                  rhs_dilation: Optional[Sequence[int]] = None,
                  dimension_numbers=None, feature_group_count: int = 1,
                  batch_group_count: int = 1, precision=None,
                  preferred_element_type=None):
    """``lax.conv_general_dilated`` over the live window of ``rhs``."""
    global _SEEN
    _SEEN += 1

    def plain(kernel, pads):
        return lax.conv_general_dilated(
            lhs, kernel, window_strides, pads, lhs_dilation, rhs_dilation,
            dimension_numbers, feature_group_count, batch_group_count,
            precision, preferred_element_type)

    if lhs_dilation is not None and any(d != 1 for d in lhs_dilation):
        return plain(rhs, padding)
    lhs_spec, rhs_spec, _ = lax.conv_dimension_numbers(
        lhs.shape, rhs.shape, dimension_numbers)
    sizes = [lhs.shape[a] for a in lhs_spec[2:]]
    taps = [rhs.shape[a] for a in rhs_spec[2:]]
    dilations = list(rhs_dilation or (1,) * len(taps))
    if isinstance(padding, str):
        pads = lax.padtype_to_pads(
            sizes, [(k - 1) * d + 1 for k, d in zip(taps, dilations)],
            window_strides, padding)
    else:
        pads = [tuple(p) for p in padding]
    window = [live_taps(n, k, s, d, lo, hi) for n, k, s, d, (lo, hi)
              in zip(sizes, taps, window_strides, dilations, pads)]
    if None in window or all(
            (t0, t1) == (0, k - 1) for (t0, t1), k in zip(window, taps)):
        return plain(rhs, padding)
    start, limit = [0] * rhs.ndim, list(rhs.shape)
    for axis, (t0, t1) in zip(rhs_spec[2:], window):
        start[axis], limit[axis] = t0, t1 + 1
    kernel = lax.slice(rhs, start, limit)
    _record(lhs.shape, rhs.shape, kernel.shape, window)
    return plain(kernel, [
        (lo - t0 * d, hi - (k - 1 - t1) * d)
        for (t0, t1), k, d, (lo, hi) in zip(window, taps, dilations, pads)])
