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

:class:`Conv` is the flax module that calls it (``nn.Conv``'s names,
shapes and initialiser), and takes its kernel EITHER in the declared
shape OR already cut to that window: a loop that trains the model can
then carry, differentiate and step the live window alone and put the
whole kernel together once, behind the loop
(``engine/client_update.py``).  Which kernels have such a window is read
from an abstract trace under :func:`collecting_windows`.
"""

from __future__ import annotations

import contextlib
import math
from typing import (Any, Callable, Dict, Iterator, Optional, Sequence,
                    Tuple)

import flax.linen as nn
from flax.linen.linear import canonicalize_padding
from jax import lax

#: ``conv_taps`` records by geometry, and the convolutions seen (cut or
#: not) since the last drain — buffered at trace time, drained by the
#: server's host tail with the attention and compile events
_PENDING: dict = {}
_SEEN = 0
_PENDING_CAP = 64
#: ``{parameter path: (start, limit)}`` while an abstract trace asks which
#: kernels have a live window (:func:`collecting_windows`), else None
_COLLECTING: Optional[Dict[Tuple[str, ...], Tuple[tuple, tuple]]] = None


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


def _record(lhs_shape, rhs_shape, cut, carried: bool = False) -> None:
    """Count one traced convolution and, where ``cut`` says it reads a
    window of its kernel, add it to that geometry's record.  ``carried``:
    the kernel arrived as the window (the caller's loop carries no more).
    An abstract trace that only asks for the windows counts nothing."""
    global _SEEN
    if _COLLECTING is not None:
        return
    _SEEN += 1
    if cut is None:
        return
    window, start, limit, _ = cut
    key = (tuple(lhs_shape), tuple(rhs_shape), tuple(window), carried)
    if key in _PENDING:
        _PENDING[key]["convs"] += 1
        _PENDING[key]["carried_live"] += int(carried)
    elif len(_PENDING) < _PENDING_CAP:
        total = math.prod(rhs_shape)
        live = math.prod(b - a for a, b in zip(start, limit))
        _PENDING[key] = {
            "kind": "conv_taps",
            "lhs_shape": list(lhs_shape),
            "kernel_shape": list(rhs_shape),
            "live_window": [list(taps) for taps in window],
            "weights_total": total,
            "weights_live": live,
            "weights_carried": live if carried else total,
            "convs": 1,
            "carried_live": int(carried),
        }


def _live_window(lhs_shape, rhs_shape, window_strides, padding,
                 lhs_dilation, rhs_dilation, dimension_numbers):
    """``(window, start, limit, pads)`` of a convolution with a dead
    tap: first and last live tap of each spatial axis, the kernel slice
    that holds them, and the padding shrunk by what the slice cuts.
    ``None`` where there is nothing to cut (no dead tap, an axis that
    meets only padding, an input dilation)."""
    if lhs_dilation is not None and any(d != 1 for d in lhs_dilation):
        return None
    lhs_spec, rhs_spec, _ = lax.conv_dimension_numbers(
        lhs_shape, rhs_shape, dimension_numbers)
    sizes = [lhs_shape[a] for a in lhs_spec[2:]]
    taps = [rhs_shape[a] for a in rhs_spec[2:]]
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
        return None
    start, limit = [0] * len(rhs_shape), list(rhs_shape)
    for axis, (t0, t1) in zip(rhs_spec[2:], window):
        start[axis], limit[axis] = t0, t1 + 1
    return window, tuple(start), tuple(limit), [
        (lo - t0 * d, hi - (k - 1 - t1) * d)
        for (t0, t1), k, d, (lo, hi) in zip(window, taps, dilations, pads)]


def live_tap_conv(lhs, rhs, window_strides: Sequence[int], padding,
                  lhs_dilation: Optional[Sequence[int]] = None,
                  rhs_dilation: Optional[Sequence[int]] = None,
                  dimension_numbers=None, feature_group_count: int = 1,
                  batch_group_count: int = 1, precision=None,
                  preferred_element_type=None):
    """``lax.conv_general_dilated`` over the live window of ``rhs``."""
    cut = _live_window(lhs.shape, rhs.shape, window_strides, padding,
                       lhs_dilation, rhs_dilation, dimension_numbers)
    _record(lhs.shape, rhs.shape, cut)
    if cut is not None:
        _, start, limit, padding = cut
        rhs = lax.slice(rhs, start, limit)
    return lax.conv_general_dilated(
        lhs, rhs, window_strides, padding, lhs_dilation, rhs_dilation,
        dimension_numbers, feature_group_count, batch_group_count,
        precision, preferred_element_type)


@contextlib.contextmanager
def collecting_windows() -> Iterator[Dict[Tuple[str, ...],
                                          Tuple[tuple, tuple]]]:
    """While open, every :class:`Conv` whose kernel has a dead tap at
    the traced shapes writes ``{its kernel's parameter path: (start,
    limit)}`` into the yielded dict, and no convolution is counted or
    recorded: the trace is a question (``jax.eval_shape`` of the
    forward), not a program."""
    global _COLLECTING
    was = _COLLECTING
    _COLLECTING = found = {}
    try:
        yield found
    finally:
        _COLLECTING = was


class Conv(nn.Module):
    """``nn.Conv`` without a bias (its name, so a tree keeps the
    ``Conv_<n>`` paths, kernel shape and initialiser it had) through
    :func:`live_tap_conv`, whose ``kernel`` may also arrive ALREADY CUT
    to its live window.  flax's own module refuses a parameter of
    another shape than it declares; this one reads what it is given and
    tells the two apart by static shape: the declared kernel is cut
    here, a window meets the padding shrunk as the cut would shrink it.
    The same products either way."""

    features: int
    kernel_size: Sequence[int]
    strides: Sequence[int] = (1, 1)
    padding: Any = 0
    kernel_init: Callable = nn.initializers.lecun_normal()
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        taps = tuple(self.kernel_size)
        declared = taps + (x.shape[-1], self.features)
        if self.has_variable("params", "kernel"):
            kernel = self.get_variable("params", "kernel")
        else:
            kernel = self.param("kernel", self.kernel_init, declared)
        x, kernel = nn.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        ones = (1,) * len(taps)
        geometry = dict(
            window_strides=tuple(self.strides),
            padding=canonicalize_padding(self.padding, len(taps)),
            lhs_dilation=ones, rhs_dilation=ones,
            dimension_numbers=nn.linear._conv_dimension_numbers(x.shape))
        cut = _live_window(x.shape, declared, **geometry)
        if kernel.shape == declared:
            if cut is not None and _COLLECTING is not None:
                _COLLECTING[self.path + ("kernel",)] = cut[1:3]
            return live_tap_conv(x, kernel, **geometry)
        if cut is None or kernel.shape != tuple(
                b - a for a, b in zip(*cut[1:3])):
            raise ValueError(
                f"{'/'.join(self.path)}: a kernel of shape {kernel.shape} "
                f"is neither the declared {declared} nor its live window")
        _record(x.shape, declared, cut, carried=True)
        return lax.conv_general_dilated(
            x, kernel, **dict(geometry, padding=cut[3]))
