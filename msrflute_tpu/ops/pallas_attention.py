"""Pallas flash attention — the long-context hot op, tiled for the MXU.

Net-new vs the reference (FLUTE has no attention models beyond HF BERT and
no long-context machinery, SURVEY.md §5.7).  Exact attention computed
blockwise in VMEM with an online softmax, O(L) memory instead of the
O(L^2) score materialization of a jnp path.  Two families call it: the
RingLM family (``models/ringlm.py``, ``ops/ring_attention.py``: one head
width, a key head a query head, global offsets, the ``lse`` cotangent,
behind the planner below) and the token models with held experts
(``models/token_blocks.causal_attention``: a value width of its own,
grouped key-value heads, static blocks, no planner:
:func:`causal_flash_attention`).  Three kernels, under stable names in
the device trace (``attn_flash_fwd``, ``attn_flash_dq``,
``attn_flash_dkv``; FlashAttention-2 style tiling); a third family, the
block-diffusion model (``models/sdar_moe.py``), runs the same tile bodies
under a mask that is not causal, on a static tile map, as three kernels
of its own (``attn_bd_fwd``, ``attn_bd_dq``, ``attn_bd_dkv``:
:func:`block_diffusion_flash_attention`, further down); a fourth, the
sliding layers of ``models/laguna.py``, runs them under the WINDOW LAW
(a query sees the ``window`` keys up to its own: a band under the
diagonal) as a second law of that tile map, again as three kernels of
its own (``attn_win_fwd``, ``attn_win_dq``, ``attn_win_dkv``:
:func:`window_flash_attention`, further down):

- forward: grid ``(B, H, Lq/block_q, Lk/block_k)`` with the key/value
  block index INNERMOST and ``arbitrary`` semantics — mosaic pipelines
  the next K/V block's HBM→VMEM fetch under the current block's work,
  and the ``(m, l, acc)`` online-softmax carry lives in VMEM scratch
  across the inner sweep.  VMEM residency is O(block), never O(L).
- backward: ``dq`` on the same grid shape; ``dk``/``dv`` on
  ``(B, KV, Lk/block_k, group * Lq/block_q)`` (the query blocks of every
  query head of the key-value head's group innermost, summed into one
  accumulator), on the TRANSPOSED score tile so that no product
  transposes its left operand.  Both recompute probabilities from the
  saved ``lse`` (no O(L^2) residuals).
- query head ``h`` reads key-value head ``h // group`` through the block
  index; values (and ``out``, ``dO``, ``dv``) have a width of their own.
- under ``causal`` the swept operand's block index stops at the
  diagonal: blocks beyond it are neither fetched nor computed, tiles
  wholly under it run WITHOUT a mask (no iota, compare or select), and
  only the tiles the diagonal crosses (and a padded last key block) pay
  for one.
- the caller scales ``q`` once; the row statistics ride lane-replicated
  ``[rows, 128]`` (whole vregs repeated to the tile's width, no lane
  broadcast) in the forward and ``dq`` kernels and as rows ``[1, bq]`` in
  the ``dk``/``dv`` kernel.

Precision: the products' operands are given in ONE dtype a call
(``mxu``).  The planner's API keeps float32 operands at the context's
precision; the token models' path takes :func:`context_mxu_dtype`:
bfloat16 operands (one MXU pass, float32 accumulation — what the TPU's
default precision does to a float32 einsum) unless the context asks for
more, float32 contracted in full under ``highest``.  Softmax, its
running maximum and sum, ``lse`` and the accumulators are float32
always.

Causal masking is GLOBAL-position based: dynamic ``q_offset``/``k_offset``
scalars (SMEM scalar-prefetch) shift the row/column ids, which is what
lets :func:`msrflute_tpu.ops.ring_attention.ring_self_attention` run these
same kernels on rotating chunk pairs whose positions differ per step.
:func:`flash_attention_lse` additionally returns the per-row logsumexp —
with a VJP that honors the lse cotangent — so rotation outputs can be
merged exactly outside the kernel.

Length/feature padding is static; masked probability entries are zeroed
explicitly (no ``-inf`` arithmetic on the MXU path).  Off-TPU the default
is an exact dense jnp reference with identical masking/lse semantics —
NOT interpret-mode kernels: the interpret machinery's cross-core barriers
deadlock when the op runs inside ``shard_map`` over multiple virtual CPU
devices (the federated round does exactly that).  Pass ``interpret=True``
to force the kernel code path (what the unit tests do, outside shard_map).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .moe import _dot_precision
from .pallas_kernels import _resolve_interpret, compiled_kernels_apply

_LANES = 128
# row statistics (lse/delta) ride lane-replicated over the trailing dim.
# PR-12 retile: the stat streams use FULL (8, 128)-aligned tiles — the
# old 8-lane blocks saved VMEM but made every stat load/store a sub-tile
# access, which mosaic serviced with masked sub-lane ops on the hot dq
# inner loop (device truth measured the kernel at 0.53x of dense at seq
# 2048 before the retile).  VMEM cost per grid step is 2 stat blocks x
# block_q x 128 x 4B — comparable to one head-dim block.
_STAT_LANES = _LANES
_NEG = -1e30  # "minus infinity" that survives exp/max without NaNs
#: default kernel tile when the caller pins blocks explicitly
_DEF_BLOCK = 128
#: the token models' tile (:func:`causal_blocks`)
_CAUSAL_BLOCK = 512


def _pad_axis(x, axis, to):
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _ceil_to(n, m):
    return int(np.ceil(n / m)) * m


#: stable kernel names (the trace's operation names; the benchmark's
#: ``attn_kernel_*`` readers find the kernels by them)
FWD_NAME = "attn_flash_fwd"
DQ_NAME = "attn_flash_dq"
DKV_NAME = "attn_flash_dkv"

#: ``a @ b.T``: every product of the three kernels contracts the LAST
#: dims of both operands or is a plain ``a @ b`` — none transposes its
#: left operand (the dk/dv kernel works on the TRANSPOSED score tile)
_NT = (((1,), (1,)), ((), ()))


def _to_width(stat, width):
    """A lane-replicated row statistic ``[rows, _STAT_LANES]`` at
    ``width`` lanes: whole vregs repeated, no lane broadcast (the
    fallback serves the interpreter's small test tiles only)."""
    if width == _STAT_LANES:
        return stat
    if width % _STAT_LANES == 0:
        return pltpu.repeat(stat, width // _STAT_LANES, axis=1)
    return jnp.broadcast_to(stat[:, :1], (stat.shape[0], width))


def _tile_mask(shape, q_axis, q_lo, k_lo, k_first, l_k, causal, pad_k):
    """Which entries of a score tile count: key rows that exist
    (``pad_k``: the last key block is padded) and, under ``causal``,
    global key position <= global query position.  ``q_axis`` is the
    tile's query axis (1 for the transposed tile of the dk/dv kernel)."""
    q_ids = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_ids = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = None
    if pad_k:
        mask = k_first + k_ids < l_k
    if causal:
        seen = q_lo + q_ids >= k_lo + k_ids
        mask = seen if mask is None else jnp.logical_and(mask, seen)
    return mask


def _each_tile(accumulate, *, causal, pad_k, q_lo, k_lo, block_q, block_k,
               last_k):
    """``accumulate(masked)`` for this grid step's tile: not at all where
    every key lies above the (global) diagonal; without a mask where every
    entry counts (the bulk of a long causal row: no iota, compare or
    select there); with the mask on the diagonal and on a padded last key
    block."""
    if causal:
        visible = k_lo <= q_lo + block_q - 1
        whole = k_lo + block_k - 1 <= q_lo      # implies visible
        if pad_k:
            whole = jnp.logical_and(whole, jnp.logical_not(last_k))
        pl.when(whole)(lambda: accumulate(False))
        pl.when(jnp.logical_and(visible, jnp.logical_not(whole)))(
            lambda: accumulate(True))
    elif pad_k:
        pl.when(jnp.logical_not(last_k))(lambda: accumulate(False))
        pl.when(last_k)(lambda: accumulate(True))
    else:
        accumulate(False)


# ----------------------------------------------------------------------
# one score tile of each kernel: what the causal kernels and the
# block-diffusion kernels (further down) run at a grid step that counts.
# ``mask_of``: falsy where every entry of the tile counts (no iota,
# compare or select), else ``shape -> bool array`` of the entries that do
# ----------------------------------------------------------------------
def _fwd_tile(q_ref, k_ref, v_ref, m_s, l_s, acc_s, mask_of, cdt):
    """The online-softmax step of the forward kernel for one tile."""
    precision = _dot_precision(cdt)
    q = q_ref[0, 0].astype(cdt)          # [bq, D], scaled by the caller
    k_blk = k_ref[0, 0].astype(cdt)      # [bk, D]
    v_blk = v_ref[0, 0].astype(cdt)      # [bk, Dv]
    s = jax.lax.dot_general(q, k_blk, _NT, precision=precision,
                            preferred_element_type=jnp.float32)
    if mask_of:
        mask = mask_of(s.shape)
        s = jnp.where(mask, s, _NEG)
    m_prev, l_prev = m_s[...], l_s[...]  # lane-replicated [bq, 128]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - _to_width(m_new, s.shape[1]))
    if mask_of:
        # rows with every entry masked have s == m_new == _NEG, and
        # exp(0) would resurrect them
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    m_s[...] = m_new
    l_s[...] = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * _to_width(corr, acc_s.shape[1]) + \
        jnp.dot(p.astype(cdt), v_blk, precision=precision,
                preferred_element_type=jnp.float32)


def _fwd_finalize(o_ref, lse_ref, m_s, l_s, acc_s):
    m, l = m_s[...], l_s[...]
    safe = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc_s[...] / _to_width(safe, acc_s.shape[1])
                   ).astype(o_ref.dtype)
    # TPU mosaic requires the last two BLOCK dims be (8k, 128m)-
    # aligned, so the per-row lse is stored lane-replicated as
    # [bq, _STAT_LANES] (same trick as jax's own tpu flash kernel);
    # rows that saw no key: zeros, lse = _NEG
    lse_ref[0, 0] = jnp.where(l > 0, m + jnp.log(safe), _NEG)


def _dq_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_s, mask_of,
             cdt):
    """One tile's term of ``dq`` (against the SCALED q: the caller
    applies ``ds``'s own factor once, at the end)."""
    precision = _dot_precision(cdt)
    q = q_ref[0, 0].astype(cdt)
    do = do_ref[0, 0].astype(cdt)
    k_blk = k_ref[0, 0].astype(cdt)
    v_blk = v_ref[0, 0].astype(cdt)
    s = jax.lax.dot_general(q, k_blk, _NT, precision=precision,
                            preferred_element_type=jnp.float32)
    # lse and delta arrive lane-replicated [bq, _STAT_LANES]
    p = jnp.exp(s - _to_width(lse_ref[0, 0], s.shape[1]))
    if mask_of:
        p = jnp.where(mask_of(s.shape), p, 0.0)
    dp = jax.lax.dot_general(do, v_blk, _NT, precision=precision,
                             preferred_element_type=jnp.float32)
    # d lse / d s = p: the caller has taken the lse cotangent off delta
    ds = p * (dp - _to_width(delta_ref[0, 0], s.shape[1]))
    dq_s[...] = dq_s[...] + jnp.dot(ds.astype(cdt), k_blk,
                                    precision=precision,
                                    preferred_element_type=jnp.float32)


def _dkv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_s, dv_s,
              mask_of, cdt):
    """One tile's terms of ``dk`` and ``dv``, on the TRANSPOSED score
    tile ``[bk, bq]``: the row statistics ride as rows ``[1, bq]`` (a
    sublane broadcast) and both accumulations are plain ``a @ b``."""
    precision = _dot_precision(cdt)
    k_blk = k_ref[0, 0].astype(cdt)      # [bk, D]
    v_blk = v_ref[0, 0].astype(cdt)      # [bk, Dv]
    q = q_ref[0, 0].astype(cdt)          # [bq, D], scaled by the caller
    do = do_ref[0, 0].astype(cdt)        # [bq, Dv]
    s_t = jax.lax.dot_general(k_blk, q, _NT, precision=precision,
                              preferred_element_type=jnp.float32)
    # padded query rows carry lse = delta = do = 0: they add nothing
    p_t = jnp.exp(s_t - lse_ref[0, 0])   # [bk, bq] - [1, bq]
    if mask_of:
        p_t = jnp.where(mask_of(s_t.shape), p_t, 0.0)
    dv_s[...] = dv_s[...] + jnp.dot(p_t.astype(cdt), do,
                                    precision=precision,
                                    preferred_element_type=jnp.float32)
    dp_t = jax.lax.dot_general(v_blk, do, _NT, precision=precision,
                               preferred_element_type=jnp.float32)
    ds_t = p_t * (dp_t - delta_ref[0, 0])
    # against the SCALED q: ds's own factor is in it
    dk_s[...] = dk_s[...] + jnp.dot(ds_t.astype(cdt), q,
                                    precision=precision,
                                    preferred_element_type=jnp.float32)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_s, l_s, acc_s, *, causal, block_q, block_k, l_k, num_k,
                cdt):
    qi, kj = pl.program_id(2), pl.program_id(3)
    q_lo = offs_ref[0] + qi * block_q
    k_lo = offs_ref[1] + kj * block_k
    pad_k = num_k * block_k != l_k

    @pl.when(kj == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def accumulate(masked):
        _fwd_tile(q_ref, k_ref, v_ref, m_s, l_s, acc_s, masked and (
            lambda shape: _tile_mask(shape, 0, q_lo, k_lo, kj * block_k,
                                     l_k, causal, pad_k)), cdt)

    _each_tile(accumulate, causal=causal, pad_k=pad_k, q_lo=q_lo, k_lo=k_lo,
               block_q=block_q, block_k=block_k, last_k=kj == num_k - 1)

    pl.when(kj == num_k - 1)(
        lambda: _fwd_finalize(o_ref, lse_ref, m_s, l_s, acc_s))


# ----------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------
def _dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_s, *, causal, scale, block_q, block_k, l_k, num_k,
               cdt):
    qi, kj = pl.program_id(2), pl.program_id(3)
    q_lo = offs_ref[0] + qi * block_q
    k_lo = offs_ref[1] + kj * block_k
    pad_k = num_k * block_k != l_k

    @pl.when(kj == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def accumulate(masked):
        _dq_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_s,
                 masked and (lambda shape: _tile_mask(
                     shape, 0, q_lo, k_lo, kj * block_k, l_k, causal,
                     pad_k)), cdt)

    _each_tile(accumulate, causal=causal, pad_k=pad_k, q_lo=q_lo, k_lo=k_lo,
               block_q=block_q, block_k=block_k, last_k=kj == num_k - 1)

    @pl.when(kj == num_k - 1)
    def _finalize():
        # q came scaled, so s was; ds's own factor is applied once here
        dq_ref[0, 0] = (dq_s[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_s, dv_s, *, causal, block_q, block_k,
                l_k, num_q, num_k, sweep, cdt):
    """One key block against every query block of every query head of
    its group (``sweep = group * num_q`` inner steps), on the TRANSPOSED
    score tile ``[bk, bq]``: the row statistics ride as rows
    ``[1, bq]`` (a sublane broadcast) and both accumulations are plain
    ``a @ b`` products."""
    ki, t = pl.program_id(2), pl.program_id(3)
    qj = t % num_q
    q_lo = offs_ref[0] + qj * block_q
    k_lo = offs_ref[1] + ki * block_k
    pad_k = num_k * block_k != l_k

    @pl.when(t == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def accumulate(masked):
        _dkv_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_s,
                  dv_s, masked and (lambda shape: _tile_mask(
                      shape, 1, q_lo, k_lo, ki * block_k, l_k, causal,
                      pad_k)), cdt)

    _each_tile(accumulate, causal=causal, pad_k=pad_k, q_lo=q_lo, k_lo=k_lo,
               block_q=block_q, block_k=block_k, last_k=ki == num_k - 1)

    @pl.when(t == sweep - 1)
    def _finalize():
        dk_ref[0, 0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[...].astype(dv_ref.dtype)


# ----------------------------------------------------------------------
# pallas_call plumbing (_fwd, _bwd jitted: one trace a shape, not a layer's)
# ----------------------------------------------------------------------
#: grid semantics: batch/head/outer-block axes are parallel; the inner
#: accumulation axis must execute in order (scratch carry)
_PARALLEL = pltpu.GridDimensionSemantics.PARALLEL
_ARBITRARY = pltpu.GridDimensionSemantics.ARBITRARY
_SEMANTICS = (_PARALLEL, _PARALLEL, _PARALLEL, _ARBITRARY)
#: the kernels' fast-memory limit: a 512 x 1024 float32 score tile and
#: its exponentials, products and masks stand beside double-buffered
#: operand blocks (the compiler's default scope is 16 MiB of the chip's
#: 128)
_VMEM_LIMIT = 64 * 2 ** 20


def _plain_interpret(interpret) -> bool:
    """The token models' path: ``None`` -> compiled on TPU, else the
    PLAIN interpreter.  The planner's API keeps the TPU-flavoured one
    (``pallas_kernels._resolve_interpret``: ``pltpu.InterpretParams``),
    but that one works through ordered callbacks, which the ``remat``
    around a token model's layer refuses."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _params():
    return pltpu.CompilerParams(dimension_semantics=_SEMANTICS,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _heads_first(x, length, width, dtype):
    """``[B, L, H, D]`` -> the kernels' ``[B, H, length, width]`` (the
    blocked dims last, as Mosaic's tiling requires), zero-padded, as
    ``dtype``: one copy, which XLA fuses with what made ``x``."""
    x = _pad_axis(_pad_axis(x, 1, length), 3, width)
    return x.transpose(0, 2, 1, 3).astype(dtype)


def _lanes(x, to):
    """[B, H, L] -> lane-replicated [B, H, to, _STAT_LANES] (f32)."""
    return jnp.broadcast_to(
        _pad_axis(x.astype(jnp.float32), 2, to)[..., None],
        x.shape[:2] + (to, _STAT_LANES))


def _offs(q_offset, k_offset):
    return jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32)])


class _Geometry:
    """What the three calls share: padded sizes, block counts, the group
    of query heads a key-value head serves, and the block specs.  The
    grid's inner axis sweeps key blocks (forward, dq) or query blocks
    (dk/dv); under ``causal`` the swept operand's index map stops at the
    diagonal, so blocks beyond it are neither fetched nor computed."""

    def __init__(self, q, k, v, causal, block_q, block_k):
        self.B, self.Lq, self.H, self.D = q.shape
        self.Lk, self.KV, self.Dv = k.shape[1], k.shape[2], v.shape[3]
        self.G = self.H // self.KV
        self.causal, self.bq, self.bk = causal, block_q, block_k
        self.lq_p, self.lk_p = _ceil_to(self.Lq, block_q), \
            _ceil_to(self.Lk, block_k)
        self.d_p, self.dv_p = _ceil_to(self.D, _LANES), \
            _ceil_to(self.Dv, _LANES)
        self.nq, self.nk = self.lq_p // block_q, self.lk_p // block_k
        self.scale = float(self.D ** -0.5)

    def static(self):
        return dict(causal=self.causal, block_q=self.bq, block_k=self.bk,
                    l_k=self.Lk, num_k=self.nk)

    # forward and dq: grid (B, H, q block i, key block j)
    def _key_block(self, offs, i, j):
        if not self.causal:
            return j
        last = (offs[0] + (i + 1) * self.bq - 1 - offs[1]) // self.bk
        return jnp.minimum(j, jnp.clip(last, 0, self.nk - 1))

    def row_specs(self):
        G = self.G

        def q_side(width):
            return pl.BlockSpec((1, 1, self.bq, width),
                                lambda b, h, i, j, offs: (b, h, i, 0))

        def k_side(width):
            return pl.BlockSpec(
                (1, 1, self.bk, width),
                lambda b, h, i, j, offs: (b, h // G,
                                          self._key_block(offs, i, j), 0))
        return q_side, k_side

    # dk/dv: grid (B, KV, key block i, t = (head of the group, q block))
    def _query_block(self, offs, i, t):
        j = t % self.nq
        if not self.causal:
            return j
        first = (offs[1] + i * self.bk - offs[0]) // self.bq
        return jnp.maximum(j, jnp.clip(first, 0, self.nq - 1))

    def column_specs(self):
        G, nq = self.G, self.nq

        def q_side(width):
            return pl.BlockSpec(
                (1, 1, self.bq, width),
                lambda b, h, i, t, offs: (b, h * G + t // nq,
                                          self._query_block(offs, i, t), 0))

        def k_side(width):
            return pl.BlockSpec((1, 1, self.bk, width),
                                lambda b, h, i, t, offs: (b, h, i, 0))
        # the row statistics as rows: [B, H, 1, lq_p] in blocks [1, bq]
        stat = pl.BlockSpec(
            (1, 1, 1, self.bq),
            lambda b, h, i, t, offs: (b, h * G + t // nq, 0,
                                      self._query_block(offs, i, t)))
        return q_side, k_side, stat


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _fwd(q, k, v, q_offset, k_offset, causal, block_q, block_k, interpret,
         mxu):
    g = _Geometry(q, k, v, causal, block_q, block_k)
    cdt = jnp.dtype(mxu)
    qp = _heads_first(q.astype(jnp.float32) * g.scale, g.lq_p, g.d_p, cdt)
    kp = _heads_first(k, g.lk_p, g.d_p, cdt)
    vp = _heads_first(v, g.lk_p, g.dv_p, cdt)
    q_side, k_side = g.row_specs()
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, cdt=cdt, **g.static()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g.B, g.H, g.nq, g.nk),
            in_specs=[q_side(g.d_p), k_side(g.d_p), k_side(g.dv_p)],
            out_specs=[q_side(g.dv_p), q_side(_STAT_LANES)],
            # m/l carry at full 128 lanes (the proven shape of jax's own
            # tpu flash kernel's carry scratch)
            scratch_shapes=[
                pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
                pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
                pltpu.VMEM((block_q, g.dv_p), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((g.B, g.H, g.lq_p, g.dv_p), q.dtype),
            jax.ShapeDtypeStruct((g.B, g.H, g.lq_p, _STAT_LANES),
                                 jnp.float32)],
        compiler_params=_params(),
        interpret=interpret, name=FWD_NAME,
    )(_offs(q_offset, k_offset), qp, kp, vp)
    return (out.transpose(0, 2, 1, 3)[:, :g.Lq, :, :g.Dv],
            lse[:, :, :g.Lq, 0])


@functools.partial(jax.jit, static_argnums=(9, 10, 11, 12, 13))
def _bwd(q, k, v, out, lse, q_offset, k_offset, do, g_lse, causal, block_q,
         block_k, interpret, mxu):
    g = _Geometry(q, k, v, causal, block_q, block_k)
    cdt = jnp.dtype(mxu)
    qp = _heads_first(q.astype(jnp.float32) * g.scale, g.lq_p, g.d_p, cdt)
    kp = _heads_first(k, g.lk_p, g.d_p, cdt)
    vp = _heads_first(v, g.lk_p, g.dv_p, cdt)
    dop = _heads_first(do, g.lq_p, g.dv_p, cdt)
    # delta_i = sum_d dO_i . O_i (rowwise), the softmax-grad correction;
    # d lse / d s = p too, so the lse cotangent comes off it here
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=3).transpose(0, 2, 1) - g_lse   # [B, H, Lq]
    offs = _offs(q_offset, k_offset)

    q_side, k_side = g.row_specs()
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=g.scale, cdt=cdt, **g.static()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g.B, g.H, g.nq, g.nk),
            in_specs=[q_side(g.d_p), k_side(g.d_p), k_side(g.dv_p),
                      q_side(g.dv_p), q_side(_STAT_LANES),
                      q_side(_STAT_LANES)],
            out_specs=q_side(g.d_p),
            scratch_shapes=[pltpu.VMEM((block_q, g.d_p), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g.B, g.H, g.lq_p, g.d_p), q.dtype),
        compiler_params=_params(), interpret=interpret, name=DQ_NAME,
    )(offs, qp, kp, vp, dop, _lanes(lse, g.lq_p), _lanes(delta, g.lq_p))

    # dk/dv: key blocks on the outer grid axis; the query blocks of every
    # head of the key-value head's group stream innermost and sum into
    # one accumulator
    q_side, k_side, stat = g.column_specs()

    def rows(x):  # [B, H, Lq] -> [B, H, 1, lq_p]
        return _pad_axis(x.astype(jnp.float32), 2, g.lq_p)[:, :, None, :]

    sweep = g.G * g.nq
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q=g.nq, sweep=sweep, cdt=cdt,
                          **g.static()),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g.B, g.KV, g.nk, sweep),
            in_specs=[q_side(g.d_p), k_side(g.d_p), k_side(g.dv_p),
                      q_side(g.dv_p), stat, stat],
            out_specs=[k_side(g.d_p), k_side(g.dv_p)],
            scratch_shapes=[pltpu.VMEM((block_k, g.d_p), jnp.float32),
                            pltpu.VMEM((block_k, g.dv_p), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((g.B, g.KV, g.lk_p, g.d_p), k.dtype),
                   jax.ShapeDtypeStruct((g.B, g.KV, g.lk_p, g.dv_p),
                                        v.dtype)],
        compiler_params=_params(), interpret=interpret, name=DKV_NAME,
    )(offs, qp, kp, vp, dop, rows(lse), rows(delta))

    def back(x, length, width):
        return x.transpose(0, 2, 1, 3)[:, :length, :, :width]
    return (back(dq, g.Lq, g.D), back(dk, g.Lk, g.D), back(dv, g.Lk, g.Dv))


def _dense_lse(q, k, v, q_offset, k_offset, causal):
    """Exact dense reference with the kernels' masking/lse semantics
    (global-position causal mask; fully-masked rows -> zeros, lse=_NEG).
    The lse cotangent flows naturally through autodiff — no custom VJP."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("blhd,bmhd->bhlm", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        q_pos = jnp.asarray(q_offset, jnp.int32) + jnp.arange(Lq)
        k_pos = jnp.asarray(k_offset, jnp.int32) + jnp.arange(Lk)
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, _NEG)
        e_mask = mask[None, None]
    else:
        e_mask = jnp.ones((1, 1, Lq, Lk), bool)
    m = jnp.max(s, axis=3)
    e = jnp.where(e_mask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(e, axis=3)
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), _NEG)
    p = e / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.einsum("bhlm,bmhd->blhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_lse(q, k, v, q_offset, k_offset, causal, block_q, block_k,
               interpret, mxu):
    """``q [B, Lq, H, D]`` over ``k [B, Lk, KV, D]``, ``v [B, Lk, KV,
    Dv]`` (query head ``h`` reads key-value head ``h // (H / KV)``),
    scale ``D ** -0.5``; ``mxu``: the dtype the products' operands are
    given in; ``interpret``: what ``pallas_call`` takes, resolved by the
    caller.  Returns ``(out [B, Lq, H, Dv], lse [B, H, Lq])``."""
    return _fwd(q, k, v, q_offset, k_offset, causal, block_q, block_k,
                interpret, mxu)


def _flash_lse_fwd(q, k, v, q_offset, k_offset, causal, block_q, block_k,
                   interpret, mxu):
    out, lse = _flash_lse(q, k, v, q_offset, k_offset, causal, block_q,
                          block_k, interpret, mxu)
    return (out, lse), (q, k, v, out, lse, q_offset, k_offset)


def _flash_lse_bwd(causal, block_q, block_k, interpret, mxu, res,
                   cotangents):
    q, k, v, out, lse, q_offset, k_offset = res
    g, g_lse = cotangents
    dq, dk, dv = _bwd(q, k, v, out, lse, q_offset, k_offset, g, g_lse,
                      causal, block_q, block_k, interpret, mxu)
    zero = np.zeros((), jax.dtypes.float0)
    return dq, dk, dv, zero, zero


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ----------------------------------------------------------------------
# AOT-cost dispatch gate (PR 12): never ship a losing kernel silently.
#
# The round-4 flash path regressed to 0.53x of dense at seq 2048 and
# shipped anyway, because nothing compared the two compiled programs.
# Now every compiled-TPU dispatch goes through a per-shape PLAN: the
# flash forward is AOT-compiled at a handful of candidate (block_q,
# block_k) tilings and the dense reference once, each scored on the
# roofline estimate max(flops/peak, bytes/bandwidth) from the compiled
# cost_analysis (telemetry/xla.py — the same helper PR 7 wired for
# device truth).  The cheapest flash tiling wins the blocks; if DENSE
# wins outright, the op falls back to dense and records an
# ``attention_fallback_dense`` event the server drains into the
# structured-event stream (docs/observability.md) — the regression is
# loud, auditable, and costs nothing but the fallback itself.
# ----------------------------------------------------------------------
#: candidate kernel tilings the planner prices (explicit caller blocks
#: are prepended); all (8, 128)-tile aligned
_BLOCK_CANDIDATES = ((128, 128), (256, 256), (512, 512),
                     (128, 256), (256, 128))
#: shape-signature -> plan dict; one AOT shootout per distinct geometry
_PLAN_CACHE: dict = {}
#: pending ``{"kind": ...}`` structured-event records, drained by the
#: server host tail (engine/server.py) — capped so an undrained CLI
#: session cannot grow it unboundedly
_PENDING_EVENTS: list = []
_EVENTS_CAP = 64


def drain_attention_events() -> list:
    """Hand the buffered dispatch-gate events to the caller (the
    server's host tail, which owns emitting them)."""
    global _PENDING_EVENTS
    out, _PENDING_EVENTS = _PENDING_EVENTS, []
    return out


def reset_attention_plans() -> None:
    """Forget cached plans + pending events (tests)."""
    _PLAN_CACHE.clear()
    del _PENDING_EVENTS[:]


def _roofline_secs(cost: Optional[dict]) -> float:
    """Estimated execution seconds of a compiled program from its cost
    analysis: ``max(flops / chip peak, bytes accessed / HBM bandwidth)``
    — the roofline bound, the one-number score the gate compares."""
    if not cost:
        return float("inf")
    from ..utils.compat import chip_hbm_bytes_per_sec, chip_peak_flops
    flops = float(cost.get("flops") or 0.0)
    bytes_acc = float(cost.get("bytes_accessed") or 0.0)
    if flops <= 0.0 and bytes_acc <= 0.0:
        return float("inf")
    _, peak = chip_peak_flops()
    _, bw = chip_hbm_bytes_per_sec()
    return max(flops / peak, bytes_acc / bw)


def _probe_costs(B, Lq, Lk, H, D, dtype, causal, candidates):
    """Compiled cost analyses for the dense reference and each flash
    candidate tiling, via the AOT path (abstract operands — nothing
    touches device memory)."""
    from ..telemetry.xla import aot_cost
    q_s = jax.ShapeDtypeStruct((B, Lq, H, D), dtype)
    kv_s = jax.ShapeDtypeStruct((B, Lk, H, D), dtype)

    def dense_fn(q, k, v):
        return _dense_lse(q, k, v, 0, 0, causal)

    dense_cost = aot_cost(dense_fn, q_s, kv_s, kv_s)
    flash_costs = {}
    for bq, bk in candidates:
        def flash_fn(q, k, v, _bq=bq, _bk=bk):
            return _fwd(q, k, v, 0, 0, causal, _bq, _bk,
                        _resolve_interpret(None), jnp.float32)
        flash_costs[(bq, bk)] = aot_cost(flash_fn, q_s, kv_s, kv_s)
    return dense_cost, flash_costs


def plan_attention(B: int, Lq: int, Lk: int, H: int, D: int, dtype,
                   causal: bool, *, block_q: Optional[int] = None,
                   block_k: Optional[int] = None,
                   cost_probe=None) -> dict:
    """Resolve (and cache) the dispatch plan for one attention geometry:
    ``{"impl": "flash"|"dense", "block_q", "block_k", "flash_secs_est",
    "dense_secs_est"}``.  Explicit ``block_q``/``block_k`` join the
    candidate set in front (so a pinned tiling is honored when it wins)
    but the gate still compares against dense — no silent-regression
    path.  ``cost_probe`` overrides the AOT prober (tests).
    """
    dtype = jnp.dtype(dtype)
    key = (B, Lq, Lk, H, D, str(dtype), bool(causal),
           block_q, block_k, jax.default_backend())
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        return plan
    candidates = []
    if block_q or block_k:
        candidates.append((int(block_q or _DEF_BLOCK),
                           int(block_k or _DEF_BLOCK)))
    candidates += [c for c in _BLOCK_CANDIDATES if c not in candidates]
    try:
        dense_cost, flash_costs = (cost_probe or _probe_costs)(
            B, Lq, Lk, H, D, dtype, bool(causal), candidates)
        dense_secs = _roofline_secs(dense_cost)
        # min() is stable: on tied roofline scores (cost_analysis often
        # cannot see intra-kernel tiling differences) the FIRST candidate
        # — the caller's pinned tiling when one was given — wins
        scored = [(_roofline_secs(flash_costs[c]), c) for c in candidates
                  if c in flash_costs]
        flash_secs, best_blocks = min(scored, key=lambda t: t[0])
        if not np.isfinite(flash_secs):
            # no usable cost analysis for ANY kernel candidate (e.g. a
            # backend whose cost_analysis() omits custom-call programs):
            # that is a telemetry gap, not a measured loss — same policy
            # as the probe-failure branch below, never a dense fallback
            raise RuntimeError("no cost analysis for any flash candidate")
    except Exception as exc:  # pragma: no cover - backend-specific
        # planning failure is NOT a fallback trigger: keep the caller's
        # pre-gate behavior (flash at the requested/default tiles) and
        # say so — falling back to dense on an exotic probe error would
        # turn a telemetry bug into an O(L^2) memory surprise
        import logging

        from ..utils.logging import print_rank
        print_rank(f"attention plan probe failed ({exc!r}); keeping the "
                   "flash kernel at the requested tiling",
                   loglevel=logging.WARNING)
        plan = {"impl": "flash",
                "block_q": int(block_q or _DEF_BLOCK),
                "block_k": int(block_k or _DEF_BLOCK),
                "flash_secs_est": None, "dense_secs_est": None}
        _PLAN_CACHE[key] = plan
        return plan
    plan = {"impl": "flash" if flash_secs <= dense_secs else "dense",
            "block_q": int(best_blocks[0]), "block_k": int(best_blocks[1]),
            "flash_secs_est": flash_secs, "dense_secs_est": dense_secs}
    _PLAN_CACHE[key] = plan
    if plan["impl"] == "dense":
        import logging

        from ..utils.logging import print_rank
        if len(_PENDING_EVENTS) < _EVENTS_CAP:
            _PENDING_EVENTS.append({
                "kind": "attention_fallback_dense",
                "batch": int(B), "seq_q": int(Lq), "seq_k": int(Lk),
                "heads": int(H), "head_dim": int(D),
                "causal": bool(causal),
                "flash_secs_est": flash_secs,
                "dense_secs_est": dense_secs,
                "block_q": int(best_blocks[0]),
                "block_k": int(best_blocks[1]),
            })
        print_rank(
            "attention dispatch gate: dense beats the flash kernel on "
            f"the compiled cost model at Lq={Lq} Lk={Lk} "
            f"(est {dense_secs:.2e}s vs {flash_secs:.2e}s) — dense "
            "fallback engaged (event: attention_fallback_dense)",
            loglevel=logging.WARNING)
    return plan


# ----------------------------------------------------------------------
# The token models' path (models/token_blocks.py): no planner.  The
# blocks follow from the shapes, there is no dense fallback (at 4,096
# tokens it does not fit beside a 1.9 GB tree), and the trace says which
# path it took.
# ----------------------------------------------------------------------
#: context precisions that give float32 operands ONE bf16 MXU pass with
#: float32 accumulation (what XLA does to a plain einsum under them)
_ONE_PASS = (None, "default", "bfloat16", "fastest")


def context_mxu_dtype(dtype):
    """The dtype the products' operands are given in: the tensors' own
    where that is bfloat16, else what the context's matmul precision
    makes of float32 operands — bfloat16 (one MXU pass, float32
    accumulation) under the default, float32 (contracted in full under
    ``highest``) under anything above.  Softmax, its running maximum and
    sum, ``lse`` and the accumulators are float32 whichever."""
    if jnp.dtype(dtype) == jnp.bfloat16 or \
            jax.config.jax_default_matmul_precision in _ONE_PASS:
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(jnp.float32)


def causal_blocks(length: int) -> tuple:
    """``(block_q, block_k)`` of the token models' causal core: static,
    from the row's length alone — square tiles of 512, or the whole
    (lane-padded) row where it is shorter.  On the chip at 4,096 tokens
    (PERF.md section 6, PR 37) 512 x 512 is within 3% (32 heads of
    192 / 128) and 8% (32 over 8 heads of 64) of the best of eight
    tilings from 256 x 256 to 2,048 x 512, forward and backward, 256 x
    256 is 25–40% slower, and 1,024 x 1,024 costs five times the
    compile seconds under ``highest``: one rule, no test of the width."""
    tile = min(_CAUSAL_BLOCK, _ceil_to(length, _LANES))
    return tile, tile


def _buffer_event(record: dict) -> None:
    """Buffer ``record`` for the next drain unless an equal one waits
    there already (a program traces its core once a layer and pass) or
    the buffer is full."""
    if record not in _PENDING_EVENTS and len(_PENDING_EVENTS) < _EVENTS_CAP:
        _PENDING_EVENTS.append(record)


def record_attention_path(impl: str, q_shape, k_shape, v_shape,
                          block_q: int, block_k: int) -> None:
    """Buffer an ``attention_path`` event: which implementation this
    trace of a token model's causal core took (``flash``: the kernels
    above; ``plain``: ``models/token_blocks._blocked_attention``), with
    the shapes and the blocks.  One record a distinct geometry between
    two drains: a program traces its core once a layer and pass."""
    record = {"kind": "attention_path", "impl": impl,
              "q_shape": [int(n) for n in q_shape],
              "k_shape": [int(n) for n in k_shape],
              "v_shape": [int(n) for n in v_shape],
              "block_q": int(block_q), "block_k": int(block_k)}
    _buffer_event(record)


def causal_flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, interpret: Optional[bool] = None):
    """Causal self-attention through the kernels, for shapes the planner's
    API does not take: ``q [B, L, H, D]`` over ``k [B, L, KV, D]`` and
    ``v [B, L, KV, Dv]`` — query head ``h`` reads key-value head
    ``h // (H / KV)`` through the block index, ``dk``/``dv`` are summed
    over the group inside the dk/dv kernel's sweep, and the value width
    is its own.  Scale ``D ** -0.5``; returns ``[B, L, H, Dv]``.

    The operands' precision is the context's (:func:`context_mxu_dtype`);
    the residuals are ``q``, ``k``, ``v``, ``out`` and ``lse``, the
    probabilities are recomputed tile by tile in the backward kernels
    and never leave VMEM."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(f"expected [B, L, heads, D]: {q.shape}, {k.shape}, "
                         f"{v.shape}")
    if q.shape[:2] != k.shape[:2] or k.shape[:3] != v.shape[:3] or \
            q.shape[3] != k.shape[3] or q.shape[2] % k.shape[2]:
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}: not one "
                         "row's queries over grouped key-value heads")
    block_q, block_k = causal_blocks(q.shape[1])
    record_attention_path("flash", q.shape, k.shape, v.shape, block_q,
                          block_k)
    return _flash_lse(q, k, v, 0, 0, True, block_q, block_k,
                      _plain_interpret(interpret),
                      context_mxu_dtype(q.dtype))[0]


# ----------------------------------------------------------------------
# Block-diffusion attention (models/sdar_moe.py): a row is TWO copies of
# ``L`` positions, the noised one (``xt``, rows 0..L-1) and the clean one
# (``x0``, rows L..2L-1), in blocks of ``B``; with ``blk(i) = (i mod L)
# // B`` a query sees
#
#   xt -> xt: blk(k) == blk(q)     xt -> x0: blk(k) <  blk(q)
#   x0 -> x0: blk(k) <= blk(q)     x0 -> xt: never
#
# ``L (L + B)`` of the ``4 L^2`` pairs, in two regions a query tile, so
# no sweep "up to the diagonal" serves it.  The three kernels below run
# a STATIC TILE MAP instead: the tiles that hold a seen pair, listed on
# the host from ``L``, ``B`` and the tile, one grid step each (a tile
# with no seen pair is no grid step: neither fetched nor computed); the
# operands' block indices and each step's flags (first / last of its
# accumulator, masked) ride scalar-prefetched tables.  A tile wholly
# seen runs without iota, compare or select; a tile a block boundary
# crosses computes its mask from position ids and the static ``L``,
# ``B`` (no mask array anywhere).  The tiles' bodies, the precision
# rules and the grouped heads are the causal kernels'.
# ----------------------------------------------------------------------
BD_FWD_NAME = "attn_bd_fwd"
BD_DQ_NAME = "attn_bd_dq"
BD_DKV_NAME = "attn_bd_dkv"
_FIRST, _LAST, _MASKED = 1, 2, 4
_BD_SEMANTICS = (_PARALLEL, _PARALLEL, _ARBITRARY)


def bd_seen(length: int, block: int) -> np.ndarray:
    """The statement: ``seen[q, k]`` over the ``2 length`` positions of
    a row ``[xt ; x0]``, as a boolean array (tests, small sizes)."""
    pos = np.arange(2 * length)
    half, blk = pos // length, (pos % length) // block
    qh, kh, qb, kb = half[:, None], half[None, :], blk[:, None], blk[None, :]
    return np.where(qh == 0, np.where(kh == 0, kb == qb, kb < qb),
                    (kh == 1) & (kb <= qb))


def bd_tile_map(length: int, block: int, block_q: int, block_k: int) -> dict:
    """Which tiles of the ``[2 lp, 2 lp]`` square run (``lp``: ``length``
    padded to whole tiles, each half on its own; padded positions are
    positions like any other, and a real query sees none of them).
    ``rows[i]``: query tile ``i``'s ``(key tile, masked)`` in sweep
    order; the counts (``tiles_run``, ``tiles_masked``, ``tiles_total``)
    and ``pairs_seen`` = ``length (length + block)``, the pairs a head
    needs."""
    if length % block or block_q % block or block_k % block:
        raise ValueError(f"block-diffusion attention: the row ({length}) "
                         f"and the tile ({block_q} x {block_k}) must be "
                         f"whole blocks of {block}")
    lp = _ceil_to(length, int(np.lcm(block_q, block_k)))
    nq, nk = lp // block_q, lp // block_k
    rows = []
    for i in range(2 * nq):
        q_lo, q_hi = ((i % nq) * block_q // block,
                      ((i % nq + 1) * block_q - 1) // block)
        row = []
        for j in range(2 * nk):
            k_lo, k_hi = ((j % nk) * block_k // block,
                          ((j % nk + 1) * block_k - 1) // block)
            if i < nq and j < nk:        # xt -> xt: the same block
                some = k_lo <= q_hi and q_lo <= k_hi
                whole = q_lo == q_hi == k_lo == k_hi
            elif i < nq:                 # xt -> x0: an earlier block
                some, whole = k_lo < q_hi, k_hi < q_lo
            elif j >= nk:                # x0 -> x0: not a later block
                some, whole = k_lo <= q_hi, k_hi <= q_lo
            else:                        # x0 -> xt
                some = whole = False
            if some:
                row.append((j, not whole))
        rows.append(row)
    run = sum(len(r) for r in rows)
    return {"rows": rows, "lp": lp, "nq": nq, "nk": nk, "key_tiles": 2 * nk,
            "tiles_run": run,
            "tiles_masked": sum(m for r in rows for _, m in r),
            "tiles_total": 4 * nq * nk,
            "pairs_seen": length * (length + block)}


def _bd_tables(tiles: dict, group: int) -> tuple:
    """The map as flat int32 tables, one entry a grid step.  Forward and
    ``dq``: ``(query tile, key tile, flags)`` query tile by query tile;
    ``dk``/``dv``: ``(key tile, head of the group, query tile, flags)``
    key tile by key tile, every head of the group in turn."""
    def flags(n, at, masked):
        return (_FIRST * (at == 0) + _LAST * (at == n - 1) +
                _MASKED * bool(masked))

    by_row = [(i, j, flags(len(row), at, m))
              for i, row in enumerate(tiles["rows"])
              for at, (j, m) in enumerate(row)]
    columns = [[] for _ in range(tiles["key_tiles"])]
    for i, row in enumerate(tiles["rows"]):
        for j, m in row:
            columns[j].append((i, m))
    by_column = []
    for j, column in enumerate(columns):
        steps = [(g, i, m) for g in range(group) for i, m in column]
        by_column += [(j, g, i, flags(len(steps), at, m))
                      for at, (g, i, m) in enumerate(steps)]

    def table(entries):
        return tuple(jnp.asarray(np.asarray(col, np.int32))
                     for col in zip(*entries))
    return table(by_row), table(by_column)


def _bd_mask(shape, q_axis, q_tile, k_tile, *, block, block_q, block_k,
             num_q, num_k):
    """The seen entries of one tile, from its two tile indices: with
    ``d = blk(q) - blk(k)``, ``xt -> xt``: ``d == 0``; ``xt -> x0``:
    ``d >= 1``; ``x0 -> x0``: ``d >= 0`` (one compare pair for the
    three; ``x0 -> xt`` is no tile of the map)."""
    q_ids = (q_tile % num_q) * block_q + \
        jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_ids = (k_tile % num_k) * block_k + \
        jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    if block & (block - 1) == 0:
        shift = block.bit_length() - 1
        d = (q_ids >> shift) - (k_ids >> shift)
    else:
        d = q_ids // block - k_ids // block
    q_clean, k_clean = q_tile >= num_q, k_tile >= num_k
    least = jnp.where(jnp.logical_and(k_clean, jnp.logical_not(q_clean)),
                      1, 0)
    most = jnp.where(k_clean, 2 * num_q * block_q, 0)
    return jnp.logical_and(d >= least, d <= most)


def _bd_step(flags, init, tile, finalize):
    """One grid step of a table-driven kernel: ``tile(masked)`` between
    its accumulator's first and last steps."""
    pl.when(flags & _FIRST != 0)(init)
    pl.when(flags & _MASKED == 0)(lambda: tile(False))
    pl.when(flags & _MASKED != 0)(lambda: tile(True))
    pl.when(flags & _LAST != 0)(finalize)


def _bd_fwd_kernel(qt_ref, kt_ref, fl_ref, q_ref, k_ref, v_ref, o_ref,
                   lse_ref, m_s, l_s, acc_s, *, cdt, mask, **geo):
    t = pl.program_id(2)

    def init():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    _bd_step(fl_ref[t], init,
             lambda masked: _fwd_tile(
                 q_ref, k_ref, v_ref, m_s, l_s, acc_s, masked and (
                     lambda shape: mask(shape, 0, qt_ref[t], kt_ref[t],
                                        **geo)), cdt),
             lambda: _fwd_finalize(o_ref, lse_ref, m_s, l_s, acc_s))


def _bd_dq_kernel(qt_ref, kt_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                  lse_ref, delta_ref, dq_ref, dq_s, *, scale, cdt, mask,
                  **geo):
    t = pl.program_id(2)

    def init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def finalize():
        dq_ref[0, 0] = (dq_s[...] * scale).astype(dq_ref.dtype)

    _bd_step(fl_ref[t], init,
             lambda masked: _dq_tile(
                 q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_s,
                 masked and (lambda shape: mask(
                     shape, 0, qt_ref[t], kt_ref[t], **geo)), cdt),
             finalize)


def _bd_dkv_kernel(kt_ref, head_ref, qt_ref, fl_ref, q_ref, k_ref, v_ref,
                   do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s, *,
                   cdt, mask, **geo):
    del head_ref  # the index maps read it
    t = pl.program_id(2)

    def init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def finalize():
        dk_ref[0, 0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[...].astype(dv_ref.dtype)

    _bd_step(fl_ref[t], init,
             lambda masked: _dkv_tile(
                 q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_s, dv_s,
                 masked and (lambda shape: mask(
                     shape, 1, qt_ref[t], kt_ref[t], **geo)), cdt),
             finalize)


class _MapGeometry:
    """What the three calls of a table-driven family share: the tile
    map, the padded sizes, the group of query heads a key-value head
    serves, the law's mask and its statics, the kernels' names.  A row
    is ``copies`` copies of ``L`` positions side by side (2: the
    block-diffusion row; 1: a windowed row), each padded to whole tiles
    on its own."""

    def __init__(self, q, k, v, tiles, copies, block_q, block_k, names,
                 mask, **law):
        self.B, rows, self.H, self.D = q.shape
        self.copies, self.L = copies, rows // copies
        self.KV, self.Dv = k.shape[2], v.shape[3]
        self.G = self.H // self.KV
        self.bq, self.bk = block_q, block_k
        self.tiles, self.lp = tiles, tiles["lp"]
        self.rows_p = copies * self.lp
        self.d_p, self.dv_p = _ceil_to(self.D, _LANES), \
            _ceil_to(self.Dv, _LANES)
        self.scale = float(self.D ** -0.5)
        self.by_row, self.by_column = _bd_tables(tiles, self.G)
        self.names = names
        self.static = dict(mask=mask, block_q=block_q, block_k=block_k,
                           **law)

    def heads_first(self, x, width, dtype):
        """``[B, copies L, heads, D]`` -> ``[B, heads, copies lp,
        width]``: each copy padded to whole tiles on its own."""
        batch, _, heads, dim = x.shape
        x = _pad_axis(x.reshape(batch, self.copies, self.L, heads, dim), 2,
                      self.lp)
        return _heads_first(x.reshape(batch, self.rows_p, heads, dim),
                            self.rows_p, width, dtype)

    def back(self, x, width):
        """The kernels' ``[B, heads, copies lp, *]`` -> ``[B, copies L,
        heads, width]``."""
        batch, heads = x.shape[:2]
        x = x.transpose(0, 2, 1, 3).reshape(batch, self.copies, self.lp,
                                            heads, -1)
        return x[:, :, :self.L, :, :width].reshape(
            batch, self.copies * self.L, heads, width)

    def row_specs(self):
        """Forward and ``dq``: grid ``(B, H, step)``, tables ``(query
        tile, key tile, flags)``."""
        G = self.G

        def q_side(width):
            return pl.BlockSpec((1, 1, self.bq, width),
                                lambda b, h, t, qt, kt, fl: (b, h, qt[t], 0))

        def k_side(width):
            return pl.BlockSpec(
                (1, 1, self.bk, width),
                lambda b, h, t, qt, kt, fl: (b, h // G, kt[t], 0))
        return q_side, k_side

    def column_specs(self):
        """``dk``/``dv``: grid ``(B, KV, step)``, tables ``(key tile,
        head of the group, query tile, flags)``; the row statistics as
        rows ``[B, H, 1, copies lp]`` in blocks ``[1, bq]``."""
        G = self.G

        def q_side(width):
            return pl.BlockSpec(
                (1, 1, self.bq, width),
                lambda b, h, t, kt, hd, qt, fl: (b, h * G + hd[t], qt[t], 0))

        def k_side(width):
            return pl.BlockSpec(
                (1, 1, self.bk, width),
                lambda b, h, t, kt, hd, qt, fl: (b, h, kt[t], 0))
        stat = pl.BlockSpec(
            (1, 1, 1, self.bq),
            lambda b, h, t, kt, hd, qt, fl: (b, h * G + hd[t], 0, qt[t]))
        return q_side, k_side, stat

    def stat(self, x):
        """``[B, H, copies L]`` row statistics, each copy padded like
        the rows (zeros: a padded query adds nothing in the backward)."""
        batch, heads = x.shape[:2]
        x = _pad_axis(x.astype(jnp.float32).reshape(
            batch, heads, self.copies, self.L), 3, self.lp)
        return x.reshape(batch, heads, self.rows_p)


def _bd_geometry(q, k, v, block, block_q, block_k):
    tiles = bd_tile_map(q.shape[1] // 2, block, block_q, block_k)
    return _MapGeometry(q, k, v, tiles, 2, block_q, block_k,
                        (BD_FWD_NAME, BD_DQ_NAME, BD_DKV_NAME), _bd_mask,
                        block=block, num_q=tiles["nq"], num_k=tiles["nk"])


def _bd_params():
    return pltpu.CompilerParams(dimension_semantics=_BD_SEMANTICS,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _map_fwd(g, q, k, v, interpret, mxu):
    """The forward call of a table-driven family: ``(out, lse [B, H,
    copies L])``."""
    cdt = jnp.dtype(mxu)
    qp = g.heads_first(q.astype(jnp.float32) * g.scale, g.d_p, cdt)
    kp, vp = g.heads_first(k, g.d_p, cdt), g.heads_first(v, g.dv_p, cdt)
    q_side, k_side = g.row_specs()
    out, lse = pl.pallas_call(
        functools.partial(_bd_fwd_kernel, cdt=cdt, **g.static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(g.B, g.H, g.tiles["tiles_run"]),
            in_specs=[q_side(g.d_p), k_side(g.d_p), k_side(g.dv_p)],
            out_specs=[q_side(g.dv_p), q_side(_STAT_LANES)],
            scratch_shapes=[
                pltpu.VMEM((g.bq, _STAT_LANES), jnp.float32),
                pltpu.VMEM((g.bq, _STAT_LANES), jnp.float32),
                pltpu.VMEM((g.bq, g.dv_p), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((g.B, g.H, g.rows_p, g.dv_p), q.dtype),
            jax.ShapeDtypeStruct((g.B, g.H, g.rows_p, _STAT_LANES),
                                 jnp.float32)],
        compiler_params=_bd_params(), interpret=interpret, name=g.names[0],
    )(*g.by_row, qp, kp, vp)
    lse = lse[..., 0].reshape(g.B, g.H, g.copies, g.lp)[..., :g.L]
    return g.back(out, g.Dv), lse.reshape(g.B, g.H, g.copies * g.L)


def _map_bwd(g, q, k, v, out, lse, do, interpret, mxu):
    """The two backward calls of a table-driven family: ``(dq, dk,
    dv)``."""
    cdt = jnp.dtype(mxu)
    qp = g.heads_first(q.astype(jnp.float32) * g.scale, g.d_p, cdt)
    kp, vp = g.heads_first(k, g.d_p, cdt), g.heads_first(v, g.dv_p, cdt)
    dop = g.heads_first(do, g.dv_p, cdt)
    delta = g.stat(jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                           axis=3).transpose(0, 2, 1))
    lse = g.stat(lse)

    def lanes(x):  # [B, H, copies lp] -> lane-replicated
        return jnp.broadcast_to(x[..., None], x.shape + (_STAT_LANES,))

    q_side, k_side = g.row_specs()
    dq = pl.pallas_call(
        functools.partial(_bd_dq_kernel, scale=g.scale, cdt=cdt, **g.static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(g.B, g.H, g.tiles["tiles_run"]),
            in_specs=[q_side(g.d_p), k_side(g.d_p), k_side(g.dv_p),
                      q_side(g.dv_p), q_side(_STAT_LANES),
                      q_side(_STAT_LANES)],
            out_specs=q_side(g.d_p),
            scratch_shapes=[pltpu.VMEM((g.bq, g.d_p), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((g.B, g.H, g.rows_p, g.d_p), q.dtype),
        compiler_params=_bd_params(), interpret=interpret, name=g.names[1],
    )(*g.by_row, qp, kp, vp, dop, lanes(lse), lanes(delta))

    # dk/dv: key tile by key tile, the query tiles of every head of the
    # key-value head's group summed into one accumulator
    q_col, k_col, stat = g.column_specs()
    dk, dv = pl.pallas_call(
        functools.partial(_bd_dkv_kernel, cdt=cdt, **g.static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(g.B, g.KV, g.G * g.tiles["tiles_run"]),
            in_specs=[q_col(g.d_p), k_col(g.d_p), k_col(g.dv_p),
                      q_col(g.dv_p), stat, stat],
            out_specs=[k_col(g.d_p), k_col(g.dv_p)],
            scratch_shapes=[pltpu.VMEM((g.bk, g.d_p), jnp.float32),
                            pltpu.VMEM((g.bk, g.dv_p), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((g.B, g.KV, g.rows_p, g.d_p), k.dtype),
            jax.ShapeDtypeStruct((g.B, g.KV, g.rows_p, g.dv_p), v.dtype)],
        compiler_params=_bd_params(), interpret=interpret, name=g.names[2],
    )(*g.by_column, qp, kp, vp, dop, lse[:, :, None, :],
      delta[:, :, None, :])
    return g.back(dq, g.D), g.back(dk, g.D), g.back(dv, g.Dv)


def _map_family(prefix, geometry):
    """The differentiable call of one law of the tile map: ``attention(q,
    k, v, law, block_q, block_k, interpret, mxu)`` with ``law`` the one
    static that ``geometry(q, k, v, law, block_q, block_k)`` takes beside
    the tile (block diffusion's block, the window's keys).  The forward
    and the backward call are jitted under ``<prefix>_fwd`` /
    ``<prefix>_bwd`` and the whole under ``<prefix>_attention``: the
    names a jaxpr and a profile show."""
    def fwd(q, k, v, law, block_q, block_k, interpret, mxu):
        return _map_fwd(geometry(q, k, v, law, block_q, block_k), q, k, v,
                        interpret, mxu)

    def bwd(q, k, v, out, lse, do, law, block_q, block_k, interpret, mxu):
        return _map_bwd(geometry(q, k, v, law, block_q, block_k), q, k, v,
                        out, lse, do, interpret, mxu)

    def attention(q, k, v, law, block_q, block_k, interpret, mxu):
        return fwd(q, k, v, law, block_q, block_k, interpret, mxu)[0]

    def attention_fwd(q, k, v, law, block_q, block_k, interpret, mxu):
        out, lse = fwd(q, k, v, law, block_q, block_k, interpret, mxu)
        return out, (q, k, v, out, lse)

    def attention_bwd(law, block_q, block_k, interpret, mxu, saved, do):
        return bwd(*saved, do, law, block_q, block_k, interpret, mxu)

    for fn, name in ((fwd, "_fwd"), (bwd, "_bwd"), (attention, "_attention"),
                     (attention_fwd, "_attention_fwd"),
                     (attention_bwd, "_attention_bwd")):
        fn.__name__ = fn.__qualname__ = prefix + name
    fwd = jax.jit(fwd, static_argnums=(3, 4, 5, 6, 7))
    bwd = jax.jit(bwd, static_argnums=(6, 7, 8, 9, 10))
    attention = jax.custom_vjp(attention, nondiff_argnums=(3, 4, 5, 6, 7))
    attention.defvjp(attention_fwd, attention_bwd)
    return attention


_bd_attention = _map_family("_bd", _bd_geometry)


def record_attention_tiles(length: int, block: int, block_q: int,
                           block_k: int) -> dict:
    """Buffer an ``attn_tiles`` event (once a distinct geometry between
    two drains, as ``attention_path``): what the block-diffusion core's
    static tile map runs for a row of ``2 length`` positions.  Returns
    the map's counts."""
    tiles = bd_tile_map(length, block, block_q, block_k)
    record = {"kind": "attn_tiles", "L": int(length), "B": int(block),
              "block_q": int(block_q), "block_k": int(block_k),
              **{key: int(tiles[key]) for key in
                 ("tiles_run", "tiles_masked", "tiles_total",
                  "pairs_seen")}}
    _buffer_event(record)
    return record


def block_diffusion_flash_attention(q, k, v, block: int, *,
                                    block_q: Optional[int] = None,
                                    block_k: Optional[int] = None,
                                    interpret: Optional[bool] = None):
    """Block-diffusion self-attention through the kernels: ``q [B, 2 L,
    H, D]`` over ``k [B, 2 L, KV, D]`` and ``v [B, 2 L, KV, Dv]``, rows
    ``[xt ; x0]``, blocks of ``block`` positions, seen as written above
    (:func:`bd_seen`); grouped heads, the value width, the scale, the
    operands' precision and the residuals as
    :func:`causal_flash_attention`.  ``block_q`` / ``block_k``: the
    tile, :func:`causal_blocks`' where not given (tests give their own).
    Returns ``[B, 2 L, H, Dv]``."""
    if q.ndim != 4 or q.shape[:2] != k.shape[:2] or \
            k.shape[:3] != v.shape[:3] or q.shape[3] != k.shape[3] or \
            q.shape[2] % k.shape[2] or q.shape[1] % 2:
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}: not a "
                         "doubled row's queries over grouped key-value "
                         "heads")
    length = q.shape[1] // 2
    tile_q, tile_k = causal_blocks(length)
    block_q, block_k = int(block_q or tile_q), int(block_k or tile_k)
    record_attention_path("flash", q.shape, k.shape, v.shape, block_q,
                          block_k)
    record_attention_tiles(length, block, block_q, block_k)
    return _bd_attention(q, k, v, int(block), block_q, block_k,
                         _plain_interpret(interpret),
                         context_mxu_dtype(q.dtype))


# ----------------------------------------------------------------------
# Sliding-window attention (models/laguna.py's sliding layers): query
# ``i`` sees key ``j`` where ``j <= i`` and ``i - j < window`` (``window``
# keys, its own among them): a band under the diagonal, ``W L - W (W -
# 1) / 2`` of the causal half's ``L (L + 1) / 2`` pairs.  The causal
# kernels stop the swept operand at the diagonal only and would pay for
# the whole half; the band's lower edge is a SECOND LAW OF THE STATIC
# TILE MAP instead (``win_tile_map`` beside ``bd_tile_map``): the tiles
# that hold a seen pair, one grid step each, so a tile wholly below the
# band (or above the diagonal) is neither fetched nor computed, a tile
# wholly inside it runs without iota, compare or select, and a tile that
# the diagonal or the lower edge crosses computes its mask from position
# ids and the static ``window`` (no mask array).  Why the map and not a
# ``window`` static of ``_Geometry`` / ``_each_tile``: a rectangular
# grid would still step through the ``Lk / block_k`` key blocks of every
# query block (64 steps a head at 4,096 / 512 where 15 hold a seen pair)
# and clamp its index map at both ends of a moving range; the map's
# kernels, tables and specs are there already and take any law that is a
# function of two tile indices.  Three kernels under names of their own
# (``attn_win_fwd``, ``attn_win_dq``, ``attn_win_dkv``) on the shared
# tile bodies.
# ----------------------------------------------------------------------
WIN_FWD_NAME = "attn_win_fwd"
WIN_DQ_NAME = "attn_win_dq"
WIN_DKV_NAME = "attn_win_dkv"


def window_seen(length: int, window: int) -> np.ndarray:
    """The statement: ``seen[q, k]`` over a row of ``length`` positions,
    as a boolean array (tests, small sizes)."""
    d = np.arange(length)[:, None] - np.arange(length)[None, :]
    return (d >= 0) & (d < window)


def window_pairs_seen(length: int, window: int) -> int:
    """Seen (query, key) pairs of one head: query ``i`` sees ``min(i +
    1, window)`` keys."""
    w = min(window, length)
    return w * length - w * (w - 1) // 2


def win_tile_map(length: int, window: int, block_q: int,
                 block_k: int) -> dict:
    """Which tiles of the ``[lp, lp]`` square run under the window law
    (``lp``: ``length`` padded to whole tiles; a real query sees no
    padded key, which lies above its diagonal).  ``rows[i]``: query tile
    ``i``'s ``(key tile, masked)`` in sweep order; the counts
    (``tiles_run``, ``tiles_whole``, ``tiles_masked``, ``tiles_total``)
    and ``pairs_seen``, the pairs a head needs."""
    if window < 1:
        raise ValueError(f"sliding-window attention: window={window}")
    lp = _ceil_to(length, int(np.lcm(block_q, block_k)))
    nq, nk = lp // block_q, lp // block_k
    rows = []
    for i in range(nq):
        q_lo, q_hi = i * block_q, (i + 1) * block_q - 1
        row = []
        for j in range(nk):
            k_lo, k_hi = j * block_k, (j + 1) * block_k - 1
            if k_lo <= q_hi and q_lo - k_hi < window:      # some pair seen
                whole = k_hi <= q_lo and q_hi - k_lo < window
                row.append((j, not whole))
        rows.append(row)
    run = sum(len(r) for r in rows)
    masked = sum(m for r in rows for _, m in r)
    return {"rows": rows, "lp": lp, "nq": nq, "nk": nk, "key_tiles": nk,
            "tiles_run": run, "tiles_whole": run - masked,
            "tiles_masked": masked, "tiles_total": nq * nk,
            "pairs_seen": window_pairs_seen(length, window)}


def _win_mask(shape, q_axis, q_tile, k_tile, *, window, block_q, block_k):
    """The seen entries of one tile, from its two tile indices: ``0 <=
    q - k < window``."""
    d = (q_tile * block_q +
         jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)) - \
        (k_tile * block_k +
         jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
    return jnp.logical_and(d >= 0, d < window)


def _win_geometry(q, k, v, window, block_q, block_k):
    return _MapGeometry(
        q, k, v, win_tile_map(q.shape[1], window, block_q, block_k), 1,
        block_q, block_k, (WIN_FWD_NAME, WIN_DQ_NAME, WIN_DKV_NAME),
        _win_mask, window=window)


_win_attention = _map_family("_win", _win_geometry)


def record_window_tiles(length: int, window: int, block_q: int,
                        block_k: int) -> dict:
    """Buffer an ``attn_window_tiles`` event (once a distinct geometry
    between two drains, as ``attn_tiles``): what the window law's tile
    map runs for a row of ``length`` positions.  Returns the record."""
    tiles = win_tile_map(length, window, block_q, block_k)
    record = {"kind": "attn_window_tiles", "L": int(length),
              "window": int(window), "block_q": int(block_q),
              "block_k": int(block_k),
              **{key: int(tiles[key]) for key in
                 ("tiles_run", "tiles_whole", "tiles_masked", "tiles_total",
                  "pairs_seen")}}
    _buffer_event(record)
    return record


def window_flash_attention(q, k, v, window: int, *,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Sliding-window causal self-attention through the kernels: ``q [B,
    L, H, D]`` over ``k [B, L, KV, D]`` and ``v [B, L, KV, Dv]``, query
    ``i`` over the keys ``i - window < j <= i`` (:func:`window_seen`; a
    window no shorter than the row is the causal law); grouped heads,
    the value width, the scale, the operands' precision and the
    residuals as :func:`causal_flash_attention`.  ``block_q`` /
    ``block_k``: the tile, :func:`causal_blocks`' where not given (tests
    give their own).  Returns ``[B, L, H, Dv]``."""
    if q.ndim != 4 or q.shape[:2] != k.shape[:2] or \
            k.shape[:3] != v.shape[:3] or q.shape[3] != k.shape[3] or \
            q.shape[2] % k.shape[2]:
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}: not one "
                         "row's queries over grouped key-value heads")
    length = q.shape[1]
    tile_q, tile_k = causal_blocks(length)
    block_q, block_k = int(block_q or tile_q), int(block_k or tile_k)
    record_attention_path("flash", q.shape, k.shape, v.shape, block_q,
                          block_k)
    record_window_tiles(length, window, block_q, block_k)
    return _win_attention(q, k, v, int(window), block_q, block_k,
                          _plain_interpret(interpret),
                          context_mxu_dtype(q.dtype))


def flash_attention_lse(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = False, *, q_offset=0, k_offset=0,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: Optional[bool] = None,
                        force_flash: bool = False):
    """Like :func:`flash_attention` but also returns the per-row
    logsumexp ``[B, H, Lq]`` (f32), with a VJP that honors its cotangent.
    ``q_offset``/``k_offset`` shift the global positions used by the
    causal mask — dynamic scalars, so ring rotations can jit one program.
    Rows whose keys are ALL masked come back as zeros with lse ≈ -1e30
    (exact identity for the rotation-merge in ring attention).

    ``block_q``/``block_k`` default to the AOT-cost planner's choice on
    the compiled TPU path (explicit ints are priced as the first
    candidate); the planner also compares the kernel against the dense
    reference and falls back to dense — recording an
    ``attention_fallback_dense`` event — when the compiled cost model
    says the kernel loses.  ``force_flash=True`` bypasses the gate (ring
    attention runs inside shard_map where per-shard planning would
    re-probe per trace; its opt-in is explicit)."""
    if q.ndim != 4:
        raise ValueError(f"expected [B, L, H, D], got {q.shape}")
    if k.shape != v.shape:
        raise ValueError(f"k/v shapes differ: {k.shape} vs {v.shape}")
    if interpret is None and not compiled_kernels_apply():
        # off-TPU default: exact dense math (see module docstring for why
        # interpret-mode kernels are not safe under shard_map); likewise
        # on TPU where GSPMD would have to partition the kernel
        return _dense_lse(q, k, v, q_offset, k_offset, bool(causal))
    if interpret is None and not force_flash:
        # compiled TPU path: the dispatch gate
        B, Lq, H, D = q.shape
        plan = plan_attention(B, Lq, k.shape[1], H, D, q.dtype,
                              bool(causal), block_q=block_q,
                              block_k=block_k)
        if plan["impl"] == "dense":
            return _dense_lse(q, k, v, q_offset, k_offset, bool(causal))
        block_q, block_k = plan["block_q"], plan["block_k"]
    return _flash_lse(q, k, v, q_offset, k_offset, bool(causal),
                      int(block_q or _DEF_BLOCK),
                      int(block_k or _DEF_BLOCK),
                      _resolve_interpret(interpret), jnp.float32)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = False, *,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    force_flash: bool = False) -> jnp.ndarray:
    """Exact attention over ``[B, L, H, D]`` tensors, tiled in VMEM.

    Softmax scale is ``1/sqrt(D)`` (matching ``models/ringlm.py``).
    ``D`` is padded to the 128-lane width and ``L`` to the block size;
    key/value blocks STREAM through VMEM (O(block_k) residency, see
    module docstring), so single-chip ``L`` is bounded by the HBM
    footprint of the tensors themselves, not by VMEM — for lengths
    beyond one chip's HBM, shard the sequence axis over a mesh and run
    these kernels per ring rotation
    (``ring_self_attention(..., use_flash=True)``).

    On a non-TPU backend with ``interpret=None`` this op computes the SAME
    math via a dense reference — O(Lq*Lk) score memory, not the tiled
    O(L) profile above (see module docstring for why).  The Pallas-tiled
    path runs only on TPU (compiled) or with ``interpret=True``.

    The compiled-TPU path routes through the AOT-cost dispatch gate
    (see :func:`flash_attention_lse`); ``force_flash=True`` bypasses it
    — for kernel-validation tools that must exercise the kernel even
    where the cost model prefers dense.
    """
    return flash_attention_lse(q, k, v, causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               force_flash=force_flash)[0]
