"""Pallas flash attention — the long-context hot op, tiled for the MXU.

Net-new vs the reference (FLUTE has no attention models beyond HF BERT and
no long-context machinery, SURVEY.md §5.7).  This is the TPU-native
answer for the RingLM family: exact attention computed blockwise in VMEM
with an online softmax, O(L) memory instead of the O(L^2) score
materialization of the jnp path (``models/ringlm.py`` local mode).  Both
passes are Pallas kernels (FlashAttention-2 style tiling):

- forward: grid ``(B, H, Lq/block_q, Lk/block_k)`` with the key/value
  block index INNERMOST and ``arbitrary`` semantics — mosaic pipelines
  the next K/V block's HBM→VMEM fetch under the current block's MXU
  work, and the ``(m, l, acc)`` online-softmax carry lives in VMEM
  scratch across the inner sweep.  VMEM residency is O(block), never
  O(L): the round-4 kernels loaded the WHOLE key sequence per program
  (the kv BlockSpec spanned padded Lk), which both capped L at VMEM
  size and serialized HBM fetches behind compute — the measured reason
  dense beat flash at every length.
- backward: ``dq`` on the same grid shape; ``dk``/``dv`` on
  ``(B, H, Lk/block_k, Lq/block_q)`` (query blocks innermost), both
  accumulating into VMEM scratch and recomputing probabilities from the
  saved ``lse`` (no O(L^2) residuals).

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

from .pallas_kernels import _resolve_interpret, compiled_kernels_apply

_LANES = 128
# row statistics (lse/delta/glse) ride lane-broadcast over the trailing
# dim.  PR-12 retile: the stat streams use FULL (8, 128)-aligned tiles —
# the old 8-lane blocks saved VMEM but made every stat load/store a
# sub-tile access, which mosaic serviced with masked sub-lane ops on the
# hot dq/dkv inner loops (device truth measured the kernel at 0.53x of
# dense at seq 2048 before the retile).  VMEM cost per grid step is
# 3 stat blocks x block_q x 128 x 4B — comparable to one head-dim block,
# well inside budget at the block sizes the planner picks.
_STAT_LANES = _LANES
_NEG = -1e30  # "minus infinity" that survives exp/max without NaNs
#: default kernel tile when the caller pins blocks explicitly
_DEF_BLOCK = 128


def _pad_axis(x, axis, to):
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _ceil_to(n, m):
    return int(np.ceil(n / m)) * m


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def _rows(stat_ref):
    """Recover a per-row vector from a lane-broadcast [rows, _STAT_LANES]
    scratch/stream (all lanes hold the same value)."""
    return jnp.max(stat_ref[...], axis=-1)


def _bcast_rows(vec, rows):
    return jax.lax.broadcast_in_dim(vec, (rows, _STAT_LANES), (0,))


def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_s, l_s, acc_s, *, causal, scale, block_q, block_k,
                l_q, l_k, num_k):
    qi, kj = pl.program_id(2), pl.program_id(3)
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(kj == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def _accumulate():
        q = q_ref[0, 0, :, :].astype(jnp.float32)       # [bq, D]
        k_blk = k_ref[0, 0, :, :].astype(jnp.float32)   # [bk, D]
        v_blk = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        k_loc = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_loc < l_k
        if causal:
            q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, q_pos >= k_off + k_loc)
        s = jnp.where(mask, s, _NEG)
        m = _rows(m_s)
        l = _rows(l_s)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        # mask p explicitly: for fully-masked rows s == m_new == _NEG and
        # exp(0) would resurrect the masked entries
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m - m_new)
        m_s[...] = jax.lax.broadcast_in_dim(m_new, m_s.shape, (0,))
        l_s[...] = jax.lax.broadcast_in_dim(
            l * corr + jnp.sum(p, axis=1), l_s.shape, (0,))
        acc_s[...] = acc_s[...] * corr[:, None] + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32)

    if causal:
        # whole key blocks above the (global) diagonal contribute nothing;
        # their fetch still pipelines but the MXU work is skipped
        @pl.when(k_off + kj * block_k <= q_off + (qi + 1) * block_q - 1)
        def _():
            _accumulate()
    else:
        _accumulate()

    @pl.when(kj == num_k - 1)
    def _finalize():
        m = _rows(m_s)
        l = _rows(l_s)
        out = acc_s[...] / jnp.maximum(l, 1e-30)[:, None]
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), _NEG)
        # TPU mosaic requires the last two BLOCK dims be (8k, 128m)-
        # aligned, so the per-row lse is stored lane-broadcast as
        # [bq, _STAT_LANES] (same trick as jax's own tpu flash kernel)
        lse_ref[0, 0, :, :] = _bcast_rows(lse, block_q)


# ----------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------
def _dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               glse_ref, dq_ref, dq_s, *, causal, scale, block_q, block_k,
               l_q, l_k, num_k):
    qi, kj = pl.program_id(2), pl.program_id(3)
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(kj == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def _accumulate():
        q = q_ref[0, 0, :, :].astype(jnp.float32)
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        k_blk = k_ref[0, 0, :, :].astype(jnp.float32)   # [bk, D]
        v_blk = v_ref[0, 0, :, :].astype(jnp.float32)
        # lse/delta/glse arrive lane-broadcast [bq, _STAT_LANES]; any
        # lane-reduce that preserves the (identical) value recovers rows
        lse = _rows(lse_ref[0, 0])
        delta = _rows(delta_ref[0, 0])
        glse = _rows(glse_ref[0, 0])
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_loc = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_loc < l_k
        if causal:
            q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, q_pos >= k_off + k_loc)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # d lse / d s = p, so the lse cotangent adds straight into ds
        ds = p * (dp - delta[:, None] + glse[:, None]) * scale
        dq_s[...] = dq_s[...] + jnp.dot(
            ds, k_blk, preferred_element_type=jnp.float32)

    if causal:
        @pl.when(k_off + kj * block_k <= q_off + (qi + 1) * block_q - 1)
        def _():
            _accumulate()
    else:
        _accumulate()

    @pl.when(kj == num_k - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                glse_ref, dk_ref, dv_ref, dk_s, dv_s, *, causal, scale,
                block_q, block_k, l_q, l_k, num_q):
    ki, qj = pl.program_id(2), pl.program_id(3)
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(qj == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    def _accumulate():
        k_blk = k_ref[0, 0, :, :].astype(jnp.float32)   # [bk, D]
        v_blk = v_ref[0, 0, :, :].astype(jnp.float32)
        q = q_ref[0, 0, :, :].astype(jnp.float32)       # [bq, D]
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        lse = _rows(lse_ref[0, 0])
        delta = _rows(delta_ref[0, 0])
        glse = _rows(glse_ref[0, 0])
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        q_loc = qj * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_loc = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_loc < l_k
        if causal:
            mask = jnp.logical_and(
                mask, q_off + q_loc >= k_off + k_loc)
        # padded q rows carry lse = _NEG -> exp(s - _NEG) would overflow;
        # mask on the valid-q side too
        mask = jnp.logical_and(mask, q_loc < l_q)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dv_s[...] = dv_s[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        ds = p * (dp - delta[:, None] + glse[:, None]) * scale
        dk_s[...] = dk_s[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]

    if causal:
        # q blocks strictly above this key block's (global) diagonal
        # start see nothing
        @pl.when(q_off + (qj + 1) * block_q - 1 >= k_off + ki * block_k)
        def _():
            _accumulate()
    else:
        _accumulate()

    @pl.when(qj == num_q - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_s[...].astype(dv_ref.dtype)


# ----------------------------------------------------------------------
# pallas_call plumbing
# ----------------------------------------------------------------------
def _specs(block_q, block_k, d_p):
    # kernel-side layout is [B, H, S, D]: the blocked dims (S, D) sit in
    # the last two positions, as TPU mosaic tiling requires.  Grid is
    # (B, H, q_block, kv_block) — the kv index j is INNERMOST so mosaic
    # double-buffers the kv fetches while q/out/stat blocks (index maps
    # ignoring j) stay resident across the inner sweep.
    q_spec = pl.BlockSpec((1, 1, block_q, d_p),
                          lambda b, h, i, j, *_: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, d_p),
                           lambda b, h, i, j, *_: (b, h, j, 0))
    # per-row lse rides lane-broadcast as [B, H, lq_p, _STAT_LANES] —
    # full (8, 128) tiles since the PR-12 retile
    lse_spec = pl.BlockSpec((1, 1, block_q, _STAT_LANES),
                            lambda b, h, i, j, *_: (b, h, i, 0))
    return q_spec, kv_spec, lse_spec


#: grid semantics: batch/head/outer-block axes are parallel; the inner
#: accumulation axis must execute in order (scratch carry)
_PARALLEL = pltpu.GridDimensionSemantics.PARALLEL
_ARBITRARY = pltpu.GridDimensionSemantics.ARBITRARY
_SEMANTICS = (_PARALLEL, _PARALLEL, _PARALLEL, _ARBITRARY)


def _bhsd(x):
    """[B, L, H, D] -> [B, H, L, D] (kernel layout)."""
    return x.transpose(0, 2, 1, 3)


def _lanes(x, to):
    """[B, H, L] -> lane-broadcast [B, H, to, _STAT_LANES] (f32)."""
    return jnp.broadcast_to(
        _pad_axis(x.astype(jnp.float32), 2, to)[..., None],
        x.shape[:2] + (to, _STAT_LANES))


def _offs(q_offset, k_offset):
    return jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32)])


def _fwd(q, k, v, q_offset, k_offset, causal, scale, block_q, block_k,
         interpret):
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    lq_p, lk_p = _ceil_to(Lq, block_q), _ceil_to(Lk, block_k)
    d_p = _ceil_to(D, _LANES)
    qp = _bhsd(_pad_axis(_pad_axis(q, 1, lq_p), 3, d_p))
    kp = _bhsd(_pad_axis(_pad_axis(k, 1, lk_p), 3, d_p))
    vp = _bhsd(_pad_axis(_pad_axis(v, 1, lk_p), 3, d_p))
    q_spec, kv_spec, lse_spec = _specs(block_q, block_k, d_p)
    nq, nk = lq_p // block_q, lk_p // block_k
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               l_q=Lq, l_k=Lk, num_k=nk)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, lse_spec],
            # m/l scratch at full 128 lanes (the proven shape of jax's
            # own tpu flash kernel's carry scratch); the lse OUTPUT keeps
            # _STAT_LANES — it is a block of a real array, where the
            # equal-to-array-dim rule applies
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, d_p), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(qp.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, lq_p, _STAT_LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=_resolve_interpret(interpret),
    )(_offs(q_offset, k_offset), qp, kp, vp)
    return _bhsd(out)[:, :Lq, :, :D], lse[:, :, :Lq, 0]


def _bwd(q, k, v, out, lse, q_offset, k_offset, g, g_lse, causal, scale,
         block_q, block_k, interpret):
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    lq_p, lk_p = _ceil_to(Lq, block_q), _ceil_to(Lk, block_k)
    d_p = _ceil_to(D, _LANES)
    qp = _bhsd(_pad_axis(_pad_axis(q, 1, lq_p), 3, d_p))
    kp = _bhsd(_pad_axis(_pad_axis(k, 1, lk_p), 3, d_p))
    vp = _bhsd(_pad_axis(_pad_axis(v, 1, lk_p), 3, d_p))
    gp = _bhsd(_pad_axis(_pad_axis(g, 1, lq_p), 3, d_p))
    lse_p = _lanes(lse, lq_p)
    glse_p = _lanes(g_lse, lq_p)
    # delta_i = sum_d dO_i . O_i  (rowwise), the softmax-grad correction
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=3)                              # [B, Lq, H]
    delta = _lanes(delta.transpose(0, 2, 1), lq_p)
    interp = _resolve_interpret(interpret)
    offs = _offs(q_offset, k_offset)
    q_spec, kv_spec, lse_spec = _specs(block_q, block_k, d_p)
    nq, nk = lq_p // block_q, lk_p // block_k

    dq_kernel = functools.partial(_dq_kernel, causal=causal, scale=scale,
                                  block_q=block_q, block_k=block_k,
                                  l_q=Lq, l_k=Lk, num_k=nk)
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, lse_spec, lse_spec,
                      lse_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((block_q, d_p), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interp,
    )(offs, qp, kp, vp, gp, lse_p, delta, glse_p)

    # dk/dv: key blocks on the outer grid axis, query blocks streamed
    # innermost (same pipelining story, axes swapped)
    kq_spec = pl.BlockSpec((1, 1, block_q, d_p),
                           lambda b, h, i, j, *_: (b, h, j, 0))
    kk_spec = pl.BlockSpec((1, 1, block_k, d_p),
                           lambda b, h, i, j, *_: (b, h, i, 0))
    kq_lse_spec = pl.BlockSpec((1, 1, block_q, _STAT_LANES),
                               lambda b, h, i, j, *_: (b, h, j, 0))
    dkv_kernel = functools.partial(_dkv_kernel, causal=causal, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   l_q=Lq, l_k=Lk, num_q=nq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nk, nq),
            in_specs=[kq_spec, kk_spec, kk_spec, kq_spec, kq_lse_spec,
                      kq_lse_spec, kq_lse_spec],
            out_specs=[kk_spec, kk_spec],
            scratch_shapes=[pltpu.VMEM((block_k, d_p), jnp.float32),
                            pltpu.VMEM((block_k, d_p), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(kp.shape, k.dtype),
                   jax.ShapeDtypeStruct(vp.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interp,
    )(offs, qp, kp, vp, gp, lse_p, delta, glse_p)
    return (_bhsd(dq)[:, :Lq, :, :D], _bhsd(dk)[:, :Lk, :, :D],
            _bhsd(dv)[:, :Lk, :, :D])


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_lse(q, k, v, q_offset, k_offset, causal, block_q, block_k,
               interpret):
    D = q.shape[3]
    scale = float(1.0 / np.sqrt(D))
    return _fwd(q, k, v, q_offset, k_offset, causal, scale, block_q,
                block_k, interpret)


def _flash_lse_fwd(q, k, v, q_offset, k_offset, causal, block_q, block_k,
                   interpret):
    out, lse = _flash_lse(q, k, v, q_offset, k_offset, causal, block_q,
                          block_k, interpret)
    return (out, lse), (q, k, v, out, lse, q_offset, k_offset)


def _flash_lse_bwd(causal, block_q, block_k, interpret, res, cotangents):
    q, k, v, out, lse, q_offset, k_offset = res
    g, g_lse = cotangents
    D = q.shape[3]
    scale = float(1.0 / np.sqrt(D))
    dq, dk, dv = _bwd(q, k, v, out, lse, q_offset, k_offset, g, g_lse,
                      causal, scale, block_q, block_k, interpret)
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
    scale = float(1.0 / np.sqrt(D))

    def dense_fn(q, k, v):
        return _dense_lse(q, k, v, 0, 0, causal)

    dense_cost = aot_cost(dense_fn, q_s, kv_s, kv_s)
    flash_costs = {}
    for bq, bk in candidates:
        def flash_fn(q, k, v, _bq=bq, _bk=bk):
            return _fwd(q, k, v, 0, 0, causal, scale, _bq, _bk, None)
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
                      int(block_k or _DEF_BLOCK), interpret)


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
