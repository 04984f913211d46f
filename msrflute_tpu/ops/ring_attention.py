"""Ring attention — sequence-parallel exact attention over a mesh axis.

Net-new vs the reference (FLUTE has no long-context machinery, SURVEY.md
§5.7); this is the TPU-native long-sequence path: shard the sequence over a
``sequence`` mesh axis and rotate key/value blocks around the ring with
``ppermute`` while accumulating a numerically-stable online softmax — exact
attention with O(L/N) memory per chip and N-1 rotations total.  (The
blockwise-computation idea follows the public ring attention literature;
implementation is independent, in pure jax/shard_map.)

Usage — on GLOBAL arrays (the function applies its own shard_map):

    attn = ring_self_attention(q, k, v, mesh, axis="sequence")

with q/k/v of global shape ``[B, L, H, D]`` sharded on L.  Code already
running *inside* a shard_map body should call :func:`ring_attention_local`
on its local chunks instead.  Causal masking uses global position ids, so
it is correct regardless of which chunk a block lives on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

SEQUENCE_AXIS = "sequence"


def _ring_scan(k0, v0, acc0, axis_name: str, n, accumulate):
    """Shared ring choreography: accumulate the held chunk, rotate k/v to
    the next device, N-1 times; accumulate the final chunk without a dead
    rotation.  ``accumulate(acc, k_cur, v_cur, owner_shift) -> acc`` is
    the per-rotation kernel (``owner = (idx - owner_shift) % n`` is where
    the held chunk originated)."""
    def step(carry, owner_shift):
        k_cur, v_cur, acc = carry
        acc = accumulate(acc, k_cur, v_cur, owner_shift)
        rotation = [(i, (i + 1) % n) for i in range(n)]
        k_next = jax.lax.ppermute(k_cur, axis_name, rotation)
        v_next = jax.lax.ppermute(v_cur, axis_name, rotation)
        return (k_next, v_next, acc), None

    (k_last, v_last, acc), _ = jax.lax.scan(
        step, (k0, v0, acc0), jnp.arange(n - 1))
    return accumulate(acc, k_last, v_last, n - 1)


def ring_flash_attention_local(q, k0, v0, axis_name: str, causal: bool,
                               q_offset, chunk: int, block_q: int = 128,
                               block_k: int = 128):
    """Blockwise-ring attention: each rotation's chunk pair runs through
    the Pallas flash kernels (:func:`msrflute_tpu.ops.pallas_attention.
    flash_attention_lse` with dynamic position offsets), and the
    per-rotation normalized outputs are merged EXACTLY via their
    logsumexps — never materializing a score matrix anywhere, forward or
    backward.  This is the composition of the two long-context levers:
    the ring bounds per-device residency at O(L/N) chunks, the kernel
    bounds per-rotation working set at O(block) tiles.
    """
    from .pallas_attention import _NEG, flash_attention_lse

    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)

    def merge(acc, k_cur, v_cur, owner_shift):
        out_acc, lse_acc = acc
        owner = (idx - owner_shift) % n
        # force_flash: the gate's AOT probe would re-run inside every
        # shard_map trace, and use_flash=True is an explicit opt-in here
        # (the crossover resolve in models/ringlm.py owns the choice)
        out_r, lse_r = flash_attention_lse(
            q, k_cur, v_cur, causal, q_offset=q_offset,
            k_offset=owner * chunk, block_q=int(block_q or 128),
            block_k=int(block_k or 128), force_flash=True)
        # exact merge of independently-normalized rotation outputs:
        # out = sum_r exp(lse_r - lse_tot) * out_r
        lse_new = jnp.logaddexp(lse_acc, lse_r)
        w_acc = jnp.exp(lse_acc - lse_new).transpose(0, 2, 1)[..., None]
        w_r = jnp.exp(lse_r - lse_new).transpose(0, 2, 1)[..., None]
        return out_acc * w_acc + out_r.astype(jnp.float32) * w_r, lse_new

    B, Lq, H, D = q.shape
    acc0 = (jnp.zeros((B, Lq, H, D), jnp.float32),
            jnp.full((B, H, Lq), _NEG, jnp.float32))
    out, _ = _ring_scan(k0, v0, acc0, axis_name, n, merge)
    return out.astype(q.dtype)


def ring_attention_local(q, k0, v0, axis_name: str, causal: bool,
                         q_offset, chunk: int):
    """Online-softmax ring accumulation over local chunks.

    For use INSIDE a shard_map body whose mesh has ``axis_name``: ``q`` /
    ``k0`` / ``v0`` are this device's ``[B, L/N, H, D]`` chunks and
    ``q_offset`` the global position of ``q``'s first row.  Performs N-1
    ``ppermute`` rotations (the final block is accumulated without a
    further rotation).  For the fully-tiled variant (no per-rotation
    score matrix at all) see :func:`ring_flash_attention_local`.
    """
    B, Lq, H, D = q.shape
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
    q_pos = q_offset + jnp.arange(Lq)

    def accumulate(state, k_cur, v_cur, owner_shift):
        m, l, acc = state
        # the held k/v block originated at owner = idx - shift on the ring
        owner = (idx - owner_shift) % n
        k_pos = owner * chunk + jnp.arange(k_cur.shape[1])
        scores = jnp.einsum("blhd,bmhd->bhlm", q, k_cur) * scale
        if causal:
            mask = (q_pos[:, None] >= k_pos[None, :])  # [Lq, Lk]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        m_blk = jnp.max(scores, axis=-1)  # [B,H,Lq]
        m_new = jnp.maximum(m, m_blk)
        # guard fully-masked rows (all -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(scores - m_safe[..., None])
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + \
            jnp.einsum("bhlm,bmhd->blhd", p, v_cur)
        return (m_new, l_new, acc_new)

    # remat the blockwise accumulate: under reverse-mode AD a scan stores
    # every step's residuals — here the [B,H,Lq,chunk] probability matrix
    # per rotation, i.e. O(L^2/N) per device, exactly the memory wall this
    # op exists to avoid.  Recomputing scores from the (q, k, v) chunks in
    # the backward keeps live memory at O(L/N) state per rotation for ~1/3
    # extra FLOPs (the blockwise-recompute backward of the ring/flash
    # attention literature).
    # prevent_cse=False: inside lax.scan the CSE-prevention barriers are
    # unnecessary (per the jax.checkpoint docs) and would inhibit fusion
    accumulate_ckpt = jax.checkpoint(accumulate, prevent_cse=False)

    state0 = (jnp.full((B, H, Lq), -jnp.inf, q.dtype),
              jnp.zeros((B, H, Lq), q.dtype),
              jnp.zeros_like(q))
    m, l, acc = _ring_scan(k0, v0, state0, axis_name, n, accumulate_ckpt)
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return acc / denom


def ring_self_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        mesh: Mesh, axis: str = SEQUENCE_AXIS,
                        causal: bool = False,
                        batch_axis: "str | None" = None,
                        use_flash: bool = False, flash_block_q: int = 128,
                        flash_block_k: int = 128) -> jnp.ndarray:
    """Exact attention with GLOBAL q/k/v ``[B, L, H, D]`` sharded on L over
    ``axis``.  Returns the output with the same sharding.  Must be called
    outside shard_map (it applies its own); inside a shard_map body use
    :func:`ring_attention_local`.

    ``batch_axis`` additionally shards B over another mesh axis (combined
    data + sequence parallelism): the ring rotations stay within each
    batch shard's ring, no cross-batch communication.

    ``use_flash`` runs each rotation through
    :func:`ring_flash_attention_local` — the Pallas flash kernels on TPU
    (no per-rotation score matrix), the dense-lse reference elsewhere;
    same numerics either way (kernel/dense parity incl. the lse cotangent
    is pinned by ``test_pallas_attention.py``).
    """
    n = mesh.shape[axis]
    L = q.shape[1]
    if k.shape[1] != L or v.shape[1] != L:
        raise ValueError(
            f"q/k/v sequence lengths differ: {L}, {k.shape[1]}, {v.shape[1]}")
    if L % n:
        raise ValueError(f"sequence length {L} not divisible by {axis}={n}")
    if batch_axis is not None:
        if batch_axis not in mesh.shape:
            raise ValueError(f"batch_axis {batch_axis!r} not in mesh axes "
                             f"{tuple(mesh.shape)}")
        if q.shape[0] % mesh.shape[batch_axis]:
            raise ValueError(f"batch {q.shape[0]} not divisible by "
                             f"{batch_axis}={mesh.shape[batch_axis]}")
    chunk = L // n
    spec = P(batch_axis, axis, None, None)

    def body(q_l, k_l, v_l):
        idx = jax.lax.axis_index(axis)
        q_offset = idx * chunk
        if use_flash:
            return ring_flash_attention_local(q_l, k_l, v_l, axis, causal,
                                              q_offset, chunk,
                                              block_q=flash_block_q,
                                              block_k=flash_block_k)
        return ring_attention_local(q_l, k_l, v_l, axis, causal, q_offset,
                                    chunk)

    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)
