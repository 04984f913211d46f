"""Expert parallelism — switch-style top-1 MoE with all-to-all dispatch.

Net-new vs the reference (FLUTE has no model partitioning); completes the
parallelism toolbox (dp / tp / sp / pp / **ep**) on the same
``jax.sharding.Mesh`` machinery — see ``docs/architecture.md``.

Design: one expert per device on an ``expert`` mesh axis.  Tokens are
data-sharded over the SAME axis; each device routes its local tokens
(top-1, softmax gate), packs them into fixed-capacity per-expert buffers
(static shapes — overflow beyond capacity is dropped, the standard switch
behavior), exchanges buffers with ``lax.all_to_all`` so every device holds
exactly its own expert's tokens, applies the expert, and a second
``all_to_all`` returns results to their owners where gates scale them.
Everything is SPMD and differentiable; XLA rides the all-to-alls on ICI.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

EXPERT_AXIS = "expert"


def _dispatch_indices(expert_id: jnp.ndarray, n_experts: int,
                      capacity: int):
    """Per-token slot in its expert's send buffer (or capacity = dropped).

    ``position_in_expert[i]`` = how many earlier local tokens chose the
    same expert; tokens beyond ``capacity`` are overflow.
    """
    onehot = jax.nn.one_hot(expert_id, n_experts, dtype=jnp.int32)  # [n, E]
    position_in_expert = (jnp.cumsum(onehot, axis=0) - 1) * onehot
    pos = jnp.sum(position_in_expert, axis=1)                       # [n]
    keep = pos < capacity
    return pos, keep


def moe_apply(router_w: jnp.ndarray, expert_params: Any,
              expert_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
              x: jnp.ndarray, mesh: Mesh, axis: str = EXPERT_AXIS,
              capacity_factor: float = 2.0) -> jnp.ndarray:
    """Top-1 MoE layer over globally ``[T, D]`` tokens.

    ``router_w``: ``[D, E]`` (replicated); ``expert_params``: pytree with
    leading axis E == mesh.shape[axis] (sharded over ``axis``);
    ``expert_fn(params_e, tokens) -> tokens`` shape-preserving.  ``x`` is
    sharded on T over ``axis`` (data-parallel tokens).  Returns the same
    sharding as ``x``; dropped (over-capacity) tokens pass through on the
    residual path (output 0 from the layer, the switch convention).
    """
    E = mesh.shape[axis]
    T, D = x.shape
    if T % E:
        raise ValueError(f"token count {T} not divisible by {axis}={E}")
    leaves = jax.tree.leaves(expert_params)
    if leaves and leaves[0].shape[0] != E:
        raise ValueError(
            f"expert_params leading axis {leaves[0].shape[0]} != {axis}={E}")
    local_t = T // E
    # per-(device, expert) buffer size; every local token fits iff one
    # expert hoards fewer than `capacity` of a device's tokens
    capacity = max(1, int(capacity_factor * local_t / E))

    def body(rw, ep, x_l):
        params_local = jax.tree.map(lambda a: a[0], ep)
        n = x_l.shape[0]
        logits = x_l @ rw                                # [n, E]
        expert_id = jnp.argmax(logits, axis=-1)
        gate = jax.nn.softmax(logits.astype(jnp.float32),
                              axis=-1)[jnp.arange(n), expert_id]
        pos, keep = _dispatch_indices(expert_id, E, capacity)

        # scatter local tokens into [E, capacity, D] send buffers
        send = jnp.zeros((E, capacity, D), x_l.dtype)
        send = send.at[expert_id, jnp.where(keep, pos, 0)].add(
            jnp.where(keep[:, None], x_l, 0.0))
        # exchange: device d's send[j] goes to device j; afterwards device
        # j holds [E_senders, capacity, D] — all tokens for ITS expert
        recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=False)
        y = expert_fn(params_local, recv.reshape(E * capacity, D))
        y = y.reshape(E, capacity, D)
        # return: device j sends results back to each owner d
        back = lax.all_to_all(y, axis, split_axis=0, concat_axis=0,
                              tiled=False)                # [E, capacity, D]
        # gather each local token's result from its expert's buffer
        out = back[expert_id, pos] * keep[:, None].astype(x_l.dtype)
        return out * gate[:, None].astype(x_l.dtype)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), jax.tree.map(lambda _: P(axis), expert_params),
                  P(axis)),
        out_specs=P(axis), check_vma=False)
    return fn(router_w, expert_params, x)


class MoEFFN(nn.Module):
    """Switch top-1 MoE feed-forward as a drop-in flax module.

    Two execution modes over the SAME parameters:

    - **local** (``ep_mesh=None``): every device evaluates all experts and
      selects per token — exact routing, no capacity drops.  The federated
      path uses this (experts are tiny, clients ride the clients axis).
    - **expert-parallel** (``ep_mesh`` set): :func:`moe_apply` all-to-all
      dispatch with one expert per device of ``expert_axis``; requires
      ``num_experts == mesh.shape[expert_axis]``.  With capacity ample
      enough that nothing drops, both modes are numerically identical
      (tested).

    Input/output: ``[..., D]`` tokens (leading axes flattened internally).
    """

    num_experts: int
    hidden: int
    dtype: Any = jnp.float32
    ep_mesh: Optional[Mesh] = None
    expert_axis: str = EXPERT_AXIS
    capacity_factor: float = 2.0

    @nn.compact
    def __call__(self, x):
        D = x.shape[-1]
        E = self.num_experts
        router = self.param("router", nn.initializers.lecun_normal(),
                            (D, E)).astype(self.dtype)
        w_in = self.param("w_in", nn.initializers.lecun_normal(),
                          (E, D, self.hidden)).astype(self.dtype)
        w_out = self.param("w_out", nn.initializers.lecun_normal(),
                           (E, self.hidden, D)).astype(self.dtype)
        lead = x.shape[:-1]
        t = x.reshape(-1, D).astype(self.dtype)

        if self.ep_mesh is not None:
            if self.ep_mesh.shape[self.expert_axis] != E:
                raise ValueError(
                    f"num_experts={E} != {self.expert_axis}="
                    f"{self.ep_mesh.shape[self.expert_axis]}")

            def expert_fn(p, tok):
                return nn.gelu(tok @ p["w_in"]) @ p["w_out"]

            y = moe_apply(router, {"w_in": w_in, "w_out": w_out}, expert_fn,
                          t, self.ep_mesh, axis=self.expert_axis,
                          capacity_factor=self.capacity_factor)
            return y.reshape(*lead, D)

        # local mode: evaluate all experts, select per token
        logits = (t @ router).astype(jnp.float32)          # [T, E]
        eid = jnp.argmax(logits, axis=-1)
        gate = jax.nn.softmax(logits, axis=-1)[
            jnp.arange(t.shape[0]), eid].astype(t.dtype)
        h = nn.gelu(jnp.einsum("td,edh->teh", t, w_in))
        y_all = jnp.einsum("teh,ehd->ted", h, w_out)       # [T, E, D]
        y = jnp.take_along_axis(y_all, eid[:, None, None], axis=1)[:, 0]
        return (y * gate[:, None]).reshape(*lead, D)


# ======================================================================
# One chip's share of an expert-parallel layer: the layer is told which
# experts it holds (``expert_offset .. expert_offset + experts_held - 1``
# of ``num_experts``), routes every token over ALL experts with the
# published experts per token, and computes its own experts' part of the
# result for the tokens routed to them.  Nothing is dropped: the pair
# buffer is sized for the worst routing.  On one chip there is no
# exchange, and no code stands in for one.
#
# The pair buffer.  Every ``[M, *]`` array on the row side (the sorted
# tokens, the two up-products, their activation, the down-product and the
# cotangent of each) has ``M = (ceil(T * k / TILE_ROWS) + held) *
# TILE_ROWS`` rows, a static shape with room for every pair of every
# token on held experts.  A step's routing fills the first ``n_active``
# tiles (``plan_pairs``: every held expert's pairs rounded up to whole
# tiles, at least one tile each), and every pass on the row side walks
# those tiles and nothing else, each a kernel on a grid of ``n_active``.
# So of a row-side array
# - a row that a pair owns holds that pair's value;
# - a padding row INSIDE an active tile (an expert's last tile beyond
#   its pairs; the whole tile of an expert without a pair) holds zeros,
#   and must: ``expert_gmm_dw`` sums ``x.T @ dy`` over whole tiles, and
#   ``0 * NaN`` is not 0.  The gathers fill zeros there and every later
#   pass maps zeros to zeros;
# - a row BEYOND the active tiles is never written and never read, and
#   may hold anything (the interpreter leaves NaN there, the chip what
#   the memory held).  The moves back to token order read only rows that
#   a pair owns (row 0, always written, for a pair held elsewhere).
# ``moe_tiles_active * TILE_ROWS / M`` is the share of the buffer that a
# step walks (a sixth to a sixteenth at the benchmark's shapes).
# ======================================================================
#: rows of one tile of the sorted pair buffer; every held expert's rows
#: start on a tile boundary, so a tile belongs to exactly one expert
TILE_ROWS = 128
#: output columns a grid step of the grouped products
TILE_COLS = 256
#: most columns a grid step of the element-wise kernels
WIDE_COLS = 1024
#: bytes of the tokens' column block that a grid step of the gather to
#: the rows reads from (float32; the pipeline holds two such blocks)
GATHER_BLOCK_BYTES = 16 * 2 ** 20
#: rows whose scalars a grid step of that gather holds in SMEM: XLA lays
#: a long 32-bit vector out in tiles of so many, and a block is whole
#: tiles of the layout
SCALAR_ROWS = 8 * TILE_ROWS
#: stable kernel names (the trace's operation names; the benchmark's
#: roofline readers find the kernels by them)
GMM_NAME = "expert_gmm_fwd"
GMM_T_NAME = "expert_gmm_dx"
TGMM_NAME = "expert_gmm_dw"
#: the element-wise kernels over the active tiles
SWIGLU_NAME = "expert_swiglu_fwd"
SWIGLU_BWD_NAME = "expert_swiglu_bwd"
ROWS_ADD_NAME = "expert_rows_add"
#: the gather to the rows of the active tiles
ROWS_GATHER_NAME = "expert_rows_gather"


def _interpret() -> bool:
    # off the TPU the plain interpreter: the TPU-flavoured one
    # (``pltpu.InterpretParams``) works through ordered callbacks, which
    # ``remat`` under a scan over layers refuses
    return jax.default_backend() != "tpu"


def _dot_precision(dtype):
    """The kernels' matmul precision: the context's default for float32
    operands (under ``highest`` Mosaic contracts them in full float32),
    one MXU pass for bfloat16 operands, which are exact MXU inputs and
    which Mosaic refuses to contract at a float32 precision."""
    return lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


def _col_tile(n: int) -> int:
    tile = min(TILE_COLS, n)
    if n % tile:
        raise ValueError(f"expert width {n} is no multiple of {tile}")
    return tile


def _gmm(x, w, tile_expert, n_active, *, transpose_rhs: bool, name: str):
    """``out[tile i] = x[tile i] @ w[tile_expert[i]]`` (``w[e].T`` with
    ``transpose_rhs``) for the first ``n_active`` tiles of ``TILE_ROWS``
    rows; the rows of later tiles are NOT written.  ``x``: ``[M, K]``,
    ``w``: ``[E, K, N]`` (``[E, N, K]`` transposed).  The whole
    contraction is one block, so consecutive tiles of one expert reuse
    the weight block that is already in VMEM."""
    m, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    tn = _col_tile(n)
    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))

    def kernel(tile_expert_ref, x_ref, w_ref, o_ref):
        del tile_expert_ref
        o_ref[...] = lax.dot_general(
            x_ref[...], w_ref[...], dims, precision=_dot_precision(x.dtype),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    if transpose_rhs:
        w_spec = pl.BlockSpec((None, tn, k), lambda j, i, te: (te[i], j, 0))
    else:
        w_spec = pl.BlockSpec((None, k, tn), lambda j, i, te: (te[i], 0, j))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((TILE_ROWS, k), lambda j, i, te: (i, 0)),
                      w_spec],
            out_specs=pl.BlockSpec((TILE_ROWS, tn),
                                   lambda j, i, te: (i, j)),
            grid=(n // tn, n_active)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(), name=name,
    )(tile_expert, x, w)


def _tgmm(x, dy, tile_expert, n_active, num_experts: int):
    """``out[e] = sum over e's tiles of x[tile].T @ dy[tile]``: the
    weight gradient ``[E, K, N]``.  Every expert owns at least one tile
    (``plan_pairs``), so every block of the output is written."""
    m, k = x.shape
    n = dy.shape[1]
    tn = _col_tile(n)
    n_tiles = tile_expert.shape[0]

    def kernel(tile_expert_ref, n_active_ref, x_ref, dy_ref, o_ref, acc_ref):
        i = pl.program_id(1)
        here = tile_expert_ref[i]
        first = jnp.logical_or(
            i == 0, tile_expert_ref[jnp.maximum(i - 1, 0)] != here)
        last = jnp.logical_or(
            i == n_active_ref[0] - 1,
            tile_expert_ref[jnp.minimum(i + 1, n_tiles - 1)] != here)

        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            precision=_dot_precision(x.dtype),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((num_experts, k, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[
                pl.BlockSpec((TILE_ROWS, k), lambda j, i, te, na: (i, 0)),
                pl.BlockSpec((TILE_ROWS, tn), lambda j, i, te, na: (i, j))],
            out_specs=pl.BlockSpec((None, k, tn),
                                   lambda j, i, te, na: (te[i], 0, j)),
            grid=(n // tn, n_active),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(), name=TGMM_NAME,
    )(tile_expert, jnp.reshape(n_active, (1,)), x, dy)


@jax.custom_vjp
def grouped_matmul(x, w, tile_expert, n_active):
    """Rows of ``x`` (sorted by expert, each expert's rows starting on a
    tile boundary) times their expert's matrix, over the first
    ``n_active`` tiles; so are both transposes (``expert_gmm_dx``,
    ``expert_gmm_dw``).  Rows beyond the active tiles are not read and
    come back unwritten: they may hold anything and must not be read
    (``held_experts_ffn`` gathers only rows that a pair owns).  Padding
    rows inside an active tile must be zeros in ``x`` and in the
    cotangent: ``expert_gmm_dw`` sums over whole tiles."""
    return _gmm(x, w, tile_expert, n_active, transpose_rhs=False,
                name=GMM_NAME)


def _grouped_matmul_fwd(x, w, tile_expert, n_active):
    return grouped_matmul(x, w, tile_expert, n_active), \
        (x, w, tile_expert, n_active)


def _grouped_matmul_bwd(saved, dy):
    x, w, tile_expert, n_active = saved
    dx = _gmm(dy, w, tile_expert, n_active, transpose_rhs=True,
              name=GMM_T_NAME)
    dw = _tgmm(x, dy, tile_expert, n_active, w.shape[0])
    return dx, dw.astype(w.dtype), None, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def _lane_tile(n: int, most: int) -> int:
    """Columns a grid step of a kernel without a contraction: all ``n``
    where that is no more than ``most`` (or no multiple of 128: a narrow
    test shape), else ``n``'s largest divisor that is whole lanes (a
    multiple of 128) and no more than ``most``, one lane tile at least."""
    if n <= most or n % 128:
        return n
    return next(tile for tile in range(max(most // 128, 1) * 128, 0, -128)
                if n % tile == 0)


def _over_active_tiles(kernel, n_out: int, n_active, *operands, name: str):
    """``kernel(*operand blocks, *output blocks)`` over the first
    ``n_active`` tiles of ``[M, N]`` operands of one shape, block by
    block of ``TILE_ROWS`` rows and ``WIDE_COLS`` columns at most;
    ``n_out`` outputs of that shape.  The rows of later tiles are
    neither read nor written."""
    m, n = operands[0].shape
    tn = _lane_tile(n, WIDE_COLS)
    block = pl.BlockSpec((TILE_ROWS, tn), lambda i, j: (i, j))
    out = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((m, n), operands[0].dtype)] * n_out,
        grid=(n_active, n // tn),
        in_specs=[block] * len(operands), out_specs=[block] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret(), name=name,
    )(*operands)
    return out[0] if n_out == 1 else tuple(out)


def _swiglu_kernel(h1_ref, h3_ref, o_ref):
    h1 = h1_ref[...].astype(jnp.float32)
    o_ref[...] = (h1 * jax.nn.sigmoid(h1) *
                  h3_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _swiglu_bwd_kernel(h1_ref, h3_ref, d_ref, d1_ref, d3_ref):
    h1 = h1_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    gate = jax.nn.sigmoid(h1)
    silu = h1 * gate
    d3_ref[...] = (d * silu).astype(d3_ref.dtype)
    d1_ref[...] = (d * h3_ref[...].astype(jnp.float32) *
                   (gate + silu * (1.0 - gate))).astype(d1_ref.dtype)


def _rows_add_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] + b_ref[...]


@jax.custom_vjp
def swiglu_rows(h1, h3, n_active):
    """``silu(h1) * h3`` over the rows of the first ``n_active`` tiles of
    the two up-products ``[M, H]``; later rows come back unwritten.  A
    padding row inside an active tile is zeros in both and stays zeros,
    in both directions."""
    return _over_active_tiles(_swiglu_kernel, 1, n_active, h1, h3,
                              name=SWIGLU_NAME)


def _swiglu_rows_fwd(h1, h3, n_active):
    return swiglu_rows(h1, h3, n_active), (h1, h3, n_active)


def _swiglu_rows_bwd(saved, d_hidden):
    h1, h3, n_active = saved
    d_h1, d_h3 = _over_active_tiles(_swiglu_bwd_kernel, 2, n_active, h1, h3,
                                    d_hidden, name=SWIGLU_BWD_NAME)
    return d_h1, d_h3, None


swiglu_rows.defvjp(_swiglu_rows_fwd, _swiglu_rows_bwd)


@jax.custom_vjp
def read_twice(rows, n_active):
    """``(rows, rows)`` for two readers of one row-side array, so that
    the sum of their two cotangents (which autodiff would take over all
    ``M`` rows) walks the active tiles like every other pass."""
    return rows, rows


def _read_twice_fwd(rows, n_active):
    return (rows, rows), n_active


def _read_twice_bwd(n_active, d_both):
    return _over_active_tiles(_rows_add_kernel, 1, n_active, *d_both,
                              name=ROWS_ADD_NAME), None


read_twice.defvjp(_read_twice_fwd, _read_twice_bwd)


#: the gate's denominator where the caller gives none: LFM2's published
#: form.  It is the caller's to give: a model whose published form has
#: another (DeepSeek-V3's block: 1e-20) passes its own
ROUTE_EPS = 1e-6


def route_tokens(z, router_w, select_bias, experts_per_token: int,
                 scaling: float = 1.0, eps: float = ROUTE_EPS,
                 scoring: str = "sigmoid"):
    """Routing over ALL experts: ``(chosen [T, k] int32, gate [T, k]
    float32)``, by one of two scoring laws.  The logits are float32 at
    ``highest`` whatever the context (a choice that flips on rounding is
    a discrete event) under both.

    - ``sigmoid`` (LFM2, DeepSeek-V3's block): scores ``sigmoid(logits)``,
      chosen = top k of ``score + select_bias``; the bias enters the
      choice only and gets no gradient; the gate is renormalised over all
      chosen experts, held here or not: ``scaling * s_i / (sum of chosen
      s + eps)``, with the CALLER's ``eps`` (the models' published forms
      differ: ``ROUTE_EPS``);
    - ``softmax`` (the ``qwen3_moe`` form): scores ``softmax(logits)``
      over all experts, chosen = top k of them, ``g_i = s_i / sum of
      chosen s``: no bias (``select_bias`` is not read), no factor, no
      epsilon."""
    logits = jnp.matmul(z.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        picked, chosen = lax.top_k(scores, experts_per_token)
        return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)
    if scoring != "sigmoid":
        raise ValueError(f"route_tokens: scoring {scoring!r}; expected "
                         "sigmoid or softmax")
    scores = jax.nn.sigmoid(logits)
    _, chosen = lax.top_k(
        scores + lax.stop_gradient(select_bias.astype(jnp.float32)),
        experts_per_token)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gate = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps) * scaling
    return chosen, gate


def plan_pairs(chosen, experts_held: int, expert_offset: int):
    """Where each (token, chosen expert) pair that falls on a held expert
    lies in the sorted pair buffer.  Every held expert gets at least one
    tile and starts on a tile boundary; the buffer has room for every
    pair of every token on held experts (``T * k`` rows) plus a tile of
    slack an expert, so no routing drops a pair.

    Returns ``row_of_pair [T, k]`` (a held pair's row; 0, which is always
    a written row, for a pair elsewhere), ``held [T, k]`` bool,
    ``pair_of_row [M]`` (the flat pair ``t * k + j`` that owns the row;
    ``T * k`` = no pair: a gather there fills zeros),
    ``tile_expert [M / TILE_ROWS]``, ``n_active`` and
    ``counts [experts_held]``."""
    tokens, k = chosen.shape
    pairs = tokens * k
    n_tiles = -(-pairs // TILE_ROWS) + experts_held
    local = chosen.reshape(pairs) - expert_offset
    held = (local >= 0) & (local < experts_held)
    onehot = jax.nn.one_hot(jnp.where(held, local, experts_held),
                            experts_held + 1, dtype=jnp.int32)[:, :-1]
    counts = jnp.sum(onehot, axis=0)
    # a pair's rank among its expert's pairs, in token order
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    tiles = jnp.maximum(-(-counts // TILE_ROWS), 1)
    tile_end = jnp.cumsum(tiles)
    start = (tile_end - tiles) * TILE_ROWS
    row = jnp.where(held, start[jnp.clip(local, 0, experts_held - 1)] + rank,
                    n_tiles * TILE_ROWS)
    pair_of_row = jnp.full((n_tiles * TILE_ROWS,), pairs, jnp.int32).at[
        row].set(jnp.arange(pairs, dtype=jnp.int32), mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles, dtype=jnp.int32),
                         side="right"), experts_held - 1).astype(jnp.int32)
    return (jnp.where(held, row, 0).reshape(tokens, k),
            held.reshape(tokens, k), pair_of_row, tile_expert,
            tile_end[-1].astype(jnp.int32), counts)


# The two moves between token order and the sorted pair buffer.  Each is
# a gather, and so is its transpose, written out here: a row belongs to
# one pair and a held pair to one row, so the scatter-add that autodiff
# would make of a gather is the other map's gather (XLA's scatter of
# 17,408 rows of 2,048 floats took 14 s to compile for the chip, per
# expert layer, and runs row by row).  The gather TO the rows is a kernel
# over the active tiles (XLA's own gather walks all ``M`` rows, a fifth
# of the memory's rate over rows of which most are never read).
def _gather_active_rows(values, pair_of_row, per_token: int, n_active,
                        weight=None):
    """``[M, D]`` rows of ``values [T, D]``: row ``r`` of the first
    ``n_active`` tiles is its pair's token's (``values[pair_of_row[r] //
    per_token]``, zeros where no pair owns the row), times its pair's
    ``weight [T * per_token]`` where one is given; later rows are
    unwritten.  A grid step holds a column block of all ``T`` tokens in
    VMEM (``GATHER_BLOCK_BYTES`` of float32 at most; read once for all
    tiles: the block's index does not change along the tiles) and
    copies a tile's 128 rows out of it one by one, the row's token
    (``T``: no pair) and weight read from SMEM."""
    tokens, dim = values.shape
    m = pair_of_row.shape[0]
    cols = _lane_tile(dim, GATHER_BLOCK_BYTES // (4 * tokens))
    scalar_rows = min(SCALAR_ROWS, m)
    tiles_a_block = scalar_rows // TILE_ROWS
    scalars = [pair_of_row // per_token]
    if weight is not None:
        scalars.append(weight.astype(jnp.float32).at[pair_of_row].get(
            mode="fill", fill_value=0))

    def kernel(*refs):
        token_ref, *weight_ref = refs[:len(scalars)]
        values_ref, out_ref, tile_ref = refs[len(scalars):]
        first = (pl.program_id(1) % tiles_a_block) * TILE_ROWS

        def row(r, carry):
            token = token_ref[first + r]
            got = values_ref[pl.ds(jnp.minimum(token, tokens - 1), 1), :]
            got = jnp.where(token < tokens, got, jnp.zeros_like(got))
            if weight_ref:
                got = got * weight_ref[0][first + r]
            tile_ref[pl.ds(r, 1), :] = got
            return carry

        lax.fori_loop(0, TILE_ROWS, row, 0)
        out_ref[...] = tile_ref[...].astype(out_ref.dtype)

    scalar_block = pl.BlockSpec(
        (scalar_rows,), lambda j, i: (i // tiles_a_block,),
        memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((m, dim), values.dtype),
        grid=(dim // cols, n_active),
        in_specs=[scalar_block] * len(scalars) +
        [pl.BlockSpec((tokens, cols), lambda j, i: (0, j))],
        out_specs=pl.BlockSpec((TILE_ROWS, cols), lambda j, i: (i, j)),
        scratch_shapes=[pltpu.VMEM((TILE_ROWS, cols), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * 4 * tokens * cols + 16 * 2 ** 20),
        interpret=_interpret(), name=ROWS_GATHER_NAME,
    )(*scalars, values.astype(jnp.float32))


@jax.custom_vjp
def rows_of_tokens(z, pair_of_row, row_of_pair, held, n_active):
    """``z [T, D]`` -> the pair buffer ``[M, D]``: a row of the first
    ``n_active`` tiles is its pair's token (zeros where no pair owns the
    row); later rows are unwritten."""
    return _gather_active_rows(z, pair_of_row, row_of_pair.shape[1],
                               n_active)


def _rows_of_tokens_fwd(z, pair_of_row, row_of_pair, held, n_active):
    return rows_of_tokens(z, pair_of_row, row_of_pair, held, n_active), \
        (row_of_pair, held)


def _rows_of_tokens_bwd(saved, d_rows):
    row_of_pair, held = saved
    d_z = jnp.sum(jnp.where(held[..., None], d_rows[row_of_pair], 0), axis=1)
    return d_z, None, None, None, None


rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@jax.custom_vjp
def tokens_of_rows(y_rows, weight, pair_of_row, row_of_pair, n_active):
    """The pair buffer ``y_rows [M, D]`` -> ``y [T, D]``: each token's
    rows, weighted (``weight [T, k]``, 0 for a pair held elsewhere).
    Reads rows that a pair owns and row 0 only; its cotangent writes the
    first ``n_active`` tiles (zeros on their padding rows)."""
    return jnp.einsum("tkd,tk->td", y_rows[row_of_pair], weight)


def _tokens_of_rows_fwd(y_rows, weight, pair_of_row, row_of_pair, n_active):
    return tokens_of_rows(y_rows, weight, pair_of_row, row_of_pair,
                          n_active), \
        (y_rows, weight, pair_of_row, row_of_pair, n_active)


def _tokens_of_rows_bwd(saved, d_y):
    y_rows, weight, pair_of_row, row_of_pair, n_active = saved
    d_weight = jnp.einsum("tkd,td->tk", y_rows[row_of_pair], d_y)
    d_rows = _gather_active_rows(d_y, pair_of_row, weight.shape[1], n_active,
                                 weight=weight.reshape(-1))
    return d_rows.astype(y_rows.dtype), d_weight.astype(weight.dtype), \
        None, None, None


tokens_of_rows.defvjp(_tokens_of_rows_fwd, _tokens_of_rows_bwd)


def held_experts_ffn(z, router_w, select_bias, w1, w3, w2, *,
                     experts_per_token: int, expert_offset: int = 0,
                     scaling: float = 1.0, route_eps: float | None = None,
                     scoring: str = "sigmoid"):
    """The held experts' part of a routed SwiGLU layer for tokens
    ``z [T, D]``: ``sum over chosen AND held i of g_i E_i(z)``.
    ``w1``/``w3``: ``[held, D, H]``, ``w2``: ``[held, H, D]``,
    ``router_w``: ``[D, num_experts]``.  ``route_eps``: the epsilon of
    the gate's denominator, the caller's to give (None: ``route_tokens``'
    own, ``ROUTE_EPS``).  ``scoring``: ``route_tokens``' law (``softmax``
    reads no ``select_bias``: None will do).  Returns ``(y [T, D],
    counters)``; the counters (float32 scalars) are what the telemetry
    reads: pairs on held experts (``moe_pairs_held``), the largest held
    expert's load
    (``moe_max_load``), pairs without a row (``moe_pairs_dropped``, 0 by
    construction), 1 for this layer-step (``moe_layer_steps``) and the
    tiles of ``TILE_ROWS`` rows that the grouped products ran
    (``moe_tiles_active``: every held expert's pairs rounded up to whole
    tiles, at least one each; ``moe_pairs_held`` over its rows is the
    share of them that are real pairs).

    Every ``[M, *]`` array between the two moves is walked over those
    tiles only, by the gathers, the three products, the activation and
    the sum of the two up-products' cotangents alike, so
    ``moe_tiles_active * TILE_ROWS / M`` is the share of the pair buffer
    that a step touches; the rows beyond hold whatever was there (the
    account of the pair buffer above says which rows are written, which
    must be zeros and which may hold anything)."""
    held_n = w1.shape[0]
    # positional, and the epsilon and the law only where the caller gave
    # one: the benchmark's planted routing faults replace
    # ``route_tokens`` with functions of these five arguments
    chosen, gate = route_tokens(
        z, router_w, select_bias, experts_per_token, scaling,
        **({} if route_eps is None else {"eps": route_eps}),
        **({} if scoring == "sigmoid" else {"scoring": scoring}))
    row_of_pair, held, pair_of_row, tile_expert, n_active, counts = \
        plan_pairs(chosen, held_n, expert_offset)
    x_gate, x_up = read_twice(
        rows_of_tokens(z, pair_of_row, row_of_pair, held, n_active),
        n_active)
    hidden = swiglu_rows(grouped_matmul(x_gate, w1, tile_expert, n_active),
                         grouped_matmul(x_up, w3, tile_expert, n_active),
                         n_active)
    y_sorted = grouped_matmul(hidden, w2, tile_expert, n_active)
    weight = jnp.where(held, gate, 0.0).astype(z.dtype)
    y = tokens_of_rows(y_sorted, weight, pair_of_row, row_of_pair, n_active)
    placed = jnp.sum((pair_of_row < chosen.size).astype(jnp.float32))
    on_held = jnp.sum(counts).astype(jnp.float32)
    counters = {"moe_pairs_held": on_held,
                "moe_max_load": jnp.max(counts).astype(jnp.float32),
                "moe_pairs_dropped": on_held - placed,
                "moe_layer_steps": jnp.ones((), jnp.float32),
                "moe_tiles_active": n_active.astype(jnp.float32)}
    return y, counters
