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
