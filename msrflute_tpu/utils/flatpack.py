"""Dtype-grouped pytree flattening for dispatch-boundary packing.

On the remote-attached chip, per-dispatch overhead scales with the
argument/result BUFFER count (measured: the fuse=1 LR round dispatches in
~88 ms against a 0.14 ms trivial-op floor; `tools/dispatch_cost_probe.py`
pins the per-buffer cost).  A ResNet server state is ~100+ leaves; packed
it is one buffer per distinct dtype (usually 1-3).

Why not ``jax.flatten_util.ravel_pytree``: it promotes mixed dtypes to a
common dtype, which corrupts uint32 PRNG keys and large int32 counters
when the common type is floating.  Here leaves are grouped BY DTYPE and
concatenated raveled within each group — the round-trip is bit-exact for
every dtype, and inside jit the pack/unpack lowers to pure
reshape/slice/concat that XLA fuses away.

Usage::

    packer = build_packer(template_tree)
    vecs = packer.pack(tree)      # {dtype_str: 1-D array}, jit-safe
    tree2 = packer.unpack(vecs)   # original structure, bit-identical

The packer is built once from a template (shapes/dtypes must match later
trees — the jit retrace guard the engine already lives by).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class FlatPacker:
    """Pack/unpack a fixed-structure pytree into one 1-D array per dtype."""

    def __init__(self, template: Any):
        leaves, treedef = jax.tree.flatten(template)
        self.treedef = treedef
        #: per-leaf (dtype_str, offset, size, shape) in flatten order
        self._slots: List[Tuple[str, int, int, Tuple[int, ...]]] = []
        sizes: Dict[str, int] = {}
        for leaf in leaves:
            # jnp.asarray, not np: python scalars must get the same dtype
            # (int32/float32 under default jax config) that jnp.ravel will
            # produce at pack time, or the group keys/sizes are mislabeled
            arr = leaf if hasattr(leaf, "dtype") else jnp.asarray(leaf)
            dt = str(arr.dtype)
            size = int(np.prod(arr.shape)) if arr.shape else 1
            off = sizes.get(dt, 0)
            self._slots.append((dt, off, size, tuple(arr.shape)))
            sizes[dt] = off + size
        self.sizes = sizes  # {dtype_str: total elements}

    def pack(self, tree: Any) -> Dict[str, jnp.ndarray]:
        """One 1-D array per dtype, concatenated in flatten order."""
        leaves, treedef = jax.tree.flatten(tree)
        if len(leaves) != len(self._slots):
            raise ValueError(
                f"tree has {len(leaves)} leaves, packer built for "
                f"{len(self._slots)}")
        if treedef != self.treedef:
            raise ValueError(
                f"tree structure {treedef} != packer template "
                f"{self.treedef}")
        groups: Dict[str, list] = {}
        for leaf, (dt, _, _, shape) in zip(leaves, self._slots):
            leaf = leaf if hasattr(leaf, "dtype") else jnp.asarray(leaf)
            if tuple(leaf.shape) != shape:
                raise ValueError(
                    f"leaf shape {tuple(leaf.shape)} != packer "
                    f"template shape {shape}")
            if str(leaf.dtype) != dt:
                # a drifted dtype would silently promote its whole group
                # through jnp.concatenate — the exact corruption this
                # module exists to prevent
                raise ValueError(
                    f"leaf dtype {leaf.dtype} != packer template dtype {dt}")
            groups.setdefault(dt, []).append(jnp.ravel(leaf))
        return {dt: (jnp.concatenate(parts) if len(parts) > 1 else parts[0])
                for dt, parts in groups.items()}

    def unpack(self, vecs: Dict[str, jnp.ndarray]) -> Any:
        """Inverse of :meth:`pack` — bit-identical leaves, original tree."""
        leaves = []
        for dt, off, size, shape in self._slots:
            part = vecs[dt][off:off + size]  # static slice — XLA fuses it
            leaves.append(jnp.reshape(part, shape))
        return jax.tree.unflatten(self.treedef, leaves)

    def unpack_np(self, vecs: Dict[str, np.ndarray]) -> Any:
        """Host-side inverse of :meth:`pack` over already-fetched numpy
        buffers — pure views/reshapes, no device round-trip (the decode
        half of the one-transfer-per-round stats contract)."""
        leaves = []
        for dt, off, size, shape in self._slots:
            part = np.asarray(vecs[dt])[off:off + size]
            leaves.append(part.reshape(shape))
        return jax.tree.unflatten(self.treedef, leaves)

    def unpack_np_stacked(self, vecs: Dict[str, np.ndarray]) -> Any:
        """Like :meth:`unpack_np` but for buffers with a leading stack
        axis (``[R, n]``, e.g. a scanned multi-round program's per-round
        packed stats): each leaf comes back as ``[R, *slot_shape]``."""
        leaves = []
        for dt, off, size, shape in self._slots:
            arr = np.asarray(vecs[dt])
            leaves.append(arr[:, off:off + size].reshape(
                (arr.shape[0],) + shape))
        return jax.tree.unflatten(self.treedef, leaves)


def build_packer(template: Any) -> FlatPacker:
    return FlatPacker(template)


# ----------------------------------------------------------------------
# host->device input staging (the flatpack idea mirrored onto the
# dispatch path): the faithful round used to device_put ~8-10 small host
# arrays per dispatch (masks, ids, lrs, chaos vectors, feature grids) —
# `tools/dispatch_cost_probe.py` measured the per-buffer RPC cost that
# makes that expensive on a remote-attached chip.  These packers collapse
# the staging to ONE host buffer (and one `jax.device_put`) per dtype
# group; the unpack runs INSIDE the jitted round program as static
# slices/reshapes that XLA fuses away, so the math is bit-identical.
# ----------------------------------------------------------------------

def canonical_np(x) -> np.ndarray:
    """Host-side dtype canonicalization matching what ``jax.device_put``
    does under the default x64-disabled config (int64 -> int32,
    float64 -> float32) — packing must group by the dtype the device
    array will actually have, or the slot table mislabels groups."""
    arr = np.asarray(x)
    if arr.dtype == np.int64:
        return arr.astype(np.int32)
    if arr.dtype == np.float64:
        return arr.astype(np.float32)
    if arr.dtype == np.uint64:
        return arr.astype(np.uint32)
    return arr


class AxisPacker:
    """Pack a fixed-structure tree of host arrays that SHARE their leading
    axes (e.g. every per-round operand is ``[K, ...]`` or ``[R, K, ...]``)
    into one ``[*lead, total]`` buffer per dtype.

    Keeping the shared axes intact (instead of raveling to 1-D like
    :class:`FlatPacker`) is what lets the staged buffer carry a clients-
    axis sharding: the round program's inputs stay sharded over the mesh
    while still crossing the host boundary as one transfer per dtype.

    Two ways to the same bytes: :meth:`pack_np` concatenates a tree that
    already has the shared axes into fresh buffers; :meth:`pack_rounds_into`
    writes the rounds' own trees, one leaf at a time and each element
    once, into buffers the caller keeps (:meth:`for_rounds` builds the
    slot table of the stacked tree without stacking anything).
    """

    def __init__(self, template: Any, lead_ndim: int):
        self.lead_ndim = int(lead_ndim)
        leaves, treedef = jax.tree.flatten(template)
        self.treedef = treedef
        self.lead_shape = None
        #: per-leaf (dtype_str, offset, trailing_size, trailing_shape)
        self._slots: List[Tuple[str, int, int, Tuple[int, ...]]] = []
        sizes: Dict[str, int] = {}
        for leaf in leaves:
            arr = canonical_np(leaf)
            if arr.ndim < self.lead_ndim:
                raise ValueError(
                    f"AxisPacker leaf has {arr.ndim} dims, needs the "
                    f"{self.lead_ndim} shared leading axes")
            lead = tuple(arr.shape[:self.lead_ndim])
            if self.lead_shape is None:
                self.lead_shape = lead
            elif lead != self.lead_shape:
                raise ValueError(
                    f"AxisPacker leaves disagree on leading axes: "
                    f"{lead} != {self.lead_shape}")
            trailing = tuple(arr.shape[self.lead_ndim:])
            size = int(np.prod(trailing)) if trailing else 1
            dt = str(arr.dtype)
            off = sizes.get(dt, 0)
            self._slots.append((dt, off, size, trailing))
            sizes[dt] = off + size
        self.sizes = sizes

    @classmethod
    def for_rounds(cls, round_tree: Any, rounds: int) -> "AxisPacker":
        """The packer of ``rounds`` trees like ``round_tree`` (leaves
        ``[K, ...]``) stacked on a new leading axis, from one of them: the
        slot table, and so the ``signature``, of
        ``AxisPacker(stacked_tree, lead_ndim=2)``.  One round has no such
        axis: ``AxisPacker(round_tree, lead_ndim=1)``."""
        packer = cls(round_tree, lead_ndim=1)
        if rounds > 1:
            packer.lead_ndim = 2
            packer.lead_shape = (int(rounds),) + packer.lead_shape
        return packer

    @property
    def signature(self) -> Tuple:
        """Cache key for jitted unpackers: the full slot table."""
        return (self.lead_ndim, self.lead_shape, tuple(self._slots),
                self.treedef)

    def buffer_shapes(self) -> Dict[str, Tuple[int, ...]]:
        """``{dtype: [*lead, total]}`` of the groups that
        :meth:`pack_rounds_into` writes into a buffer.  A group of one
        leaf with no round axis to stack is not among them: there is no
        copy to make, and it is handed through as it is."""
        leaves: Dict[str, int] = {}
        for dt, _, _, _ in self._slots:
            leaves[dt] = leaves.get(dt, 0) + 1
        return {dt: self.lead_shape + (total,)
                for dt, total in self.sizes.items()
                if self.lead_ndim > 1 or leaves[dt] > 1}

    def pack_rounds_into(self, bufs: Dict[str, np.ndarray],
                         round_trees: list) -> Dict[str, np.ndarray]:
        """Write each round's leaves, once each, into their slots of
        ``bufs`` (one array per entry of :meth:`buffer_shapes`, of that
        shape and dtype; their contents are overwritten whole).  No
        stack, no concatenate, no allocation.  Returns the per-dtype
        dict to transfer: byte for byte what :meth:`pack_np` gives for
        the stacked tree."""
        stacked = self.lead_ndim > 1
        rounds = self.lead_shape[0] if stacked else 1
        lead = self.lead_shape[1:] if stacked else self.lead_shape
        if len(round_trees) != rounds:
            raise ValueError(
                f"{len(round_trees)} round trees != the packer's "
                f"{rounds} rounds")
        shapes = self.buffer_shapes()
        for dt, shape in shapes.items():
            buf = bufs.get(dt)
            if buf is None or buf.shape != shape or str(buf.dtype) != dt:
                raise ValueError(
                    f"staging buffer for {dt} is "
                    f"{None if buf is None else (buf.dtype, buf.shape)}, "
                    f"needs {shape}")
        out = {dt: bufs[dt] for dt in shapes}
        for r, tree in enumerate(round_trees):
            for arr, (dt, off, size, _) in self._checked(tree, lead):
                part = arr.reshape(lead + (size,))
                if dt not in shapes:
                    out[dt] = part
                    continue
                dest = out[dt][r] if stacked else out[dt]
                dest[..., off:off + size] = part
        return out

    def _checked(self, tree: Any, lead: Tuple[int, ...]):
        """The tree's leaves as the device will type them, each beside
        its slot; another structure, shape (``lead`` + the slot's) or
        dtype than the template's raises."""
        leaves, treedef = jax.tree.flatten(tree)
        if treedef != self.treedef or len(leaves) != len(self._slots):
            raise ValueError(
                f"tree structure {treedef} != packer template "
                f"{self.treedef}")
        for leaf, slot in zip(leaves, self._slots):
            arr = canonical_np(leaf)
            if tuple(arr.shape) != lead + slot[3]:
                raise ValueError(
                    f"leaf shape {arr.shape} != packer template "
                    f"{lead}+{slot[3]}")
            if str(arr.dtype) != slot[0]:
                raise ValueError(
                    f"leaf dtype {arr.dtype} != packer template dtype "
                    f"{slot[0]}")
            yield arr, slot

    def pack_np(self, tree: Any) -> Dict[str, np.ndarray]:
        """One fresh ``[*lead, total]`` numpy buffer per dtype from a
        tree that has the shared axes already (host-side: one
        concatenate a group in place of N per-leaf transfers)."""
        groups: Dict[str, list] = {}
        for arr, (dt, _, size, _) in self._checked(tree, self.lead_shape):
            groups.setdefault(dt, []).append(
                arr.reshape(self.lead_shape + (size,)))
        return {dt: (np.concatenate(parts, axis=-1) if len(parts) > 1
                     else parts[0])
                for dt, parts in groups.items()}

    def unpack(self, vecs: Dict[str, jnp.ndarray]) -> Any:
        """Traced inverse of :meth:`pack_np` — static last-axis slices +
        reshapes, fused away by XLA inside the round program."""
        leaves = []
        for dt, off, size, trailing in self._slots:
            part = vecs[dt][..., off:off + size]
            leaves.append(jnp.reshape(part, self.lead_shape + trailing))
        return jax.tree.unflatten(self.treedef, leaves)


class ScalarStager:
    """FlatPacker + host-side pack for the replicated scalar operands
    (lrs, round indices, thresholds): one tiny 1-D buffer per dtype."""

    def __init__(self, template: Any):
        self.packer = FlatPacker(jax.tree.map(canonical_np, template))

    @property
    def signature(self) -> Tuple:
        return (tuple(self.packer._slots), self.packer.treedef)

    def pack_np(self, tree: Any) -> Dict[str, np.ndarray]:
        leaves, treedef = jax.tree.flatten(jax.tree.map(canonical_np, tree))
        if treedef != self.packer.treedef:
            raise ValueError(
                f"tree structure {treedef} != stager template "
                f"{self.packer.treedef}")
        groups: Dict[str, list] = {}
        for leaf, (dt, _, _, shape) in zip(leaves, self.packer._slots):
            arr = np.asarray(leaf)
            if str(arr.dtype) != dt or tuple(arr.shape) != shape:
                raise ValueError(
                    f"leaf {arr.dtype}{tuple(arr.shape)} != template "
                    f"{dt}{shape}")
            groups.setdefault(dt, []).append(arr.ravel())
        return {dt: (np.concatenate(parts) if len(parts) > 1 else parts[0])
                for dt, parts in groups.items()}

    def unpack(self, vecs: Dict[str, jnp.ndarray]) -> Any:
        return self.packer.unpack(vecs)
