"""Synthetic federated token sequences from a seed, as the hdf5 user blob
the CLI's token tasks load (``users`` / ``num_samples`` /
``user_data/<user>/x``, no ``y``: the task shifts ``x`` for its targets).

A configuration's ``data`` block is its parameters: ``vocab``,
``samples_per_user``, ``len_min``, ``len_max``, ``noise`` and the user
counts ``train_users`` / ``val_users`` / ``test_users``.

- Ids lie in ``[1, vocab)``; 0 is the padding id of the packed batch
  (``featurize.pad_token_matrix``).  A row is written ``len_max`` wide
  with -1 beyond its length, which that function takes as padding.  A
  sliced vocabulary is a smaller ``vocab``.
- Lengths, the stated law: every user holds the same ``samples_per_user``
  lengths, evenly spaced over ``[len_min, len_max]`` and rounded
  (``len_min == len_max``: fixed length), in an order drawn from the
  seed.  So every seed, and every user, gives the same rows and the same
  real tokens, the same work, and every cohort holds a row of ``len_max``
  (a length bucket never crops).
- Ids, a first-order chain: the first id is uniform; each next id is
  ``successor[previous]`` (one seeded permutation of the ids, shared by
  the three splits) with probability ``1 - noise``, else uniform.  The
  next token is a function of the previous one up to that share of
  noise, so the loss can fall.
"""

from __future__ import annotations

import os

import numpy as np


def write_split(path: str, rng: np.random.Generator, successor: np.ndarray,
                users: int, lengths: np.ndarray, vocab: int,
                noise: float) -> None:
    import h5py
    samples, width = len(lengths), int(lengths.max())
    length = rng.permuted(np.tile(lengths, (users, 1)), axis=1)
    ids = np.empty((users, samples, width), np.int32)
    ids[..., 0] = rng.integers(1, vocab, size=(users, samples))
    for t in range(1, width):
        fresh = rng.integers(1, vocab, size=(users, samples))
        keep = rng.random((users, samples)) >= noise
        ids[..., t] = np.where(keep, successor[ids[..., t - 1]], fresh)
    ids[np.arange(width) >= length[..., None]] = -1
    names = [f"u{u:05d}" for u in range(users)]
    with h5py.File(path, "w") as fh:
        group = fh.create_group("user_data")
        for u, name in enumerate(names):
            group.create_group(name).create_dataset("x", data=ids[u])
        fh.create_dataset("users",
                          data=np.asarray(names, dtype=h5py.string_dtype()))
        fh.create_dataset("num_samples", data=np.full((users,), samples))


def write_splits(data_dir: str, seed: int, spec: dict) -> None:
    os.makedirs(data_dir, exist_ok=True)
    vocab = int(spec["vocab"])
    lengths = np.rint(np.linspace(
        int(spec["len_min"]), int(spec["len_max"]),
        int(spec["samples_per_user"]))).astype(np.int64)
    streams = np.random.SeedSequence(int(seed)).spawn(4)
    successor = np.zeros(vocab, np.int32)
    successor[1:] = 1 + np.random.default_rng(streams[0]).permutation(
        vocab - 1)
    for stream, split in zip(streams[1:], ("train", "val", "test")):
        write_split(os.path.join(data_dir, f"{split}.hdf5"),
                    np.random.default_rng(stream), successor,
                    int(spec[f"{split}_users"]), lengths, vocab,
                    float(spec["noise"]))
