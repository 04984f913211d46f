"""Synthetic federated image data from a seed, as an hdf5 user blob the
CLI loads (``users`` / ``num_samples`` / ``user_data/<user>/{x,y}``).

One general generator; a configuration's ``data`` block is its
parameters.  Images are ``scale`` x (class prototype + ``noise`` x
standard normal), so the label is a function of the image and the loss
can fall.  Every user holds the same number of samples, so every seed
gives the same amount of work: only the pixels, the labels and the
prototypes change with the seed.
"""

from __future__ import annotations

import os

import numpy as np


def write_split(path: str, rng: np.random.Generator, prototypes: np.ndarray,
                users: int, samples: int, shape: tuple, scale: float,
                noise: float) -> None:
    import h5py
    classes, dim = prototypes.shape
    y = rng.integers(0, classes, size=(users, samples))
    x = rng.standard_normal((users, samples, dim), dtype=np.float32)
    x *= np.float32(noise)
    x += prototypes[y]
    x *= np.float32(scale)
    x = x.reshape((users, samples) + tuple(shape))
    names = [f"u{u:05d}" for u in range(users)]
    with h5py.File(path, "w") as fh:
        group = fh.create_group("user_data")
        for u, name in enumerate(names):
            user = group.create_group(name)
            user.create_dataset("x", data=x[u])
            user.create_dataset("y", data=y[u].astype(np.int64))
        fh.create_dataset("users",
                          data=np.asarray(names, dtype=h5py.string_dtype()))
        fh.create_dataset("num_samples", data=np.full((users,), samples))


def write_splits(data_dir: str, seed: int, spec: dict) -> None:
    """``spec``: ``shape``, ``classes``, ``samples_per_user``, ``scale``,
    ``noise`` and the user counts ``train_users`` / ``val_users`` /
    ``test_users``."""
    os.makedirs(data_dir, exist_ok=True)
    shape = tuple(spec["shape"])
    streams = np.random.SeedSequence(int(seed)).spawn(4)
    prototypes = np.random.default_rng(streams[0]).standard_normal(
        (int(spec["classes"]), int(np.prod(shape))), dtype=np.float32)
    for stream, split in zip(streams[1:], ("train", "val", "test")):
        write_split(os.path.join(data_dir, f"{split}.hdf5"),
                    np.random.default_rng(stream), prototypes,
                    int(spec[f"{split}_users"]),
                    int(spec["samples_per_user"]), shape,
                    float(spec["scale"]), float(spec["noise"]))
