"""Test harness: force an 8-device virtual CPU mesh before jax imports.

Mirrors the reference's testing philosophy (``testing/README.md:3``: tiny
dummy data, exercise the machinery not the accuracy) — but with unit tests
per layer, which the reference lacks (SURVEY.md §4).  Multi-chip sharding is
exercised on ``xla_force_host_platform_device_count=8`` virtual devices.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the shared virtual-CPU-mesh set-up (also used by __graft_entry__.py
# and the CPU-only tools)
from msrflute_tpu.utils.backend import force_cpu_backend  # noqa: E402

force_cpu_backend(8)

import jax  # noqa: E402

assert all(d.platform == "cpu" for d in jax.devices()), jax.devices()
assert len(jax.devices()) == 8, jax.devices()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from msrflute_tpu.parallel import make_mesh
    return make_mesh()


def make_synthetic_classification(num_users=16, samples_lo=6, samples_hi=24,
                                  dim=8, num_classes=4, seed=0):
    """Tiny linearly-separable federated dataset (the unit-test analogue of
    reference ``testing/create_data.py``)."""
    from msrflute_tpu.data import ArraysDataset

    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(dim, num_classes))
    users, per_user, counts = [], [], []
    for u in range(num_users):
        n = int(rng.integers(samples_lo, samples_hi + 1))
        x = rng.normal(size=(n, dim)).astype(np.float32)
        y = np.argmax(x @ w_true + 0.1 * rng.normal(size=(n, num_classes)),
                      axis=-1).astype(np.int32)
        users.append(f"user{u:03d}")
        per_user.append({"x": x, "y": y})
        counts.append(n)
    return ArraysDataset(users, per_user, counts)


@pytest.fixture(scope="session")
def synth_dataset():
    return make_synthetic_classification()


def pytest_configure(config):
    # tier-1 CI runs `-m 'not slow'` under a hard wall-clock budget
    # (ROADMAP.md); heavyweight end-to-end/training tests carry this
    # marker so the default selection stays inside it on small hosts
    config.addinivalue_line(
        "markers",
        "slow: heavyweight e2e/accuracy tests excluded from tier-1")
    # under `--dist loadfile` xdist queues the files by their number of
    # cases, most first, which says nothing of their time: the file that
    # is most of tier-1's limit in one worker
    # (tests/benchmarks/test_benchmark_harness.py, 9 cases) then starts
    # minutes in, behind every file with more cases, and the whole run
    # is its start plus its length.  Off, the queue is the collection
    # order, in which that file is among the first.  The option exists
    # only where xdist is loaded, hence the hasattr.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
