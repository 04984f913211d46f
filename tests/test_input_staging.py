"""Single-buffer input staging (PR 6) — packers and the transfer-count
guard.

The dispatch half of the flatpack idea: per-round host inputs (feature/
index grids, masks, ids, chaos vectors, lr/round scalars) cross the
host->device boundary as ONE staged buffer per dtype group
(``utils/flatpack.py`` ``AxisPacker``/``ScalarStager``) instead of the
~8-10 per-leaf ``device_put``s the faithful dispatch used to pay
(``tools/dispatch_cost_probe.py``).  The unpack runs inside the jitted
round program as static slices XLA fuses away.  CPU-safe: the transfer
count is counted by intercepting ``jax.device_put`` itself.  That the
staged dispatch computes the right round is held by an independent plain
reference in ``tests/benchmarks/`` and by single- against multi-round
dispatch in ``tests/test_multi_round.py``.
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_synthetic_classification
from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.engine import OptimizationServer
from msrflute_tpu.models import make_task
from msrflute_tpu.utils.flatpack import AxisPacker, ScalarStager, canonical_np


# ======================================================================
# packer unit math
# ======================================================================
def test_axis_packer_round_trip_is_bit_identical():
    rng = np.random.default_rng(0)
    tree = {
        "grid": rng.normal(size=(4, 3, 5)).astype(np.float32),
        "mask": rng.integers(0, 2, (4, 7)).astype(np.float32),
        "ids": np.arange(4, dtype=np.int32),
        "extra": (rng.integers(0, 9, (4, 2)).astype(np.int32),),
    }
    packer = AxisPacker(tree, lead_ndim=1)
    bufs = packer.pack_np(tree)
    # one buffer per dtype group, leading axis preserved
    assert sorted(bufs) == ["float32", "int32"]
    assert all(b.shape[0] == 4 for b in bufs.values())
    out = jax.jit(packer.unpack)({k: jnp.asarray(v)
                                  for k, v in bufs.items()})
    flat_in = jax.tree.leaves(tree)
    flat_out = jax.tree.leaves(out)
    for a, b in zip(flat_in, flat_out):
        assert np.array_equal(np.asarray(b), a)


def test_axis_packer_refuses_mismatched_leading_axes_and_structure():
    tree = {"a": np.zeros((4, 2), np.float32),
            "b": np.zeros((3, 2), np.float32)}
    with pytest.raises(ValueError, match="leading axes"):
        AxisPacker(tree, lead_ndim=1)
    good = {"a": np.zeros((4, 2), np.float32)}
    packer = AxisPacker(good, lead_ndim=1)
    with pytest.raises(ValueError, match="structure"):
        packer.pack_np({"renamed": np.zeros((4, 2), np.float32)})
    with pytest.raises(ValueError, match="!= packer template"):
        packer.pack_np({"a": np.zeros((4, 3), np.float32)})


def test_scalar_stager_groups_scalars_per_dtype():
    tree = {"lr": np.float32(0.1), "round": np.int32(7),
            "quant": np.float32(-1.0)}
    stager = ScalarStager(tree)
    bufs = stager.pack_np(tree)
    assert sorted(bufs) == ["float32", "int32"]
    assert bufs["float32"].shape == (2,)
    out = stager.unpack({k: jnp.asarray(v) for k, v in bufs.items()})
    assert float(out["lr"]) == np.float32(0.1)
    assert int(out["round"]) == 7
    assert float(out["quant"]) == -1.0


def test_canonical_np_matches_device_dtype_demotion():
    # packing groups by the dtype the DEVICE array will have; x64 host
    # dtypes demote exactly like jax.device_put under default config
    assert canonical_np(np.arange(3)).dtype == np.int32
    assert canonical_np(np.zeros(3)).dtype == np.float32
    assert canonical_np(np.zeros(3, np.float32)).dtype == np.float32


# ======================================================================
# server fixtures
# ======================================================================
def _cfg(depth=1, chaos=False, fuse=1, max_iteration=4):
    sc = {
        "max_iteration": max_iteration, "num_clients_per_iteration": 4,
        "initial_lr_client": 0.2, "pipeline_depth": depth,
        "rounds_per_step": fuse,
        "val_freq": 100, "initial_val": False,
        "optimizer_config": {"type": "sgd", "lr": 1.0},
        "data_config": {"val": {"batch_size": 8}},
    }
    if chaos:
        sc["chaos"] = {"enable": True, "seed": 3, "dropout_rate": 0.25,
                       "straggler_rate": 0.25}
    return FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": "fedavg",
        "server_config": sc,
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.2},
            "data_config": {"train": {"batch_size": 4}}},
    })


# ======================================================================
# the dispatch-cost regression guard (tier-1): intercept jax.device_put
# around the engine's dispatch and pin the one-staged-buffer-per-dtype
# contract
# ======================================================================
class _PutCounter:
    """Counts ``jax.device_put`` calls + staged leaves while armed."""

    def __init__(self, monkeypatch):
        self.calls = 0
        self.leaves = 0
        self.dtypes = []
        self.armed = False
        real = jax.device_put

        def counting(x, *args, **kwargs):
            if self.armed:
                self.calls += 1
                for leaf in jax.tree.leaves(x):
                    self.leaves += 1
                    self.dtypes.append(str(np.asarray(leaf).dtype))
            return real(x, *args, **kwargs)

        monkeypatch.setattr(jax, "device_put", counting)

    def arm_dispatch(self, engine):
        """Count only inside the engine's dispatch window."""
        orig = engine.dispatch_rounds

        def wrapped(*args, **kwargs):
            self.armed = True
            try:
                return orig(*args, **kwargs)
            finally:
                self.armed = False

        engine.dispatch_rounds = wrapped


def _dispatch_counts(monkeypatch, chaos=False, fuse=1):
    cfg = _cfg(chaos=chaos, fuse=fuse, max_iteration=2 * fuse)
    ds = make_synthetic_classification()
    task = make_task(cfg.model_config)
    counter = _PutCounter(monkeypatch)
    with tempfile.TemporaryDirectory() as tmp:
        server = OptimizationServer(task, cfg, ds, model_dir=tmp, seed=7)
        counter.arm_dispatch(server.engine)
        server.train()
        return counter, server.engine


def test_staged_dispatch_pays_one_buffer_per_dtype_group(monkeypatch):
    counter, engine = _dispatch_counts(monkeypatch)
    n_dispatches = 2
    # two put CALLS per dispatch (clients-axis groups, scalar groups) —
    # each on a whole per-dtype dict
    assert counter.calls == 2 * n_dispatches
    # ... and one staged BUFFER per dtype group: the LR protocol stages
    # float32+int32 on the clients axis and float32+int32 scalars
    per_dispatch = counter.leaves // n_dispatches
    assert per_dispatch == 4
    assert engine.last_dispatch_puts == per_dispatch
    assert engine.last_staged_bytes > 0


def test_staged_dispatch_chaos_rides_existing_dtype_groups(monkeypatch):
    # chaos fault vectors are f32/int32 — they merge into the existing
    # groups, so the transfer count does NOT grow with the fault streams
    counter, engine = _dispatch_counts(monkeypatch, chaos=True)
    assert counter.leaves // 2 == 4
    assert counter.calls == 4
