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

import contextlib
import hashlib
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_synthetic_classification
from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.data import pack_round_batches
from msrflute_tpu.engine import OptimizationServer
from msrflute_tpu.engine.round import RoundEngine, StagingPool
from msrflute_tpu.models import make_task
from msrflute_tpu.strategies import select_strategy
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


# ----------------------------------------------------------------------
# the rounds' own trees written once into kept buffers
# ----------------------------------------------------------------------
def _round_trees(rounds, leaves, K=6, seed=0):
    """``rounds`` trees shaped like the engine's ``_round_tree``: with
    ``several`` leaves a group the fault vectors and the carry slots
    ride along (and the ids come as int64, as a sampler hands them
    over); with ``one`` each dtype group is a single leaf."""
    rng = np.random.default_rng(seed)

    def one_round():
        tree = {"arrays": {"x": rng.normal(
                    size=(K, 3, 4, 5)).astype(np.float32)},
                "client_ids": rng.integers(0, 99, K).astype(np.int32)}
        if leaves == "several":
            tree.update({
                "arrays": {"x": tree["arrays"]["x"],
                           "y": rng.integers(0, 9, (K, 3, 4)
                                             ).astype(np.int32)},
                "client_ids": rng.integers(0, 99, K),  # int64
                "sample_mask": rng.integers(0, 2, (K, 3, 4)
                                            ).astype(np.float32),
                "client_mask": np.ones(K, np.float32),
                "carry_slots": rng.integers(0, 32, K).astype(np.int32),
                "chaos": (rng.random(K).astype(np.float32),
                          np.full(K, 3.0, np.float32),
                          rng.integers(0, 4, K).astype(np.int32)),
            })
        return tree

    return [one_round() for _ in range(rounds)]


def _stacked(trees):
    return trees[0] if len(trees) == 1 else jax.tree.map(
        lambda *leaves: np.stack(leaves), *trees)


def _empty(shapes):
    return {dt: np.empty(shape, dt) for dt, shape in shapes.items()}


@pytest.mark.parametrize("leaves", ["one", "several"])
@pytest.mark.parametrize("rounds", [1, 5])
def test_pack_rounds_into_is_pack_np_of_the_stacked_tree(rounds, leaves):
    trees = _round_trees(rounds, leaves)
    stacked = _stacked(trees)
    old = AxisPacker(stacked, lead_ndim=2 if rounds > 1 else 1)
    want = old.pack_np(stacked)
    packer = AxisPacker.for_rounds(trees[0], rounds)
    # the same slot table: the jitted unpackers' cache key is the parent's
    assert packer.signature == old.signature
    shapes = packer.buffer_shapes()
    # a buffer is filled whole, whatever it held before
    bufs = {dt: np.full(shape, 7, dt) for dt, shape in shapes.items()}
    got = packer.pack_rounds_into(bufs, trees)
    assert sorted(got) == sorted(want) == ["float32", "int32"]
    for dt in want:
        assert got[dt].dtype == want[dt].dtype
        assert got[dt].shape == want[dt].shape
        assert got[dt].tobytes() == want[dt].tobytes()
    # the buffered groups come back as the very buffers handed in
    assert all(got[dt] is bufs[dt] for dt in shapes)
    if rounds == 1 and leaves == "one":
        # nothing to stack and nothing to concatenate: no buffer is
        # asked for and the leaf goes through as it is, as in pack_np
        assert shapes == {}
        assert np.shares_memory(got["int32"], trees[0]["client_ids"])
        assert np.shares_memory(got["float32"], trees[0]["arrays"]["x"])
        assert np.shares_memory(want["int32"], stacked["client_ids"])
    else:
        assert sorted(shapes) == ["float32", "int32"]
        assert not any(np.shares_memory(got[dt], leaf) for dt in got
                       for tree in trees for leaf in jax.tree.leaves(tree))
    # and the traced inverse gives the stacked tree back
    out = jax.jit(packer.unpack)({k: jnp.asarray(v) for k, v in got.items()})
    for a, b in zip(jax.tree.leaves(jax.tree.map(canonical_np, stacked)),
                    jax.tree.leaves(out)):
        assert np.array_equal(np.asarray(b), a)


@pytest.mark.parametrize("fault", [
    "rounds", "structure", "shape", "dtype", "buffer_shape",
    "buffer_dtype", "buffer_missing"])
def test_pack_rounds_into_refuses_what_pack_np_refuses(fault):
    trees = _round_trees(2, "several")
    packer = AxisPacker.for_rounds(trees[0], 2)
    bufs = _empty(packer.buffer_shapes())
    match = "!= packer template"
    if fault == "rounds":
        trees, match = trees[:1], "round trees"
    elif fault == "structure":
        trees[1] = {k: v for k, v in trees[1].items() if k != "chaos"}
        match = "structure"
    elif fault == "shape":
        trees[1]["sample_mask"] = np.zeros((6, 3, 5), np.float32)
    elif fault == "dtype":
        trees[1]["client_mask"] = np.ones(6, np.int32)
    elif fault == "buffer_shape":
        bufs["float32"], match = bufs["float32"][:1], "staging buffer"
    elif fault == "buffer_dtype":
        bufs["int32"] = bufs["int32"].astype(np.float32)
        match = "staging buffer"
    else:
        del bufs["int32"]
        match = "staging buffer"
    with pytest.raises(ValueError, match=match):
        packer.pack_rounds_into(bufs, trees)
    if fault in ("structure", "shape", "dtype"):
        # two such rounds, stacked, are refused by pack_np in the same
        # words
        good = AxisPacker(_stacked(_round_trees(2, "several")), lead_ndim=2)
        with pytest.raises(ValueError, match=match):
            good.pack_np(_stacked([trees[1], trees[1]]))


def test_staging_pool_keeps_only_the_last_dispatchs_shapes():
    pool = StagingPool(keep=2)
    shapes = {"float32": (2, 4, 9), "int32": (2, 4, 3)}
    first, reused = pool.take(shapes)
    assert not reused and first["float32"].shape == (2, 4, 9)
    assert first["int32"].dtype == np.int32
    pool.give(first)
    again, reused = pool.take(shapes)
    assert reused and all(again[dt] is first[dt] for dt in shapes)
    # no more than `keep` of a shape are held
    extra = [pool.take(shapes)[0] for _ in range(3)]
    for bufs in [again] + extra:
        pool.give(bufs)
    assert [len(free) for free in pool._free.values()] == [2, 2]
    # another signature: what the old one left is dropped, and a buffer
    # of the old one that comes back late is not kept either
    short = {"float32": (4, 9), "int32": (4, 3)}
    bufs, reused = pool.take(short)
    assert not reused and sorted(k[1] for k in pool._free) == [(4, 3), (4, 9)]
    pool.give(again)
    assert all(not free for free in pool._free.values())
    pool.give(bufs)
    assert pool.take(short)[1]


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


# ======================================================================
# the engine's kept buffers: taken for a dispatch, back at the fence of
# the chunk whose program read them
# ======================================================================
class _Spans:
    """A span factory that keeps what the engine's spans said."""

    def __init__(self):
        self.done = []

    @contextlib.contextmanager
    def __call__(self, name, **args):
        rec = {"name": name, **args}
        yield rec
        self.done.append(rec)

    def last(self, name):
        return [s for s in self.done if s["name"] == name][-1]


def _watch_takes(pool):
    """Every ``(bufs, reused)`` the pool hands out from now on."""
    taken, take = [], pool.take

    def watched(shapes):
        taken.append(take(shapes))
        return taken[-1]

    pool.take = watched
    return taken


def _engine(mesh, **cfg_over):
    cfg = _cfg(**cfg_over)
    task = make_task(cfg.model_config)
    return RoundEngine(task, cfg, select_strategy("fedavg")(cfg, None), mesh)


def _chunk(synth_dataset, rounds, seed):
    return [pack_round_batches(synth_dataset, [0, 1, 2, 3], 4, 3,
                               rng=np.random.default_rng(seed + i),
                               pad_clients_to=8)
            for i in range(rounds)]


def test_buffer_comes_back_at_its_chunks_fence_not_before(synth_dataset,
                                                          mesh8):
    engine = _engine(mesh8, depth=1)
    spans = engine.span_factory = _Spans()
    taken = _watch_takes(engine._staging)
    state = engine.init_state(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)

    def dispatch(state, seed):
        return engine.dispatch_rounds(
            state, _chunk(synth_dataset, 2, seed), [0.2] * 2, [1.0] * 2,
            rng)

    def same(a, b):
        return sorted(a) == sorted(b) and all(a[dt] is b[dt] for dt in a)

    # nothing kept yet: a fresh buffer a group, and the span says so
    state, stats_a = dispatch(state, 0)
    assert spans.last("stage_host")["reused"] is False
    assert spans.last("stage_host")["bytes"] == engine.last_staged_bytes
    assert sorted(taken[0][0]) == ["float32", "int32"]
    # chunk A was neither waited for nor fetched: its buffers are still
    # its program's to read, whatever is_ready() says
    stats_a.is_ready()
    state, stats_b = dispatch(state, 10)
    assert spans.last("stage_host")["reused"] is False
    assert not any(taken[1][0][dt] is taken[0][0][dt] for dt in taken[0][0])
    # waited for: A's buffers, and only they, may be written again
    stats_a.wait()
    assert stats_a.release is None
    state, stats_c = dispatch(state, 20)
    assert spans.last("stage_host")["reused"] is True
    assert same(taken[2][0], taken[0][0])
    # fetched (no wait first, the untraced path): B's come back
    out_b = stats_b.fetch()
    assert out_b["train_loss_sum"].shape == (2,)
    state, stats_d = dispatch(state, 30)
    assert spans.last("stage_host")["reused"] is True
    assert same(taken[3][0], taken[1][0])
    # a second fetch gives nothing back twice
    stats_a.fetch()
    stats_b.fetch()
    assert all(not free for free in engine._staging._free.values())
    stats_c.fetch()
    stats_d.fetch()
    assert [len(free) for free in engine._staging._free.values()] == [2, 2]


def test_one_round_dispatch_stages_in_a_kept_buffer_too(synth_dataset,
                                                        mesh8):
    engine = _engine(mesh8)
    spans = engine.span_factory = _Spans()
    state = engine.init_state(jax.random.PRNGKey(0))
    for i, want in enumerate([False, True, True]):
        state, stats = engine.run_round(
            state, _chunk(synth_dataset, 1, i)[0], 0.2, 1.0,
            jax.random.PRNGKey(i))
        assert spans.last("stage_host")["reused"] is want
        stats.fetch()
    # [K, total] buffers: no round axis on a one-round dispatch
    assert sorted(len(k[1]) for k in engine._staging._free) == [2, 2]


@pytest.mark.parametrize("rounds", [1, 5])
def test_staged_program_lowers_to_the_text_of_the_stacked_packer(
        synth_dataset, mesh8, rounds):
    """The round program is a function of the packer's slot table alone:
    built from one round's tree it lowers to the text the packer of the
    stacked tree gave (what every dispatch built before ISSUE 34)."""
    engine = _engine(mesh8, fuse=rounds)
    state = engine.init_state(jax.random.PRNGKey(0))
    batches = _chunk(synth_dataset, rounds, 0)
    ax_packer, stager, trees, sc_bufs, pool_args = \
        engine._stage_host(state, batches, [0.2] * rounds, [1.0] * rounds,
                           None, None, None)
    ax_bufs = ax_packer.pack_rounds_into(
        _empty(ax_packer.buffer_shapes()), trees)
    old = AxisPacker(_stacked(trees), lead_ndim=2 if rounds > 1 else 1)
    assert {dt: b.tobytes() for dt, b in ax_bufs.items()} == \
        {dt: b.tobytes() for dt, b in old.pack_np(_stacked(trees)).items()}

    def text(packer):
        lowered = engine._build_staged_fn(rounds, packer, stager).lower(
            state.params, state.opt_state, state.strategy_state, ax_bufs,
            sc_bufs, jax.random.PRNGKey(1), *pool_args)
        return hashlib.sha256(lowered.as_text().encode()).hexdigest()

    assert text(ax_packer) == text(old)


def _train(fuse, max_iteration, keep=None, depth=1):
    cfg = _cfg(depth=depth, fuse=fuse, max_iteration=max_iteration)
    ds = make_synthetic_classification()
    task = make_task(cfg.model_config)
    with tempfile.TemporaryDirectory() as tmp:
        server = OptimizationServer(task, cfg, ds, model_dir=tmp, seed=7)
        if keep is not None:
            server.engine._staging = StagingPool(keep)
        seen = _watch_takes(server.engine._staging)
        state = server.train()
        params = [np.asarray(leaf) for leaf in
                  jax.tree.leaves(jax.device_get(state.params))]
        return params, seen, server.engine._staging


def test_shorter_last_chunk_drops_the_old_buffers_same_bits():
    # chunks of 2, 2, 2 and 1 rounds: the last one's buffers have no
    # round axis, and what the two-round chunks left goes
    params, seen, pool = _train(fuse=2, max_iteration=7)
    assert [bufs["float32"].ndim for bufs, _ in seen] == [3, 3, 3, 2]
    assert [reused for _, reused in seen] == [False, False, True, False]
    assert all(len(key[1]) == 2 for key in pool._free)
    # a pool that keeps nothing is the parent's fresh buffer a dispatch
    fresh, seen_fresh, _ = _train(fuse=2, max_iteration=7, keep=0)
    assert not any(reused for _, reused in seen_fresh)
    for a, b in zip(params, fresh):
        assert a.tobytes() == b.tobytes()
