"""The async ``latest`` writer's device snapshot (ISSUE 25): one device
program over the whole payload, whatever the leaf count, that really
copies, keeps each leaf's sharding, and leaves the single-slot contract
as it was.

Why one program: a runtime lets only so many programs be in flight (32
here).  A copy per leaf on a 62-leaf model made the 32nd copy wait for
the running round program, so the host sat in the pre-dispatch submit
instead of staging the next chunk.
"""

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from msrflute_tpu.engine import checkpoint as ckpt_mod
from msrflute_tpu.engine.checkpoint import CheckpointManager
from msrflute_tpu.engine.round import ServerState

N_LEAVES = 70  # more than the 32 programs a runtime keeps in flight


def _state(round_no=1, host_leaf=None, scale=1.0):
    params = {f"layer{i:02d}": {"w": jnp.full((4, 3), scale * (i + 1.0)),
                                "b": jnp.full((3,), -scale * (i + 1.0))}
              for i in range(N_LEAVES // 2)}
    sstate = {} if host_leaf is None else {"residual": host_leaf}
    return ServerState(params=params,
                       opt_state={"count": jnp.asarray(round_no, jnp.int32)},
                       strategy_state=sstate, round=round_no)


def _parked(tmp_path):
    """A manager whose writer never starts: a submit parks its snapshot
    in the mailbox, where it can be looked at."""
    mgr = CheckpointManager(str(tmp_path), backend="msgpack",
                            async_latest=True)
    mgr._mp_worker = threading.current_thread()
    return mgr


def _pointers(tree):
    return {shard.data.unsafe_buffer_pointer()
            for leaf in jax.tree.leaves(tree) if isinstance(leaf, jax.Array)
            for shard in leaf.addressable_shards}


def test_snapshot_is_one_program_whatever_the_leaf_count(tmp_path,
                                                         monkeypatch):
    eager, traced, programs = [], [], []
    real_copy = jnp.copy

    def counting_copy(x, *a, **k):
        (traced if isinstance(x, jax.core.Tracer) else eager).append(x.shape)
        return real_copy(x, *a, **k)

    monkeypatch.setattr(ckpt_mod.jnp, "copy", counting_copy)
    # the same body under a jit of this test's own (a new function, so
    # a new cache): the test sees its one trace whatever ran before
    body = ckpt_mod._copy_device_leaves.__wrapped__
    fresh = jax.jit(lambda leaves: body(leaves))

    def counting_program(leaves):
        programs.append(len(leaves))
        return fresh(leaves)

    monkeypatch.setattr(ckpt_mod, "_copy_device_leaves", counting_program)

    mgr = _parked(tmp_path)
    state = _state()
    n_device = len(jax.tree.leaves(ckpt_mod._payload(state))) - 1  # round
    assert n_device == N_LEAVES + 1 >= 64

    launched = mgr._mp_submit(state)
    assert launched == {"leaves": n_device, "programs": 1}
    assert programs == [n_device]
    assert not eager, "a copy per leaf was dispatched outside the program"
    assert len(traced) == n_device  # the one trace of the one program

    # same structure again: the compiled program is reused as it is
    mgr._mp_mailbox = None
    assert mgr._mp_submit(_state(2, scale=2.0)) == launched
    assert programs == [n_device, n_device]
    assert not eager and len(traced) == n_device
    assert fresh._cache_size() == 1


def test_submit_does_not_wait_for_a_running_program(tmp_path):
    """The regression itself, without a clock: with a long program in
    flight, the submit of a 70-leaf state comes back while that program
    is still running.  A copy per leaf came back only after it."""

    @jax.jit
    def long_program(x):
        return jax.lax.fori_loop(
            0, 1500, lambda i, a: jnp.tanh(a @ a) * 0.5 + a * 0.5, x)

    x = jnp.eye(384) * 0.5
    long_program(x).block_until_ready()        # compiled before the race
    mgr = _parked(tmp_path)
    mgr._mp_submit(_state())                   # the snapshot program too
    mgr._mp_mailbox = None
    # built before the race: making 70 leaves is 70 programs of its own
    state = jax.block_until_ready(_state(2))

    running = long_program(x)
    assert not running.is_ready(), "the long program is too short here"
    mgr._mp_submit(state)
    still_running = not running.is_ready()
    running.block_until_ready()
    assert still_running, \
        "the submit returned only after the program in flight had retired"


def test_snapshot_survives_the_donating_step_bit_for_bit(tmp_path):
    host_leaf = np.arange(8, dtype=np.float32)
    state = _state(round_no=3, host_leaf=host_leaf)
    # the same values from a second, independent state: a device_get of
    # `state` itself would pin its buffers on the CPU and stop the donation
    before = jax.device_get(ckpt_mod._payload(
        _state(round_no=3, host_leaf=host_leaf.copy())))

    mgr = _parked(tmp_path)
    mgr._mp_submit(state)
    snap = mgr._mp_mailbox
    # fresh buffers: none of the snapshot's is one of the state's
    assert len(_pointers(snap)) == len(_pointers(state.params)) + 1
    assert not _pointers(snap) & _pointers(ckpt_mod._payload(state))
    assert snap["strategy_state"]["residual"] is not host_leaf
    assert snap["round"] == 3 and isinstance(snap["round"], int)

    # the next round step donates the live buffers and the training
    # thread mutates its host leaf in place
    step = jax.jit(lambda tree: jax.tree.map(lambda a: a + 1, tree),
                   donate_argnums=0)
    donated = (state.params, state.opt_state)
    jax.block_until_ready(step(donated))
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(donated))
    host_leaf[:] = -1.0

    # only now does the writer run: what it writes is the pre-donation
    # state, bit for bit, numpy leaf included
    mgr._mp_worker = threading.Thread(target=mgr._mp_loop, daemon=True,
                                      name="ckpt-latest-writer")
    mgr._mp_worker.start()
    mgr.wait()
    restored = mgr.load(_state(round_no=0, host_leaf=np.zeros(8, np.float32),
                               scale=0.0))
    assert restored is not None and restored.round == 3
    got = jax.device_get(ckpt_mod._payload(restored))
    flat_before, tree_before = jax.tree.flatten(before)
    flat_got, tree_got = jax.tree.flatten(got)
    assert tree_before == tree_got
    for want, have in zip(flat_before, flat_got):
        np.testing.assert_array_equal(np.asarray(want), np.asarray(have))


def test_snapshot_keeps_each_leafs_sharding(tmp_path, mesh8):
    from jax.sharding import NamedSharding, PartitionSpec as P
    replicated = NamedSharding(mesh8, P())
    split = NamedSharding(mesh8, P(mesh8.axis_names[0]))
    state = ServerState(
        params={"w": jax.device_put(jnp.ones((8, 4)), replicated)},
        opt_state={},
        strategy_state={"pool": jax.device_put(
            jnp.arange(64.0).reshape(16, 4), split)},
        round=1)
    mgr = _parked(tmp_path)
    assert mgr._mp_submit(state) == {"leaves": 2, "programs": 1}
    snap = mgr._mp_mailbox
    for live, copy in ((state.params["w"], snap["params"]["w"]),
                       (state.strategy_state["pool"],
                        snap["strategy_state"]["pool"])):
        assert copy.sharding.is_equivalent_to(live.sharding, live.ndim)
        np.testing.assert_array_equal(np.asarray(copy), np.asarray(live))
    assert not _pointers(snap) & _pointers(ckpt_mod._payload(state))


def test_state_without_device_leaves_launches_nothing(tmp_path):
    mgr = _parked(tmp_path)
    state = ServerState(params={"w": np.ones(3, np.float32)}, opt_state={},
                        strategy_state={}, round=4)
    assert mgr._mp_submit(state) == {"leaves": 0, "programs": 0}
    assert mgr._mp_mailbox["params"]["w"] is not state.params["w"]


def test_second_submit_waits_for_the_busy_writer_before_it_copies(
        tmp_path, monkeypatch):
    """The single-slot contract is as it was: while the writer is busy a
    second submit blocks, and it takes its device snapshot only once the
    writer is idle — at most one extra copy of the state in HBM."""
    gate, entered = threading.Event(), threading.Event()
    real_write = CheckpointManager._write_blob

    def gated_write(self, path, blob, keep_prev=False):
        entered.set()
        assert gate.wait(timeout=60), "test gate never opened"
        return real_write(self, path, blob, keep_prev=keep_prev)

    monkeypatch.setattr(CheckpointManager, "_write_blob", gated_write)
    programs = []
    real_program = ckpt_mod._copy_device_leaves

    def counting_program(leaves):
        programs.append(threading.current_thread().name)
        return real_program(leaves)

    monkeypatch.setattr(ckpt_mod, "_copy_device_leaves", counting_program)

    mgr = CheckpointManager(str(tmp_path), backend="msgpack",
                            async_latest=True)
    mgr.save_latest(_state(1))
    assert entered.wait(timeout=60), "writer thread never started the save"
    assert len(programs) == 1

    done = threading.Event()
    second = threading.Thread(
        target=lambda: (mgr.save_latest(_state(2, scale=2.0)), done.set()),
        name="second-submit", daemon=True)
    second.start()
    assert not done.wait(timeout=0.3), \
        "second submit returned while the first save was still in flight"
    assert len(programs) == 1, "snapshot taken before the writer was idle"

    gate.set()
    assert done.wait(timeout=60), "second submit never unblocked"
    second.join(timeout=60)
    assert not second.is_alive()
    mgr.wait()
    assert programs == ["MainThread", "second-submit"]
    restored = mgr.load(_state(0, scale=0.0))
    assert restored is not None and restored.round == 2
    np.testing.assert_array_equal(
        np.asarray(restored.params["layer00"]["w"]), 2.0)


def test_writer_asks_for_the_transfer_only_once_the_snapshot_is_computed(
        tmp_path, monkeypatch):
    """A ``device_get`` on arrays still to be computed queues its
    transfers to fire at the running program's end, where they got ahead
    of the training thread's stats fetch: the writer waits first."""

    @jax.jit
    def long_program(x):
        return jax.lax.fori_loop(
            0, 1500, lambda i, a: jnp.tanh(a @ a) * 0.5 + a * 0.5, x)

    x = jnp.eye(384) * 0.5
    long_program(x).block_until_ready()
    ready_when_fetched = []
    real_chunks = ckpt_mod._state_chunks

    def watching_chunks(tree):
        # where the writer starts every leaf's transfer to the host
        ready_when_fetched.append(all(
            leaf.is_ready() for leaf in jax.tree.leaves(tree)
            if isinstance(leaf, jax.Array)))
        return real_chunks(tree)

    monkeypatch.setattr(ckpt_mod, "_state_chunks", watching_chunks)
    mgr = CheckpointManager(str(tmp_path), backend="msgpack",
                            async_latest=True)
    # the state is what the program in flight is still computing
    state = ServerState(params={"w": long_program(x)}, opt_state={},
                        strategy_state={}, round=1)
    assert not state.params["w"].is_ready(), "the program is too short here"
    mgr.save_latest(state)
    mgr.wait()
    assert ready_when_fetched == [True]


def test_a_best_model_save_is_durable_on_return_beside_a_busy_writer(
        tmp_path, monkeypatch):
    """An evaluation round saves its state as the best model and then as
    ``latest``.  The best-model save runs on the caller's thread, also
    with the async writer, and is on the disk when it returns, while the
    writer is still inside an earlier round's ``latest``: the status log
    that names the new best value is written next.  The ``latest`` of
    that state is a link to the file, made in its turn: it waits for the
    earlier ``latest`` to land, then rotates it to ``.prev``."""
    gate, entered = threading.Event(), threading.Event()
    real_write = CheckpointManager._write_blob

    def gated_write(self, path, blob, keep_prev=False):
        if threading.current_thread().name == "ckpt-latest-writer":
            entered.set()
            assert gate.wait(timeout=60), "test gate never opened"
        return real_write(self, path, blob, keep_prev=keep_prev)

    monkeypatch.setattr(CheckpointManager, "_write_blob", gated_write)
    programs = []
    real_program = ckpt_mod._copy_device_leaves
    monkeypatch.setattr(
        ckpt_mod, "_copy_device_leaves",
        lambda leaves: programs.append(len(leaves)) or real_program(leaves))

    mgr = CheckpointManager(str(tmp_path), backend="msgpack",
                            async_latest=True)
    mgr.save_latest(_state(3))
    assert entered.wait(timeout=60), "the writer never started the save"
    state = _state(4, scale=2.0)
    written = mgr.save_best(state, "loss", "acc")
    template = _state(0, scale=0.0)
    for name in ("loss", "acc"):  # durable now, the writer still busy
        assert os.path.exists(os.path.join(
            str(tmp_path), f"best_val_{name}_model.msgpack.sum"))
    assert len(programs) == 1  # no snapshot program for the best model

    linked = threading.Event()
    link = threading.Thread(
        target=lambda: (mgr.save_latest(state, same_as=written),
                        linked.set()), name="link-latest")
    link.start()
    assert not linked.wait(timeout=0.5), \
        "the link did not wait for the earlier latest to land"
    gate.set()
    assert linked.wait(timeout=60)
    link.join(timeout=60)
    assert not link.is_alive()
    assert len(programs) == 1
    for name in ("loss", "acc"):
        assert mgr.load_best(template, name).round == 4
    assert mgr.load(template).round == 4
    assert mgr.load(template, ckpt_mod.LATEST_PREV).round == 3
