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


class _Spans:
    """The two calls the manager makes on its telemetry scope; keeps
    what it was given."""

    def __init__(self):
        self.spans, self.events = [], []

    def span(self, name, **args):
        import contextlib

        @contextlib.contextmanager
        def record():
            span = {"name": name, "thread": threading.current_thread().name,
                    **args}
            self.spans.append(span)
            yield span

        return record()

    def event(self, name, **args):
        self.events.append((name, args))

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]


def _gate_best_writes(monkeypatch):
    """The writer stops at the door of every best-model file until the
    gate opens; says when it got there."""
    gate, entered = threading.Event(), threading.Event()
    real_write = CheckpointManager._write_blob

    def gated_write(self, path, blob, keep_prev=False):
        if threading.current_thread().name == "ckpt-latest-writer" and \
                "best_val_" in os.path.basename(path):
            entered.set()
            assert gate.wait(timeout=60), "test gate never opened"
        return real_write(self, path, blob, keep_prev=keep_prev)

    monkeypatch.setattr(CheckpointManager, "_write_blob", gated_write)
    return gate, entered


def test_a_best_model_save_rides_the_writer_and_lands_before_any_name(
        tmp_path, monkeypatch):
    """With the async writer a best-model save is a device snapshot in
    the writer's slot: ``save_best`` returns at once, with nothing on
    the disk.  Whoever writes a name for that state calls ``land_best``
    first, which waits for the file (a ``ckpt_wait`` span that says it
    waited for the best model) and links the other metric names to it;
    the ``latest`` of that state is then a link to the file."""
    gate, entered = _gate_best_writes(monkeypatch)
    programs = []
    real_program = ckpt_mod._copy_device_leaves
    monkeypatch.setattr(
        ckpt_mod, "_copy_device_leaves",
        lambda leaves: programs.append(len(leaves)) or real_program(leaves))

    mgr = CheckpointManager(str(tmp_path), backend="msgpack",
                            async_latest=True)
    mgr.telemetry = spans = _Spans()
    mgr.save_latest(_state(3))
    state = _state(4, scale=2.0)
    written = mgr.save_best(state, "loss", "acc")
    assert written == os.path.join(str(tmp_path),
                                   "best_val_loss_model.msgpack")
    assert len(programs) == 2  # the latest's snapshot, then the best's
    assert entered.wait(timeout=60), "the writer never reached the file"
    assert not os.path.exists(written)

    landed = []
    waiter = threading.Thread(target=lambda: landed.append(mgr.land_best()),
                              name="land-best")
    waiter.start()
    waiter.join(timeout=0.5)
    assert waiter.is_alive() and not landed, \
        "land_best returned while the file was still with the writer"
    gate.set()
    waiter.join(timeout=60)
    assert not waiter.is_alive() and landed == [True]
    for name in ("loss", "acc"):
        assert os.path.samefile(written, os.path.join(
            str(tmp_path), f"best_val_{name}_model.msgpack"))
        assert os.path.exists(os.path.join(
            str(tmp_path), f"best_val_{name}_model.msgpack.sum"))
    waits = [s for s in spans.named("ckpt_wait") if s["thread"] == "land-best"]
    assert [s["for"] for s in waits] == ["best"]
    writes = spans.named("ckpt_async_write")
    assert [s["file"] for s in writes] == [ckpt_mod.LATEST,
                                          "best_val_loss_model.msgpack"]
    assert all(s["bytes"] > 0 for s in writes)

    mgr.save_latest(state, same_as=written)
    assert len(programs) == 2
    assert os.path.samefile(written, os.path.join(str(tmp_path),
                                                  ckpt_mod.LATEST))
    template = _state(0, scale=0.0)
    for name in ("loss", "acc"):
        assert mgr.load_best(template, name).round == 4
    assert mgr.load(template).round == 4
    assert mgr.load(template, ckpt_mod.LATEST_PREV).round == 3


@pytest.mark.parametrize("reader", ["load_best", "backup", "wait"])
def test_whoever_reads_the_files_sees_the_submitted_best_landed(
        tmp_path, monkeypatch, reader):
    """``fall_back_to_best`` (``load_best``), a backup copy and the exit
    wait, each right after a best-model save was handed to a slow
    writer: every one of them finds the new file, whole, links made."""
    import time
    real_write = CheckpointManager._write_blob

    def slow_write(self, path, blob, keep_prev=False):
        if threading.current_thread().name == "ckpt-latest-writer":
            time.sleep(0.3)
        return real_write(self, path, blob, keep_prev=keep_prev)

    monkeypatch.setattr(CheckpointManager, "_write_blob", slow_write)
    mgr = CheckpointManager(str(tmp_path), backend="msgpack",
                            async_latest=True, backup_freq=2)
    mgr.save_best(_state(2), "loss", "acc")
    mgr.land_best()
    mgr.save_best(_state(4, scale=2.0), "loss", "acc")
    template = _state(0, scale=0.0)
    if reader == "load_best":
        assert mgr.load_best(template, "acc").round == 4
    elif reader == "backup":
        mgr.backup(_state(4, scale=2.0), 4, best_names=("loss", "acc"))
        copy = mgr.load(template, "best_val_acc_model_epoch4.msgpack")
        assert copy is not None and copy.round == 4
    else:
        mgr.wait()
        assert not mgr._mp_busy and mgr._best_pending is None
    for name in ("loss", "acc"):
        path = os.path.join(str(tmp_path), f"best_val_{name}_model.msgpack")
        assert ckpt_mod._state_from_bytes(
            open(path, "rb").read(), template).round == 4


@pytest.mark.parametrize("threshold", [1, 3], ids=["abort", "counted"])
def test_a_failed_best_model_write_surfaces_on_the_calling_thread(
        tmp_path, threshold):
    """The writer never raises: a best-model write that failed all its
    attempts is counted, ``land_best`` says so on the training thread
    (and aborts there once the run's budget of failures is spent), and
    nothing is linked to a file that is not there."""
    from msrflute_tpu.resilience.integrity import (CheckpointEscalationError,
                                                   RetryPolicy)

    def always():
        raise OSError("injected")

    mgr = CheckpointManager(
        str(tmp_path), backend="msgpack", async_latest=True, io_fault=always,
        retry=RetryPolicy(retries=2, backoff_base_s=0.0, backoff_max_s=0.0,
                          jitter=0.0, escalation_threshold=threshold))
    state = _state(5)
    written = mgr.save_best(state, "loss", "acc")
    if threshold == 1:
        with pytest.raises(CheckpointEscalationError):
            mgr.land_best()
    else:
        assert mgr.land_best() is False
        assert mgr.escalator.consecutive == 1
        mgr.save_latest(state, same_as=written)
    assert not any(n.endswith(".msgpack") for n in os.listdir(str(tmp_path)))


# ----------------------------------------------------------------------
# the round loop: the status log waits for the writer, the training
# thread does not wait for the disk
# ----------------------------------------------------------------------
def _server(tmp_path, dataset, mesh, depth, **server_over):
    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task
    cfg = FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": 6, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.5, "pipeline_depth": depth,
            "checkpoint_async": True, "rounds_per_step": 2,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 2, "rec_freq": 2, "initial_val": True,
            "telemetry": {"enable": True},
            "checkpoint_retry": {"retries": 2, "backoff_base_s": 0.0,
                                 "jitter": 0.0},
            "data_config": {"val": {"batch_size": 8},
                            "test": {"batch_size": 8}},
            **server_over},
        "client_config": {"optimizer_config": {"type": "sgd", "lr": 0.5},
                          "data_config": {"train": {"batch_size": 4}}}})
    server = OptimizationServer(
        make_task(cfg.model_config), cfg, dataset, val_dataset=dataset,
        test_dataset=dataset, model_dir=str(tmp_path), mesh=mesh, seed=7)
    assert server.ckpt.async_latest
    return server


def _status(tmp_path):
    import json
    path = os.path.join(str(tmp_path), ckpt_mod.STATUS_LOG)
    if not os.path.exists(path):
        return {}
    with open(path) as fh:
        return json.load(fh)


def _span_records(tmp_path, name):
    import json
    path = os.path.join(str(tmp_path), "telemetry", "events.jsonl")
    with open(path) as fh:
        records = [json.loads(line) for line in fh if line.strip()]
    return [r for r in records
            if r.get("kind") == "span" and r.get("name") == name]


@pytest.mark.parametrize("depth", [0, 1], ids=["serial", "ring"])
def test_the_status_log_waits_for_the_writer_behind_the_next_dispatch(
        tmp_path, monkeypatch, synth_dataset, mesh8, depth):
    """Every dispatch ends at an evaluation that improves (a young run).
    While the best-model file of round 2 is still with the writer, the
    next dispatch HAS been launched and ``status_log.json`` names neither
    the round nor its ``best_val_*``; once the file lands the log names
    both and ``latest`` is a link to the file."""
    from msrflute_tpu.engine.round import RoundEngine
    gate, entered = _gate_best_writes(monkeypatch)
    gate.set()  # the initial evaluation's save goes through
    server = _server(tmp_path, synth_dataset, mesh8, depth)
    launches = []
    dispatch = RoundEngine.dispatch_rounds

    def counted(engine, state, *args, **kwargs):
        out = dispatch(engine, state, *args, **kwargs)
        launches.append(int(state.round))
        if len(launches) == 1:
            # from now on the writer stops at the door
            entered.clear()
            gate.clear()
        return out

    monkeypatch.setattr(RoundEngine, "dispatch_rounds", counted)
    failed = []

    def run():
        try:
            server.train()
        except BaseException as exc:  # noqa: BLE001 - shown to the test
            failed.append(exc)
            raise

    trainer = threading.Thread(target=run, name="MainThread-train")
    trainer.start()
    try:
        assert entered.wait(timeout=120), "no best-model save reached " \
            "the writer"
        # round 2's file is at the door: wait for the NEXT launch
        import time
        deadline = time.time() + 120
        while len(launches) < 2 and time.time() < deadline and \
                trainer.is_alive():
            time.sleep(0.01)
        assert len(launches) == 2, \
            "the next dispatch waited for the best-model file"
        time.sleep(0.2)  # the tail is at the writer's door by now
        status = _status(tmp_path)
        assert status.get("i", 0) == 0 and not any(
            r[0] == 2 for r in status.get("status_ring", []))
        best = os.path.join(str(tmp_path), "best_val_loss_model.msgpack")
        before = os.path.getmtime(best)  # the initial evaluation's
    finally:
        gate.set()
        trainer.join(timeout=300)
    assert not trainer.is_alive() and not failed
    status = _status(tmp_path)
    assert status["i"] == 6
    assert os.path.getmtime(best) >= before
    assert os.path.samefile(best, os.path.join(str(tmp_path),
                                               ckpt_mod.LATEST))
    assert status["best_val_loss"] == server.best_val["loss"].value
    template = server.state
    assert server.ckpt.load_best(template, "loss").round == 6
    # the counter that says it engaged: every evaluation round but the
    # last ran its tail behind the next launch
    submits = {s["round"]: s["deferred"]
               for s in _span_records(tmp_path, "ckpt_submit")}
    assert submits == {2: True, 4: True, 6: False}
    waits = _span_records(tmp_path, "ckpt_wait")
    assert {"best"} <= {s["for"] for s in waits} <= {"best", "latest",
                                                     "exit"}
    writes = _span_records(tmp_path, "ckpt_async_write")
    best = sorted((s for s in writes if s["file"].startswith("best_val_")),
                  key=lambda s: s["ts"])
    assert len(best) == 4 and all(s["bytes"] > 0 for s in best), \
        "rounds 0, 2, 4, 6: one whole file each"
    # the snapshot is handed over once the test evaluation has its
    # numbers: its fetch would queue behind the snapshot's transfers
    tests = sorted((s for s in _span_records(tmp_path, "eval")
                    if s["split"] == "test"), key=lambda s: s["round"])
    assert [s["round"] for s in tests] == [2, 4, 6]
    for write, evaluation in zip(best[1:], tests):
        assert write["ts"] >= evaluation["ts"] + evaluation["dur_s"] - 1e-3


def test_a_failed_best_model_write_aborts_before_the_log_names_it(
        tmp_path, synth_dataset, mesh8):
    """The escalator's abort for a write the writer lost is raised on
    the training thread, in the tail's wait: the status log never names
    the value whose file is not there."""
    from msrflute_tpu.resilience.integrity import CheckpointEscalationError
    server = _server(tmp_path, synth_dataset, mesh8, 1,
                     initial_val=False,
                     checkpoint_retry={"retries": 1, "backoff_base_s": 0.0,
                                       "jitter": 0.0,
                                       "escalation_threshold": 1})

    def always():
        raise OSError("injected")

    server.ckpt._io_fault = always
    with pytest.raises(CheckpointEscalationError):
        server.train()
    status = _status(tmp_path)
    assert not any(key.startswith("best_val_") for key in status)
    assert not os.path.exists(os.path.join(
        str(tmp_path), "best_val_loss_model.msgpack"))


@pytest.mark.parametrize("depth", [0, 1], ids=["serial", "ring"])
def test_a_preemption_during_an_in_flight_best_save_leaves_all_paired(
        tmp_path, monkeypatch, synth_dataset, mesh8, depth):
    """The scheduler's signal lands while round 2's best-model file is
    with the writer: no further dispatch, the tail runs at once
    (``deferred: false``), and ``train()`` returns resumable with file,
    sidecar, status log and ``latest`` all of round 2."""
    import json
    import time

    from msrflute_tpu.resilience.integrity import blob_checksum
    server = _server(tmp_path, synth_dataset, mesh8, depth)
    real_write = CheckpointManager._write_blob

    def slow_write(self, path, blob, keep_prev=False):
        if threading.current_thread().name == "ckpt-latest-writer":
            time.sleep(0.3)  # the loop meets the request, the file not yet
        return real_write(self, path, blob, keep_prev=keep_prev)

    monkeypatch.setattr(CheckpointManager, "_write_blob", slow_write)
    save_best = server.ckpt.save_best

    def signalled_save(state, *names, **how):
        out = save_best(state, *names, **how)
        if int(state.round) == 2:
            assert server.ckpt._mp_busy or \
                server.ckpt._mp_mailbox is not None
            server.preemption.request("drill")
        return out

    server.ckpt.save_best = signalled_save
    state = server.train()
    assert server.preempted and state.round == 2
    status = _status(tmp_path)
    assert status["i"] == 2 and status["preempted"] == "drill"
    best = os.path.join(str(tmp_path), "best_val_loss_model.msgpack")
    latest = os.path.join(str(tmp_path), ckpt_mod.LATEST)
    assert os.path.samefile(best, latest)
    with open(best + ".sum") as fh:
        meta = json.load(fh)
    with open(best, "rb") as fh:
        assert meta["crc32"] == blob_checksum(fh.read())
    assert status["best_val_loss"] == server.best_val["loss"].value
    assert server.ckpt.load(state).round == 2
    assert [s["deferred"] for s in _span_records(tmp_path, "ckpt_submit")
            if s["round"] == 2] == [False]


@pytest.mark.parametrize("depth", [0, 1], ids=["serial", "ring"])
def test_a_latest_of_its_own_is_fetched_behind_the_next_dispatch(
        tmp_path, monkeypatch, synth_dataset, mesh8, depth):
    """Every dispatch ends at an evaluation that finds nothing better
    (client learning rate 0: the state of round 0 stays).  The round's
    ``latest`` is then a snapshot of its own, and the writer asks for
    its transfers only once the NEXT dispatch is launched: asked for at
    the submit, they held that dispatch's inputs back (on the chip such
    a period took 0.8 s longer than one whose evaluation improved)."""
    from msrflute_tpu.engine.round import RoundEngine
    server = _server(tmp_path, synth_dataset, mesh8, depth,
                     initial_lr_client=0.0)
    launches, fetched = [], []
    dispatch = RoundEngine.dispatch_rounds

    def counted(engine, state, *args, **kwargs):
        out = dispatch(engine, state, *args, **kwargs)
        launches.append(int(state.round))
        return out

    monkeypatch.setattr(RoundEngine, "dispatch_rounds", counted)
    real_chunks = ckpt_mod._state_chunks

    def seen(payload):
        fetched.append(len(launches))
        return real_chunks(payload)

    monkeypatch.setattr(ckpt_mod, "_state_chunks", seen)
    state = server.train()
    assert state.round == 6 and launches == [0, 2, 4]
    writes = sorted(_span_records(tmp_path, "ckpt_async_write"),
                    key=lambda s: s["ts"])
    assert [s["file"] for s in writes] == [
        "best_val_loss_model.msgpack"] + [ckpt_mod.LATEST] * 3, \
        "the initial evaluation's best model, then rounds 2, 4, 6"
    # round 0's file before any launch; round 2's latest behind the
    # launch of rounds 2-4, round 4's behind that of 4-6, the last at
    # the loop's end
    assert fetched == [0, 2, 3, 3]
    assert [s["deferred"] for s in _span_records(tmp_path, "ckpt_submit")
            ] == [False] * 3
    assert server.ckpt.load(state).round == 6
    assert _status(tmp_path)["i"] == 6
