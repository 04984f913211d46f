"""Checkpoint integrity, retry, escalation, and crash-window recovery.

Backend-level tests (no training loop): the two-slot msgpack latest with
crc32 sidecars, the orbax pointer checksum + other-slot fallback, the
bounded retry-with-backoff policy, the consecutive-failure escalation,
and the satellite crash windows — a kill between ``_drain``'s two
renames (stale ``.old``) and between the ptr-tmp write and its
``os.replace``.
"""

import json
import os

import numpy as np
import pytest

from msrflute_tpu.engine.checkpoint import (LATEST, LATEST_PREV,
                                            CheckpointManager)
from msrflute_tpu.engine.round import ServerState
from msrflute_tpu.resilience.integrity import (CheckpointEscalationError,
                                               RetryPolicy, blob_checksum,
                                               run_with_retry, tree_checksum)


def _state(round_no: int, scale: float = 1.0) -> ServerState:
    return ServerState(
        params={"w": np.full((4, 3), scale, np.float32),
                "b": np.arange(3, dtype=np.float32) * scale},
        opt_state={"m": np.zeros((4, 3), np.float32)},
        strategy_state={}, round=round_no)


def _no_sleep_policy(**over):
    kw = dict(retries=3, backoff_base_s=0.0, backoff_max_s=0.0,
              jitter=0.0, escalation_threshold=3)
    kw.update(over)
    return RetryPolicy(**kw)


# ----------------------------------------------------------------------
# msgpack: sidecars + two-slot fallback
# ----------------------------------------------------------------------
def test_msgpack_latest_rotates_prev_and_writes_sidecars(tmp_path):
    cm = CheckpointManager(str(tmp_path), retry=_no_sleep_policy())
    cm.save_latest(_state(1, scale=1.0))
    cm.save_latest(_state(2, scale=2.0))
    for name in (LATEST, LATEST + ".sum", LATEST_PREV, LATEST_PREV + ".sum"):
        assert (tmp_path / name).exists(), name
    meta = json.load(open(tmp_path / (LATEST + ".sum")))
    blob = open(tmp_path / LATEST, "rb").read()
    assert meta["crc32"] == blob_checksum(blob)
    assert meta["size"] == len(blob)
    # latest holds round 2, prev holds round 1
    assert cm.load(_state(0)).round == 2
    os.remove(tmp_path / LATEST)
    restored = cm.load(_state(0))
    assert restored.round == 1
    assert any(e["event"] == "restored from backup slot"
               for e in cm.recovery_events)


def test_msgpack_flipped_byte_falls_back_with_recovery_event(tmp_path):
    cm = CheckpointManager(str(tmp_path), retry=_no_sleep_policy())
    cm.save_latest(_state(1, scale=1.0))
    cm.save_latest(_state(2, scale=2.0))
    path = tmp_path / LATEST
    blob = bytearray(path.read_bytes())
    blob[len(blob) // 3] ^= 0xFF
    path.write_bytes(bytes(blob))
    restored = cm.load(_state(0))
    assert restored.round == 1
    assert restored.params["w"][0, 0] == 1.0
    events = [e["event"] for e in cm.recovery_events]
    assert any("integrity check failed" in e for e in events)


def test_msgpack_torn_write_truncation_falls_back(tmp_path):
    """A torn write (truncated file, size mismatch vs sidecar) must fall
    back too — not just a clean bit flip."""
    cm = CheckpointManager(str(tmp_path), retry=_no_sleep_policy())
    cm.save_latest(_state(1))
    cm.save_latest(_state(2))
    path = tmp_path / LATEST
    path.write_bytes(path.read_bytes()[: path.stat().st_size // 2])
    assert cm.load(_state(0)).round == 1


def test_msgpack_checkpoint_without_sidecar_still_loads(tmp_path):
    """Pre-integrity checkpoints (no .sum sidecar) keep loading —
    verification is vacuous, not fatal."""
    cm = CheckpointManager(str(tmp_path), retry=_no_sleep_policy())
    cm.save_latest(_state(4))
    os.remove(tmp_path / (LATEST + ".sum"))
    assert cm.load(_state(0)).round == 4
    assert cm.recovery_events == []


# ----------------------------------------------------------------------
# retry + escalation
# ----------------------------------------------------------------------
def test_retry_recovers_from_transient_io_faults(tmp_path):
    fails = iter([True, True, False, False])
    cm = CheckpointManager(str(tmp_path), retry=_no_sleep_policy(),
                           io_fault=lambda: next(fails) and
                           (_ for _ in ()).throw(OSError("transient")))
    cm.save_latest(_state(3))
    assert cm.load(_state(0)).round == 3
    assert cm.escalator.consecutive == 0  # success reset the counter


def test_escalation_aborts_after_consecutive_failures(tmp_path):
    def always_fail():
        raise OSError("disk on fire")

    cm = CheckpointManager(str(tmp_path),
                           retry=_no_sleep_policy(escalation_threshold=2),
                           io_fault=always_fail)
    cm.save_latest(_state(1))  # failure 1: warn and continue
    with pytest.raises(CheckpointEscalationError):
        cm.save_latest(_state(2))  # failure 2: hits the threshold
    assert cm.escalator.consecutive == 2


def test_run_with_retry_propagates_fatal_signals():
    def interrupt():
        raise KeyboardInterrupt

    with pytest.raises(KeyboardInterrupt):
        run_with_retry(interrupt, _no_sleep_policy())


def test_retry_backoff_is_exponential_capped_and_jitter_free_when_zero():
    pol = RetryPolicy(retries=5, backoff_base_s=1.0, backoff_max_s=4.0,
                      jitter=0.0)
    assert [pol.delay(a) for a in range(4)] == [1.0, 2.0, 4.0, 4.0]
    jittered = RetryPolicy(backoff_base_s=1.0, jitter=0.5)
    assert all(0.5 <= jittered.delay(0) <= 1.5 for _ in range(16))


# ----------------------------------------------------------------------
# orbax: pointer checksum, slot fallback, crash windows, drain re-queue
# ----------------------------------------------------------------------
def _orbax_cm(tmp_path, **kw):
    kw.setdefault("retry", _no_sleep_policy())
    return CheckpointManager(str(tmp_path), backend="orbax", **kw)


def _commit_latest(cm, state):
    cm.save_latest(state)
    cm.wait()  # commits the pointer at the slot


def test_orbax_ptr_records_tree_checksum_and_verifies(tmp_path):
    cm = _orbax_cm(tmp_path)
    _commit_latest(cm, _state(1))
    ptr = json.load(open(tmp_path / cm._LATEST_PTR))
    slot_dir = cm._orbax_path(ptr["slot"])
    assert ptr["crc32"] == tree_checksum(slot_dir)
    assert cm.load(_state(0)).round == 1


def test_orbax_corrupted_slot_falls_back_to_other_slot(tmp_path):
    cm = _orbax_cm(tmp_path)
    _commit_latest(cm, _state(1, scale=1.0))
    _commit_latest(cm, _state(2, scale=2.0))  # lands in the OTHER slot
    ptr = json.load(open(tmp_path / cm._LATEST_PTR))
    slot_dir = cm._orbax_path(ptr["slot"])
    # flip a byte in some file of the committed slot
    for root, _dirs, files in os.walk(slot_dir):
        if files:
            victim = os.path.join(root, sorted(files)[0])
            break
    blob = bytearray(open(victim, "rb").read() or b"\0")
    blob[0] ^= 0xFF
    open(victim, "wb").write(bytes(blob))

    restored = cm.load(_state(0))
    assert restored.round == 1  # the surviving slot's generation
    events = [e["event"] for e in cm.recovery_events]
    assert any("checksum" in e for e in events)
    assert any("backup slot" in e for e in events)


def test_orbax_legacy_bare_slot_pointer_still_loads(tmp_path):
    cm = _orbax_cm(tmp_path)
    _commit_latest(cm, _state(3))
    slot = json.load(open(tmp_path / cm._LATEST_PTR))["slot"]
    (tmp_path / cm._LATEST_PTR).write_text(slot)  # pre-integrity format
    assert cm.load(_state(0)).round == 3


def test_crash_between_ptr_tmp_write_and_replace_keeps_old_anchor(tmp_path):
    """Satellite crash window: a kill after writing ``ptr.tmp`` but
    before ``os.replace`` must leave the committed pointer (and its
    round) authoritative."""
    cm = _orbax_cm(tmp_path)
    _commit_latest(cm, _state(1))
    # simulate the torn commit of round 2: slot saved, ptr.tmp written,
    # replace never happened
    other = cm._LATEST_SLOTS[1]
    cm._orbax_save(cm._orbax_path(other), _state(2))
    cm._drain()
    (tmp_path / (cm._LATEST_PTR + ".tmp")).write_text(
        json.dumps({"slot": other, "crc32": "dead"}))
    cm2 = _orbax_cm(tmp_path)
    assert cm2.load(_state(0)).round == 1


def test_crash_between_best_swap_renames_recovers_from_old(tmp_path):
    """Satellite crash window: killed between ``final -> final.old`` and
    ``tmp -> final`` leaves only ``.old`` + the tmp dir; ``load`` must
    restore the previous best from ``.old``."""
    cm = _orbax_cm(tmp_path)
    cm.save_best(_state(1), "loss")
    cm.wait()  # the swap committed: best_val_loss_model.orbax exists
    final = cm._orbax_path("best_val_loss_model.orbax")
    assert os.path.isdir(final)
    # round-2 best: save the .new dir, then simulate the kill mid-swap
    cm.save_best(_state(2), "loss")
    cm._orbax.wait_until_finished()
    os.rename(final, final + ".old")
    cm._pending_renames.clear()  # the process died; nothing pending

    cm2 = _orbax_cm(tmp_path)
    restored = cm2.load_best(_state(0), "loss")
    assert restored is not None and restored.round == 1


def test_drain_requeues_failed_renames(tmp_path, monkeypatch):
    """Satellite fix: one failed rename must be RE-QUEUED, not dropped —
    the next drain commits the stranded save."""
    cm = _orbax_cm(tmp_path)
    cm.save_best(_state(5), "acc")
    cm._orbax.wait_until_finished()  # orbax's own commit must land first

    real_rename = os.rename
    boom = {"left": 1}
    final_name = "best_val_acc_model.orbax"

    def flaky_rename(src, dst):
        # fail only OUR .new -> final swap, not orbax-internal renames
        if boom["left"] and str(dst).endswith(final_name):
            boom["left"] -= 1
            raise OSError("transient NFS blip")
        return real_rename(src, dst)

    monkeypatch.setattr(os, "rename", flaky_rename)
    cm._drain()  # rename fails once -> re-queued
    assert len(cm._pending_renames) == 1
    final = cm._orbax_path("best_val_acc_model.orbax")
    assert not os.path.isdir(final)
    cm._drain()  # next drain commits it
    assert cm._pending_renames == []
    assert os.path.isdir(final)
    assert cm.load_best(_state(0), "acc").round == 5


def test_drain_failure_counts_toward_escalation_but_keeps_renames(
        tmp_path, monkeypatch):
    cm = _orbax_cm(tmp_path)
    cm._pending_renames.append((str(tmp_path / "ghost.new"),
                                str(tmp_path / "ghost")))
    monkeypatch.setattr(cm._orbax, "wait_until_finished",
                        lambda: (_ for _ in ()).throw(OSError("io")))
    before = cm.escalator.consecutive
    cm._drain()
    assert cm.escalator.consecutive == before + 1
    # the queued rename survives (its tmp dir may belong to an EARLIER
    # successful save; the isdir guard skips truly-failed ones)
    assert len(cm._pending_renames) == 1


# ----------------------------------------------------------------------
# the streamed msgpack form (PR 28): flax's bytes, no assembled blob
# ----------------------------------------------------------------------
def _awkward_tree():
    """Every kind of leaf the framing has to tell apart: sizes on both
    sides of msgpack's 1-, 2- and 4-byte length forms, a 0-d array, an
    empty one, bfloat16, integers, numpy and python scalars, None, more
    than 15 keys in one dict, and keys that sort differently from their
    insertion order."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    many = {f"k{i:02d}": np.float32(i) for i in range(20)}
    return {
        "params": {
            "z_last": rng.standard_normal((300, 70)).astype(np.float32),
            "a_first": rng.standard_normal((3,)).astype(np.float32),
            "tiny": np.zeros((1,), np.int8),
            "scalar0d": np.array(2.5, np.float32),
            "empty": np.zeros((0, 4), np.float32),
            "mid": np.arange(100, dtype=np.int32),       # 400 bytes: bin16
            "wide": np.arange(40000, dtype=np.int16),    # 80 kB: bin32
            "bf16": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4),
            "on_device": jnp.linspace(0.0, 1.0, 1000).reshape(10, 100),
        },
        "opt_state": {"0": {"count": np.int32(7), "mu": None}, "1": many},
        "strategy_state": {},
        "round": 12,
    }


def test_streamed_chunks_are_flax_msgpack_byte_for_byte():
    from flax import serialization

    from msrflute_tpu.engine import checkpoint as ckpt_mod
    tree = _awkward_tree()
    chunks = ckpt_mod._state_chunks(tree)
    mine = b"".join(bytes(ckpt_mod._host_bytes(c)) for c in chunks)
    assert mine == serialization.msgpack_serialize(
        serialization.to_state_dict(tree))
    assert ckpt_mod._chunks_size(chunks) == len(mine)
    # array leaves ride as they are (no copy is assembled): the big ones
    # are chunks of their own, and a device leaf stays on the device
    # until its chunk is written
    import jax
    assert any(isinstance(c, jax.Array) for c in chunks)
    assert any(isinstance(c, np.ndarray) and c.nbytes == 84000
               for c in chunks)


def test_one_fetch_serves_every_best_name_and_each_loads(tmp_path):
    from msrflute_tpu.engine import checkpoint as ckpt_mod
    cm = CheckpointManager(str(tmp_path), retry=_no_sleep_policy())
    built = []
    real = ckpt_mod._state_chunks
    ckpt_mod._state_chunks = lambda payload: built.append(1) or real(payload)
    try:
        cm.save_best(_state(3, scale=3.0), "loss", "acc")
    finally:
        ckpt_mod._state_chunks = real
    assert built == [1]
    loss, acc = (tmp_path / f"best_val_{n}_model.msgpack"
                 for n in ("loss", "acc"))
    assert os.path.samefile(loss, acc)  # a link, not a second write
    for name in ("loss", "acc"):
        assert cm.load_best(_state(0), name).round == 3
    # a later save of one name replaces that name's file only
    cm.save_best(_state(5, scale=5.0), "acc")
    assert not os.path.samefile(loss, acc)
    assert cm.load_best(_state(0), "loss").round == 3
    assert cm.load_best(_state(0), "acc").round == 5
    meta = json.load(open(str(loss) + ".sum"))
    assert meta["crc32"] == blob_checksum(open(loss, "rb").read())


def test_a_retried_streamed_write_lands_whole(tmp_path):
    """The first attempt dies after the chunks were fetched; the retry
    finds the host bytes in the list and writes the same file."""
    import jax.numpy as jnp
    fails = iter([True, False, False])
    cm = CheckpointManager(
        str(tmp_path), retry=_no_sleep_policy(),
        io_fault=lambda: next(fails) and (_ for _ in ()).throw(
            OSError("injected")))
    state = ServerState(params={"w": jnp.full((64, 32), 2.0)},
                        opt_state={}, strategy_state={}, round=9)
    cm.save_latest(state)
    restored = cm.load(ServerState(params={"w": np.zeros((64, 32),
                                                         np.float32)},
                                   opt_state={}, strategy_state={}, round=0))
    assert restored.round == 9
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), 2.0)


@pytest.mark.parametrize("async_latest", [False, True],
                         ids=["sync", "async_writer"])
def test_the_latest_of_a_state_just_saved_as_best_is_a_link(tmp_path,
                                                            async_latest):
    """An evaluation round saves its state twice, as the best model and
    as ``latest``.  The best-model file is on the disk once
    ``land_best`` returns (at once without the writer; the status log
    names the new best value after that call); told that the ``latest``
    is of the very same state (``same_as``), the manager makes it a link
    to that file, durable on return (no second fetch, no second 1.9 GB
    through the disk), rotation and sidecars as ever; without that word
    a state is written whole."""
    import jax.numpy as jnp

    from msrflute_tpu.engine import checkpoint as ckpt_mod

    def device_state(round_no):
        return ServerState(params={"w": jnp.full((8, 4), float(round_no))},
                           opt_state={"m": jnp.zeros((8, 4))},
                           strategy_state={}, round=round_no)

    template = device_state(0)
    cm = CheckpointManager(str(tmp_path), retry=_no_sleep_policy(),
                           async_latest=async_latest)
    built = []
    real = ckpt_mod._state_chunks
    ckpt_mod._state_chunks = lambda payload: built.append(1) or real(payload)
    latest, best = tmp_path / LATEST, tmp_path / "best_val_loss_model.msgpack"
    try:
        cm.save_latest(device_state(1))
        state = device_state(2)
        written = cm.save_best(state, "loss", "acc")
        assert written == str(best) and cm.land_best()
        # the best model is on the disk, whole, with its sidecar
        meta = json.load(open(str(best) + ".sum"))
        assert meta["crc32"] == blob_checksum(open(best, "rb").read())
        assert cm.save_latest(state, same_as=written) is None
        # no wait here either: round 1's latest landed first and rotated
        assert os.path.samefile(latest, best)
        assert json.load(open(str(latest) + ".sum")) == meta
        assert len(built) == 2  # round 1's latest, round 2's best
        assert cm.load(template).round == 2
        assert cm.load(template, LATEST_PREV).round == 1
        # without the caller's word the same values are written whole
        cm.save_latest(state)
        cm.wait()
        assert len(built) == 3 and not os.path.samefile(latest, best)
        assert cm.load(template, LATEST_PREV).round == 2
        # a later best model replaces its own name's file only
        cm.save_best(device_state(4), "loss")
        assert cm.load_best(template, "loss").round == 4
        assert cm.load_best(template, "acc").round == 2
        assert cm.load(template).round == 2
    finally:
        ckpt_mod._state_chunks = real


def test_links_and_whole_saves_interleaved_under_the_async_writer(tmp_path):
    """The training thread and the writer meet on files: the links to
    a best-model file are made on this thread, the file itself and the
    `latest` saves of the rounds between on the writer's.  Many rounds without a wait
    between them, the interpreter switching threads every few
    instructions: every file that lands is whole and of the state it is
    named for."""
    import sys
    import time

    import jax.numpy as jnp

    def device_state(round_no):
        return ServerState(params={"w": jnp.full((16, 8), float(round_no))},
                           opt_state={}, strategy_state={}, round=round_no)

    template = device_state(0)
    cm = CheckpointManager(str(tmp_path), retry=_no_sleep_policy(),
                           async_latest=True)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    deadline = time.time() + 60
    last_best = None
    try:
        for round_no in range(1, 121):
            assert time.time() < deadline, "the saves stalled"
            state = device_state(round_no)
            written = None
            if round_no % 3 == 0:  # an evaluation round: best, then latest
                written = cm.save_best(state, "loss", "acc")
                last_best = round_no
            cm.save_latest(state, same_as=written)
            if round_no % 10 == 0:
                # whatever has landed by now is whole and consistent
                got = cm.load(template)
                assert got.round == round_no
                np.testing.assert_array_equal(
                    np.asarray(got.params["w"]), float(round_no))
        cm.wait()
    finally:
        sys.setswitchinterval(interval)
    assert cm.load(template).round == 120
    assert cm.load(template, LATEST_PREV).round == 119
    for name in ("loss", "acc"):
        best = cm.load_best(template, name)
        assert best.round == last_best == 120
        np.testing.assert_array_equal(np.asarray(best.params["w"]), 120.0)
    assert cm.escalator.consecutive == 0
