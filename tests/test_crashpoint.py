"""Crash-point fuzzing (flutearmor leg 3), tier-1 slice.

``tools/crashpoint.py`` intercepts the atomic-commit syscalls
(``os.replace`` / ``os.rename`` / ``os.link``) under one model dir,
kills the run with a ``BaseException`` at a chosen commit index, then
relaunches with ``resume_from_checkpoint`` and asserts the finished
params are bit-identical to an uninterrupted run.  CI runs the FULL
kill matrix (every commit, serial and depth-3); this file keeps a
representative slice inside tier-1's budget: the first commit (death
before ANY durable state), a mid-sequence row spill, a point inside the
two-slot ``latest`` rotation, and the final ``status_log`` commit; and,
with the async writer (``writer=True``), the durable points of an
evaluation round whose best-model file is the writer's commit and whose
status log and ``latest`` link follow it behind the next dispatch.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))

from crashpoint import CrashPoint, KillSwitch, fuzz  # noqa: E402


def test_killswitch_census_sees_every_durable_sequence(tmp_path):
    """The interception layer itself: a census run counts commits only
    under the armed scope and logs the op census the fuzzer enumerates
    — row spills + marker, latest rotation, sidecars, status log."""
    rec = fuzz(depth=0, rounds=3, kill_points=[], verbose=False,
               workdir=str(tmp_path))
    assert rec["points_fuzzed"] == 0
    census = rec["census"]
    assert rec["durable_ops"] == len(census) > 10
    joined = "\n".join(census)
    for needle in ("fleet_carry/row_", "fleet_carry/fleet_round.npy",
                   "latest_model.msgpack", "latest_model.msgpack.sum",
                   "link:latest_model.msgpack.prev.lnk",
                   "status_log.json"):
        assert needle in joined, f"census missing {needle}:\n{joined}"


def test_crashpoint_is_uncatchable_by_retry_ladders():
    """CrashPoint must ride through ``except Exception`` — the whole
    point of modelling a kill, not an IO error."""
    assert issubclass(CrashPoint, BaseException)
    assert not issubclass(CrashPoint, Exception)

    from msrflute_tpu.resilience.integrity import (DurableIOLadder,
                                                   RetryPolicy)
    calls = {"n": 0}

    def die():
        calls["n"] += 1
        raise CrashPoint("kill")

    ladder = DurableIOLadder(
        policy=RetryPolicy(retries=3, backoff_base_s=0.0, jitter=0.0))
    with pytest.raises(CrashPoint):
        ladder.run(die, surface="store_write", what="crashpoint-probe")
    assert calls["n"] == 1  # no retry consumed the kill


def test_kill_matrix_slice_serial_resumes_bit_identical(tmp_path):
    """Serial loop: kill before the FIRST commit (no durable state at
    all — resume must cold-start), inside the latest rotation, and at
    the final status-log commit; every point resumes bit-identical."""
    rec = fuzz(depth=0, rounds=3, kill_points=[0, 12, 31],
               verbose=False, workdir=str(tmp_path))
    assert rec["points_fuzzed"] == 3  # fuzz() asserts parity per point


def test_kill_matrix_slice_pipelined_resumes_bit_identical(tmp_path):
    """Depth-3 ring: same contract with the pipelined loop's commit
    interleaving — one early spill, one mid-matrix point, post-phase
    kill (commit landed, process state lost) on the last commit."""
    rec = fuzz(depth=3, rounds=3, kill_points=[1, 15], verbose=False,
               workdir=str(tmp_path))
    assert rec["points_fuzzed"] == 2
    last = rec["durable_ops"] - 1
    rec_post = fuzz(depth=3, rounds=3, phase="post", kill_points=[last],
                    verbose=False, workdir=str(tmp_path / "post"))
    assert rec_post["points_fuzzed"] == 1


# ----------------------------------------------------------------------
# the async writer's half (ISSUE 32): round 2 is an evaluation round of
# a young run, so its state goes to the writer as the best model and its
# durable tail runs behind round 3's dispatch.  Of each name the FIRST
# commit is the initial evaluation's, the second round 2's.
# ----------------------------------------------------------------------
TAIL_OF_ROUND_2 = [
    # snapshot submitted, nothing of it on the disk (the writer's commit)
    ("replace:best_val_loss_model.msgpack", 1),
    # file landed, its sidecar not
    ("replace:best_val_loss_model.msgpack.sum", 1),
    # file and sidecar landed, the other metric's name not linked to them
    ("link:best_val_acc_model.msgpack.lnk", 1),
    # everything of the best model landed, the status log not written
    ("replace:status_log.json", 1),
    # status written (it names the new best value), `latest` not linked
    ("link:latest_model.msgpack.tmp.lnk", 0),
]


def test_writer_census_lists_the_tails_durable_points_in_order(tmp_path):
    """One whole best-model file per improving evaluation, through the
    writer; then, on the training thread, the other name's link, the
    status log, the ``latest`` link: the order a hard kill may cut
    anywhere."""
    rec = fuzz(depth=0, rounds=4, kill_points=[], verbose=False,
               writer=True, workdir=str(tmp_path))
    census = rec["census"]

    def nth(name, n):
        return [i for i, op in enumerate(census) if op == name][n]

    order = [nth(name, n) for name, n in TAIL_OF_ROUND_2]
    order.append(nth("replace:latest_model.msgpack", 1))  # the link lands
    assert order == sorted(order), (order, census)
    # rounds 0, 2 and 4 improved: three files, each written once
    assert census.count("replace:best_val_loss_model.msgpack") == 3
    assert census.count("replace:status_log.json") == 4
    # a linked `latest` rotates the previous generation like a written one
    assert census[order[-2]:order[-1] + 1] == [
        "link:latest_model.msgpack.tmp.lnk",
        "replace:latest_model.msgpack.tmp",
        "link:latest_model.msgpack.prev.lnk",
        "replace:latest_model.msgpack.prev",
        "link:latest_model.msgpack.prev.sum.lnk",
        "replace:latest_model.msgpack.prev.sum",
        "replace:latest_model.msgpack"]


def test_a_kill_on_the_writer_thread_is_the_process_death(tmp_path):
    """The switch's own contract for a commit on another thread: once it
    has fired, no later commit of any thread lands."""
    import os
    import threading
    switch = KillSwitch()
    switch.install()
    try:
        switch.arm(str(tmp_path), kill_at=("replace:b", 0))
        for name in ("a", "b", "c"):
            (tmp_path / f"{name}.tmp").write_text(name)
        os.replace(tmp_path / "a.tmp", tmp_path / "a")
        died = []

        def writer():
            try:
                os.replace(tmp_path / "b.tmp", tmp_path / "b")
            except CrashPoint as exc:
                died.append(exc)

        thread = threading.Thread(target=writer)
        thread.start()
        thread.join(timeout=60)
        assert not thread.is_alive() and len(died) == 1
        with pytest.raises(CrashPoint):
            os.replace(tmp_path / "c.tmp", tmp_path / "c")
    finally:
        switch.uninstall()
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "a", "b.tmp", "c.tmp"]


# the writer thread dies of the kill, as it is meant to
@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
@pytest.mark.parametrize("depth", [0, 3], ids=["serial", "pipelined"])
def test_writer_kill_matrix_slice_resumes_bit_identical(tmp_path, depth):
    """A kill before each durable point of round 2's tail, and one right
    after its ``latest`` link landed, each resumed to the uninterrupted
    run's final parameters, bit for bit."""
    rec = fuzz(depth=depth, rounds=4, kill_points=TAIL_OF_ROUND_2,
               verbose=False, writer=True, workdir=str(tmp_path))
    assert rec["points_fuzzed"] == len(TAIL_OF_ROUND_2)
    rec_post = fuzz(depth=depth, rounds=4, phase="post",
                    kill_points=[("replace:latest_model.msgpack", 1)],
                    verbose=False, writer=True,
                    workdir=str(tmp_path / "post"))
    assert rec_post["points_fuzzed"] == 1
