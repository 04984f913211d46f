"""Checkpoint / resume.

Parity target: reference §5.4 — tar checkpoints of
model/optimizer/lr-scheduler state (``core/trainer.py:753-775``),
``latest_model`` every round + ``epoch<i>`` and best-model copies every
``model_backup_freq`` (``core/server.py:530-558``), ``status_log.json``
(``core/server.py:477-490``), resume (``core/server.py:183-204``), and
fallback-to-best (``core/server.py:561-578``).

Format: flax msgpack serialization of the full :class:`ServerState` pytree
(+ a sidecar JSON with round/best-metric bookkeeping).  Saves run under
the bounded retry-with-backoff policy (``server_config.checkpoint_retry``,
generalizing the reference's fixed 3-retry wrapper,
``utils/utils.py:348-359``) with crc32 integrity sidecars and two-slot
fallback on load — see :mod:`msrflute_tpu.resilience.integrity`.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import struct
import threading
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
from flax import serialization

from ..resilience.integrity import (CheckpointCorruptionError,
                                    SIDECAR_SUFFIX, FailureEscalator,
                                    RetryPolicy, checksum_hex,
                                    read_sidecar, run_with_retry,
                                    tree_checksum, verify_blob,
                                    write_sidecar)
from ..telemetry import NULL_SPAN, emit_event
from ..utils.io import update_json_log
from ..utils.logging import print_rank
from .round import ServerState

LATEST = "latest_model.msgpack"
#: previous-generation latest (two-slot msgpack scheme): rotated into
#: place on every latest save, so a corrupted/torn ``latest_model`` falls
#: back one round instead of losing the run
LATEST_PREV = LATEST + ".prev"
STATUS_LOG = "status_log.json"
#: flax's msgpack extension code of an ndarray (``_MsgpackExtType``)
_NDARRAY_EXT = 1


def _payload(state: ServerState) -> dict:
    """The one checkpointed dict, shared by every backend — add new
    ServerState fields HERE (and in :func:`_merge`) only."""
    return {
        "params": state.params,
        "opt_state": state.opt_state,
        "strategy_state": state.strategy_state,
        "round": state.round,
    }


def _merge(template: ServerState, restored: dict) -> ServerState:
    """Restore typed pytrees (optax namedtuples etc.) from a plain
    state-dict by merging onto the RAW template payload."""
    merged = serialization.from_state_dict(
        _payload(template), restored)
    return ServerState(
        params=merged["params"],
        opt_state=merged["opt_state"],
        strategy_state=merged["strategy_state"],
        round=int(restored.get("round", 0)),
    )


@jax.jit
def _copy_device_leaves(leaves: list) -> list:
    """Fresh buffers for every device leaf, as ONE device program.  The
    body asks for the copies: ``jax.jit`` may hand an output that is
    just its input back as the input's own buffer, the very one the
    next round step donates.  Each copy keeps its leaf's sharding."""
    return [jnp.copy(x) for x in leaves]


def _rotate(src: str, dst: str) -> None:
    """``dst`` becomes a LINK to ``src`` (a copy where hardlinks are
    unsupported), atomically: ``src`` — the committed latest — never
    disappears, so at every instant of the rotate+write sequence at
    least one slot passes its integrity check (a plain rename here would
    open a crash window with NO loadable latest at all)."""
    lnk = dst + ".lnk"
    try:
        if os.path.exists(lnk):
            os.remove(lnk)
        os.link(src, lnk)
    except OSError:
        shutil.copyfile(src, lnk)
    os.replace(lnk, dst)


def _header(small: int, codes: tuple, n: int) -> bytes:
    """A msgpack length header as ``msgpack.Packer`` writes it: the
    smallest of the 1-, 2- and 4-byte forms (``codes``) that holds
    ``n``; ``small`` >= 0 is the format's fix form for ``n`` < 16."""
    if 0 <= small and n <= 0x0F:
        return bytes([small | n])
    for code, width, fmt in zip(codes, (0xFF, 0xFFFF, 0xFFFFFFFF),
                                (">B", ">H", ">I")):
        if code is not None and n <= width:
            return bytes([code]) + struct.pack(fmt, n)
    raise ValueError(f"msgpack cannot frame a length of {n}")


def _msgpack_chunks(node, out: list) -> None:
    """Chunks whose concatenation IS ``flax.serialization.
    msgpack_serialize(node)`` (held byte for byte by a test).  A dict is
    framed here and an array leaf is framed here around the ARRAY ITSELF
    (numpy, or a device array that :func:`_host_bytes` fetches when the
    chunk is written); everything else (python and numpy scalars, an
    empty array or one over flax's chunking size, an object dtype) goes
    through flax's own call.  flax builds each array's bytes three times
    over (``tobytes``, the inner ``packb``, the outer one) and only once
    the whole tree is on the host: 11 s for a 1.9 GB state."""
    if isinstance(node, dict):
        out.append(_header(0x80, (None, 0xDE, 0xDF), len(node)))
        # flax copies the tree with ``tree_map``, which sorts a dict's keys
        for key in sorted(node):
            out.append(msgpack.packb(key, strict_types=True))
            _msgpack_chunks(node[key], out)
    elif (isinstance(node, (np.ndarray, jax.Array))
          and not node.dtype.hasobject and not node.dtype.isalignedstruct
          and 0 < node.nbytes <= serialization.MAX_CHUNK_SIZE):
        # ExtType(ndarray) around packb((shape, dtype name, bytes))
        head = b"\x93" + msgpack.packb(
            (node.shape, node.dtype.name), use_bin_type=True)[1:] + \
            _header(-1, (0xC4, 0xC5, 0xC6), node.nbytes)
        size = len(head) + node.nbytes
        fix = {1: 0xD4, 2: 0xD5, 4: 0xD6, 8: 0xD7, 16: 0xD8}.get(size)
        out.append((bytes([fix]) if fix is not None else
                    _header(-1, (0xC7, 0xC8, 0xC9), size)) +
                   bytes([_NDARRAY_EXT]) + head)
        out.append(node)
    else:
        out.append(serialization.msgpack_serialize(node))


def _host_bytes(chunk):
    """A chunk as bytes on the host.  A device array is fetched HERE: its
    transfer was started with the others' (:func:`_state_chunks`), so
    the checksum and the write of one leaf overlap the transfers of the
    leaves behind it."""
    if isinstance(chunk, (bytes, bytearray)):
        return chunk
    if isinstance(chunk, jax.Array):
        # the explicit transfer call: strict-transfer mode counts any
        # other way to the host as an implicit sync
        chunk = jax.device_get(chunk)
    return np.ascontiguousarray(chunk).reshape(-1).view(np.uint8)


def _state_chunks(payload) -> list:
    """The msgpack form of a checkpoint payload as a list of chunks
    (:func:`_msgpack_chunks`), written one after the other by
    :meth:`CheckpointManager._write_blob`: no second copy of the state
    is ever assembled.  Starts every device leaf's transfer to the host."""
    chunks: list = []
    _msgpack_chunks(serialization.to_state_dict(payload), chunks)
    for chunk in chunks:
        if isinstance(chunk, jax.Array):
            chunk.copy_to_host_async()
    return chunks


def _chunks_size(chunks: list) -> int:
    return sum(len(c) if isinstance(c, (bytes, bytearray)) else c.nbytes
               for c in chunks)


class _LinkTo:
    """In place of a blob: the file to make is, byte for byte, the one
    already at ``path`` (with its sidecar)."""

    def __init__(self, path: str):
        self.path = path


def _state_from_bytes(data: bytes, template: ServerState) -> ServerState:
    return _merge(template, serialization.msgpack_restore(data))


def load_pretrained_params(path: str, template_params,
                           data_path: Optional[str] = None):
    """Load model params from a checkpoint file for warm-starting training
    (reference ``model_config.pretrained_model_path``, ``core/config.py:93``;
    relative paths resolve against ``data_path``, ``core/config.py:744-745``).

    Accepts a full :class:`ServerState` dump from EITHER backend (msgpack
    file or orbax checkpoint directory — anything this module wrote:
    ``latest``/``epoch<i>``/``best_val_*``) or a bare params-pytree
    msgpack; only the params are taken.
    """
    if not os.path.isabs(path) and not os.path.exists(path) and data_path:
        path = os.path.join(data_path, path)
    if os.path.isdir(path):
        # orbax checkpoint directory
        import orbax.checkpoint as ocp
        with ocp.Checkpointer(ocp.StandardCheckpointHandler()) as cp:
            restored = cp.restore(os.path.abspath(path))
    else:
        with open(path, "rb") as fh:
            restored = serialization.msgpack_restore(fh.read())
    target = jax.device_get(template_params)
    if isinstance(restored, dict) and "params" in restored:
        restored = restored["params"]
    return serialization.from_state_dict(target, restored)


class CheckpointManager:
    """latest/every-N/best checkpoint policy + status log.

    Backends: ``msgpack`` (default; one flat file, synchronous) or
    ``orbax`` (``server_config.checkpoint_backend: orbax``) — async saves
    via ``orbax.checkpoint.AsyncCheckpointer``, so serialization/IO of the
    previous round's state overlaps the next rounds' device compute (the
    TPU-framework norm for big models; the reference's torch.save has no
    async path).

    Async durability contract: a round's checkpoint becomes the committed
    resume anchor at the NEXT save/load/wait (two-slot + pointer for
    ``latest``, tmp-dir + rename for ``best``), so a hard crash can lose
    at most the one most recent round — the inherent async window.

    Resilience contract (resilience/integrity.py): every physical write
    retries under the bounded backoff policy
    (``server_config.checkpoint_retry``); a fully-failed save warns and
    training continues UNTIL ``escalation_threshold`` consecutive
    failures, which abort via :class:`CheckpointEscalationError`.  Saves
    record crc32 checksums (``.sum`` sidecars / the orbax pointer);
    loads verify them and fall back to the surviving slot
    (``latest_model.msgpack.prev`` / the other orbax slot) on
    corruption, logging a recovery event.
    """

    def __init__(self, model_dir: str, backup_freq: int = 100,
                 backend: str = "msgpack", async_latest: bool = False,
                 retry: Optional[RetryPolicy] = None,
                 io_fault: Optional[Callable[[], None]] = None):
        self.model_dir = model_dir
        self.backup_freq = max(int(backup_freq), 1)
        if backend not in ("msgpack", "orbax"):
            raise ValueError(f"unknown checkpoint backend {backend!r}")
        self.backend = backend
        #: bounded retry + backoff for transient IO failures
        #: (``server_config.checkpoint_retry``) and the consecutive-
        #: failure escalation that aborts instead of training
        #: uncheckpointed forever
        self.retry = retry or RetryPolicy()
        self.escalator = FailureEscalator(self.retry.escalation_threshold)
        #: optional flutescope scope (assigned by the server): writer-
        #: thread spans + structured recovery/fault events; None keeps
        #: every emission a metrics-stream-only record or a no-op
        self.telemetry = None
        #: chaos hook: called at the start of every physical write
        #: attempt; raises to inject a deterministic IO fault — wrapped
        #: so every injected fault leaves a structured event record
        #: (tools/chaos_smoke.py asserts these reach the trace)
        base_fault = io_fault or (lambda: None)

        def _fault_probe():
            try:
                base_fault()
            except Exception:
                emit_event(self.telemetry, "ckpt_io_fault")
                raise

        self._io_fault = _fault_probe
        #: load-time integrity/fallback observability: one dict per
        #: recovery (corrupted slot skipped, backup slot used, ...)
        self.recovery_events: List[Dict[str, str]] = []
        self._orbax = None
        self._pending_slot = None
        self._pending_renames = []  # [(tmp_dir, final_dir)] after async save
        if backend == "orbax":
            import orbax.checkpoint as ocp
            self._ocp = ocp
            self._orbax = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
        # msgpack async-latest: per-round ``latest`` saves, and then the
        # best-model saves too, hand a DEVICE snapshot to a writer
        # thread, so the device->host transfer and the disk write
        # overlap the next rounds' compute (the per-round
        # sync fetch is the faithful-mode fullrun's dominant cost on a
        # remote-attached chip; SURVEY §7 explicitly budgets for async
        # checkpointing).  Same durability contract as the orbax path: a
        # hard crash can lose at most the in-flight save.
        self.async_latest = bool(async_latest) and backend == "msgpack"
        self._mp_cond = threading.Condition()
        self._mp_mailbox = None   # single-slot device snapshot (see _mp_submit)
        self._mp_target = None    # ... and the file it is to become
        #: the writer keeps a held snapshot on the device until the
        #: training thread waits for it (:meth:`_await_writer`) or lets
        #: it go (:meth:`release`)
        self._mp_hold = False
        self._mp_busy = False
        self._mp_worker = None
        #: file -> whether the writer's newest save of it landed
        self._mp_landed: Dict[str, bool] = {}
        #: the files of a best-model save still with the writer, until
        #: :meth:`land_best` has seen it land and linked its twins
        self._best_pending: Optional[Tuple[str, ...]] = None
        self._best_ok = True
        os.makedirs(model_dir, exist_ok=True)

    # -- orbax helpers -------------------------------------------------
    _LATEST_SLOTS = ("latest_model.orbax.a", "latest_model.orbax.b")
    _LATEST_PTR = "latest_model.orbax.ptr"

    def _orbax_path(self, name: str) -> str:
        # orbax checkpoints are directories; keep the msgpack names with a
        # .orbax suffix so both backends can coexist in one model_dir
        return os.path.join(os.path.abspath(self.model_dir),
                            name.replace(".msgpack", ".orbax"))

    def _recover(self, event: str, path: str) -> None:
        """Record + log one integrity-recovery event (corrupt slot
        skipped, fallback slot used) — also a structured record in the
        metrics stream (and the trace, when telemetry is on) instead of
        a log-line-only breadcrumb."""
        self.recovery_events.append({"event": event, "path": path})
        emit_event(self.telemetry, "checkpoint_recovery", detail=event,
                   path=path)
        print_rank(f"checkpoint recovery: {event} ({path})",
                   loglevel=logging.WARNING)

    def _orbax_save(self, path: str, state: ServerState) -> None:
        """Issue one async save, with the bounded-retry policy on the
        submit itself (actual IO failures surface later in ``_drain``);
        a fully-failed submit counts toward the failure escalation."""
        payload = serialization.to_state_dict(_payload(state))
        self._drain()  # one in-flight save at a time + commit renames

        def _submit():
            self._io_fault()
            self._orbax.save(path, args=self._ocp.args.StandardSave(payload),
                             force=True)

        if run_with_retry(_submit, self.retry,
                          what=f"orbax save {os.path.basename(path)}"):
            self.escalator.record_success()
        else:
            self.escalator.record_failure(f"orbax save {path}")
        self.escalator.check()

    def _drain(self) -> None:
        """Finish the in-flight save (tolerating failure, which counts
        toward the escalation threshold) and perform any deferred
        directory renames.  Failed renames are RE-QUEUED for the next
        drain — a transient NFS error must not strand a completed save
        in its tmp dir forever."""
        try:
            self._orbax.wait_until_finished()
        except (KeyboardInterrupt, SystemExit):
            # fatal signals propagate — a Ctrl-C mid-wait must kill the
            # run, not be logged away as a failed save
            raise
        except Exception as exc:
            print_rank(f"async checkpoint save failed: {exc!r}",
                       loglevel=logging.WARNING)
            self._pending_slot = None
            self.escalator.record_failure("orbax async save")
            # pending renames are NOT cleared: they reference tmp dirs of
            # earlier, possibly successful saves — the isdir() guard below
            # skips any whose save really did fail
            return
        survivors = []
        for tmp, final in self._pending_renames:
            if not os.path.isdir(tmp):
                continue
            old = final + ".old"
            try:
                # a crash between the renames below can leave a stale .old
                # behind; clear it or os.rename onto it raises ENOTEMPTY
                # forever after
                shutil.rmtree(old, ignore_errors=True)
                if os.path.isdir(final):
                    os.rename(final, old)
                os.rename(tmp, final)
                shutil.rmtree(old, ignore_errors=True)
            except OSError as exc:
                print_rank(f"checkpoint rename {tmp} -> {final} failed: "
                           f"{exc!r}; re-queued for the next drain",
                           loglevel=logging.WARNING)
                survivors.append((tmp, final))
        self._pending_renames = survivors

    def _orbax_load(self, path: str,
                    template: ServerState) -> Optional[ServerState]:
        if not os.path.isdir(path):
            return None
        self._orbax.wait_until_finished()
        target = serialization.to_state_dict(jax.device_get(
            _payload(template)))
        restored = self._orbax.restore(
            path, args=self._ocp.args.StandardRestore(target))
        return _merge(template, restored)

    def _commit_pending_latest(self) -> None:
        """Point the latest-pointer at the slot whose async save has now
        finished (two-slot scheme: the previous committed slot stays valid
        through the entire save window, so a crash mid-save never loses
        the resume anchor — the async analogue of tmp+os.replace).  The
        pointer records the slot's tree checksum, verified at load."""
        if self._pending_slot is None:
            self._drain()
            return
        slot = self._pending_slot
        self._pending_slot = None
        self._drain()
        slot_dir = self._orbax_path(slot)
        if not os.path.isdir(slot_dir):
            return  # the save failed; keep pointing at the old slot
        self.escalator.record_success()
        ptr = os.path.join(self.model_dir, self._LATEST_PTR)
        tmp = ptr + ".tmp"
        with open(tmp, "w") as fh:
            json.dump({"slot": slot, "crc32": tree_checksum(slot_dir)}, fh)
        os.replace(tmp, ptr)

    def _latest_ptr(self) -> Optional[Dict[str, Any]]:
        """Parse the latest pointer: new JSON form ``{"slot", "crc32"}``
        or the legacy bare slot-name string (no checksum -> no
        verification, so pre-integrity checkpoints keep loading)."""
        ptr = os.path.join(self.model_dir, self._LATEST_PTR)
        if not os.path.exists(ptr):
            return None
        with open(ptr) as fh:
            text = fh.read().strip()
        if not text:
            return None
        try:
            parsed = json.loads(text)
            if isinstance(parsed, dict) and "slot" in parsed:
                return parsed
        except json.JSONDecodeError:
            pass
        return {"slot": text, "crc32": None}

    def _latest_slot(self) -> Optional[str]:
        parsed = self._latest_ptr()
        return None if parsed is None else parsed.get("slot")

    def wait(self) -> None:
        """Block until pending async saves are durable (call before reading
        checkpoint files externally or at process exit)."""
        if self._orbax is not None:
            self._commit_pending_latest()
        self._mp_wait("exit")

    # -- msgpack async writer -------------------------------------------
    def _await_writer(self, why: Optional[str] = None) -> None:
        """The training thread waits for the writer's slot to empty: a
        span of its own (``ckpt_wait``), since it is the one place where
        the disk can hold the training thread."""
        # `for`: the save last handed to the writer is what is waited for
        why = why or ("best" if "best_val_" in (self._mp_target or "")
                      else "latest")
        with (self.telemetry.span("ckpt_wait", **{"for": why})
              if self.telemetry is not None else NULL_SPAN):
            with self._mp_cond:
                self._mp_hold = False
                self._mp_cond.notify_all()
                while self._mp_mailbox is not None or self._mp_busy:
                    self._mp_cond.wait()

    def release(self) -> None:
        """Let the writer fetch a snapshot it was handed on hold, without
        waiting for the file (nothing held: nothing happens)."""
        with self._mp_cond:
            self._mp_hold = False
            self._mp_cond.notify_all()

    def _mp_wait(self, why: Optional[str] = None) -> None:
        """Wait until the writer holds nothing; a best-model save that
        was with it gets its other metric names linked to the file it
        wrote (:meth:`land_best` then says how it went)."""
        if self._mp_worker is None and self._best_pending is None:
            return
        self._await_writer(why)
        paths, self._best_pending = self._best_pending, None
        if paths is not None:
            self._best_ok = self._mp_landed.pop(paths[0], False) and \
                self._link_twins(paths)
        # surface the writer thread's accumulated failures HERE, on the
        # training thread — an exception raised inside the daemon writer
        # would vanish and the run would train uncheckpointed forever
        self.escalator.check()

    def _mp_loop(self) -> None:
        while True:
            with self._mp_cond:
                while self._mp_mailbox is None:
                    self._mp_cond.wait()
                snap, path = self._mp_mailbox, self._mp_target
                self._mp_mailbox = None
                self._mp_busy = True
            landed = False
            try:
                # flutescope: the async writer's fetch+serialize+write
                # appears on ITS OWN thread track in the trace — the
                # direct visual of checkpoint IO overlapping (or
                # stalling) device rounds
                with (self.telemetry.span("ckpt_async_write",
                                          file=os.path.basename(path))
                      if self.telemetry is not None else NULL_SPAN) as span:
                    # wait for the snapshot program BEFORE asking for its
                    # transfers: a device_get on arrays still to be
                    # computed queues its device-to-host copies to fire at
                    # the round program's end, where they can get ahead of
                    # the training thread's stats fetch that the same end
                    # releases (the fence was then seen ~2 ms late)
                    jax.block_until_ready(snap)
                    with self._mp_cond:
                        # a best-model snapshot's transfers start when
                        # the training thread comes to wait for the file,
                        # which it does once the next dispatch is
                        # launched: asked for earlier, 1.9 GB of them
                        # queue ahead of that dispatch's inputs (0.4 s of
                        # an idle device on the chip) or of an
                        # evaluation's fetch
                        while self._mp_hold:
                            self._mp_cond.wait()
                    blob = _state_chunks(snap)
                    if span is not None:
                        span["bytes"] = _chunks_size(blob)
                    # the chunks hold the HBM snapshot now, each leaf
                    # until the write has fetched it
                    del snap
                    # _write_blob already retries + counts the failure
                    # toward escalation; the abort itself surfaces at the
                    # training thread's next submit/wait (escalator.check
                    # there), never out of this daemon thread where it
                    # would vanish
                    landed = self._write_blob(
                        path, blob,
                        keep_prev=os.path.basename(path) == LATEST)
                    del blob
            except Exception as exc:  # never kill training from the writer
                print_rank(f"async save of {os.path.basename(path)} "
                           f"failed: {exc!r}", loglevel=logging.WARNING)
                self.escalator.record_failure(
                    f"async serialize {os.path.basename(path)}")
            except BaseException:
                # fatal signals must not be logged away; they take this
                # thread with them, and the next submit starts another
                # (a wait on a dead writer would never return)
                self._mp_worker = None
                raise
            finally:
                with self._mp_cond:
                    self._mp_landed[path] = landed
                    self._mp_busy = False
                    self._mp_cond.notify_all()

    def _mp_submit(self, state: ServerState, name: str = LATEST,
                   hold: bool = False) -> Dict[str, int]:
        """Hand a snapshot of ``state`` to the writer thread, to become
        the file ``name`` of the model directory; returns what the
        snapshot launched on the device (``leaves`` copied, ``programs``
        dispatched) for the caller's span.  ``hold``: the writer fetches
        it only once the training thread waits for the writer."""
        # single-slot, not latest-wins: wait for the in-flight save first,
        # so the on-disk latest can lag the status log by AT MOST the one
        # in-flight round — the same durability window the orbax path
        # documents.  (Latest-wins would let a slow disk stack unbounded
        # skew between latest_model and status_log.json, and resume pairs
        # the two.)  The wait also bounds snapshot HBM to one extra copy.
        self.escalator.check()  # abort on the training thread, not the writer
        self._await_writer()
        if self._mp_worker is None:
            self._mp_worker = threading.Thread(
                target=self._mp_loop, name="ckpt-latest-writer", daemon=True)
            self._mp_worker.start()
        # device-side copy: the round step donates the live param/opt
        # buffers, so the snapshot must be arrays nothing else consumes.
        # ONE program copies every device leaf (a copy per leaf ran into
        # the runtime's bound on programs in flight on a 62-leaf model:
        # the submit then sat until the running round program retired).
        # It is enqueued on the device stream BEFORE any later donating
        # program, so it reads the pre-donation values; the writer
        # thread's fetch then overlaps the next rounds.
        # Host numpy leaves (e.g. mutable strategy_state arrays) are
        # np.copy'd for the same reason: a by-reference share would let
        # an in-place mutation on the training thread reach the writer's
        # serialize mid-flight and persist a torn value.
        leaves, treedef = jax.tree.flatten(_payload(state))
        on_device = [i for i, x in enumerate(leaves)
                     if isinstance(x, jax.Array)]
        if on_device:
            copies = _copy_device_leaves([leaves[i] for i in on_device])
            for i, copied in zip(on_device, copies):
                leaves[i] = copied
        snap = jax.tree.unflatten(
            treedef, [np.copy(x) if isinstance(x, np.ndarray) else x
                      for x in leaves])
        with self._mp_cond:
            self._mp_mailbox = snap
            # flint: disable=thread-escape a bool, immutable: nothing to tear
            self._mp_hold = hold
            # flint: disable=thread-escape a str, immutable: nothing to tear
            self._mp_target = os.path.join(self.model_dir, name)
            self._mp_cond.notify()
        return {"leaves": len(on_device), "programs": int(bool(on_device))}

    # -- save ----------------------------------------------------------
    def save_latest(self, state: ServerState,
                    same_as: Optional[str] = None, hold: bool = False
                    ) -> Optional[Dict[str, int]]:
        """Save ``latest``; the async msgpack path returns what its
        device snapshot launched (see :meth:`_mp_submit`).  ``same_as``:
        the file :meth:`save_best` was given THIS VERY STATE for (the
        caller's knowledge: an evaluation round's state goes out as the
        best model and then as that round's ``latest``).  The msgpack
        ``latest`` is then a link to it, made here once that file has
        landed (:meth:`land_best`): durable on return, and no second
        1.9 GB through the disk.  A best-model save that failed leaves
        nothing to link to: the round then has no ``latest`` of its own,
        as after any failed save.  ``hold``: the async writer copies the
        snapshot on the device now and fetches it once the caller says
        :meth:`release` (or waits for the writer), as :meth:`save_best`
        does."""
        if self.backend == "orbax":
            self._commit_pending_latest()
            committed = self._latest_slot()
            slot = (self._LATEST_SLOTS[1]
                    if committed == self._LATEST_SLOTS[0]
                    else self._LATEST_SLOTS[0])
            self._orbax_save(self._orbax_path(slot), state)
            self._pending_slot = slot
            return None
        path = os.path.join(self.model_dir, LATEST)
        if same_as is not None:
            self._mp_wait()  # an earlier round's latest lands first
            if self._best_ok:
                self._write_blob(path, _LinkTo(same_as), keep_prev=True)
            self.escalator.check()
            return None
        if self.async_latest:
            return self._mp_submit(state, hold=hold)
        self._write((path,), state)
        return None

    def backup(self, state: ServerState, round_no: int,
               best_names: Tuple[str, ...] = ()) -> None:
        """Every ``backup_freq`` rounds: ``epoch<i>`` copy + snapshots of the
        best-model files (reference ``core/server.py:530-558``)."""
        if round_no % self.backup_freq:
            return
        if self.backend == "orbax":
            self.wait()  # copies must see complete checkpoints
            slot = self._latest_slot()
            src = self._orbax_path(slot) if slot else ""
            if src and os.path.isdir(src):
                dst = self._orbax_path(f"epoch{round_no}.orbax")
                if not os.path.isdir(dst):
                    shutil.copytree(src, dst)
            for name in best_names:
                best = self._orbax_path(f"best_val_{name}_model.orbax")
                dst = self._orbax_path(
                    f"best_val_{name}_model_epoch{round_no}.orbax")
                if os.path.isdir(best) and not os.path.isdir(dst):
                    shutil.copytree(best, dst)
            return
        self._mp_wait()  # the copies must see the newest files, whole
        src = os.path.join(self.model_dir, LATEST)
        if os.path.exists(src):
            shutil.copyfile(src, os.path.join(self.model_dir,
                                              f"epoch{round_no}.msgpack"))
        for name in best_names:
            best = os.path.join(self.model_dir, f"best_val_{name}_model.msgpack")
            if os.path.exists(best):
                shutil.copyfile(best, os.path.join(
                    self.model_dir, f"best_val_{name}_model_epoch{round_no}.msgpack"))

    def save_best(self, state: ServerState, metric_name: str,
                  *more_names: str, hold: bool = False) -> Optional[str]:
        """Best-val checkpoint on improvement (reference
        ``core/evaluation.py:103-109``), one file per metric name.
        Metrics that improved at the same evaluation hold the same
        state: it is fetched and written once, the other names are links
        to that file.  Returns the file written (for
        :meth:`save_latest`'s ``same_as``), None with orbax.  With the
        async msgpack writer the state goes to the writer as a device
        snapshot and the file is on the disk, with its links, once
        :meth:`land_best` returns: whoever writes a NAME for this state
        (the status log's ``best_val_*``, the ``latest`` link, a backup
        copy) calls that first; ``hold``: the caller is about to, and
        the writer starts the snapshot's transfers only then
        (:meth:`_mp_submit`).  Without the writer the save is
        synchronous: durable on return."""
        if self.backend == "orbax":
            for name in more_names:
                self.save_best(state, name)
            # async save to a .new dir; the rename into place happens at
            # the next drain, with the previous best parked at .old until
            # the swap completes — no moment without a readable best
            final = self._orbax_path(f"best_val_{metric_name}_model.orbax")
            tmp = final + ".new"
            shutil.rmtree(tmp, ignore_errors=True)
            self._orbax_save(tmp, state)
            self._pending_renames.append((tmp, final))
            return None
        paths = tuple(
            os.path.join(self.model_dir, f"best_val_{name}_model.msgpack")
            for name in (metric_name,) + more_names)
        self.land_best()  # an earlier one's links are made from ITS file
        if self.async_latest:
            self._mp_submit(state, os.path.basename(paths[0]), hold=hold)
            self._best_pending = paths
            return paths[0]
        self._best_ok = self._write(paths, state)
        return paths[0] if self._best_ok else None

    def land_best(self) -> bool:
        """If a best-model save is with the writer, wait for it (its
        failure surfaces here, on the training thread).  Returns whether
        the newest best-model save is on the disk, sidecar and links and
        all; a failure is already counted toward escalation.  A
        ``latest`` in flight is not waited for: the status log may run
        that one round ahead (the single slot's bound)."""
        if self._best_pending is not None:
            self._mp_wait()
        return self._best_ok

    def _link_twins(self, targets: Tuple[str, ...]) -> bool:
        """Every target after the first becomes a link to the first,
        with its sidecar."""
        first = targets[0]

        def link():
            for twin in targets[1:]:
                _rotate(first, twin)
                _rotate(first + SIDECAR_SUFFIX, twin + SIDECAR_SUFFIX)

        if not targets[1:] or run_with_retry(link, self.retry,
                                             what="checkpoint links"):
            return True
        self.escalator.record_failure(f"save {targets[1]}")
        return False

    def _write_blob(self, path: str, blob,
                    keep_prev: bool = False) -> bool:
        """Atomic tmp-write + rename under the bounded-retry policy —
        THE write recipe, shared by the sync and async-latest paths.
        ``blob``: bytes, or the chunks of :func:`_state_chunks`, which
        are fetched, checksummed and written one after the other.
        Records a crc32 sidecar (verified at load) and, for the latest
        slot (``keep_prev``), rotates the previous generation to
        ``.prev`` first so corruption always has a fallback.  Returns
        success; the failure is already counted toward escalation (the
        CALLER decides where the abort surfaces — training thread only).
        """
        same_as = blob.path if isinstance(blob, _LinkTo) else None
        chunks = [] if same_as else \
            blob if isinstance(blob, list) else [blob]

        def _save():
            self._io_fault()
            tmp = path + ".tmp"
            if same_as:
                meta = read_sidecar(same_as)
                _rotate(same_as, tmp)
            else:
                crc = size = 0
                with open(tmp, "wb") as fh:
                    for i, chunk in enumerate(chunks):
                        # the host bytes stay in the list: a retry finds
                        # them there, and the device leaf is let go
                        chunk = chunks[i] = _host_bytes(chunk)
                        crc = zlib.crc32(chunk, crc)
                        fh.write(chunk)
                        size += len(chunk)
                meta = {"crc32": checksum_hex(crc), "size": size}
            if keep_prev and os.path.exists(path):
                # blob then sidecar: a crash between the two leaves
                # .prev's sidecar one generation stale, which the
                # integrity check REJECTS (fail-safe) — the still-intact
                # `path` remains the loadable anchor through that window
                _rotate(path, path + ".prev")
                if os.path.exists(path + ".sum"):
                    _rotate(path + ".sum", path + ".prev.sum")
            os.replace(tmp, path)
            write_sidecar(path, meta["crc32"], meta["size"])

        if run_with_retry(_save, self.retry,
                          what=f"checkpoint save {os.path.basename(path)}"):
            self.escalator.record_success()
            return True
        self.escalator.record_failure(f"save {path}")
        emit_event(self.telemetry, "checkpoint_save_failed",
                   path=os.path.basename(path),
                   consecutive=self.escalator.consecutive)
        return False

    def _write(self, targets: Tuple[str, ...], state: ServerState) -> bool:
        """The synchronous save, on the caller's thread: ``targets[0]``
        written (the ``latest`` slot keeps its previous generation),
        every further target a link to it with its sidecar — a later
        save of any of these names replaces that name's file and leaves
        the others'."""
        first = targets[0]
        with (self.telemetry.span("ckpt_write")
              if self.telemetry is not None else NULL_SPAN) as span:
            blob = _state_chunks(_payload(state))
            if span is not None:
                span["bytes"] = _chunks_size(blob)
            done = self._write_blob(
                first, blob, keep_prev=os.path.basename(first) == LATEST)
            del blob
        done = done and self._link_twins(targets)
        self.escalator.check()
        return done

    # -- load ----------------------------------------------------------
    def load(self, template: ServerState,
             name: str = LATEST) -> Optional[ServerState]:
        if self.backend == "orbax":
            self._commit_pending_latest()
            if name == LATEST:
                return self._orbax_load_latest(template)
            path = self._orbax_path(name)
            restored = self._orbax_load(path, template)
            if restored is None:
                # crash mid-swap: the previous version is parked at .old
                restored = self._orbax_load(path + ".old", template)
            return restored
        self._mp_wait()  # what is with the writer must land first
        path = os.path.join(self.model_dir, name)
        candidates = [path]
        if name == LATEST:
            # two-slot fallback: the previous generation survives at
            # .prev; a corrupted/torn latest resumes one round back
            # instead of not at all
            candidates.append(os.path.join(self.model_dir, LATEST_PREV))
        for cand in candidates:
            if not os.path.exists(cand):
                continue
            with open(cand, "rb") as fh:
                blob = fh.read()
            try:
                verify_blob(cand, blob)
                state = _state_from_bytes(blob, template)
            except (KeyboardInterrupt, SystemExit):
                raise
            except CheckpointCorruptionError as exc:
                self._recover(f"integrity check failed: {exc}", cand)
                continue
            except Exception as exc:  # torn/truncated msgpack
                self._recover(f"unreadable checkpoint: {exc!r}", cand)
                continue
            if cand != path:
                self._recover("restored from backup slot", cand)
            return state
        return None

    def _orbax_load_latest(self, template: ServerState
                           ) -> Optional[ServerState]:
        """Latest via the pointer, with checksum verification and
        automatic fallback to the OTHER slot on corruption/torn-write
        (the previous committed generation keeps living there until the
        slot is reused two saves later)."""
        parsed = self._latest_ptr()
        if parsed is None:
            return None
        slot = parsed.get("slot")
        other = (self._LATEST_SLOTS[1] if slot == self._LATEST_SLOTS[0]
                 else self._LATEST_SLOTS[0])
        for cand in (slot, other):
            path = self._orbax_path(cand)
            if not os.path.isdir(path):
                continue
            if cand == slot and parsed.get("crc32"):
                actual = tree_checksum(path)
                if actual != parsed["crc32"]:
                    self._recover(
                        f"slot checksum {actual} != recorded "
                        f"{parsed['crc32']}", path)
                    continue
            try:
                restored = self._orbax_load(path, template)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as exc:
                self._recover(f"unreadable orbax slot: {exc!r}", path)
                continue
            if restored is None:
                continue
            if cand != slot:
                self._recover("restored from backup slot", path)
            return restored
        return None

    def load_best(self, template: ServerState,
                  metric_name: str) -> Optional[ServerState]:
        return self.load(template, f"best_val_{metric_name}_model.msgpack")

    # -- status log ----------------------------------------------------
    def update_status(self, update: Dict[str, Any]) -> Dict[str, Any]:
        return update_json_log(os.path.join(self.model_dir, STATUS_LOG), update)

    def read_status(self) -> Dict[str, Any]:
        path = os.path.join(self.model_dir, STATUS_LOG)
        if os.path.exists(path):
            with open(path) as fh:
                return json.load(fh)
        return {}
