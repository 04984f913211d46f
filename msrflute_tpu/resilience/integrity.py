"""Checkpoint integrity + bounded-retry primitives.

Three small, composable pieces the checkpoint backends share:

- **checksums**: crc32 of a serialized blob (msgpack files) or of a
  checkpoint directory tree (orbax slots), recorded in a ``.sum``
  sidecar / the ``latest_model.orbax.ptr`` pointer and verified at load
  time.  A mismatch means corruption or a torn write — the loader falls
  back to the surviving slot instead of resuming garbage.
- **RetryPolicy**: bounded retry with exponential backoff + jitter for
  transient IO failures (NFS blips, disk-full races), replacing the
  fixed 3x1s loop.  Config-capped via ``server_config.checkpoint_retry``.
- **FailureEscalator**: counts CONSECUTIVE fully-failed saves; at the
  configured threshold it raises :class:`CheckpointEscalationError`
  instead of letting training run uncheckpointed forever behind
  warn-and-continue logs nobody reads.
"""

from __future__ import annotations

import json
import logging
import os
import random
import time
import zlib
from dataclasses import dataclass
from typing import Callable, Optional

from ..utils.logging import print_rank

#: suffix of the checksum sidecar written next to msgpack checkpoints
SIDECAR_SUFFIX = ".sum"


class CheckpointCorruptionError(RuntimeError):
    """A checkpoint failed its integrity check (checksum mismatch or an
    unreadable/torn file)."""


class CheckpointEscalationError(RuntimeError):
    """Too many consecutive checkpoint-save failures: the run can no
    longer be considered resumable and must stop instead of silently
    training uncheckpointed."""


# ----------------------------------------------------------------------
# checksums
# ----------------------------------------------------------------------
def checksum_hex(crc: int) -> str:
    """A (running) ``zlib.crc32`` as the sidecars record it."""
    return f"{crc & 0xFFFFFFFF:08x}"


def blob_checksum(blob: bytes) -> str:
    """crc32 (hex) of a serialized checkpoint blob.  crc32, not a
    cryptographic hash: the threat model is torn writes and bit rot, not
    an adversary, and crc32 streams at memory bandwidth."""
    return checksum_hex(zlib.crc32(blob))


def tree_checksum(dir_path: str) -> str:
    """crc32 (hex) over a checkpoint DIRECTORY: relative file names and
    contents, walked in sorted order so the digest is layout-stable.
    Used for orbax slots, whose checkpoint is a directory tree."""
    crc = 0
    for root, dirs, files in os.walk(dir_path):
        dirs.sort()
        for name in sorted(files):
            path = os.path.join(root, name)
            rel = os.path.relpath(path, dir_path).replace(os.sep, "/")
            crc = zlib.crc32(rel.encode("utf-8"), crc)
            with open(path, "rb") as fh:
                while True:
                    chunk = fh.read(1 << 20)
                    if not chunk:
                        break
                    crc = zlib.crc32(chunk, crc)
    return f"{crc & 0xFFFFFFFF:08x}"


def write_sidecar(path: str, checksum: str, size: int) -> None:
    """Atomically record a blob's checksum next to it (``<path>.sum``).
    Written AFTER the blob itself lands, so a sidecar always describes a
    fully-written file; a missing sidecar downgrades load-time
    verification to a warning (pre-integrity checkpoints stay loadable)."""
    sidecar = path + SIDECAR_SUFFIX
    tmp = sidecar + ".tmp"
    with open(tmp, "w") as fh:
        json.dump({"crc32": checksum, "size": size}, fh)
    os.replace(tmp, sidecar)


def read_sidecar(path: str) -> Optional[dict]:
    sidecar = path + SIDECAR_SUFFIX
    if not os.path.exists(sidecar):
        return None
    try:
        with open(sidecar) as fh:
            return json.load(fh)
    except (json.JSONDecodeError, OSError):
        # a torn sidecar must not make a good blob unloadable
        return None


def verify_blob(path: str, blob: bytes) -> None:
    """Raise :class:`CheckpointCorruptionError` if ``blob`` does not
    match the sidecar recorded for ``path``.  No sidecar (pre-integrity
    checkpoint) verifies vacuously."""
    meta = read_sidecar(path)
    if meta is None:
        return
    if meta.get("size") is not None and meta["size"] != len(blob):
        raise CheckpointCorruptionError(
            f"{path}: size {len(blob)} != recorded {meta['size']} "
            "(torn write?)")
    actual = blob_checksum(blob)
    if meta.get("crc32") and actual != meta["crc32"]:
        raise CheckpointCorruptionError(
            f"{path}: crc32 {actual} != recorded {meta['crc32']}")


# ----------------------------------------------------------------------
# retry + escalation
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class RetryPolicy:
    """Bounded retry with exponential backoff + jitter
    (``server_config.checkpoint_retry``).  ``escalation_threshold``
    consecutive fully-failed SAVES (each already retried ``retries``
    times) abort the run via :class:`CheckpointEscalationError`."""

    retries: int = 3
    backoff_base_s: float = 0.5
    backoff_max_s: float = 30.0
    jitter: float = 0.25          # +- fraction of the computed delay
    escalation_threshold: int = 10

    @classmethod
    def from_config(cls, raw: Optional[dict]) -> "RetryPolicy":
        if not raw:
            return cls()
        return cls(
            retries=int(raw.get("retries", cls.retries)),
            backoff_base_s=float(raw.get("backoff_base_s",
                                         cls.backoff_base_s)),
            backoff_max_s=float(raw.get("backoff_max_s", cls.backoff_max_s)),
            jitter=float(raw.get("jitter", cls.jitter)),
            escalation_threshold=int(raw.get("escalation_threshold",
                                             cls.escalation_threshold)),
        )

    def delay(self, attempt: int) -> float:
        """Backoff before retry ``attempt`` (0-based): exponential,
        capped, jittered.  Jitter decorrelates concurrent writers hitting
        the same overloaded filesystem — it deliberately does NOT come
        from any seeded stream (the chaos schedule's determinism
        guarantee covers which faults fire, never how long IO sleeps)."""
        base = min(self.backoff_max_s, self.backoff_base_s * (2.0 ** attempt))
        if self.jitter <= 0.0:
            return base
        return base * (1.0 + self.jitter * (2.0 * random.random() - 1.0))


def run_with_retry(fn: Callable[[], None], policy: RetryPolicy,
                   what: str = "save",
                   sleep: Callable[[float], None] = time.sleep) -> bool:
    """Run ``fn`` under ``policy``; True on success.  Transient
    exceptions are retried with backoff; ``KeyboardInterrupt`` /
    ``SystemExit`` always propagate (a Ctrl-C mid-save must kill the
    run, not burn the retry budget)."""
    for attempt in range(max(policy.retries, 1)):
        try:
            fn()
            return True
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as exc:  # noqa: BLE001 - deliberate: best-effort IO
            last = attempt == max(policy.retries, 1) - 1
            print_rank(
                f"{what} attempt {attempt + 1}/{policy.retries} failed: "
                f"{exc!r}" + ("" if last else "; backing off"),
                loglevel=logging.WARNING)
            if not last:
                sleep(policy.delay(attempt))
    return False


class DurableIOError(RuntimeError):
    """A durable-IO operation whose loss would corrupt training state
    (row-store read, writeback ``device_get``) exhausted its retry
    budget.  Raised FROM the training thread so the server's
    BaseException tail persists the flight record before aborting."""


class DurableIOLadder:
    """One retry/degradation policy object for ALL durable host IO.

    Generalizes the checkpoint-only RetryPolicy + FailureEscalator pair
    into the explicit degradation table flutearmor documents (RUNBOOK
    "Infrastructure-fault drill"): every surface shares ONE
    :class:`RetryPolicy` (``server_config.checkpoint_retry`` — one knob,
    one ladder), but keeps its OWN consecutive-failure escalator and its
    own exhaustion mode:

    - ``mode="escalate"`` (row-store SPILL, ControlStore marker): the
      failed rows stay host-visible (the caller keeps them dirty / in
      the spilling map), so a lost write degrades capacity, not
      correctness — but ``escalation_threshold`` consecutive exhausted
      writes abort via :class:`CheckpointEscalationError` exactly like
      an uncheckpointable run would.
    - ``mode="raise"`` (row-store READ, writeback ``device_get``):
      exhaustion raises :class:`DurableIOError` immediately — silently
      losing carry rows corrupts training, so the only honest move is a
      flight-recorded abort.
    - ``mode="drop"`` (rollup/metrics writers): exhaustion returns False
      and the caller drops the window + counts it — telemetry loss must
      never become a host-tail exception.
    """

    #: surface -> exhaustion mode; also the registry of valid surfaces
    MODES = {
        "store_write": "escalate",
        "store_read": "raise",
        "marker": "escalate",
        "writeback": "raise",
        "writer": "drop",
    }

    def __init__(self, policy: Optional[RetryPolicy] = None,
                 fault_hooks: Optional[dict] = None):
        self.policy = policy if policy is not None else RetryPolicy()
        #: surface -> zero-arg chaos raise-hook (InfraFaults.hook), run
        #: before each physical attempt so retries redraw fresh decisions
        self.fault_hooks = dict(fault_hooks or {})
        #: optional instant-event emitter ``event(kind, **fields)`` the
        #: server wires to flutescope — every failed attempt on a
        #: store-family surface lands a ``store_io_fault`` event, so the
        #: infra drill's degradations are all structured, never log-only
        self.event: Optional[Callable[..., None]] = None
        self.escalators = {
            name: FailureEscalator(self.policy.escalation_threshold)
            for name, mode in self.MODES.items() if mode == "escalate"
        }

    def run(self, fn: Callable[[], None], surface: str,
            what: str = "") -> bool:
        """Run one durable operation on ``surface`` under the ladder.
        True on success; on exhaustion, behave per the surface's mode
        (see class docstring).  ``what`` labels log lines."""
        mode = self.MODES[surface]
        hook = self.fault_hooks.get(surface)

        def attempt() -> None:
            try:
                if hook is not None:
                    hook()
                fn()
            except Exception as exc:
                # structured observability per failed attempt (injected
                # OR real), on the surfaces whose loss is a store/state
                # problem; writer failures get their own rollup event
                if self.event is not None and surface != "writer":
                    self.event("store_io_fault", surface=surface,
                               what=what, error=repr(exc))
                raise
        ok = run_with_retry(attempt, self.policy,
                            what=what or f"{surface} io")
        if ok:
            if mode == "escalate":
                self.escalators[surface].record_success()
            return True
        if mode == "raise":
            raise DurableIOError(
                f"{surface} IO exhausted its retry budget "
                f"({self.policy.retries} attempts){': ' + what if what else ''}"
                " — losing this data would corrupt training state")
        if mode == "escalate":
            esc = self.escalators[surface]
            esc.record_failure(what or surface)
            esc.check()
        return False


class FailureEscalator:
    """Consecutive-failure counter shared by the checkpoint writer paths.
    Thread-safe enough for its use (int ops under the GIL; the writer
    thread records, the training thread checks)."""

    def __init__(self, threshold: int):
        self.threshold = max(int(threshold), 1)
        self.consecutive = 0
        self.total = 0

    def record_failure(self, what: str) -> None:
        self.consecutive += 1
        self.total += 1
        print_rank(
            f"checkpoint failure #{self.consecutive} (consecutive) in "
            f"{what}; run aborts at {self.threshold}",
            loglevel=logging.WARNING)

    def record_success(self) -> None:
        self.consecutive = 0

    def check(self) -> None:
        """Raise once the consecutive-failure budget is spent.  Called
        from the TRAINING thread (submit/wait points), never from the
        async writer — a daemon thread's exception would vanish."""
        if self.consecutive >= self.threshold:
            raise CheckpointEscalationError(
                f"{self.consecutive} consecutive checkpoint-save failures "
                f"(threshold {self.threshold}): training is no longer "
                "resumable — aborting instead of running uncheckpointed. "
                "Fix the storage path or raise "
                "server_config.checkpoint_retry.escalation_threshold.")
