"""Benchmark harness — the four reference protocols on whatever chip JAX sees.

Reference headline numbers (BASELINE.md, from reference ``README.md:38-41``,
wall-clock for the full run incl. periodic eval):

    LR_MNIST             00:01:35 /  100 rounds  -> 0.9500 s/round
    CNN_FEMNIST          00:08:22 / 1500 rounds  -> 0.3347 s/round  (headline)
    RESNET_FEDCIFAR100   01:42:01 / 4000 rounds  -> 1.5303 s/round
    RNN_FEDSHAKESPEARE   00:21:50 / 1200 rounds  -> 1.0917 s/round

This harness replays each per-round protocol (synthetic data shaped like the
real dataset, real compute) and measures steady-state seconds/round with eval
amortized at the reference cadence.  It prints ONE JSON line:

    {"metric": "...", "value": N, "unit": "...", "vs_baseline": N, ...}

``vs_baseline`` > 1 means faster than FLUTE's published number.  The headline
metric is CNN_FEMNIST; the other three protocols, per-chunk percentiles, an
MFU estimate, and the backend used ride in the same line under ``extras``.

Backend handling: the process measures on the platform JAX gives it and
there is no fallback.  A platform other than ``tpu`` is a non-zero exit
unless ``BENCH_BACKEND=cpu`` asks for the CPU explicitly (the contract
tests do; the line then says ``"backend": "cpu"`` and carries no
utilisation).  Every line names ``platform``, ``device_kind`` and the
device count, and a protocol that raises makes the exit code non-zero
after the line is flushed.

Deadline contract: the JSON line is emitted even if this process is
SIGTERMed mid-run or its caller's deadline expires — results accumulate in
a module-global line state, kill-signal handlers flush it, and total
runtime is capped by ``BENCH_DEADLINE_SECS`` (default 25 min).
``BENCH_PARTIAL.json`` mirrors progress on disk against SIGKILL.
"""

from __future__ import annotations

import contextlib
import json
import os
import signal
import sys
import threading
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

BASELINES_SECS_PER_ROUND = {
    "lr_mnist": (1 * 60 + 35) / 100.0,
    "cnn_femnist": (8 * 60 + 22) / 1500.0,
    "resnet_fedcifar100": (1 * 3600 + 42 * 60 + 1) / 4000.0,
    "rnn_fedshakespeare": (21 * 60 + 50) / 1200.0,
}
# the bf16 extra races against the same published fp32 number
BASELINES_SECS_PER_ROUND["cnn_femnist_bf16"] = \
    BASELINES_SECS_PER_ROUND["cnn_femnist"]
HEADLINE = "cnn_femnist"
# TPU v5e peak: 197 TFLOP/s bf16 (394 int8).  We report model FLOPs utilisation
# against the bf16 peak even for f32 programs — a deliberately conservative
# denominator, stated here so the number is interpretable.  Source of
# truth is utils.compat.TPU_PEAK_FLOPS["v5e"] — mirrored as a literal
# because this module must not import anything jax-adjacent before
# backend selection; the mirror is pinned by tests/test_xla_truth.py.
V5E_BF16_PEAK_FLOPS = 197e12


# ----------------------------------------------------------------------
# deadline discipline: the JSON contract must survive being killed
# ----------------------------------------------------------------------
# A caller's `timeout` SIGTERMs this process mid-run; the "always emits
# its JSON line" promise must hold exactly then.  Three rules:
#
#   1. A module-global line state (`_LINE`) is updated incrementally as
#      each protocol finishes, so a flush at ANY moment carries every
#      result obtained so far.
#   2. SIGTERM/SIGALRM handlers flush that state to stdout and exit.
#      (SIGKILL can't be caught; for that, each update also mirrors the
#      state to `BENCH_PARTIAL.json` on disk.)
#   3. `BENCH_DEADLINE_SECS` (or the conservative default) caps total
#      runtime, with a margin reserved for the flush.
_LINE = {
    "metric": f"{HEADLINE}_secs_per_round",
    "value": None,
    "unit": "s/round",
    "vs_baseline": None,
    "extras": {},
}
_FLUSHED = False
_START = time.time()
# If the caller doesn't say how long we may run, assume a driver-style
# timeout and keep total runtime under it.
_DEADLINE_SECS = float(os.environ.get("BENCH_DEADLINE_SECS", 25 * 60))


def _remaining() -> float:
    return _DEADLINE_SECS - (time.time() - _START)


#: names of the sections that raised: the line records each under its
#: name as ``{"error": ...}`` and a non-empty list makes the exit code
#: non-zero once the line is out
_FAILED: list = []


def _record_failure(name: str, exc: BaseException) -> None:
    _LINE["extras"][name] = {"error": f"{type(exc).__name__}: {exc}"}
    _FAILED.append(name)
    print(f"[bench] section {name} failed: {type(exc).__name__}: {exc}",
          file=sys.stderr, flush=True)


#: popped exactly once (atomic under the GIL, safe from signal handlers
#: and threads alike) — whoever gets the token owns the one stdout line
_FLUSH_TOKEN = [None]
#: set once the line's owner has written and drained it.  A rescuer that
#: lost the token waits for this before it may ``os._exit``: one SIGALRM
#: reaches both the signal-watcher thread and the main thread's handler,
#: and an exit between the winner's pop and its write left stdout empty
_DELIVERED = threading.Event()
_FLUSH_OWNER = None

#: wall-clock of the last section boundary; the watchdog thread measures
#: stall time against this
_PROGRESS_TS = time.time()


def _note_progress() -> None:
    global _PROGRESS_TS
    _PROGRESS_TS = time.time()


def _flush(note: str | None = None) -> bool:
    """Emit the JSON contract line exactly once, whatever state we're in.
    Returns True iff THIS call owned (and delivered) the line."""
    global _FLUSHED, _FLUSH_OWNER
    try:
        _FLUSH_TOKEN.pop()
    except IndexError:
        # another thread/handler already owns the line: let it finish,
        # unless this is a handler on top of that very thread's flush
        if _FLUSH_OWNER != threading.get_ident():
            _DELIVERED.wait(5.0)
        return False
    _FLUSH_OWNER = threading.get_ident()
    _FLUSHED = True
    if note:
        _LINE["extras"]["flush_note"] = note
    head = _LINE["extras"].get(HEADLINE, {})
    if isinstance(head, dict):
        _LINE["value"] = head.get("secs_per_round")
        _LINE["vs_baseline"] = head.get("vs_baseline")
    sys.stdout.write(json.dumps(_LINE) + "\n")
    sys.stdout.flush()
    _DELIVERED.set()
    # a fully-delivered line supersedes the on-disk partial mirror: a
    # stale one would read as evidence of an aborted run
    if not note:
        try:
            os.remove(_partial_path())
        except OSError:
            pass
    return True


def _partial_path() -> str:
    # overridable so concurrent bench processes (e.g. the contract tests
    # running beside a real measurement) cannot delete each other's crash
    # evidence
    return os.environ.get(
        "BENCH_PARTIAL_PATH", os.path.join(REPO_ROOT, "BENCH_PARTIAL.json"))


def _mirror_partial() -> None:
    """Best-effort on-disk mirror of the current line state (survives
    even SIGKILL; overwritten by every later update)."""
    try:
        with open(_partial_path(), "w") as fh:
            json.dump(_LINE, fh, indent=1)
    except Exception:
        pass


def _on_kill_signal(signum, frame):  # noqa: ARG001 - signal API
    was_flushed = _FLUSHED
    _flush(f"killed by signal {signum} after {time.time() - _START:.0f}s; "
           "partial results")
    # _flush no-ops if the main thread already emitted the line but may
    # not have drained the pipe yet — drain unconditionally, or os._exit
    # below discards buffered stdio and stdout ends up empty after all
    try:
        sys.stdout.flush()
    except Exception:
        pass
    if not was_flushed:
        # a signal AFTER the successful flush must not resurrect the
        # partial mirror the flush just removed
        _mirror_partial()
    # exit immediately: we may be inside a device call that never
    # returns; os._exit skips atexit/GC that could block on the backend
    os._exit(1 if _FAILED else 0)


#: cap on how long ONE protocol may hold the process without finishing:
#: without it a single hung protocol (a device call that never returns)
#: eats the entire BENCH_DEADLINE_SECS before the self-flush fires.
#: Healthy on-chip protocols finish in well under this (compile included).
_STALL_SECS = float(os.environ.get("BENCH_PROTOCOL_STALL_SECS", 20 * 60))


def _margin() -> float:
    """Safety margin between self-rescue and the caller's deadline;
    shared by the SIGALRM arming and the watchdog backstop."""
    return min(20.0, _DEADLINE_SECS * 0.2)


def _rearm(stall: float | None = None) -> None:
    """Arm SIGALRM for the earlier of (final deadline - margin) and an
    optional per-protocol stall budget."""
    due = max(_remaining() - _margin(), 1.0)
    if stall is not None:
        due = min(due, stall)
    signal.alarm(int(max(due, 1.0)))


@contextlib.contextmanager
def _stall_scope(name: str):
    """One bench section under the stall alarm: `_in_flight` names it in
    any mid-section flush, the alarm drops back to the final deadline on
    the way out, and progress is mirrored to disk whatever happened."""
    extras = _LINE["extras"]
    extras["_in_flight"] = name
    _note_progress()
    _rearm(stall=_STALL_SECS)
    try:
        yield
    finally:
        extras.pop("_in_flight", None)
        _note_progress()
        _rearm()
        _mirror_partial()


def _watchdog_loop() -> None:
    """Daemon-thread deadline/stall backstop.

    Signals are NOT sufficient: a main thread stuck inside a native
    call that never re-enters the interpreter never runs its Python-level
    SIGTERM/SIGALRM handlers.
    ``os._exit`` from another thread is the only exit that still works;
    the flush token keeps the contract line exactly-once either way."""
    while not _FLUSHED:
        time.sleep(2.0)
        if _FLUSHED:
            return
        stall_for = time.time() - _PROGRESS_TS
        # the stall budget is PER SECTION: setup phases (jax import,
        # backend selection, dataset synthesis) are governed by the
        # final deadline only, so small stall budgets cannot kill a
        # healthy run before its first protocol
        stalled = ("_in_flight" in _LINE["extras"]
                   and stall_for > _STALL_SECS)
        if not stalled and _remaining() > _margin() * 0.5:
            continue
        why = (f"no section progress for {stall_for:.0f}s"
               if stalled else "deadline reached")
        if not _flush(f"watchdog exit: {why}; partial results"):
            return  # main delivered the line; let it finish normally
        try:
            sys.stdout.flush()
        except Exception:
            pass
        _mirror_partial()
        os._exit(1 if _FAILED else 0)


def _signal_watcher_loop(fd: int) -> None:
    """Thread-side signal delivery: ``signal.set_wakeup_fd`` writes the
    signal number to this pipe from the C-level handler the moment a
    signal lands — even while the main thread sits inside a long native
    call (an XLA compile, a hung device op) where the Python-level
    handler cannot run until the interpreter resumes.  Without this, a
    driver SIGTERM during a multi-minute compile missed its exit window
    (observed: the sigterm contract test timing out once real protocols
    compile in-process)."""
    while True:
        try:
            data = os.read(fd, 1)
        except OSError:
            return
        if not data:
            return
        signum = int(data[0])
        # only the two flush-and-exit signals end the run from here:
        # set_wakeup_fd reports EVERY Python-handled signal (e.g. a
        # Ctrl-C SIGINT, whose KeyboardInterrupt must keep its normal
        # non-zero, no-contract-line exit) — ignore the rest
        if signum in (signal.SIGTERM, signal.SIGALRM):
            _on_kill_signal(signum, None)  # flush + mirror + os._exit


def install_deadline_guards() -> None:
    """SIGTERM/SIGALRM -> flush-and-exit; SIGALRM armed a safety margin
    before the deadline so we self-flush even if nobody signals us.  The
    margin scales down with small deadlines so jax import + backend
    selection still fit inside tiny test budgets.  A watchdog thread
    backstops both signals (see ``_watchdog_loop``), and a wakeup-fd
    watcher thread delivers them even mid-native-call (see
    ``_signal_watcher_loop``)."""
    signal.signal(signal.SIGTERM, _on_kill_signal)
    signal.signal(signal.SIGALRM, _on_kill_signal)
    rfd, wfd = os.pipe()
    os.set_blocking(wfd, False)
    signal.set_wakeup_fd(wfd, warn_on_full_buffer=False)
    threading.Thread(target=_signal_watcher_loop, args=(rfd,),
                     name="bench-signal-watcher", daemon=True).start()
    _rearm()
    threading.Thread(target=_watchdog_loop, name="bench-watchdog",
                     daemon=True).start()


# ----------------------------------------------------------------------
# backend selection
# ----------------------------------------------------------------------
def select_backend() -> dict:
    """Return the device this process measures on, ``{"platform", "kind",
    "count"}`` as JAX reports them.  No probe and no fallback:
    ``BENCH_BACKEND=cpu`` asks for the CPU explicitly (contract tests),
    otherwise anything but a TPU raises.

    Must be called before anything initializes a jax backend in this process.
    """
    want = os.environ.get("BENCH_BACKEND") or None
    if want not in (None, "cpu"):
        raise ValueError(f"BENCH_BACKEND={want!r}: only 'cpu' is a valid "
                         "override (the default is whatever JAX finds)")
    from msrflute_tpu.utils.backend import device_report, force_cpu_backend
    if want == "cpu":
        force_cpu_backend()
    device = device_report()
    if device["platform"] != "tpu" and want != "cpu":
        raise RuntimeError(
            f"bench.py found no TPU (jax platform {device['platform']!r}) "
            "and does not fall back: run it on the chip, or set "
            "BENCH_BACKEND=cpu to measure the CPU explicitly")
    return device


# ----------------------------------------------------------------------
# synthetic federated datasets shaped like the real ones
# ----------------------------------------------------------------------
def _image_dataset(pool, samples_per_user, shape, classes, rng):
    from msrflute_tpu.data import ArraysDataset
    users, per_user = [], []
    for u in range(pool):
        # uint8 pixels on the host (like real dataset bytes); cast to f32 on
        # device — 4x less host->device traffic per round
        x = rng.integers(0, 256, size=(samples_per_user,) + shape,
                         dtype=np.uint8)
        y = rng.integers(0, classes, size=(samples_per_user,)).astype(np.int32)
        users.append(f"u{u:04d}")
        per_user.append({"x": x, "y": y})
    return ArraysDataset(users, per_user)


def _token_dataset(pool, seqs_per_user, seq_len, vocab, rng):
    from msrflute_tpu.data import ArraysDataset
    users, per_user = [], []
    for u in range(pool):
        x = rng.integers(1, vocab, size=(seqs_per_user, seq_len),
                         dtype=np.int64).astype(np.int32)
        users.append(f"u{u:04d}")
        per_user.append({"x": x})
    return ArraysDataset(users, per_user)


def _flute_config(model_cfg, batch_size, client_lr, fuse, eval_bs=128):
    from msrflute_tpu.config import FLUTEConfig
    return FLUTEConfig.from_dict({
        "model_config": model_cfg,
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": 0,
            "num_clients_per_iteration": 10,
            "initial_lr_client": client_lr,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 10_000, "initial_val": False,
            # fuse rounds into one scanned device program (TPU-native perf
            # feature; see RoundEngine.run_rounds) — amortizes dispatch
            "rounds_per_step": fuse,
            "data_config": {"val": {"batch_size": eval_bs},
                            "test": {"batch_size": eval_bs}},
        },
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": client_lr},
            # device-resident pool: upload samples to HBM once, ship only
            # [K,S,B] int32 indices per chunk (bit-identical training,
            # tests/test_device_pool.py) instead of feature bytes
            "data_config": {"train": {"batch_size": batch_size,
                                      "device_resident": True}},
        },
    })


# ----------------------------------------------------------------------
# measurement
# ----------------------------------------------------------------------
def _one_client_batch(dataset, batch_size, max_steps):
    """One client's packed ``[S, B, ...]`` batch + sample mask (what the
    MFU estimate here counts)."""
    from msrflute_tpu.data import pack_round_batches
    rb = pack_round_batches(dataset, [0], batch_size, max_steps,
                            rng=np.random.default_rng(0))
    one = {k: v[0, 0] for k, v in rb.arrays.items()}
    one["sample_mask"] = rb.sample_mask[0, 0]
    return one


def grad_step_cost(task, params, batch):
    """XLA cost + memory analysis of one client fwd+bwd step, or None.

    Routed through the ONE compiled-analysis helper
    (``msrflute_tpu.telemetry.xla.aot_cost`` — the same code behind the
    live device-truth layer), so the MFU numerator can never drift
    between bench and telemetry.
    Keys are the normalized ``flops`` / ``bytes_accessed`` /
    ``hbm_bytes`` spellings."""
    import jax

    from msrflute_tpu.telemetry.xla import aot_cost

    def step(p, b):
        def loss(pp):
            return task.loss(pp, b, jax.random.PRNGKey(0), True)[0]
        return jax.grad(loss)(p)

    return aot_cost(step, params, batch)


def make_val_ds(dataset, eval_users):
    """Val split used by the bench's ``secs_eval`` measurement: the first
    ``eval_users`` users of the train pool."""
    from msrflute_tpu.data import ArraysDataset
    n = min(int(eval_users), len(dataset.user_list))
    return ArraysDataset(dataset.user_list[:n],
                         [dataset.user_arrays(i) for i in range(n)])


def bench_protocol(name, cfg, dataset, eval_users, *, warmup_rounds,
                   timed_chunks, eval_every, want_mfu=False):
    """Run one protocol; return its result dict.

    Timed region covers what the reference's wall-clock covers per round:
    sampling, host packing, the device step, and the per-chunk
    latest-checkpoint write (the reference saves ``latest_model`` every
    round, ``core/server.py:530``, so keeping it timed is protocol-fair —
    and we write once per R fused rounds, not once per round).  Eval cost
    is measured separately on the pure jitted eval; best-model checkpoint
    I/O is excluded there because it only fires on improvement, not in the
    steady state.
    """
    import tempfile

    import jax
    from msrflute_tpu.data import ArraysDataset, pack_eval_batches
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.engine.evaluation import evaluate
    from msrflute_tpu.models import make_task
    from msrflute_tpu.parallel import make_mesh
    from msrflute_tpu.parallel.mesh import CLIENTS_AXIS
    from msrflute_tpu.telemetry.timing import Stopwatch

    mesh = make_mesh()
    task = make_task(cfg.model_config)
    fuse = int(cfg.server_config.get("rounds_per_step", 1))
    val_ds = make_val_ds(dataset, eval_users)
    with tempfile.TemporaryDirectory() as tmp:
        server = OptimizationServer(task, cfg, dataset, val_dataset=val_ds,
                                    model_dir=tmp, mesh=mesh, seed=0)

        # ---- warmup (compiles the fused-round program) ----
        server.config.server_config.max_iteration = warmup_rounds
        server.train()
        # ---- timed chunks (telemetry.timing.Stopwatch: the same
        # perf_counter stopwatch as the server spans and the tools, so
        # bench numbers and trace spans share one clock; JSON field
        # names unchanged) ----
        per_chunk = []
        for _ in range(timed_chunks):
            server.config.server_config.max_iteration += fuse
            with Stopwatch() as sw:
                server.train()
                jax.block_until_ready(server.state.params)
            per_chunk.append(sw.secs / fuse)

        # ---- eval cost (pure jitted eval; no checkpoint I/O).  Batches
        # are pre-staged on device like the server's per-split cache, so
        # the steady-state number excludes the one-time transfer ----
        batches = server._packed_eval_batches("val")
        evaluate(task, server._eval_fn, server.state.params, batches, mesh,
                 server.engine.partition_mode)  # compile
        with Stopwatch() as sw:
            evaluate(task, server._eval_fn, server.state.params, batches,
                     mesh, server.engine.partition_mode)
        secs_eval = sw.secs

        # device-truth numbers on EVERY protocol (the ISSUE 7 bench
        # contract): compiled grad-step cost through the shared helper,
        # MFU vs this chip's peak (a CPU run prints no utilisation),
        # HBM footprint, and the engine's always-on recompile counter.
        from msrflute_tpu.telemetry.xla import mfu as mfu_of
        from msrflute_tpu.utils.compat import chip_peak_flops
        one_batch = _one_client_batch(dataset, int(
            cfg.client_config.data_config.train["batch_size"]),
            server.max_steps)
        cost = grad_step_cost(task, server.state.params, one_batch)
        mfu = None
        flops_per_round = None
        if cost is not None and cost.get("flops"):
            steps = server.max_steps
            clients = int(cfg.server_config.num_clients_per_iteration)
            flops_per_round = float(cost["flops"]) * steps * clients
            if want_mfu:
                # the historical headline column: pinned to the v5e
                # bf16 peak whatever chip ran, for artifact continuity
                mfu = mfu_of(flops_per_round, float(np.median(per_chunk)),
                             peak_flops=V5E_BF16_PEAK_FLOPS)
        device0 = jax.devices()[0]
        chip_kind, chip_peak = chip_peak_flops(device0)
        device_truth = {
            "chip": chip_kind,
            "platform": device0.platform,
            "device_count": len(jax.devices()),
            "mfu": (round(mfu_of(flops_per_round,
                                 float(np.median(per_chunk)),
                                 peak_flops=chip_peak) or 0.0, 6)
                    if flops_per_round and device0.platform == "tpu"
                    else None),
            "hbm_peak_bytes": (cost or {}).get("hbm_bytes"),
            "recompiles": int(server.engine.recompile_count),
            "compiled_programs": len(server.engine.compile_log),
        }
        # compile-cost observability (ISSUE 12 satellite): the grad-step
        # probe's own lower+compile seconds always, plus the per-entry-
        # point map when the device-truth layer observed the run's
        # compiles (telemetry.xla wraps every entry in _InstrumentedFn,
        # which times the AOT path)
        if cost is not None and cost.get("compile_seconds") is not None:
            device_truth["grad_step_compile_seconds"] = \
                cost["compile_seconds"]
        if server.engine.xla is not None:
            device_truth["compile_seconds"] = {
                entry: rec["compile_seconds"]
                for entry, rec in server.engine.xla.summary().items()
                if "compile_seconds" in rec}

    secs_train = float(np.median(per_chunk))
    secs_per_round = secs_train + secs_eval / eval_every
    baseline = BASELINES_SECS_PER_ROUND.get(name)  # None: no published number
    out = {
        "secs_per_round": round(secs_per_round, 4),
        "secs_train_p50": round(float(np.percentile(per_chunk, 50)), 4),
        "secs_train_p90": round(float(np.percentile(per_chunk, 90)), 4),
        "secs_eval": round(secs_eval, 4),
        "vs_baseline": (round(baseline / secs_per_round, 2)
                        if baseline is not None else None),
    }
    if mfu is not None:
        out["mfu_vs_bf16_peak"] = round(mfu, 5)
    out["device_truth"] = device_truth
    out.update(_server_overhead_extras(server))
    return out


def _server_overhead_extras(server) -> dict:
    """Host-side overhead observability riding every protocol entry:
    staged host->device bytes per round (the communication story) and the
    per-round host-tail seconds (what the pipelined loop overlaps with
    device execution — ISSUE 1 satellite).  When the run injected faults
    (``server_config.chaos``), the chaos config + fault counters ride
    along too, so a chaos run can never be silently compared against a
    clean baseline (ISSUE 3 satellite — the ``strict_transfers``
    discipline applied to fault injection)."""
    out = {}
    staged = server.run_stats.get("hostToDeviceBytesPerRound") or []
    tail = server.run_stats.get("secsPerRoundHostTail") or []
    if staged:
        out["staged_mb_per_round"] = round(
            float(np.mean(staged)) / 2 ** 20, 4)
    if tail:
        out["host_tail_secs_p50"] = round(
            float(np.percentile(tail, 50)), 5)
    # dispatch-cost observability (ISSUE 6 satellite): what the last
    # faithful dispatch staged
    engine = getattr(server, "engine", None)
    if engine is not None:
        out["dispatch"] = {
            "staged_kb": round(
                getattr(engine, "last_staged_bytes", 0) / 1024.0, 2),
        }
    # padding efficiency (cohort shape-bucketing's meter): run-total
    # real samples / padded grid slots — recorded on EVERY protocol so
    # the monolithic baseline and a bucketed run are directly
    # comparable, and `tools/scope trend` can gate a drop between
    # committed artifacts
    pad_eff = getattr(server, "padding_efficiency", None)
    if pad_eff is not None:
        out["padding_efficiency"] = round(float(pad_eff), 4)
    cb = getattr(server, "cohort_bucketing", None)
    if cb is not None:
        # contract marker (the chaos/telemetry/robust discipline): a
        # bucketed run can never be silently compared against a
        # monolithic baseline
        out["cohort_bucketing"] = {
            "enabled": True,
            "boundaries": list(cb["boundaries"]),
            "max_buckets": int(cb["max_buckets"]),
            "bucket_grid_variants":
                len(getattr(server.engine, "bucket_shapes_seen", ())),
        }
    mgb = getattr(server, "megabatch", None)
    if mgb is None:
        # megabatch joins the contract trio: a super-batch-taped run
        # reshapes the per-bucket compute entirely — comparing it
        # against a per-client-vmap baseline without the marker would
        # misattribute the win
        out["megabatch"] = {"enabled": False}
    else:
        util = server.megabatch_utilization
        out["megabatch"] = {
            "enabled": True,
            "lanes": [int(l) for l in mgb["lanes"]],
            "utilization": (round(float(util), 4)
                            if util is not None else None),
            "gate_arms": {f"K{k}_S{s}": arm for (k, s), arm in
                          sorted(server.engine._mega_gate.items())},
        }
    chaos = getattr(server, "chaos", None)
    if chaos is not None:
        out["chaos"] = dict(chaos.describe(),
                            fault_counters={k: round(float(v), 1)
                                            for k, v in
                                            chaos.counters.items()})
    # telemetry mode is part of the bench CONTRACT (the chaos-mode rule
    # applied to instrumentation): an instrumented run can never be
    # silently compared against an uninstrumented baseline
    scope = getattr(server, "scope", None)
    out["telemetry"] = ({"enabled": False} if scope is None else
                        {"enabled": True,
                         "trace": scope.tracer is not None,
                         "devbus": server.engine.devbus.enabled,
                         "watchdog_findings":
                             len(scope.watchdog.findings)})
    # endurance marker (ISSUE 13): whether the longitudinal layer —
    # windowed rollups + flight recorder — was live for this protocol,
    # and how many rollup windows actually flushed; a run babysat by
    # `scope watch`/`scope health` can never be silently compared
    # against one that wasn't
    rollup = getattr(scope, "rollup", None)
    out["endurance"] = ({"enabled": False} if rollup is None else
                        {"enabled": True,
                         "rollup_windows": int(rollup.windows_flushed),
                         "flight": getattr(scope, "flight", None)
                         is not None})
    # precision mode joins the contract trio: a bf16-compute run is NOT
    # comparable against an f32 baseline (different arithmetic, different
    # convergence), so the policy rides every protocol entry — absent
    # means the bit-identical f32 path
    prec = None
    sc_cfg = getattr(getattr(server, "config", None), "server_config",
                     None)
    if sc_cfg is not None:
        prec = sc_cfg.get("precision")
    out["precision"] = ({"enabled": False} if not prec else
                        dict(prec, enabled=prec.get("enable", True)))
    # fleet marker (ISSUE 14): paged-carry / O(cohort)-sampling runs
    # join the contract trio — a fleet run pays page-in/writeback
    # transfers per round and draws (optionally) a different sampling
    # trail, so comparing it against a resident baseline without the
    # marker would misattribute both
    pager = getattr(server, "fleet_pager", None)
    if getattr(server, "_fleet_cfg", None) is None:
        out["fleet"] = {"enabled": False}
    else:
        out["fleet"] = dict(
            {"enabled": True,
             "sampling": str(server._fleet_cfg.get("sampling",
                                                   "uniform")),
             "paged_carry": pager is not None},
            **(pager.describe() if pager is not None else {}))
    # robust mode completes the trio: a fluteshield-defended run pays
    # screening (and possibly a sort-based robust combine) per round —
    # comparing it against an undefended baseline without the marker
    # would misattribute that cost (or hide that a "baseline" was
    # silently quarantining clients)
    shield = getattr(server, "shield", None)
    out["robust"] = ({"enabled": False} if shield is None else
                     dict(shield.describe(),
                          quarantine_counters={
                              k: round(float(v), 1)
                              for k, v in shield.counters.items()}))
    # secure-agg marker (ISSUE 18): a masked run pays per-client pairwise
    # mask generation plus the server-side cancellation pass, and a
    # dropout round folds mask recovery into the finalize — comparing it
    # against an unmasked baseline without the marker would misattribute
    # that cost (or hide that a run was silently aborting thin rounds)
    strat = getattr(server, "strategy", None)
    if not getattr(strat, "wants_cohort", False):
        out["secure_agg"] = {"enabled": False}
    else:
        out["secure_agg"] = {
            "enabled": True,
            "frac_bits": int(strat.frac_bits),
            "clip": float(strat.clip),
            "graph": str(strat.graph),
            "min_survivors": int(strat.min_survivors),
            "recovery_counters": {k: round(float(v), 1)
                                  for k, v in strat.counters.items()}}
    # traffic marker (ISSUE 19): an arrival-plane run draws its cohorts
    # from a seeded timeline — and, buffered, aggregates STALE work —
    # so comparing it against a boundary-sampled baseline without the
    # marker would misattribute both the sampling trail and the
    # convergence
    traffic = getattr(server, "traffic", None)
    if traffic is None:
        out["traffic"] = {"enabled": False}
    else:
        out["traffic"] = dict(
            traffic.describe(),
            arrival_rate=round(float(traffic.arrival_rate()), 6),
            stale_hist=[int(c) for c in traffic.stale_hist],
            target_accuracy=getattr(server, "target_accuracy", None),
            counters={k: round(float(v), 1)
                      for k, v in traffic.counters.items()})
    # infra marker (ISSUE 20): a run under injected host-service faults
    # pays retry/degradation overhead on every durable-IO surface (and
    # may have shed its prefetch daemon mid-run) — comparing it against
    # an unfaulted baseline without the marker would misattribute the
    # tail, so the fault ledger rides every protocol entry
    infra = getattr(chaos, "infra", None) if chaos is not None else None
    out["infra"] = ({"enabled": False} if infra is None else
                    dict(infra.describe(),
                         fault_counters={k: round(float(v), 1)
                                         for k, v in
                                         infra.counters.items()}))
    # convergence tier: first round whose val accuracy reached
    # traffic.target_accuracy — recorded on EVERY protocol entry (null
    # when no target is configured or the run never got there), so
    # `scope trend` can gate async-tier claims alongside secs_per_round
    out["rounds_to_target_accuracy"] = getattr(
        server, "rounds_to_target_accuracy", None)
    return out


def _bench_fuse(on_tpu: bool) -> int:
    """BENCH_FUSE: rounds fused per device dispatch.  Eval cost is timed
    separately and amortized per eval_every, so fuse need not divide the
    eval cadence.  fused==unfused bit-equality is pinned by
    tests/test_multi_round.py.  Single source of truth for the
    default: main()'s warmup must span one fused chunk."""
    return int(os.environ.get("BENCH_FUSE", 50 if on_tpu else 2))


def build_protocols(on_tpu: bool, rng, with_bf16: bool = False) -> dict:
    """The protocol table (BASELINE.md `README.md:22-27`): model cfg,
    batch, lr, samples/user (real-dataset average), data maker, eval
    cadence.  Off-TPU (CI smoke on host CPU) the full protocols are
    compute-bound on host cores; shrink so harnesses still complete — the
    recorded number only means "vs baseline" on real TPU.  Shared with
    ``tools/static_flops_report.py``."""
    fuse = _bench_fuse(on_tpu)

    def img(pool, spu, shape, classes):
        return lambda: _image_dataset(pool, spu, shape, classes, rng)

    base_protocols = {
        "lr_mnist": dict(
            cfg=_flute_config({"model_type": "LR", "num_classes": 10,
                               "input_dim": 784}, 10, 0.03, fuse),
            data=img(64 if on_tpu else 16, 60 if on_tpu else 20, (784,), 10),
            eval_every=20),
        "cnn_femnist": dict(
            cfg=_flute_config({"model_type": "CNN", "num_classes": 62},
                              20, 0.1, fuse),
            data=img(64 if on_tpu else 16, 240 if on_tpu else 40,
                     (28, 28, 1), 62),
            eval_every=50),
        "resnet_fedcifar100": dict(
            cfg=_flute_config({"model_type": "RESNET", "num_classes": 100,
                               "image_size": 32}, 20, 0.1, fuse),
            data=img(32 if on_tpu else 12, 100 if on_tpu else 20,
                     (32, 32, 3), 100),
            eval_every=50),
        "rnn_fedshakespeare": dict(
            cfg=_flute_config({"model_type": "LSTM", "vocab_size": 90,
                               "seq_len": 80}, 4, 0.8, fuse, eval_bs=32),
            data=lambda: _token_dataset(32 if on_tpu else 12,
                                        32 if on_tpu else 8, 80, 90, rng),
            eval_every=50),
    }
    # dict order = measurement order; the HEADLINE protocol runs first
    # on TPU so a deadline self-flush mid-bench still carries the
    # number the driver contract is scored on
    protocols = ({HEADLINE: base_protocols[HEADLINE], **base_protocols}
                 if on_tpu else dict(base_protocols))
    # mlm_bert federated rounds (reference experiments/mlm_bert; the
    # README publishes no wall-clock for it, so this entry records
    # absolute s/round + MFU-relevant sizes rather than a vs_baseline).
    # TPU: an 8-layer/512-hidden BERT, bf16, full 30522 vocab; CPU: tiny.
    bert_model = ({"vocab_size": 30522, "hidden_size": 512,
                   "num_hidden_layers": 8, "num_attention_heads": 8,
                   "intermediate_size": 2048, "max_seq_length": 128,
                   "mlm_probability": 0.15, "mask_token_id": 103,
                   "dtype": "bfloat16"}
                  if on_tpu else
                  {"vocab_size": 120, "hidden_size": 32,
                   "num_hidden_layers": 2, "num_attention_heads": 2,
                   "intermediate_size": 64, "max_seq_length": 16,
                   "mlm_probability": 0.15, "mask_token_id": 4})
    bL, bV = bert_model["max_seq_length"], bert_model["vocab_size"]
    # bert's fuse caps at 25: at 1.16 s/round dispatch overhead is ~0.4%
    # so deeper fusion buys nothing, while doubling the scan length is a
    # fresh multi-minute on-chip compile risking the caller's deadline
    # (the one fuse=50 bert attempt watchdog-expired in that section,
    # `bench_tpu_full_fuse50.json` flush_note — cause ambiguous, but the
    # upside is zero) — the cap keeps the program identical to the
    # already-cached fuse=25 compile
    protocols["mlm_bert"] = dict(
        cfg=_flute_config({"model_type": "BERT",
                           "BERT": {"model": bert_model,
                                    "training": {"seed": 0}}},
                          16 if on_tpu else 4, 5e-5, min(fuse, 25),
                          eval_bs=32),
        data=lambda: _token_dataset(16 if on_tpu else 8,
                                    32 if on_tpu else 8, bL, bV, rng),
        eval_every=50)
    if on_tpu:
        # TPU-native extra (round 5): same BERT protocol with the gathered
        # MLM head (models/bert.py::_gather_masked) — the vocab projection
        # and its [B, L, 30522] f32 logits run only on the ~15% masked
        # positions.  Kept as a separate entry so mlm_bert stays
        # round-over-round comparable while this records the optimized
        # path's s/round + MFU.
        gathered_model = dict(bert_model, mlm_head="gathered")
        protocols["mlm_bert_gathered"] = dict(
            cfg=_flute_config({"model_type": "BERT",
                               "BERT": {"model": gathered_model,
                                        "training": {"seed": 0}}},
                              16, 5e-5, min(fuse, 25), eval_bs=32),
            data=lambda: _token_dataset(16, 32, bL, bV, rng),
            eval_every=50)
    if with_bf16:
        # TPU-native extra: same CNN protocol with bf16 compute (MXU full
        # rate); baselined against the same published fp32 number
        protocols["cnn_femnist_bf16"] = dict(
            cfg=_flute_config({"model_type": "CNN", "num_classes": 62,
                               "dtype": "bfloat16"}, 20, 0.1, fuse),
            data=img(64 if on_tpu else 16, 240 if on_tpu else 40,
                     (28, 28, 1), 62),
            eval_every=50)
    # the heaviest protocol (longest compile, most device memory)
    # measures last: a deadline or a failure there costs no other
    # protocol's number in THIS process
    protocols["resnet_fedcifar100"] = protocols.pop("resnet_fedcifar100")
    return protocols


def bench_longctx(on_tpu: bool) -> dict:
    """Net-new long-context protocol (no reference baseline — FLUTE has no
    long-context machinery, SURVEY.md §5.7): tokens/s of a jitted RingLM
    causal-LM train step, dense-softmax attention vs the Pallas flash
    kernel (``ops/pallas_attention.py``).  Off-TPU this only smokes the
    code path (interpret-mode kernels are not a measurement)."""
    import jax
    import jax.numpy as jnp
    from msrflute_tpu.config import ModelConfig
    from msrflute_tpu.models import make_task

    L = 2048 if on_tpu else 64
    B = 4 if on_tpu else 2
    mc = {"vocab_size": 256, "embed_dim": 256, "num_heads": 4,
          "head_dim": 64, "mlp_dim": 1024, "num_layers": 4, "seq_len": L}
    if on_tpu:
        mc["dtype"] = "bfloat16"
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, 256, size=(B, L)), jnp.int32)
    out = {"seq_len": L, "batch": B}

    def step_time(flash: bool) -> float:
        task = make_task(ModelConfig(model_type="RINGLM", extra=dict(
            mc, flash_attention=flash)))
        params = task.init_params(jax.random.PRNGKey(0))
        batch = {"x": tokens,
                 "sample_mask": jnp.ones((B,), jnp.float32)}

        # the step returns a SCALAR tree-sum of the grads, fetched to host
        # each rep: the float() round-trip is the fence, and the
        # full-reduction sum keeps XLA from dead-code-eliminating any
        # part of the backward pass
        @jax.jit
        def step(p):
            def loss(pp):
                return task.loss(pp, batch, jax.random.PRNGKey(0), True)[0]
            g = jax.grad(loss)(p)
            return jax.tree_util.tree_reduce(
                lambda a, b: a + jnp.sum(b.astype(jnp.float32)),
                g, jnp.float32(0))

        float(step(params))  # compile + first run
        reps = 5 if on_tpu else 1
        tic = time.time()
        for _ in range(reps):
            float(step(params))
        return (time.time() - tic) / reps

    dense = step_time(False)
    flash = step_time(True)
    out["dense_secs_per_step"] = round(dense, 4)
    out["flash_secs_per_step"] = round(flash, 4)
    out["flash_speedup"] = round(dense / flash, 2)
    out["flash_tokens_per_sec"] = round(B * L / flash, 1)
    return out


def bench_varlen_bucketing(on_tpu: bool) -> dict:
    """Length-bucketing win on a variable-length token round (round 2
    item 5): same LSTM client-update grid with the real-data length
    distribution (GRU-Reddit-like: short sentences inside a max-L grid),
    timed at full L vs the cropped power-of-two bucket
    (``data.batching.seq_length_bucket``).  Math identical — the delta is
    pure padding FLOPs/bandwidth."""
    import jax

    from msrflute_tpu.config import ModelConfig, OptimizerConfig
    from msrflute_tpu.data import ArraysDataset
    from msrflute_tpu.data.batching import (pack_round_batches,
                                            seq_length_bucket)
    from msrflute_tpu.engine.client_update import (ClientHParams,
                                                   build_client_update)
    from msrflute_tpu.models import make_task

    L, real_max = (80, 22) if on_tpu else (32, 9)
    K, S, B = (10, 8, 8) if on_tpu else (4, 2, 4)
    rng = np.random.default_rng(0)
    per_user = []
    for _ in range(K):
        x = np.zeros((S * B, L), np.int32)
        for r in range(S * B):
            n = rng.integers(4, real_max + 1)
            x[r, :n] = rng.integers(1, 90, size=n)
        per_user.append({"x": x})
    ds = ArraysDataset([f"u{i}" for i in range(K)], per_user)
    task = make_task(ModelConfig(model_type="LSTM",
                                 extra={"vocab_size": 90, "seq_len": L}))
    params = task.init_params(jax.random.PRNGKey(0))
    upd = jax.jit(jax.vmap(
        build_client_update(task, OptimizerConfig.from_dict(
            {"type": "sgd", "lr": 0.5}), ClientHParams()),
        in_axes=(None, 0, 0, None, None)))

    out = {}
    for tag, crop in (("full_len", False), ("bucketed", True)):
        batch = pack_round_batches(ds, list(range(K)), B, S,
                                   rng=np.random.default_rng(0))
        stats = seq_length_bucket([batch], task.seq_pad_keys) if crop \
            else None
        args = (params, {"x": batch.arrays["x"]}, batch.sample_mask,
                np.float32(0.5), jax.random.PRNGKey(1))

        # scalar-fetch sync (see bench_longctx): tree-sum of the full
        # client-update output, fetched per rep
        import jax.numpy as jnp
        probe = jax.jit(lambda *a: jax.tree_util.tree_reduce(
            lambda acc, x: acc + jnp.sum(x.astype(jnp.float32)),
            upd(*a), jnp.float32(0)))
        float(probe(*args))  # compile + first run
        reps = 10 if on_tpu else 2
        tic = time.time()
        for _ in range(reps):
            float(probe(*args))
        out[tag] = {"secs_per_round": round((time.time() - tic) / reps, 5),
                    "grid_L": int(batch.arrays["x"].shape[-1])}
        if stats:
            out[tag]["pad_eff"] = round(
                stats["tokens_real"] / max(stats["tokens_grid_after"], 1), 3)
            out["pad_eff_full"] = round(
                stats["tokens_real"] / max(stats["tokens_grid_before"], 1), 3)
    out["speedup"] = round(out["full_len"]["secs_per_round"]
                           / out["bucketed"]["secs_per_round"], 2)
    return out


def bench_pipeline_ab(on_tpu: bool) -> dict:
    """Faithful-mode (rounds_per_step=1) A/B of the overlapped host/device
    round pipeline (ISSUE 1 acceptance): the SAME protocol run serial
    (``pipeline_depth=0``, sync per-round checkpoint) vs pipelined
    (``pipeline_depth=1``, async checkpoint writer), many rounds inside
    one ``train()`` call so the pipeline actually spans rounds.  Reports
    steady-state s/round per arm + the speedup; per-round results are
    bit-identical by contract (tests/test_server_pipeline.py).

    Protocol: CNN_FEMNIST on-chip (the regime the pipeline targets —
    device rounds of tens of ms with an 88 ms-class dispatch/host tail).
    Off-TPU the A/B drops to the LR protocol: on a weak CPU host the CNN
    round is pure device compute for minutes (nothing to overlap) and
    would blow the bench deadline; the LR arm still exercises the whole
    pipelined loop end-to-end.  The ``regime`` field says which resource
    bounded the measured loop so a ~1.0 speedup on a host-bound CPU box
    is attributable (host and "device" share the same cores there)."""
    import tempfile

    import jax
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task
    from msrflute_tpu.parallel import make_mesh

    from msrflute_tpu.utils.strict import strict_transfers_enabled

    warm, rounds = (5, 40) if on_tpu else (3, 30)
    # under MSRFLUTE_STRICT_TRANSFERS=1 both arms run with implicit
    # device->host transfers DISALLOWED (utils/strict.py, applied by
    # server.train itself): completing the A/B proves zero
    # transfer_guard violations per round — the runtime counterpart of
    # the fluteguard host-sync lint, pinned by tests/test_bench_contract
    out = {"rounds_per_arm": rounds,
           "protocol": "cnn_femnist" if on_tpu else "lr_mnist",
           "strict_transfers": strict_transfers_enabled()}
    tails = {}
    for depth in (0, 1):
        if on_tpu:
            cfg = _flute_config({"model_type": "CNN", "num_classes": 62},
                                20, 0.1, fuse=1)
            data = _image_dataset(64, 240, (28, 28, 1), 62,
                                  np.random.default_rng(0))
        else:
            cfg = _flute_config({"model_type": "LR", "num_classes": 10,
                                 "input_dim": 784}, 10, 0.03, fuse=1)
            data = _image_dataset(16, 60, (784,), 10,
                                  np.random.default_rng(0))
        cfg.server_config["pipeline_depth"] = depth
        task = make_task(cfg.model_config)
        with tempfile.TemporaryDirectory() as tmp:
            server = OptimizationServer(task, cfg, data, model_dir=tmp,
                                        mesh=make_mesh(), seed=0)
            cfg.server_config.max_iteration = warm
            server.train()  # compile + steady the checkpoint writer
            cfg.server_config.max_iteration = warm + rounds
            tic = time.time()
            server.train()
            jax.block_until_ready(server.state.params)
            secs = (time.time() - tic) / rounds
        key = "pipelined" if depth else "serial"
        out[f"{key}_secs_per_round"] = round(secs, 4)
        tails[depth] = server.run_stats.get("secsPerRoundHostTail") or [0.0]
        if depth:
            out["pipelined_chunks"] = server.pipelined_chunks
            out.update(_server_overhead_extras(server))
    out["speedup"] = round(out["serial_secs_per_round"]
                           / max(out["pipelined_secs_per_round"], 1e-9), 3)
    serial_tail = float(np.percentile(tails[0], 50))
    out["serial_host_tail_secs_p50"] = round(serial_tail, 5)
    # regime attribution: the pipeline hides the host tail behind device
    # execution, so its headroom is bounded by tail/round; when that
    # ratio is tiny (device-dominated) or host and device share the same
    # cores (CPU fallback), ~1.0 is the expected honest result
    ratio = serial_tail / max(out["serial_secs_per_round"], 1e-9)
    out["regime"] = (
        f"host tail is {100 * ratio:.1f}% of the serial round"
        + ("" if on_tpu else
           "; CPU fallback: host tail and device compute share the same "
           "cores, so overlap cannot add throughput here — the on-chip "
           "A/B (BENCH_PIPELINE_AB=1) is the regime this targets"))
    return out


def bench_fused_carry_ab(on_tpu: bool) -> dict:
    """Pipeline A/B for a FORMERLY-SERIAL strategy (ISSUE 6 acceptance):
    SCAFFOLD — whose control-variate flow forced the serial host
    fallback since PR 1 — run with device-resident carry
    (``fused_carry: true``) serial (``pipeline_depth: 0``) vs pipelined
    with a depth-2 ring, under flutescope telemetry.  The pipelined
    arm's trace feeds ``tools/scope``'s overlap summary, so the
    host-tail overlap is recorded evidence (``overlap.efficiency_pct``
    > 0 when the loop actually pipelined) together with the per-depth
    rounds-in-flight breakdown.  Params are bit-identical across arms
    by the pinned carry contract (tests/test_universal_overlap.py)."""
    import tempfile

    import jax
    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task
    from msrflute_tpu.parallel import make_mesh
    from msrflute_tpu.telemetry.scope_cli import summarize
    from msrflute_tpu.utils.strict import strict_transfers_enabled

    warm, rounds = (5, 40) if on_tpu else (3, 30)
    out = {"rounds_per_arm": rounds, "strategy": "scaffold",
           "protocol": "cnn_femnist" if on_tpu else "lr_mnist",
           "strict_transfers": strict_transfers_enabled()}

    def _cfg(depth):
        if on_tpu:
            model = {"model_type": "CNN", "num_classes": 62}
            bs, lr = 20, 0.1
        else:
            model = {"model_type": "LR", "num_classes": 10,
                     "input_dim": 784}
            bs, lr = 10, 0.03
        return FLUTEConfig.from_dict({
            "model_config": model,
            "strategy": "scaffold",
            "server_config": {
                "max_iteration": 0, "num_clients_per_iteration": 10,
                "initial_lr_client": lr, "pipeline_depth": depth,
                "fused_carry": True, "rounds_per_step": 1,
                "telemetry": {"enable": True},
                "optimizer_config": {"type": "sgd", "lr": 1.0},
                "val_freq": 10_000, "initial_val": False,
                "data_config": {"val": {"batch_size": 128}},
            },
            "client_config": {
                "optimizer_config": {"type": "sgd", "lr": lr},
                "data_config": {"train": {"batch_size": bs}},
            },
        })

    for depth in (0, 2):
        cfg = _cfg(depth)
        if on_tpu:
            data = _image_dataset(64, 240, (28, 28, 1), 62,
                                  np.random.default_rng(0))
        else:
            data = _image_dataset(16, 60, (784,), 10,
                                  np.random.default_rng(0))
        task = make_task(cfg.model_config)
        with tempfile.TemporaryDirectory() as tmp:
            server = OptimizationServer(task, cfg, data, model_dir=tmp,
                                        mesh=make_mesh(), seed=0)
            cfg.server_config.max_iteration = warm
            server.train()
            cfg.server_config.max_iteration = warm + rounds
            tic = time.time()
            server.train()
            jax.block_until_ready(server.state.params)
            secs = (time.time() - tic) / rounds
            key = "pipelined" if depth else "serial"
            out[f"{key}_secs_per_round"] = round(secs, 4)
            if depth:
                out["pipelined_chunks"] = server.pipelined_chunks
                out.update(_server_overhead_extras(server))
                # materialized by server.train()'s final flush; the
                # overlap block is the acceptance evidence
                scope = summarize(tmp)
                out["scope_overlap"] = scope.get("overlap")
    out["speedup"] = round(out["serial_secs_per_round"]
                           / max(out["pipelined_secs_per_round"], 1e-9), 3)
    return out


def _config_block_ab(on_tpu: bool, key: str, arms: dict,
                     data_fn=None, protocol=None, per_arm=None,
                     server_over=None, arm_setup=None) -> dict:
    """Shared off-vs-on overhead harness: run the SAME faithful-mode
    protocol once per arm with ``server_config[key]`` set to that arm's
    block (``None`` = block absent), many rounds inside one ``train()``
    call, and record steady-state ``{key}_{arm}_secs_per_round``.  The
    subsystem A/Bs (telemetry, robust, cohort_bucketing) ride this so
    their warm-up and measurement protocols can never drift apart; ratio
    keys are the caller's job (arm sets differ).

    ``data_fn()`` overrides the default homogeneous dataset (the
    cohort-bucketing A/B needs heterogeneous client sizes — the whole
    point of the optimization); ``protocol`` labels it; ``per_arm(server,
    arm)`` returns extra per-arm fields recorded under ``{key}_{arm}_*``;
    ``server_over`` applies extra server_config blocks to EVERY arm (the
    megabatch A/B needs cohort_bucketing live on both sides);
    ``arm_setup(cfg, arm)`` mutates the config per arm beyond the block
    itself (the secagg A/B flips the top-level ``strategy`` field and
    folds a chaos block into its dropout arm).
    """
    import tempfile

    import jax
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task
    from msrflute_tpu.parallel import make_mesh
    from msrflute_tpu.telemetry.timing import Stopwatch

    warm, rounds = (5, 40) if on_tpu else (3, 30)
    out = {"rounds_per_arm": rounds,
           "protocol": protocol or
           ("cnn_femnist" if on_tpu else "lr_mnist")}
    for arm, block in arms.items():
        if on_tpu:
            cfg = _flute_config({"model_type": "CNN", "num_classes": 62},
                                20, 0.1, fuse=1)
            data = (data_fn() if data_fn is not None else
                    _image_dataset(64, 240, (28, 28, 1), 62,
                                   np.random.default_rng(0)))
        else:
            cfg = _flute_config({"model_type": "LR", "num_classes": 10,
                                 "input_dim": 784}, 10, 0.03, fuse=1)
            data = (data_fn() if data_fn is not None else
                    _image_dataset(16, 60, (784,), 10,
                                   np.random.default_rng(0)))
        if server_over:
            for okey, oval in server_over.items():
                cfg.server_config[okey] = (dict(oval)
                                           if isinstance(oval, dict)
                                           else oval)
        if block is not None:
            cfg.server_config[key] = dict(block)
        if arm_setup is not None:
            arm_setup(cfg, arm)
        task = make_task(cfg.model_config)
        with tempfile.TemporaryDirectory() as tmp:
            server = OptimizationServer(task, cfg, data, model_dir=tmp,
                                        mesh=make_mesh(), seed=0)
            cfg.server_config.max_iteration = warm
            server.train()  # compile + steady state
            cfg.server_config.max_iteration = warm + rounds
            with Stopwatch() as sw:
                server.train()
                jax.block_until_ready(server.state.params)
            if per_arm is not None:
                for name, value in per_arm(server, arm).items():
                    out[f"{key}_{arm}_{name}"] = value
        out[f"{key}_{arm}_secs_per_round"] = round(sw.secs / rounds, 5)
    return out


def bench_telemetry_ab(on_tpu: bool) -> dict:
    """Telemetry-off vs telemetry-on A/B (flutescope's zero-overhead
    acceptance, ISSUE 4): the SAME faithful-mode protocol run with no
    ``server_config.telemetry`` block and with the full subsystem on
    (spans + trace export + devbus + watchdogs), many rounds inside one
    ``train()`` call.  Records steady-state s/round per arm and the
    ratio; params are bit-identical by contract
    (tests/test_telemetry_contract.py pins that plus the
    zero-implicit-materialization property)."""
    out = _config_block_ab(on_tpu, "telemetry",
                           {"off": None, "on": {"enable": True}})
    off = out["telemetry_off_secs_per_round"]
    out["overhead_ratio"] = round(
        out["telemetry_on_secs_per_round"] / max(off, 1e-9), 3)
    return out


def bench_robust_ab(on_tpu: bool) -> dict:
    """fluteshield overhead A/B (ISSUE 5 satellite): the SAME
    faithful-mode protocol run undefended, with screened mean
    (finite + median-of-norms quarantine inside the round program), and
    with coordinate-wise trimmed mean on top.  Records steady-state
    s/round per arm and the ratios vs the undefended baseline — the
    screening cost is a handful of fused reductions + one all_gather of
    per-client norm scalars; the trimmed-mean arm adds the K-way
    coordinate sort, the estimator's real price.  Firewall bit-identity
    of the off arm is pinned by tests/test_robust.py, not timed here."""
    out = _config_block_ab(on_tpu, "robust", {
        "off": None,
        "screened_mean": {"screen_nonfinite": True, "norm_multiplier": 5.0,
                          "aggregator": "mean"},
        "trimmed_mean": {"screen_nonfinite": True, "norm_multiplier": 5.0,
                         "aggregator": "trimmed_mean",
                         "trim_fraction": 0.1},
    })
    off = out["robust_off_secs_per_round"]
    for arm in ("screened_mean", "trimmed_mean"):
        out[f"{arm}_overhead_ratio"] = round(
            out[f"robust_{arm}_secs_per_round"] / max(off, 1e-9), 3)
    return out


def bench_secagg_ab(on_tpu: bool) -> dict:
    """Straggler-tolerant SecAgg overhead A/B (ISSUE 18 satellite): the
    SAME faithful-mode protocol run unmasked (fedavg), masked
    (secure_agg, full pairwise graph), masked under seeded
    dropout+straggler chaos (the recovery path live every round), and
    masked with the ``graph: log`` topology — so the mask-generation
    cost splits cleanly: full minus unmasked is the O(K^2)-edge price,
    log minus unmasked the O(K log K) one, and the dropout arm adds the
    server-side cancellation pass on top.  Decode exactness and
    bit-identity to the unmasked sum on the same survivor set are pinned
    by tests/test_secagg_compose.py, not timed here."""
    mask = {"frac_bits": 12, "clip": 4.0, "seed": 0}

    def setup(cfg, arm):
        if arm != "unmasked":
            cfg.strategy = "secure_agg"
        if arm == "masked_dropout":
            cfg.server_config["chaos"] = {
                "seed": 3, "dropout_rate": 0.2, "straggler_rate": 0.2,
                "straggler_inflation": 2.0}

    def recovery(server, arm):
        strat = getattr(server, "strategy", None)
        if not getattr(strat, "wants_cohort", False):
            return {}
        return {"recovered_dropout":
                round(float(strat.counters["recovered_dropout"]), 1)}

    out = _config_block_ab(on_tpu, "secure_agg", {
        "unmasked": None,
        "masked": dict(mask, graph="full"),
        "masked_log": dict(mask, graph="log"),
        "masked_dropout": dict(mask, graph="full"),
    }, arm_setup=setup, per_arm=recovery)
    off = out["secure_agg_unmasked_secs_per_round"]
    for arm in ("masked", "masked_log", "masked_dropout"):
        out[f"{arm}_overhead_ratio"] = round(
            out[f"secure_agg_{arm}_secs_per_round"] / max(off, 1e-9), 3)
    out["maskgen_log_vs_full_ratio"] = round(
        out["secure_agg_masked_log_secs_per_round"] /
        max(out["secure_agg_masked_secs_per_round"], 1e-9), 3)
    return out


def _separable_dataset(pool, spu, dim, classes, rng, spread=3.0):
    """Learnable synthetic federated pool (class-mean + noise): the
    traffic A/B races two orchestrations TO A TARGET ACCURACY, so the
    labels must actually be learnable — the other protocols' random-
    label pools would pin every arm at chance and record null."""
    from msrflute_tpu.data import ArraysDataset
    means = (rng.normal(size=(classes, dim)) * spread).astype(np.float32)
    users, per_user = [], []
    for u in range(pool):
        y = rng.integers(0, classes, size=(spu,)).astype(np.int32)
        x = (means[y] + rng.normal(size=(spu, dim))).astype(np.float32)
        users.append(f"u{u:04d}")
        per_user.append({"x": x, "y": y})
    return ArraysDataset(users, per_user)


def bench_traffic_ab(on_tpu: bool) -> dict:
    """flutetraffic sync-vs-buffered A/B on the SAME seeded bursty trace
    (ISSUE 19 acceptance): classic synchronous rounds (``traffic.mode:
    sync`` — the barrier discards work computed against a superseded
    broadcast and waits for a fresh cohort) vs FedBuff-style buffered
    async (``traffic.mode: buffered`` + ``strategy: fedbuff`` — stale
    updates aggregate under the staleness discount), both arms drawing
    the identical arrival timeline, so the A/B compares orchestration,
    not luck.  Each arm trains round-by-round at ``val_freq: 1`` until
    val accuracy reaches ``traffic.target_accuracy`` or the round
    budget runs out, and records ``rounds_to_target_accuracy`` (null
    when never reached), wall-clock seconds to target, and the
    arrival-plane TICK at the crossing fire — the simulated-time axis
    where the async claim actually lives: the sync barrier's discarded
    deliveries push its crossing tick later even when its round count
    is lower.  Numbers are recorded as measured, whichever arm wins."""
    import tempfile

    import jax
    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task
    from msrflute_tpu.parallel import make_mesh

    pool, spu, dim, classes = 32, 24, 32, 4
    ncpi = 8
    # spread/lr/target tuned so the race takes ~20 rounds: wide enough
    # separation to be learnable, slow enough that orchestration (not
    # the first cohort) decides the crossing
    spread, client_lr, target = 0.5, 0.01, 0.75
    max_rounds = 80 if on_tpu else 60
    trace = {"enable": True, "seed": 9, "trace": "bursty", "rate": 2.0,
             "burst_rate": 24.0, "burst_every": 12, "burst_len": 4,
             "target_accuracy": target}
    out = {"protocol": "lr_separable", "trace": "bursty",
           "target_accuracy": target, "round_budget": max_rounds,
           "population": pool, "buffer_size": ncpi}
    for arm, strategy in (("sync", "fedavg"), ("buffered", "fedbuff")):
        raw = {
            "model_config": {"model_type": "LR", "num_classes": classes,
                             "input_dim": dim},
            "strategy": strategy,
            "server_config": {
                "max_iteration": 0,
                "num_clients_per_iteration": ncpi,
                "initial_lr_client": client_lr,
                "optimizer_config": {"type": "sgd", "lr": 1.0},
                "val_freq": 1, "initial_val": False,
                "rounds_per_step": 1,
                "traffic": dict(trace, mode=arm),
                "data_config": {"val": {"batch_size": 64}},
            },
            "client_config": {
                "optimizer_config": {"type": "sgd", "lr": client_lr},
                "data_config": {"train": {"batch_size": 8}},
            },
        }
        if strategy == "fedbuff":
            raw["server_config"]["fedbuff"] = {"max_staleness": 4}
        cfg = FLUTEConfig.from_dict(raw)
        data = _separable_dataset(pool, spu, dim, classes,
                                  np.random.default_rng(3),
                                  spread=spread)
        task = make_task(cfg.model_config)
        with tempfile.TemporaryDirectory() as tmp:
            server = OptimizationServer(task, cfg, data,
                                        val_dataset=make_val_ds(data, 8),
                                        model_dir=tmp, mesh=make_mesh(),
                                        seed=0)
            secs_to_target = None
            tic = time.time()
            for r in range(1, max_rounds + 1):
                cfg.server_config.max_iteration = r
                server.train()
                if server.rounds_to_target_accuracy is not None:
                    jax.block_until_ready(server.state.params)
                    secs_to_target = round(time.time() - tic, 4)
                    break
            reached = server.rounds_to_target_accuracy
            best = server.best_val.get("acc")
            rec = {
                "strategy": strategy,
                "rounds_to_target_accuracy": reached,
                "secs_to_target": secs_to_target,
                "rounds_run": int(server.state.round),
                "best_val_acc": (round(float(best.value), 4)
                                 if best is not None else None),
                "sync_discarded": int(
                    server.traffic.counters["sync_discarded"]),
                "stale_sum": int(server.traffic.counters["stale_sum"]),
            }
            if reached is not None:
                # fires are 0-indexed; round numbers 1-indexed
                rec["tick_at_target"] = int(
                    server.traffic.fire(reached - 1)["tick"])
            out[arm] = rec
    a, b = out["sync"], out["buffered"]
    sa, sb = a.get("secs_to_target"), b.get("secs_to_target")
    out["async_fewer_secs_to_target"] = (
        bool(sb < sa) if isinstance(sa, (int, float)) and
        isinstance(sb, (int, float)) else None)
    ta, tb = a.get("tick_at_target"), b.get("tick_at_target")
    out["async_earlier_tick_at_target"] = (
        bool(tb < ta) if isinstance(ta, (int, float)) and
        isinstance(tb, (int, float)) else None)
    return out


def _hetero_image_dataset(pool, shape, classes, rng, min_samples=4,
                          max_samples=256, small_frac=0.75):
    """Heterogeneous federated pool: ``small_frac`` of users hold a
    handful of samples (uniform near ``min_samples``) and the rest a
    log-uniform tail up to ``max_samples`` — the real-federated shape
    (most phones have little data, a few have lots) that the monolithic
    [K, S, B] grid pads worst: every client pays the biggest client's
    step count.  What cohort bucketing exists for."""
    from msrflute_tpu.data import ArraysDataset
    users, per_user = [], []
    n_small = int(pool * small_frac)
    lo_tail = min(10 * min_samples, max_samples)
    counts = np.concatenate([
        rng.integers(min_samples, lo_tail + 1, size=n_small),
        np.exp(rng.uniform(np.log(lo_tail), np.log(max_samples),
                           size=pool - n_small)).astype(int)])
    counts = np.clip(counts, min_samples, max_samples)
    counts[-1] = max_samples  # pin the worst case so S_max is stable
    for u in range(pool):
        n = int(counts[u])
        x = rng.integers(0, 256, size=(n,) + shape, dtype=np.uint8)
        y = rng.integers(0, classes, size=(n,)).astype(np.int32)
        users.append(f"u{u:04d}")
        per_user.append({"x": x, "y": y})
    return ArraysDataset(users, per_user)


def _bimodal_image_dataset(pool, shape, classes, rng, n_big=3,
                           small=(30, 61), big=1500):
    """Bimodal federated pool: nearly all users tiny (uniform over
    ``small`` samples), ``n_big`` users at ``big`` samples.  Under
    COARSE bucketing every tiny client pads to the big clients' step
    count — the regime cross-client megabatching exists for: the tape
    repacks the tiny clients' step-t batches into a few dense lanes
    while the per-client vmap arm pays the full ``K x S_max`` grid."""
    from msrflute_tpu.data import ArraysDataset
    users, per_user = [], []
    for u in range(pool):
        n = big if u >= pool - n_big else int(rng.integers(*small))
        x = rng.integers(0, 256, size=(n,) + shape, dtype=np.uint8)
        y = rng.integers(0, classes, size=(n,)).astype(np.int32)
        users.append(f"u{u:04d}")
        per_user.append({"x": x, "y": y})
    return ArraysDataset(users, per_user)


def bench_cohort_bucketing_ab(on_tpu: bool) -> dict:
    """Monolithic vs bucketed A/B on a HETEROGENEOUS cohort (ISSUE 8
    acceptance): same protocol, same log-uniform client-size spread,
    ``cohort_bucketing`` off vs on.  Records per-arm wall-clock,
    padding efficiency (real samples / padded grid slots), the padded
    grid slots per round (the masked-FLOPs proxy — grid slots x the
    per-step cost IS the round's compute), compiled bucket-grid
    variants, and the engine's always-on recompile counter — so the
    win is measured against the ``<= max_buckets`` compiled-program
    budget, not asserted."""
    def data_fn():
        # strongly heterogeneous (log-uniform over two orders of
        # magnitude) — the real-federated shape: most clients tiny, a
        # few huge, so the monolithic grid pads nearly everyone to the
        # biggest client's step count
        if on_tpu:
            return _hetero_image_dataset(64, (28, 28, 1), 62,
                                         np.random.default_rng(7),
                                         min_samples=20, max_samples=4800)
        return _hetero_image_dataset(48, (784,), 10,
                                     np.random.default_rng(7),
                                     min_samples=4, max_samples=1200)

    def per_arm(server, arm):
        pad = getattr(server, "padding_efficiency", None)
        extra = {
            "padding_efficiency": round(float(pad), 4)
            if pad is not None else None,
            "recompiles": int(server.engine.recompile_count),
            "compiled_programs": len(server.engine.compile_log),
            "bucket_grid_variants":
                len(server.engine.bucket_shapes_seen),
        }
        # masked-FLOPs proxy: padded grid slots per round — slots x the
        # (identical per arm) per-step cost IS the round's compute;
        # monolithic pays K * S_max * B whatever the cohort needed
        rounds = max(int(server.state.round), 1)
        extra["grid_slots_per_round"] = int(server._pad_slots / rounds)
        # communication side: staged host->device kb per round (in pool
        # mode these are int32 index bytes, not feature bytes)
        staged = server.run_stats.get("hostToDeviceBytesPerRound") or []
        if staged:
            extra["staged_kb_per_round"] = round(
                float(np.mean(staged)) / 1024.0, 2)
        return extra

    max_buckets = 4
    out = _config_block_ab(
        on_tpu, "cohort_bucketing",
        {"off": None, "on": {"enable": True, "max_buckets": max_buckets,
                             "slack": 1.25}},
        data_fn=data_fn,
        protocol=("cnn_femnist_hetero" if on_tpu else "lr_mnist_hetero"),
        per_arm=per_arm)
    out["max_buckets"] = max_buckets
    off = out["cohort_bucketing_off_secs_per_round"]
    out["speedup"] = round(
        off / max(out["cohort_bucketing_on_secs_per_round"], 1e-9), 3)
    pe_off = out.get("cohort_bucketing_off_padding_efficiency")
    pe_on = out.get("cohort_bucketing_on_padding_efficiency")
    # `is not None`, not truthiness: a legitimately 0.0 efficiency arm
    # (all-padding pathology) must still report its gain and FLOPs
    # ratio, else the exact run that most needs the evidence drops it
    if pe_off is not None and pe_on is not None:
        out["padding_efficiency_gain"] = round(
            pe_on / max(pe_off, 1e-9), 3)
        # FLOPs ratio == slots ratio at fixed per-step cost: padding
        # efficiency is real/slots with identical real work per arm
        out["flops_ratio_bucketed_vs_monolithic"] = round(
            pe_off / max(pe_on, 1e-9), 3)
    return out


def bench_megabatch_ab(on_tpu: bool) -> dict:
    """Cross-client megabatching A/B (ISSUE 16 acceptance): the SAME
    heterogeneous protocol with cohort bucketing live in BOTH arms,
    ``server_config.megabatch`` off vs on.  The pool is BIMODAL (most
    clients tiny, a few huge) and bucketing deliberately COARSE
    (``max_buckets: 1``) — the regime megabatch exists for: a wide
    step-need spread inside one bucket means the per-client vmap arm
    pays ``K_b * S_b`` slots while the tape pays only ``lanes *
    depth``, fusing many small clients' step-t batches into one
    device-saturating super-batch per scan step.  ``lanes`` is pinned
    so the worst-case cohort fits one tape group — group membership
    then matches the vmap arm and the finalize sum association is
    unchanged.  Records per-arm steady-state s/round, padding
    efficiency (tape-slot-aware: real samples / compute sample slots),
    megabatch_utilization, mfu_p50 where the device-truth layer is
    live, recompiles, the dispatch gate's chosen arm per bucket shape
    — and pins EQUAL FINAL PARAMS across arms (bitwise on this f32
    single-epoch protocol), so the speedup can never be bought with
    different math."""
    def data_fn():
        if on_tpu:
            return _bimodal_image_dataset(64, (28, 28, 1), 62,
                                          np.random.default_rng(7),
                                          n_big=3, small=(40, 81),
                                          big=4800)
        return _bimodal_image_dataset(48, (784,), 10,
                                      np.random.default_rng(7),
                                      n_big=3, small=(30, 61), big=1500)

    flats = {}

    def per_arm(server, arm):
        import jax
        from jax.flatten_util import ravel_pytree
        flats[arm] = np.asarray(ravel_pytree(
            jax.device_get(server.state.params))[0])
        pad = getattr(server, "padding_efficiency", None)
        util = (server.megabatch_utilization
                if getattr(server, "megabatch", None) is not None
                else None)
        rounds = max(int(server.state.round), 1)
        extra = {
            "padding_efficiency": round(float(pad), 4)
            if pad is not None else None,
            "megabatch_utilization": round(float(util), 4)
            if util is not None else None,
            "recompiles": int(server.engine.recompile_count),
            "compiled_programs": len(server.engine.compile_log),
            "gate_arms": {f"K{k}_S{s}": a for (k, s), a in
                          sorted(server.engine._mega_gate.items())},
            # compute proxy: sample slots the round programs actually
            # paid for (tape slots on taped buckets, grid slots else)
            "compute_slots_per_round": int(server._pad_slots / rounds),
        }
        mfus = server.run_stats.get("mfuPerRound") or []
        if mfus:
            extra["mfu_p50"] = round(
                float(np.percentile(mfus, 50)), 5)
        return extra

    # lanes=4 covers the worst-case cohort (3 big + 7 tiny clients) in
    # ONE tape group, so the on-arm never splits the cohort differently
    # from the vmap arm and final params stay bitwise-comparable
    out = _config_block_ab(
        on_tpu, "megabatch",
        {"off": None, "on": {"enable": True, "lanes": 4}},
        data_fn=data_fn,
        protocol=("cnn_femnist_bimodal" if on_tpu else "lr_mnist_bimodal"),
        per_arm=per_arm,
        server_over={
            # a wide cohort is the point: 24 clients x B rows per step in
            # the vmap grid vs lanes x B in the tape
            "num_clients_per_iteration": 24,
            "cohort_bucketing": {
                "enable": True, "max_buckets": 1, "slack": 1.25}})
    off = out["megabatch_off_secs_per_round"]
    out["speedup"] = round(
        off / max(out["megabatch_on_secs_per_round"], 1e-9), 3)
    pe_off = out.get("megabatch_off_padding_efficiency")
    pe_on = out.get("megabatch_on_padding_efficiency")
    if pe_off is not None and pe_on is not None:
        out["padding_efficiency_gain"] = round(
            pe_on / max(pe_off, 1e-9), 3)
        out["flops_ratio_mega_vs_vmap"] = round(
            pe_off / max(pe_on, 1e-9), 3)
    if "off" in flats and "on" in flats:
        out["final_params_max_abs_diff"] = float(
            np.max(np.abs(flats["on"] - flats["off"])))
        out["final_params_bitwise_equal"] = bool(
            np.array_equal(flats["on"], flats["off"]))
    return out


def scale_probe(backend: str) -> dict:
    """K-clients-per-round scaling curve (the reference's "tens of
    thousands sampled / millions total" axis, ``README.md:9``).  Run via
    ``BENCH_SCALE_PROBE=1``.

    TPU: the CNN protocol over the device pool at K up to 1024 — find
    where ``[K, S, B, ...]`` staging hits the memory ceiling and how
    s/round grows.  CPU: the LR protocol at K=8/100/1000 through the
    ``LazyHDF5Users``/``LazyUserDataset`` host loader (per-user
    on-demand IO + bounded LRU — the path a million-client pool rides),
    recording s/round and host RSS so the curve demonstrates the host
    side scales sub-linearly in pool size."""
    curve = {}
    on_tpu = backend == "tpu"
    if on_tpu:
        ks = (64, 128, 256, 512, 1024, 2048)
        for k in ks:
            cfg = _flute_config({"model_type": "CNN", "num_classes": 62},
                                20, 0.1, fuse=4)
            cfg.server_config.num_clients_per_iteration = k
            if k >= 1024:
                # vmap over 1024 whole clients OOMs the 16G chip (measured:
                # 20.26G needed); scan-over-chunks bounds activation memory.
                # NB item assignment: attribute-set on a non-field lands
                # outside the MutableMapping view and .get() never sees it
                cfg.server_config["clients_per_chunk"] = 256
            try:
                data = _image_dataset(max(k, 8), 240, (28, 28, 1), 62,
                                      np.random.default_rng(0))
                res = bench_protocol("cnn_femnist", cfg, data, eval_users=4,
                                     warmup_rounds=4, timed_chunks=2,
                                     eval_every=50)
                curve[str(k)] = {"secs_per_round": res["secs_per_round"]}
            except Exception as exc:
                curve[str(k)] = {"error": f"{type(exc).__name__}: {exc}"}
                msg = str(exc).upper()
                if "RESOURCE_EXHAUSTED" in msg or "OUT OF MEMORY" in msg:
                    break  # memory ceiling found; larger K is only worse
                _FAILED.append(f"scale_probe[{k}]")
        return curve

    import resource
    import tempfile

    from msrflute_tpu.data.dataset import LazyUserDataset
    from msrflute_tpu.data.user_blob import (LazyHDF5Users, UserBlob,
                                             save_user_blob_hdf5)

    pool = 1000
    spu = 20
    rng = np.random.default_rng(0)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "pool.hdf5")
        blob = UserBlob(
            user_list=[f"u{i:05d}" for i in range(pool)],
            num_samples=[spu] * pool,
            user_data=[{"x": rng.normal(size=(spu, 784)).astype(np.float32)}
                       for _ in range(pool)],
            user_labels=[rng.integers(0, 10, size=(spu,)).astype(np.int64)
                         for _ in range(pool)],
        )
        save_user_blob_hdf5(path, blob)
        users = LazyHDF5Users(path)
        for k in (8, 100, 1000):
            cfg = _flute_config({"model_type": "LR", "num_classes": 10,
                                 "input_dim": 784}, 10, 0.1, fuse=2)
            cfg.server_config.num_clients_per_iteration = k
            try:
                # fresh lazy view per K: the LRU starts cold, so the
                # first rounds pay real per-user hdf5 IO like a cold pool
                data = LazyUserDataset(users, cache_users=128)
                res = bench_protocol("lr_mnist", cfg, data, eval_users=4,
                                     warmup_rounds=2, timed_chunks=2,
                                     eval_every=50)
                curve[str(k)] = {
                    "secs_per_round": res["secs_per_round"],
                    "host_rss_mb": round(
                        resource.getrusage(resource.RUSAGE_SELF)
                        .ru_maxrss / 1024.0, 1),
                }
            except Exception as exc:
                curve[str(k)] = {"error": f"{type(exc).__name__}: {exc}"}
                _FAILED.append(f"scale_probe[{k}]")
    curve["note"] = ("cpu curve: LR protocol via LazyHDF5Users on-demand "
                     "host loader, pool=1000 users on disk; host_rss_mb "
                     "is the process peak (monotone across Ks)")
    return curve


def main() -> None:
    install_deadline_guards()
    device = select_backend()
    backend = device["platform"]
    on_tpu = backend == "tpu"
    if on_tpu:
        # persistent XLA compilation cache: first-compile on TPU is tens of
        # seconds per program; repeat bench runs then start hot
        from msrflute_tpu.utils.backend import enable_compilation_cache
        enable_compilation_cache()
        # the chip's dispatch floor: median round-trip of a trivial
        # jitted op.  Context for every small absolute in this file —
        # e.g. `secs_eval` ≈ one staged dispatch, so for tiny models it
        # reads as ~the floor, not as eval compute.
        import jax
        import jax.numpy as jnp
        trivial = jax.jit(lambda x: x + 1.0)
        jax.block_until_ready(trivial(jnp.float32(0)))
        samples = []
        for _ in range(15):
            tic = time.time()
            jax.block_until_ready(trivial(jnp.float32(0)))
            samples.append(time.time() - tic)
        _LINE["extras"]["dispatch_floor_secs"] = round(
            float(np.median(samples)), 5)
    rng = np.random.default_rng(0)
    # warmup must span at least one fused chunk, else the timed chunks
    # would compile a program shape warmup never ran
    warmup = max(25, _bench_fuse(on_tpu)) if on_tpu else 2
    chunks = 4 if on_tpu else 2
    protocols = build_protocols(on_tpu, rng,
                                with_bf16=on_tpu or
                                bool(os.environ.get("BENCH_BF16")))

    only = os.environ.get("BENCH_PROTOCOLS")  # e.g. "cnn_femnist,lr_mnist"
    keep = set(only.split(",")) if only else None
    if keep is not None:
        protocols = {k: v for k, v in protocols.items() if k in keep}

    extras = _LINE["extras"]  # global so a kill-signal flush sees updates
    extras.update({"backend": backend, "platform": device["platform"],
                   "device_kind": device["kind"],
                   "device_count": device["count"]})
    # subsystem modes are part of the bench CONTRACT: always recorded,
    # so a fault-injected / instrumented / fluteshield-defended run can
    # never be silently compared against a clean, uninstrumented, or
    # undefended baseline.  BENCH_<X> enables the block for every
    # protocol — "1" for the subsystem's default drill, or a JSON
    # server_config.<key> block for a custom one.  The marker honours an
    # explicit `"enable": false` (it must say what the run actually
    # was, not that the env var was set); per-protocol entries also
    # carry the modes via _server_overhead_extras.
    def _env_block(key, env_var, default_block):
        env = os.environ.get(env_var)
        if not env:
            extras[key] = {"enabled": False}
            return
        block = (json.loads(env) if env.strip().startswith("{")
                 else dict(default_block))
        for spec in protocols.values():
            spec["cfg"].server_config[key] = dict(block)
        extras[key] = dict(block, enabled=block.get("enable", True))

    _env_block("chaos", "BENCH_CHAOS",
               {"seed": 0, "dropout_rate": 0.1, "straggler_rate": 0.1,
                "straggler_inflation": 2.0, "ckpt_io_error_rate": 0.05})
    _env_block("telemetry", "BENCH_TELEMETRY", {"enable": True})
    _env_block("robust", "BENCH_ROBUST",
               {"screen_nonfinite": True, "norm_multiplier": 5.0,
                "aggregator": "mean"})
    # precision contract marker (ISSUE 12): BENCH_PRECISION=1 runs every
    # protocol under the default bf16-compute drill (f32 master params +
    # f32 stats accumulators), or a JSON server_config.precision block
    _env_block("precision", "BENCH_PRECISION", {"compute": "bfloat16"})
    # endurance guard (ISSUE 13): BENCH_ENDURANCE=1 arms the days-long
    # posture on every protocol — rollups + flight recorder +
    # longitudinal watchdogs AND the chaos drill — or a JSON object of
    # server_config blocks for a custom drill.  Composite (telemetry
    # plus chaos), so it cannot ride the single-block _env_block helper;
    # the marker discipline is the same: always recorded.
    env = os.environ.get("BENCH_ENDURANCE")
    if not env:
        extras["endurance"] = {"enabled": False}
    else:
        blocks = (json.loads(env) if env.strip().startswith("{") else {
            "telemetry": {"enable": True, "rollup_window": 4,
                          "max_log_mb": 64,
                          "watchdog": {"rss_leak_action": "log",
                                       "throughput_drift_action": "log",
                                       "stall_action": "log",
                                       "stall_grace_secs": 300.0}},
            "chaos": {"seed": 0, "dropout_rate": 0.1,
                      "straggler_rate": 0.1,
                      "straggler_inflation": 2.0,
                      "ckpt_io_error_rate": 0.05}})
        for spec in protocols.values():
            for key, blk in blocks.items():
                spec["cfg"].server_config[key] = dict(blk)
        extras["endurance"] = dict(blocks, enabled=True)

    def _section(name, fn):
        """One bench section under the stall alarm.  A section that
        raises is recorded under its name AND fails the run: the line
        still goes out, then the exit code is non-zero."""
        if _remaining() < 60:
            extras[name] = {"skipped": "caller deadline imminent"}
            _mirror_partial()
            return
        try:
            with _stall_scope(name):
                extras[name] = fn()
        except Exception as exc:  # the line must survive a bad section
            _record_failure(name, exc)
            _mirror_partial()

    def _protocol(name, spec):
        if os.environ.get("BENCH_TEST_HANG_PROTOCOL") == name:
            if os.environ.get("BENCH_TEST_HANG_BLOCK_SIGNALS"):
                # simulate native code that never returns to the
                # interpreter, so signal handlers cannot run and only
                # the watchdog thread helps
                signal.pthread_sigmask(
                    signal.SIG_BLOCK, {signal.SIGTERM, signal.SIGALRM})
            time.sleep(10 * 3600)  # test hook: a hung device call
        return bench_protocol(
            name, spec["cfg"], spec["data"](), eval_users=8,
            warmup_rounds=warmup, timed_chunks=chunks,
            eval_every=spec["eval_every"],
            want_mfu=on_tpu)  # MFU on every protocol (judging input)

    for name, spec in protocols.items():
        _section(name, lambda name=name, spec=spec: _protocol(name, spec))

    # (name, fn, env switch): a section runs by default on the side its
    # gate names — longctx/varlen on TPU, the A/B harnesses on CPU runs
    # (where they are the acceptance evidence) — or when its BENCH_*
    # switch is set; all respect the BENCH_PROTOCOLS narrowing
    gated = [
        ("longctx_ringlm", bench_longctx, on_tpu, "BENCH_LONGCTX"),
        ("varlen_bucketing", bench_varlen_bucketing, on_tpu,
         "BENCH_VARLEN"),
        ("faithful_pipeline_ab", bench_pipeline_ab, not on_tpu,
         "BENCH_PIPELINE_AB"),
        ("fused_carry_pipeline_ab", bench_fused_carry_ab, not on_tpu,
         "BENCH_FUSED_AB"),
        ("telemetry_overhead_ab", bench_telemetry_ab, not on_tpu,
         "BENCH_TELEMETRY_AB"),
        ("robust_overhead_ab", bench_robust_ab, not on_tpu,
         "BENCH_ROBUST_AB"),
        ("secagg_overhead_ab", bench_secagg_ab, not on_tpu,
         "BENCH_SECAGG_AB"),
        ("cohort_bucketing_ab", bench_cohort_bucketing_ab, not on_tpu,
         "BENCH_BUCKETING_AB"),
        ("megabatch_ab", bench_megabatch_ab, not on_tpu,
         "BENCH_MEGABATCH_AB"),
        ("traffic_ab", bench_traffic_ab, not on_tpu, "BENCH_TRAFFIC_AB"),
    ]
    for name, fn, default_on, env_var in gated:
        if (default_on or os.environ.get(env_var)) and \
                (keep is None or name in keep):
            _section(name, lambda fn=fn: fn(on_tpu))

    if os.environ.get("BENCH_SCALE_PROBE"):
        _section("scale_probe", lambda: scale_probe(backend))

    signal.alarm(0)  # the line is about to go out; disarm the self-flush
    _flush()
    if _FAILED:
        raise SystemExit(1)


if __name__ == "__main__":
    try:
        main()
    except BaseException as exc:  # noqa: BLE001 - contract: always emit
        if not _FLUSHED:
            _flush(f"crashed: {type(exc).__name__}: {exc}")
            _mirror_partial()
        raise
