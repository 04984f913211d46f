"""The bench harness's JSON contract must survive every exit path.

A caller's ``timeout`` SIGTERMs ``bench.py`` mid-run; a kill signal or an
expired caller deadline must still produce the one JSON line (with
whatever partial results exist).  The harness runs on the platform JAX
gives it: these tests ask for the CPU explicitly (``BENCH_BACKEND=cpu``).

Reference contract under test: the driver runs ``python bench.py`` and
expects exactly one JSON object on stdout (repo convention; reference
publishes its numbers in ``/root/reference/README.md:38-41``).
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _env(**over):
    env = dict(os.environ)
    env.update({"BENCH_BACKEND": "cpu"}, **over)
    # keep the contract-test subprocesses' partial mirror away from the
    # repo-root one (and from any operator-exported BENCH_PARTIAL_PATH):
    # a real measurement's crash evidence must not be deleted by our
    # successful flushes
    if "BENCH_PARTIAL_PATH" not in over:
        env["BENCH_PARTIAL_PATH"] = os.path.join(
            os.environ.get("TMPDIR", "/tmp"),
            f"BENCH_PARTIAL_test_{os.getpid()}.json")
    return env


def _json_line(stdout: str) -> dict:
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    assert lines, "bench.py emitted nothing on stdout"
    assert len(lines) == 1, f"expected exactly one JSON line, got: {lines}"
    out = json.loads(lines[0])
    assert out["metric"] == "cnn_femnist_secs_per_round"
    assert "extras" in out
    return out


def test_expired_deadline_still_emits_json():
    """A caller deadline too small for any protocol -> skips + JSON line,
    rc=0 (never a silent empty exit)."""
    proc = subprocess.run(
        [sys.executable, BENCH], env=_env(BENCH_DEADLINE_SECS="25"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-500:]
    out = _json_line(proc.stdout)
    skipped = [k for k, v in out["extras"].items()
               if isinstance(v, dict) and "skipped" in v]
    assert skipped, out["extras"]
    # chaos AND telemetry modes are part of the contract on every line,
    # even a deadline-skipped one — uninstrumented here
    assert out["extras"]["chaos"] == {"enabled": False}
    assert out["extras"]["telemetry"] == {"enabled": False}


def test_bench_telemetry_mode_recorded_when_instrumented():
    """BENCH_TELEMETRY=1 must brand the line as instrumented (the PR 3
    chaos-mode guard applied to flutescope): an instrumented run can
    never be silently compared against an uninstrumented baseline."""
    proc = subprocess.run(
        [sys.executable, BENCH],
        env=_env(BENCH_DEADLINE_SECS="25", BENCH_TELEMETRY="1"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-500:]
    out = _json_line(proc.stdout)
    assert out["extras"]["telemetry"].get("enabled") is True


def test_bench_records_device_truth_for_every_measured_protocol():
    """ISSUE 7 bench contract: every protocol line carries the
    `device_truth` block — chip kind, platform and device count, MFU vs
    THIS chip's peak (absent on a CPU run: a CPU number is never written
    under a device metric's name), `hbm_peak_bytes` from the compiled
    program's memory analysis, and the engine's always-on `recompiles`
    counter."""
    proc = subprocess.run(
        [sys.executable, BENCH],
        env=_env(BENCH_PROTOCOLS="lr_mnist", BENCH_DEADLINE_SECS="300"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-500:]
    out = _json_line(proc.stdout)
    # the line names the device it ran on
    assert out["extras"]["backend"] == "cpu"
    assert out["extras"]["platform"] == "cpu"
    assert out["extras"]["device_kind"]
    assert out["extras"]["device_count"] >= 1
    measured = {k: v for k, v in out["extras"].items()
                if isinstance(v, dict) and "secs_per_round" in v}
    assert measured, out["extras"]
    for name, line in measured.items():
        truth = line.get("device_truth")
        assert truth is not None, (name, line)
        assert set(truth) >= {"chip", "mfu", "hbm_peak_bytes",
                              "recompiles", "compiled_programs"}, truth
        # fleet marker (ISSUE 14): every protocol entry declares its
        # fleet posture — the chaos/telemetry/robust/endurance guard
        # discipline applied to paged-carry / O(cohort)-sampling runs,
        # so a fleet run can never be silently compared against a
        # resident baseline
        assert line.get("fleet") == {"enabled": False}, (name, line)
        # traffic marker (ISSUE 19): every protocol entry declares its
        # arrival-plane posture and carries the convergence field —
        # null here because no traffic.target_accuracy is configured,
        # never a fabricated number
        assert line.get("traffic") == {"enabled": False}, (name, line)
        assert "rounds_to_target_accuracy" in line, (name, line)
        assert line["rounds_to_target_accuracy"] is None, (name, line)
        # a steady-state bench protocol never recompiles (the sentinel's
        # no-churn invariant holds on the bench path too)
        assert truth["recompiles"] == 0, (name, truth)
        assert truth["chip"] and truth["platform"] == "cpu", truth
        # CPU contract: no utilisation is printed from a CPU run
        assert truth["mfu"] is None, truth
        assert "mfu_vs_bf16_peak" not in line, line


def test_sigterm_mid_run_flushes_partial_json():
    """SIGTERM while protocols are running -> partial results + flush_note
    on stdout, clean exit."""
    proc = subprocess.Popen(
        [sys.executable, BENCH], env=_env(),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    time.sleep(15)  # enough for jax import + at least backend selection
    proc.send_signal(signal.SIGTERM)
    try:
        stdout, _ = proc.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        proc.kill()
        pytest.fail("bench.py did not exit after SIGTERM")
    out = _json_line(stdout)
    assert "flush_note" in out["extras"], out["extras"]
    assert "signal 15" in out["extras"]["flush_note"]


def test_stalled_protocol_flushes_well_before_deadline():
    """A protocol that wedges (device call never returns) may hold the
    process only BENCH_PROTOCOL_STALL_SECS, not the whole deadline: the
    stall alarm flushes the line naming the in-flight protocol."""
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, BENCH],
        env=_env(BENCH_DEADLINE_SECS="600",
                 BENCH_PROTOCOL_STALL_SECS="5",
                 BENCH_TEST_HANG_PROTOCOL="lr_mnist",
                 BENCH_PROTOCOLS="lr_mnist"),
        capture_output=True, text=True, timeout=180)
    took = time.time() - t0
    assert proc.returncode == 0, proc.stderr[-500:]
    out = _json_line(proc.stdout)
    note = out["extras"].get("flush_note", "")
    # the stall alarm and the watchdog thread race; either rescuer
    # satisfies the contract
    assert "signal 14" in note or "watchdog exit" in note, out["extras"]
    assert out["extras"].get("_in_flight") == "lr_mnist", out["extras"]
    assert took < 120, f"stall budget not honored ({took:.0f}s)"


def test_a_rescuer_that_lost_the_line_waits_for_its_owner(monkeypatch):
    """One SIGALRM reaches the signal-watcher thread and the main thread's
    handler; the one that loses the flush token goes on to ``os._exit``,
    so it must not come back from ``_flush`` before the winner's line is
    out (seen once in tier-1 as rc 0 and an empty stdout)."""
    import io
    import threading

    import bench

    class SlowOut(io.StringIO):
        def write(self, text):
            time.sleep(0.5)  # the window between the pop and the write
            return super().write(text)

    out = SlowOut()
    monkeypatch.setattr(sys, "stdout", out)
    monkeypatch.setattr(bench, "_FLUSH_TOKEN", [None])
    monkeypatch.setattr(bench, "_FLUSHED", False)
    monkeypatch.setattr(bench, "_FLUSH_OWNER", None)
    monkeypatch.setattr(bench, "_DELIVERED", threading.Event())
    monkeypatch.setitem(bench._LINE, "extras", {})
    owner = threading.Thread(target=bench._flush, args=("the owner",))
    owner.start()
    time.sleep(0.1)
    assert bench._flush("the loser") is False
    line = out.getvalue()
    owner.join()
    assert json.loads(line)["extras"]["flush_note"] == "the owner"


def test_wedged_native_call_rescued_by_watchdog_thread():
    """A hung native call: the main thread never re-enters the
    interpreter (simulated by blocking the signals on it), so main-thread
    SIGTERM/SIGALRM handlers cannot run — a rescuer THREAD must flush the
    line and os._exit.  Two independent rescuers exist: the wakeup-fd
    signal watcher (the C-level handler delivers the signal number to a
    pipe another thread reads — signals stay unblocked on that thread)
    and the stall watchdog; either satisfies the contract."""
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, BENCH],
        env=_env(BENCH_DEADLINE_SECS="600",
                 BENCH_PROTOCOL_STALL_SECS="5",
                 BENCH_TEST_HANG_PROTOCOL="lr_mnist",
                 BENCH_TEST_HANG_BLOCK_SIGNALS="1",
                 BENCH_PROTOCOLS="lr_mnist"),
        capture_output=True, text=True, timeout=180)
    took = time.time() - t0
    assert proc.returncode == 0, proc.stderr[-500:]
    out = _json_line(proc.stdout)
    note = out["extras"].get("flush_note", "")
    assert "watchdog exit" in note or "signal 14" in note, out["extras"]
    assert out["extras"].get("_in_flight") == "lr_mnist", out["extras"]
    assert took < 120, f"no rescuer flushed the wedge ({took:.0f}s)"


def test_tpu_measurement_order_headline_first_heaviest_last():
    """dict order = measurement order: the driver-scored headline runs
    first so ANY early flush carries it; resnet (the heaviest compile and
    footprint) runs last so a deadline or failure there costs nothing
    else."""
    sys.path.insert(0, REPO)
    import bench
    import numpy as np
    names = list(bench.build_protocols(True, np.random.default_rng(0),
                                       with_bf16=False))
    assert names[0] == "cnn_femnist", names
    assert names[-1] == "resnet_fedcifar100", names


def test_protocol_geometry_pinned_to_reference():
    """The comparability contract behind every vs_baseline claim: the
    bench replays the reference's protocol geometry (10 clients/round —
    core/server.py sampling; the experiment configs' batch sizes and
    client LRs; K=10 at `README.md:22-41`'s published wall-clocks).  A
    drifted geometry would silently invalidate the on-chip speedup
    table, so pin it."""
    import importlib.util

    import numpy as np
    spec = importlib.util.spec_from_file_location("bench_geom", BENCH)
    b = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(b)
    ps = b.build_protocols(True, np.random.default_rng(0), with_bf16=True)
    expected = {
        # protocol: (client batch, client lr)
        "lr_mnist": (10, 0.03),
        "cnn_femnist": (20, 0.1),
        "cnn_femnist_bf16": (20, 0.1),
        "resnet_fedcifar100": (20, 0.1),
        "rnn_fedshakespeare": (4, 0.8),
    }
    for name, (bs, lr) in expected.items():
        cfg = ps[name]["cfg"]
        assert cfg.server_config["num_clients_per_iteration"] == 10, name
        assert cfg.client_config.data_config.train["batch_size"] == bs, name
        assert float(cfg.client_config.optimizer_config["lr"]) == lr, name
        assert cfg.server_config.optimizer_config["type"] == "sgd", name
        assert float(cfg.server_config.optimizer_config["lr"]) == 1.0, name
    # headline-first ordering is part of the driver contract
    assert next(iter(ps)) == "cnn_femnist"


def test_packed_stats_one_host_fetch_per_round(tmp_path, monkeypatch):
    """Transfer-count regression guard for the packed-stats invariant:
    a faithful-mode (rounds_per_step=1) round loop must pay exactly ONE
    host fetch per round per dtype group — the single packed stats
    buffer — never the ~dozen per-scalar ``device_get``/``float(...)``
    pulls the pipelined loop was built to eliminate.  Counted under a
    ``jax.device_get`` shim on the training thread (the async checkpoint
    writer's fetches live on its own thread and are excluded — they
    overlap device compute by design)."""
    import threading

    import jax
    import numpy as np

    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task

    cfg = FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": 3, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.2, "rounds_per_step": 1,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 100, "initial_val": False, "data_config": {}},
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.2},
            "data_config": {"train": {"batch_size": 4}}},
    })
    rng = np.random.default_rng(0)
    from msrflute_tpu.data import ArraysDataset
    users, per = [], []
    for u in range(8):
        users.append(f"u{u}")
        per.append({"x": rng.normal(size=(8, 8)).astype(np.float32),
                    "y": rng.integers(0, 4, 8).astype(np.int32)})
    ds = ArraysDataset(users, per)
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, ds, model_dir=str(tmp_path),
                                seed=0)

    fetches = []  # leaf-buffer count of each training-thread device_get
    real = jax.device_get
    train_thread = threading.current_thread()

    def counting_get(x):
        if threading.current_thread() is train_thread:
            fetches.append(len(jax.tree.leaves(x)))
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting_get)
    state = server.train()
    monkeypatch.setattr(jax, "device_get", real)

    assert state.round == 3
    # one fetch event per round, each carrying exactly one buffer per
    # dtype group (this config's stats are all-float32: one group)
    assert fetches == [1, 1, 1], fetches
    packers = server.engine._stats_packers
    assert len(packers) == 1
    assert set(next(iter(packers.values())).sizes) == {"float32"}


def test_pipeline_ab_zero_transfer_guard_violations_under_strict_mode(
        tmp_path, monkeypatch):
    """The faithful-mode pipeline A/B's strict-transfers contract
    (fluteguard's runtime half): under ``MSRFLUTE_STRICT_TRANSFERS=1``
    both arms — serial (pipeline_depth=0) and pipelined (depth=1) — run
    with implicit device->host transfers disallowed, finish
    bit-identically, and the bench A/B records the mode.

    jax's own ``transfer_guard`` cannot fire on the CPU backend (device
    memory IS host memory, no transfer exists), so the zero-violation
    assertion is enforced directly at jax's host-materialization points:
    ``ArrayImpl._value`` / ``__array__`` accesses on the training thread
    that do NOT come through an explicit ``jax.device_get`` are implicit
    syncs, and there must be none."""
    import threading

    import jax
    import jax._src.array as jarray
    import numpy as np

    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.data import ArraysDataset
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task
    from msrflute_tpu.utils.strict import strict_transfers_enabled

    monkeypatch.setenv("MSRFLUTE_STRICT_TRANSFERS", "1")
    assert strict_transfers_enabled()

    rng = np.random.default_rng(0)
    users, per = [], []
    for u in range(8):
        users.append(f"u{u}")
        per.append({"x": rng.normal(size=(8, 8)).astype(np.float32),
                    "y": rng.integers(0, 4, 8).astype(np.int32)})

    # sanctioned-fetch shim: explicit device_get sets a thread-local
    # flag; any _value/__array__ materialization without it is implicit
    sanctioned = threading.local()
    real_get = jax.device_get

    def sanctioning_get(x):
        sanctioned.on = True
        try:
            return real_get(x)
        finally:
            sanctioned.on = False

    implicit = []
    train_thread = threading.current_thread()
    real_value = jarray.ArrayImpl._value
    real_array = jarray.ArrayImpl.__array__

    def spy_value(self):
        if not getattr(sanctioned, "on", False) and \
                threading.current_thread() is train_thread:
            implicit.append("_value")
        return real_value.fget(self)

    def spy_array(self, *args, **kwargs):
        if not getattr(sanctioned, "on", False) and \
                threading.current_thread() is train_thread:
            implicit.append("__array__")
        return real_array(self, *args, **kwargs)

    params_by_depth = {}
    for depth in (0, 1):
        cfg = FLUTEConfig.from_dict({
            "model_config": {"model_type": "LR", "num_classes": 4,
                             "input_dim": 8},
            "strategy": "fedavg",
            "server_config": {
                "max_iteration": 6, "num_clients_per_iteration": 4,
                "initial_lr_client": 0.2, "rounds_per_step": 1,
                "pipeline_depth": depth,
                "optimizer_config": {"type": "sgd", "lr": 1.0},
                "val_freq": 100, "initial_val": False, "data_config": {}},
            "client_config": {
                "optimizer_config": {"type": "sgd", "lr": 0.2},
                "data_config": {"train": {"batch_size": 4}}},
        })
        ds = ArraysDataset(list(users), [dict(p) for p in per])
        server = OptimizationServer(make_task(cfg.model_config), cfg, ds,
                                    model_dir=str(tmp_path / f"d{depth}"),
                                    seed=0)
        monkeypatch.setattr(jax, "device_get", sanctioning_get)
        monkeypatch.setattr(jarray.ArrayImpl, "_value",
                            property(spy_value))
        monkeypatch.setattr(jarray.ArrayImpl, "__array__", spy_array)
        try:
            state = server.train()
        finally:
            monkeypatch.setattr(jarray.ArrayImpl, "_value", real_value)
            monkeypatch.setattr(jarray.ArrayImpl, "__array__", real_array)
            monkeypatch.setattr(jax, "device_get", real_get)
        assert state.round == 6
        params_by_depth[depth] = jax.device_get(state.params)
        if depth:
            assert server.pipelined_chunks > 0  # the A arm really overlapped

    assert implicit == [], (
        f"implicit device->host syncs under strict mode: {implicit}")
    # bit-identical across arms — the A/B's standing equivalence contract
    a = jax.tree.leaves(params_by_depth[0])
    b = jax.tree.leaves(params_by_depth[1])
    for la, lb in zip(a, b):
        assert np.array_equal(np.asarray(la), np.asarray(lb))
    # and bench.py's A/B section reports the mode it measured under
    sys.path.insert(0, REPO)
    import bench  # noqa: F401  (import proves the flag plumbing exists)
    import inspect
    assert "strict_transfers" in inspect.getsource(bench.bench_pipeline_ab)


def test_bench_bert_gathered_entry_configures_the_gathered_head():
    """The round-5 mlm_bert_gathered TPU entry must actually select the
    gathered MLM head (and keep the base mlm_bert entry untouched so
    rounds stay comparable)."""
    import importlib.util

    import numpy as np
    spec = importlib.util.spec_from_file_location("bench_gather", BENCH)
    b = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(b)
    ps = b.build_protocols(True, np.random.default_rng(0), with_bf16=False)
    gathered = ps["mlm_bert_gathered"]["cfg"].model_config["BERT"]["model"]
    base = ps["mlm_bert"]["cfg"].model_config["BERT"]["model"]
    assert gathered.get("mlm_head") == "gathered"
    assert "mlm_head" not in base or base["mlm_head"] == "full"
    # same geometry otherwise: any drift would confound the A/B
    for key in ("vocab_size", "hidden_size", "num_hidden_layers",
                "max_seq_length", "dtype"):
        assert gathered[key] == base[key], key


def test_bench_traffic_ab_contract():
    """ISSUE 19 acceptance surface: the traffic_ab harness races sync
    vs buffered on the SAME seeded bursty trace and records
    rounds_to_target_accuracy / secs_to_target / the crossing tick per
    arm — null when an arm never reaches the target, and the comparison
    verdicts are computed from the recorded numbers, not asserted."""
    import inspect

    sys.path.insert(0, REPO)
    import bench

    src = inspect.getsource(bench.bench_traffic_ab)
    for needle in ("rounds_to_target_accuracy", "secs_to_target",
                   "tick_at_target", '"sync"', '"buffered"',
                   "target_accuracy", "sync_discarded", "stale_sum",
                   "async_fewer_secs_to_target",
                   "async_earlier_tick_at_target"):
        assert needle in src, needle
    # both arms draw the identical trace: ONE trace dict, mode-only
    # difference per arm
    assert 'dict(trace, mode=arm)' in src
    # per-protocol record: every protocol entry carries the convergence
    # field and the arrival-plane marker via the shared extras helper
    extras_src = inspect.getsource(bench._server_overhead_extras)
    assert "rounds_to_target_accuracy" in extras_src
    assert '"traffic"' in extras_src
    # main() wires the arm in (default-on for CPU, env-gated on TPU)
    main_src = inspect.getsource(bench.main)
    assert "traffic_ab" in main_src and "BENCH_TRAFFIC_AB" in main_src
