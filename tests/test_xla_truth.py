"""flutescope device-truth layer (ISSUE 7): compiled cost capture,
recompile sentinel, live MFU/HBM scorecard, and the cross-run gates.

The acceptance pyramid:

1. unit — operand signatures, the sentinel's diff payload, the shared
   MFU formula and chip table;
2. watchdog — ``recompile_storm`` actions off/log/mark/abort over the
   engine's cumulative recompile counter, warmup semantics;
3. end-to-end — a pipelined depth-3 chaos run with telemetry on
   (strict transfers) reports per-round MFU + HBM watermark in
   ``scorecard.json``, emits ZERO recompile events after warmup (this
   pins PR 6's no-recompile data-operand invariant, previously
   untested), stays bit-identical to telemetry-off, and
   ``tools/scope diff --gate`` flags a seeded round-time regression
   between two runs with a non-zero exit code;
4. tooling — the committed scorecard fixtures gate (clean pair passes,
   seeded-regression pair exits 3 naming the metric), the bench-artifact
   trend gate, and the bench contract's device-truth fields.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.data import ArraysDataset
from msrflute_tpu.engine import OptimizationServer
from msrflute_tpu.models import make_task
from msrflute_tpu.telemetry.watchdog import Watchdog, WatchdogAbort
from msrflute_tpu.telemetry.xla import (XlaIntrospector, aot_cost, mfu,
                                        operand_signature, signature_diff)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCORECARDS = os.path.join(REPO, "tests", "data", "scorecards")


def _cfg(depth, telemetry=None, chaos=None, rounds=6):
    raw = {
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": rounds, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.2, "rounds_per_step": 1,
            "pipeline_depth": depth,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 100, "initial_val": False, "data_config": {}},
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.2},
            "data_config": {"train": {"batch_size": 4}}},
    }
    if telemetry is not None:
        raw["server_config"]["telemetry"] = telemetry
    if chaos is not None:
        raw["server_config"]["chaos"] = chaos
    return FLUTEConfig.from_dict(raw)


def _dataset():
    rng = np.random.default_rng(0)
    users, per = [], []
    for u in range(8):
        users.append(f"u{u}")
        per.append({"x": rng.normal(size=(8, 8)).astype(np.float32),
                    "y": rng.integers(0, 4, 8).astype(np.int32)})
    return ArraysDataset(users, per)


# ======================================================================
# 1. unit: signatures, sentinel, shared MFU math
# ======================================================================
def test_operand_signature_is_structural():
    a = ({"x": jnp.ones((4, 8))}, jnp.ones((4,), jnp.int32))
    b = ({"x": jnp.ones((4, 8)) * 2}, jnp.zeros((4,), jnp.int32))
    assert operand_signature(a)[0] == operand_signature(b)[0]  # values free
    c = ({"x": jnp.ones((8, 8))}, jnp.ones((4,), jnp.int32))
    assert operand_signature(a)[0] != operand_signature(c)[0]  # shape
    d = ({"x": jnp.ones((4, 8), jnp.bfloat16)}, jnp.ones((4,), jnp.int32))
    assert operand_signature(a)[0] != operand_signature(d)[0]  # dtype
    e = ({"x": jnp.ones((4, 8)), "y": jnp.ones(())},
         jnp.ones((4,), jnp.int32))
    assert operand_signature(a)[0] != operand_signature(e)[0]  # treedef


def test_signature_diff_names_the_changed_leaf():
    _, da = operand_signature((jnp.ones((4, 8)),))
    _, db = operand_signature((jnp.ones((8, 8)),))
    diff = signature_diff(da, db)
    assert list(diff) == ["changed"]
    (path, entry), = diff["changed"].items()
    assert entry["was"][0] == [4, 8] and entry["now"][0] == [8, 8]


def test_forced_shape_change_emits_exactly_one_recompile_with_diff():
    """The sentinel's contract: warmup compile -> ``xla_compile``;
    steady-state repeats -> NOTHING; one operand-shape change -> exactly
    one ``recompile`` event carrying the correct old/new shapes."""
    reg = XlaIntrospector()
    fn = reg.wrap("toy", jax.jit(lambda x: (x @ x.T).sum()))
    fn(jnp.ones((4, 8)))
    fn(jnp.ones((4, 8)) * 3)          # same signature: cached executable
    events = reg.drain_events()
    assert [e["entry"] for e in events] == ["toy"]
    assert events[0]["kind"] == "xla_compile"
    assert events[0].get("flops", 0) > 0
    assert reg.recompiles == 0

    out = fn(jnp.ones((6, 8)))        # forced operand-shape change
    assert float(out) == pytest.approx(float((np.ones((6, 8)) @
                                              np.ones((6, 8)).T).sum()))
    events = reg.drain_events()
    assert len(events) == 1 and events[0]["kind"] == "recompile"
    (path, entry), = events[0]["diff"]["changed"].items()
    assert entry["was"][0] == [4, 8] and entry["now"][0] == [6, 8]
    assert reg.recompiles == 1
    assert reg.entries["toy"]["compiles"] == 2


def test_note_dispatch_attributes_the_dispatched_variant():
    """With two coexisting compiled variants of one entry point (bucket
    churn — the exact case the sentinel observes), the live-MFU snapshot
    must carry the cost of the variant actually dispatched, not
    whichever compiled last."""
    reg = XlaIntrospector()
    fn = reg.wrap("toy", jax.jit(lambda x: (x @ x.T).sum()))
    fn(jnp.ones((4, 64)))
    small_flops = reg.last_dispatch["flops"]
    fn(jnp.ones((32, 64)))            # bigger bucket: recompile
    big_flops = reg.last_dispatch["flops"]
    assert big_flops > small_flops
    fn(jnp.ones((4, 64)))             # back to the SMALL cached variant
    assert reg.last_dispatch["flops"] == small_flops
    assert reg.recompiles == 1        # the return dispatch is cached


def test_eval_compiles_feed_the_always_on_recompile_counter(tmp_path):
    """Server-level accounting: eval_step compiles join
    ``engine.compile_log`` (and so the recompile counter the storm
    watchdog and scorecard gate on) — an eval-grid churn cannot hide
    from the sentinel behind the event stream."""
    from msrflute_tpu.data import ArraysDataset

    cfg = FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": 4, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.2, "rounds_per_step": 1,
            "pipeline_depth": 0, "telemetry": {"enable": True},
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 2, "initial_val": False,
            "data_config": {"val": {"batch_size": 8}}},
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.2},
            "data_config": {"train": {"batch_size": 4}}},
    })
    rng = np.random.default_rng(5)
    vusers, vper = [], []
    for u in range(4):
        vusers.append(f"v{u}")
        vper.append({"x": rng.normal(size=(12, 8)).astype(np.float32),
                     "y": rng.integers(0, 4, 12).astype(np.int32)})
    server = OptimizationServer(make_task(cfg.model_config), cfg,
                                _dataset(),
                                val_dataset=ArraysDataset(vusers, vper),
                                model_dir=str(tmp_path), seed=0)
    server.train()
    assert "eval_step" in server.engine.compile_log
    # one stable eval grid: one compile, still zero recompiles
    assert server.engine.compile_log.count("eval_step") == 1
    assert server.engine.recompile_count == 0
    # and the scorecard's compile count includes it
    card = server.build_scorecard()
    assert card["compiles"] == len(server.engine.compile_log) >= 2


def test_mfu_formula_and_chip_table():
    from msrflute_tpu.utils.compat import (CPU_NOMINAL_PEAK_FLOPS,
                                           TPU_PEAK_FLOPS,
                                           chip_peak_flops)
    assert mfu(1e12, 1.0, peak_flops=197e12) == pytest.approx(1e12 / 197e12)
    assert mfu(0.0, 1.0, peak_flops=197e12) is None
    assert mfu(1e12, 0.0, peak_flops=197e12) is None
    kind, peak = chip_peak_flops()  # this suite runs on CPU
    assert peak == CPU_NOMINAL_PEAK_FLOPS and "cpu" in kind
    # the v5e "lite" device_kind spelling resolves like the short name
    class _Dev:
        device_kind = "TPU v5 lite"
    assert chip_peak_flops(_Dev())[1] == TPU_PEAK_FLOPS["v5e"]
    # bench.py's pre-backend-selection mirror cannot drift
    sys.path.insert(0, REPO)
    import bench
    assert bench.V5E_BF16_PEAK_FLOPS == TPU_PEAK_FLOPS["v5e"]


def test_aot_cost_normalized_keys():
    cost = aot_cost(lambda x: jnp.tanh(x @ x.T), jnp.ones((8, 8)))
    assert cost is not None
    assert cost["flops"] > 0 and cost["bytes_accessed"] > 0
    assert cost["hbm_bytes"] == (cost["temp_bytes"] +
                                 cost["argument_bytes"] +
                                 cost["output_bytes"])


# ======================================================================
# 2. recompile_storm watchdog actions
# ======================================================================
def _storm_watchdog(action, fired, marked):
    return Watchdog({"recompile_storm_action": action,
                     "recompile_storm_threshold": 2,
                     "recompile_storm_warmup_rounds": 2,
                     "round_time_action": "off", "nan_loss": "off",
                     "ckpt_failure_action": "off"},
                    on_event=lambda kind, **f: fired.append((kind, f)),
                    on_mark=lambda kind, f: marked.append(kind))


@pytest.mark.parametrize("action", ["off", "log", "mark", "abort"])
def test_recompile_storm_actions(action):
    fired, marked = [], []
    wd = _storm_watchdog(action, fired, marked)
    # warmup rounds: recompiles 0 -> 3 set the baseline, never fire
    wd.observe_round(0, recompiles=0)
    wd.observe_round(1, recompiles=3)
    assert fired == []

    def feed(round_no, recompiles):
        wd.observe_round(round_no, recompiles=recompiles)

    if action == "abort":
        feed(2, 4)  # storm=1 < threshold: armed but quiet
        assert fired == []
        with pytest.raises(WatchdogAbort):
            feed(3, 5)  # storm=2 == threshold
        assert fired and fired[0][0] == "watchdog_recompile_storm"
        assert marked == ["recompile_storm"]
        return
    feed(2, 4)
    feed(3, 5)
    if action == "off":
        assert fired == [] and marked == []
        return
    assert len(fired) == 1
    kind, fields = fired[0]
    assert kind == "watchdog_recompile_storm"
    assert fields["recompiles_after_warmup"] == 2
    assert marked == (["recompile_storm"] if action == "mark" else [])
    # each NEW recompile past the threshold re-fires; a flat counter is
    # quiet
    feed(4, 5)
    assert len(fired) == 1
    feed(5, 6)
    assert len(fired) == 2


# ======================================================================
# 3. the end-to-end acceptance: depth-3 pipelined chaos run
# ======================================================================
def test_depth3_chaos_device_truth_acceptance(tmp_path, monkeypatch):
    monkeypatch.setenv("MSRFLUTE_STRICT_TRANSFERS", "1")
    chaos = {"seed": 7, "dropout_rate": 0.3, "straggler_rate": 0.3,
             "straggler_inflation": 2.0}

    # ---- run A: telemetry on, depth 3, chaos ----
    cfg = _cfg(3, telemetry={"enable": True}, chaos=dict(chaos), rounds=9)
    server = OptimizationServer(make_task(cfg.model_config), cfg,
                                _dataset(), model_dir=str(tmp_path / "a"),
                                seed=0)
    state = server.train()
    assert state.round == 9 and server.pipelined_chunks > 0
    a_params = jax.device_get(state.params)

    # ZERO recompile events after warmup: every chaos vector is a data
    # operand, every chunk reuses the one compiled staged program (the
    # PR 6 invariant, now pinned by the sentinel itself)
    assert server.engine.recompile_count == 0
    assert server.engine.xla.recompiles == 0
    assert server.engine.compile_log == ["staged_r1"]

    # scorecard: per-round MFU + HBM watermark + recompiles, machine form
    card_path = tmp_path / "a" / "telemetry" / "scorecard.json"
    with open(card_path) as fh:
        card = json.load(fh)
    assert card["rounds"] == 9 and card["pipeline_depth"] == 3
    assert card["mfu_p50"] is not None and card["mfu_p50"] > 0
    assert card["hbm_peak_bytes"] > 0
    assert card["recompiles"] == 0
    assert card["entry_points"]["staged_r1"]["flops"] > 0
    assert card["chip"]["peak_flops"] > 0
    assert card["overlap_efficiency_pct"] > 0
    assert len(server.run_stats["mfuPerRound"]) > 0

    # the compile event (and the per-round MFU bus counters) are in the
    # structured streams — read through the ONE reader, which also
    # surfaces the scorecard verbatim
    from msrflute_tpu.telemetry.scope_cli import summarize
    summary = summarize(str(tmp_path / "a"))
    assert summary["events"].get("xla_compile", 0) >= 1
    assert "recompile" not in summary["events"]
    assert summary["counters"]["devbus/mfu"]["samples"] >= 1
    assert summary["counters"]["devbus/hbm_program_gb"]["samples"] >= 1
    assert summary["scorecard"]["recompiles"] == 0

    # ---- bit-identity: telemetry off, same chaos/depth/seed ----
    cfg_off = _cfg(3, chaos=dict(chaos), rounds=9)
    server_off = OptimizationServer(make_task(cfg_off.model_config),
                                    cfg_off, _dataset(),
                                    model_dir=str(tmp_path / "off"),
                                    seed=0)
    off_params = jax.device_get(server_off.train().params)
    for la, lb in zip(jax.tree.leaves(a_params),
                      jax.tree.leaves(off_params)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))
    assert server_off.engine.xla is None

    # ---- run B: seeded round-time regression (a slow dispatch) ----
    cfg_b = _cfg(3, telemetry={"enable": True}, chaos=dict(chaos),
                 rounds=6)
    server_b = OptimizationServer(make_task(cfg_b.model_config), cfg_b,
                                  _dataset(),
                                  model_dir=str(tmp_path / "b"), seed=0)
    import time as _time
    orig = server_b.engine.dispatch_rounds

    def slow_dispatch(*args, **kwargs):
        _time.sleep(0.06)
        return orig(*args, **kwargs)

    server_b.engine.dispatch_rounds = slow_dispatch
    server_b.train()

    # ---- the gate: scope diff flags B's round time, exit code 3 ----
    from msrflute_tpu.telemetry.scope_cli import main as scope_main
    rc = scope_main(["diff", str(tmp_path / "a"), str(tmp_path / "b"),
                     "--gate"])
    assert rc == 3
    rc = scope_main(["diff", str(tmp_path / "a"), str(tmp_path / "a")])
    assert rc == 0


def test_scorecard_keeps_a_small_mfu_above_zero(tmp_path):
    """Six significant digits, not six decimals: the acceptance run
    above reads 2e-6 on an idle host and a few 1e-7 on a busy one, and
    a utilisation that is measured is never 0."""
    cfg = _cfg(1, telemetry={"enable": True}, rounds=2)
    server = OptimizationServer(make_task(cfg.model_config), cfg,
                                _dataset(), model_dir=str(tmp_path),
                                seed=0)
    server.run_stats["mfuPerRound"] = [3.1234567e-8, 4.1234567e-8, 5e-8]
    assert server.build_scorecard()["mfu_p50"] == 4.12346e-8
    server.run_stats["mfuPerRound"] = []
    assert server.build_scorecard()["mfu_p50"] is None


# ======================================================================
# 4. tooling gates: committed fixtures + trend + bench contract
# ======================================================================
def test_scope_diff_gate_clean_pair_passes():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scope"), "diff",
         os.path.join(SCORECARDS, "baseline.json"),
         os.path.join(SCORECARDS, "clean.json"), "--gate"],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-500:]
    out = json.loads(proc.stdout)
    assert out["ok"] is True and out["regressions"] == []


def test_scope_diff_gate_seeded_regression_exits_nonzero():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scope"), "diff",
         os.path.join(SCORECARDS, "baseline.json"),
         os.path.join(SCORECARDS, "regressed.json"), "--gate"],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 3, (proc.returncode, proc.stderr[-500:])
    out = json.loads(proc.stdout)
    names = {r["metric"] for r in out["regressions"]}
    # the seeded fixture regresses round time AND recompiles — both
    # named, machine-readable
    assert "round_secs_p50" in names and "recompiles" in names
    assert "REGRESSION" in proc.stderr
    # without --gate the finding is reported but the exit stays 0
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scope"), "diff",
         os.path.join(SCORECARDS, "baseline.json"),
         os.path.join(SCORECARDS, "regressed.json")],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0


def test_scope_trend_gates_bench_artifacts(tmp_path):
    def bench_line(value, cnn_secs):
        return {"metric": "cnn_femnist_secs_per_round", "value": value,
                "extras": {"backend": "tpu",
                           "cnn_femnist": {"secs_per_round": cnn_secs}}}

    a, b_ok, b_bad = (tmp_path / "BENCH_A.json", tmp_path / "BENCH_B.json",
                      tmp_path / "BENCH_C.json")
    a.write_text(json.dumps(bench_line(0.10, 0.10)))
    b_ok.write_text(json.dumps(bench_line(0.105, 0.104)))
    b_bad.write_text(json.dumps(bench_line(0.20, 0.21)))

    from msrflute_tpu.telemetry.scope_cli import main as scope_main
    assert scope_main(["trend", str(a), str(b_ok), "--gate"]) == 0
    assert scope_main(["trend", str(a), str(b_bad), "--gate"]) == 3
    # a skipped (value: null) artifact between two measured ones is
    # ignored, not treated as a regression anchor
    skipped = tmp_path / "BENCH_SKIP.json"
    skipped.write_text(json.dumps({"metric": "cnn_femnist_secs_per_round",
                                   "value": None, "extras": {}}))
    assert scope_main(["trend", str(a), str(skipped), str(b_ok),
                       "--gate"]) == 0


def test_scope_trend_gates_rounds_to_target_accuracy(tmp_path):
    """The convergence tier joins the trend gate: more rounds to the
    same target regresses, and a previously-reached target decaying to
    null (while the newer artifact still configures one) regresses too;
    null without a configured target never gates."""
    def bench_line(rtt, with_target=True):
        proto = {"secs_per_round": 0.10,
                 "rounds_to_target_accuracy": rtt}
        if with_target:
            proto["traffic"] = {"enabled": True, "mode": "buffered",
                                "target_accuracy": 0.75}
        return {"metric": "cnn_femnist_secs_per_round", "value": 0.10,
                "extras": {"backend": "tpu", "cnn_femnist": proto}}

    import json as _json

    from msrflute_tpu.telemetry.scope_cli import main as scope_main
    paths = {}
    for name, line in (("a", bench_line(20)), ("ok", bench_line(21)),
                       ("slow", bench_line(40)),
                       ("lost", bench_line(None)),
                       ("untargeted", bench_line(None,
                                                 with_target=False))):
        p = tmp_path / f"BENCH_{name}.json"
        p.write_text(_json.dumps(line))
        paths[name] = str(p)
    assert scope_main(["trend", paths["a"], paths["ok"], "--gate"]) == 0
    assert scope_main(["trend", paths["a"], paths["slow"],
                       "--gate"]) == 3
    assert scope_main(["trend", paths["a"], paths["lost"],
                       "--gate"]) == 3
    # no target configured in the newer artifact: not a convergence
    # run, so the null never gates
    assert scope_main(["trend", paths["a"], paths["untargeted"],
                       "--gate"]) == 0


def test_bench_device_truth_contract():
    """Every protocol line must carry the device-truth fields (mfu /
    hbm_peak_bytes / recompiles), and bench's cost analysis goes through
    the ONE shared helper."""
    import inspect

    sys.path.insert(0, REPO)
    import bench

    src = inspect.getsource(bench.bench_protocol)
    for needle in ("device_truth", "hbm_peak_bytes", "recompiles",
                   "chip_peak_flops"):
        assert needle in src, needle
    assert "aot_cost" in inspect.getsource(bench.grad_step_cost)

    # the shared helper really yields the normalized keys on a live task
    task = make_task(_cfg(0).model_config)
    params = task.init_params(jax.random.PRNGKey(0))
    batch = bench._one_client_batch(_dataset(), 4, 2)
    cost = bench.grad_step_cost(task, params, batch)
    assert cost is not None
    assert cost["flops"] > 0 and "bytes_accessed" in cost
    assert cost["hbm_bytes"] > 0
