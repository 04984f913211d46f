"""The benchmark harness: one cell, once, through the real CLI.

Everything that belongs to one cell, configuration, traffic mix, layer
metric, data generator or model reference is a FILE found by name under
``root`` (``workloads/``, ``configs/``, ``traffic/``, ``controls/``,
``layer_metrics/``), and for ``generators/`` and ``reference/`` under
``root`` first, then here; no such name is written here or in ``run.py``.

A configuration's file may name (every such key is optional):

- ``data.generator``: ``generators/<name>.py`` with
  ``write_splits(data_dir, seed, spec)``, ``spec`` = the ``data`` block.
  Absent: ``images`` = ``datagen.py``.  Shipped beside it: ``tokens``.
- ``reference.model``: ``reference/<name>.py`` (required), the plain
  model.  It defines ``init(rng, model_config)`` and
  ``forward(params, x, model_config)`` and may define, over one step's
  batch (every ``[K, S, B, ...]`` array of the packed round at ``[k, s]``
  and ``sample_mask``):
  ``loss(params, batch, model_config)``, absent: cross entropy of
  ``forward(x)`` against ``y`` over the real rows;
  ``sample_count(batch)``, the strategy's weight, absent: the real rows;
  ``required_flops(params, batch, model_config)``, the operations a
  step requires (what the utilisation reader counts, ``readers.py``),
  absent: the dots of that loss's forward and backward as written
  (``flops.py``).

A run, in order (all in one process, which holds the chips):

1. data from ``--seed`` (``datagen.py``), weights from ``--seed`` (the
   model reference's ``init``), the shipped experiment yaml with the
   configuration's and the traffic mix's overlays;
2. ``e2e_trainer.main()`` in-process with ``sys.argv`` set, observed from
   outside by wrapping ``OptimizationServer.train`` (hand the program the
   seeded weights), ``RoundEngine.dispatch_rounds`` and
   ``PackedStats.fetch`` (the fence) — same arguments, same results;
3. before the first timed dispatch, the CHECK program: the engine's own
   one-round dispatch of round 0's cohort traced under
   ``jax.default_matmul_precision("highest")``, on a copy of the state,
   and compiled at the effort of what is compared and never timed
   (``compared_never_timed``, as is 5's round at ``highest``);
4. one evaluation period (the traffic mix's ``period_rounds``) of warm-up
   (every program of a period has then run once), the window of whole
   evaluation periods, then a graceful preemption
   (``server.preemption.request``), the drain and ``SystemExit(75)``;
5. after the trainer has returned: the plain reference
   (``reference/fedround.py``), round 0 at ``highest`` and every round of
   the first timed dispatch at the default precision, and the comparison
   (``check.py``).

``setup_s`` is process start to window open and so holds 1-4's set-up
and the check program, not the reference (5), which no user pays.

The run's seconds by part, on the host's clock, are an earlier line of
their own (``parts_s``, ``Parts``; ``run_s`` from ``run.py`` is the whole,
clean-up included): what a cell costs every later check beside its
window, and what a new cell is sized against (a run may take 360 s).
"""

from __future__ import annotations

import contextlib
import copy
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import typing

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def say(record: dict) -> None:
    """An earlier line of the run's output (the result line is the last)."""
    print(json.dumps(record), flush=True)


# ----------------------------------------------------------------------
# files by name
# ----------------------------------------------------------------------
def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_module(root: str, sub: str, name: str):
    """``<root>/<sub>/<name>.py``, else the benchmark's own."""
    for base in (root, BENCH_DIR):
        path = os.path.join(base, sub, f"{name}.py")
        if os.path.exists(path):
            return load_module(path)
    raise FileNotFoundError(f"no {sub}/{name}.py under {root} or {BENCH_DIR}")


def load_generator(root: str, data: dict):
    """The data generator a configuration's ``data`` block names."""
    name = data.get("generator", "images")
    if name == "images":
        from . import datagen
        return datagen
    return find_module(root, "generators", name)


def load_cell(root: str, name: str) -> dict:
    """``workloads/<name>.json`` -> its configuration and traffic mix."""
    cell = read_json(os.path.join(root, "workloads", f"{name}.json"))
    cell["name"] = name
    cell["config_doc"] = read_json(
        os.path.join(root, "configs", f"{cell['config']}.json"))
    cell["traffic_doc"] = read_json(
        os.path.join(root, "traffic", f"{cell['traffic']}.json"))
    return cell


def load_layer_metrics(root: str) -> dict:
    """Every reader under ``layer_metrics/``: ``{name: module}``; a module
    has ``UNIT`` and ``read(ctx) -> float | None``."""
    return {os.path.splitext(os.path.basename(p))[0]: load_module(p)
            for p in sorted(glob.glob(
                os.path.join(root, "layer_metrics", "*.py")))}


def merge(base: dict, overlay: dict) -> dict:
    out = dict(base)
    for key, value in overlay.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merge(out[key], value)
        else:
            out[key] = copy.deepcopy(value)
    return out


def build_config(cell: dict, trace: bool, control: dict | None) -> dict:
    import yaml
    doc = cell["config_doc"]
    with open(os.path.join(REPO, doc["base_yaml"])) as fh:
        cfg = yaml.safe_load(fh)
    cfg = merge(cfg, doc.get("overlay", {}))
    cfg = merge(cfg, cell["traffic_doc"].get("overlay", {}))
    if control:
        cfg = merge(cfg, control["overlay"])
    data = cfg["server_config"]["data_config"]
    data["val"]["val_data"] = "val.hdf5"
    data["test"]["test_data"] = "test.hdf5"
    cfg["client_config"]["data_config"]["train"]["list_of_train_data"] = \
        "train.hdf5"
    # the window ends by preemption, never by running out of rounds
    cfg["server_config"]["max_iteration"] = 10 ** 9
    if trace:
        # host spans only: the device-metric bus and the compile
        # introspection stay off, so the round program is the untraced
        # run's program
        cfg["server_config"]["telemetry"] = {
            "enable": True, "trace": True, "devbus": False, "xla": False,
            "rollup": False, "flight": False, "scorecard": False}
    return cfg


# ----------------------------------------------------------------------
# observation from outside
# ----------------------------------------------------------------------
class Compile(typing.NamedTuple):
    """One program requested from the backend."""
    end_ts: float
    seconds: float
    name: str
    missed: bool   # the persistent cache had no entry for it
    effort: float  # the compile effort it was requested at


class CompileLog:
    """Every program jax requests from the backend (a persistent-cache hit
    is still a request) and every cache hit/miss, with the time it ended
    (copied from ``chip_smoke.py``)."""

    def __init__(self):
        self.compiles = []  # Compile, in the order they ended
        self.hits = []
        self.misses = []
        self._missed = False

    def install(self) -> None:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == BACKEND_COMPILE_EVENT:
            # jax records a miss inside the request that it belongs to,
            # and calls back on the thread that asked, inside its scope
            from jax._src import config as jax_config
            self.compiles.append(Compile(
                time.time(), float(duration), str(kwargs.get("fun_name")),
                self._missed,
                float(jax_config.exec_time_optimization_effort.value)))
            self._missed = False

    def _event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT:
            self.hits.append(time.time())
        elif event == CACHE_MISS_EVENT:
            self.misses.append(time.time())
            self._missed = True

    def between(self, t0: float, t1: float) -> list:
        return [c for c in self.compiles if t0 < c.end_ts <= t1]


class Parts(dict):
    """Seconds of the run by part, on the host's clock: ``{name: s}``;
    ``<name>_compile`` beside a part = the backend compile requests
    (cache hits too) that ended inside it."""

    def __init__(self, compiles: CompileLog):
        super().__init__()
        self.compiles = compiles

    @contextlib.contextmanager
    def part(self, name: str):
        t0 = time.time()
        try:
            yield
        finally:
            t1 = time.time()
            self[name] = self.get(name, 0.0) + t1 - t0
            inside = sum(c.seconds for c in self.compiles.between(t0, t1))
            if inside:
                self[f"{name}_compile"] = \
                    self.get(f"{name}_compile", 0.0) + inside


_COMPILES = None


def compile_log() -> CompileLog:
    """One listener per process (jax keeps listeners for good)."""
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = CompileLog()
        _COMPILES.install()
    return _COMPILES


def _tree_copy(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, tree)


def compared_never_timed():
    """The compile effort of what is compared and never timed AND states
    its precision (the check program and the plain reference's round 0,
    both under ``highest``; a reader's counting program): XLA's lowest,
    -1.0, for this thread and for the ``with`` block only.  Under
    ``highest`` the TPU's compiler spends 5-7 core-seconds on every large
    float32 product at its default effort; nobody reads these programs'
    speed, and every run of every later check pays their compile or
    their cache's miss.  Not for the reference's default-precision
    rounds: "default" is what the compiler makes of it at the effort the
    timed program is compiled at.  Nothing process-wide: the timed
    programs are requested outside the block, with the options (and so
    the cache entries) they always had.
    The effort is no part of a jitted function's in-memory key, so what
    is compiled inside must not be called again outside: the check
    program is a trace of its own (``highest``), and the reference runs
    after the trainer has returned."""
    from jax._src import config as jax_config
    return jax_config.exec_time_optimization_effort(-1.0)


def round_inputs(batches, client_lrs, server_lrs, quant) -> list:
    """Copies of every round's packed input of one dispatch (every array
    of the packed batch, the two masks and the round's three numbers):
    what the reference follows after the run."""
    return [{
        **{key: np.array(value) for key, value in batch.arrays.items()},
        "sample_mask": np.array(batch.sample_mask),
        "client_mask": np.array(batch.client_mask),
        "client_lr": float(client_lrs[r]),
        "server_lr": float(server_lrs[r]),
        "quant_quantile": float(quant[r]) if quant else None,
    } for r, batch in enumerate(batches)]


class Run:
    """State of one run: the hooks write it, the reduction reads it."""

    def __init__(self, cell: dict, seconds: float, trace: bool,
                 work: str, weights: dict, t_start: float,
                 parts: Parts, readings: bool = False):
        traffic = cell["traffic_doc"]
        self.parts = parts
        self.readings = bool(readings)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.work = work
        self.weights = weights
        self.t_start = t_start
        # one evaluation period of warm-up, one period traced
        self.period = int(traffic["period_rounds"])
        self.server = None
        self.dispatches = []     # dict(rounds, clients)
        # dict(ts, rounds, rounds_done, losses, client_count)
        self.fences = []
        self.first_rounds = None  # the first dispatch's inputs, per round
        self.check = None        # the check program's round 0
        self.first_state_params = None   # device copy, until the fence
        self.first_dispatch_params = None
        self.window_open = None  # index into fences
        self.window_close = None
        self.profile = None      # dict(dir, t0, t1, sync_ts)
        self.profile_state = "idle"

    # -- the wrapped calls ---------------------------------------------
    def on_train(self, server) -> None:
        """Hand the program the benchmark's seeded weights."""
        import jax

        from msrflute_tpu.engine.round import ServerState
        self.server = server
        state = server.state
        have = jax.tree.structure(state.params)
        want = jax.tree.structure(self.weights)
        if have != want:
            raise RuntimeError(
                "the reference's weights do not match the program's "
                f"parameter tree: {want} vs {have}")
        for mine, theirs in zip(jax.tree.leaves(self.weights),
                                jax.tree.leaves(state.params)):
            if mine.shape != theirs.shape or mine.dtype != theirs.dtype:
                raise RuntimeError(
                    f"weight leaf {mine.shape}/{mine.dtype} vs the "
                    f"program's {theirs.shape}/{theirs.dtype}")
        placed = jax.tree.map(
            lambda mine, theirs: jax.device_put(mine, theirs.sharding),
            self.weights, state.params)
        server.state = ServerState(placed, state.opt_state,
                                   state.strategy_state, state.round)

    def run_check_program(self, dispatch, fetch, engine, state, batches,
                          client_lrs, server_lrs, rng, kwargs) -> None:
        """Round 0's cohort through the engine's own dispatch, one round,
        traced under ``highest``, on a copy of the state (the call
        donates its state)."""
        import jax

        from msrflute_tpu.engine.round import ServerState
        quant = kwargs.get("quant_thresholds")
        self.first_rounds = round_inputs(batches, client_lrs, server_lrs,
                                         quant)
        t0 = time.time()
        self.parts["before_check_program_s"] = t0 - self.t_start
        scratch = ServerState(_tree_copy(state.params),
                              _tree_copy(state.opt_state),
                              _tree_copy(state.strategy_state), state.round)
        one = dict(kwargs)
        if quant:
            one["quant_thresholds"] = list(quant[:1])
        if one.get("chaos_vecs"):
            one["chaos_vecs"] = list(one["chaos_vecs"][:1])
        # trace + lower + compile (or the cache's hit) + launch
        with self.parts.part("check_dispatch_s"), \
                jax.default_matmul_precision("highest"), \
                compared_never_timed():
            new_state, stats = dispatch(
                engine, scratch, batches[:1], list(client_lrs[:1]),
                list(server_lrs[:1]), rng, **one)
        with self.parts.part("check_execute_s"):
            jax.block_until_ready(stats.vecs)
        with self.parts.part("check_fetch_s"):
            out = fetch(stats)
            self.check = {
                "stats": {k: np.asarray(v)[0] for k, v in out.items()},
                "new_params": jax.device_get(new_state.params),
            }
        self.check["seconds"] = time.time() - t0
        del new_state, scratch
        say({"check_program_s": self.check["seconds"]})

    def on_fence(self, out: dict, rounds: int) -> None:
        ts = time.time()
        counts = np.maximum(out["client_count"], 1.0)
        done = (self.fences[-1]["rounds_done"] if self.fences else 0) + rounds
        self.fences.append({
            "ts": ts, "rounds": rounds, "rounds_done": done,
            "losses": (out["train_loss_sum"] / counts).tolist(),
            "client_count": np.asarray(out["client_count"]).tolist()})
        if len(self.fences) == 1:
            import jax
            self.fences[0]["agg_grad_norm"] = np.asarray(
                out["agg_grad_norm"]).tolist()
            with self.parts.part("first_params_fetch_s"):
                self.first_dispatch_params = jax.device_get(
                    self.first_state_params)
            self.first_state_params = None
        at_boundary = done % self.period == 0
        index = len(self.fences) - 1
        if self.readings:
            # the compared numbers only: no warm-up and no window
            if index == 0:
                self.window_open = self.window_close = 0
                self.server.preemption.request("readings taken")
            return
        if self.window_open is None:
            if at_boundary and done >= self.period and index >= 1:
                self.window_open = index
                if self.trace:
                    self.start_profile()
            return
        if self.profile_state == "on" and \
                done - self.fences[self.window_open]["rounds_done"] >= \
                self.period:
            self.stop_profile()
        if self.window_close is None and at_boundary and \
                ts - self.fences[self.window_open]["ts"] >= self.seconds:
            self.window_close = index
            if self.profile_state == "on":
                self.stop_profile()
            self.server.preemption.request("benchmark window closed")

    def start_profile(self) -> None:
        import jax
        trace_dir = os.path.join(self.work, "profile")
        # the Python call tracer would slow the host it is observing
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        sync_ts = time.time()
        with jax.profiler.TraceAnnotation("bench_clock_sync"):
            pass
        self.profile = {"dir": trace_dir, "t0": time.time(),
                        "sync_ts": sync_ts}
        self.profile_state = "on"

    def stop_profile(self) -> None:
        import jax
        self.profile["t1"] = time.time()
        with self.parts.part("profile_stop_s"):
            jax.profiler.stop_trace()
        self.profile_state = "done"

    @contextlib.contextmanager
    def watching(self):
        from msrflute_tpu.engine import round as round_mod
        from msrflute_tpu.engine import server as server_mod

        dispatch = round_mod.RoundEngine.dispatch_rounds
        fetch = round_mod.PackedStats.fetch
        train = server_mod.OptimizationServer.train
        run = self

        def watched_train(server):
            run.on_train(server)
            return train(server)

        def timed_dispatch(engine, state, batches, client_lrs, server_lrs,
                           rng, **kwargs):
            if run.first_rounds is None:
                run.run_check_program(dispatch, fetch, engine, state,
                                      batches, client_lrs, server_lrs, rng,
                                      kwargs)
            out = dispatch(engine, state, batches, client_lrs, server_lrs,
                           rng, **kwargs)
            if not run.dispatches:
                # read back at the first fence: what the timed program
                # made of the seeded weights
                run.first_state_params = _tree_copy(out[0].params)
            run.dispatches.append({
                "rounds": len(batches),
                "clients": float(sum(np.sum(b.client_mask)
                                     for b in batches))})
            return out

        def timed_fetch(stats):
            import jax
            jax.block_until_ready(stats.vecs)
            out = fetch(stats)
            run.on_fence(out, stats.rounds)
            return out

        round_mod.RoundEngine.dispatch_rounds = timed_dispatch
        round_mod.PackedStats.fetch = timed_fetch
        server_mod.OptimizationServer.train = watched_train
        try:
            yield self
        finally:
            round_mod.RoundEngine.dispatch_rounds = dispatch
            round_mod.PackedStats.fetch = fetch
            server_mod.OptimizationServer.train = train
            if self.profile_state == "on":
                self.stop_profile()


def run_cli(cfg: dict, task: str, data_dir: str, out_dir: str) -> int:
    """``e2e_trainer.main()`` with ``sys.argv`` set (as ``chip_smoke.py``);
    returns the exit status the trainer asked for (75 when preempted)."""
    import yaml
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import e2e_trainer
    os.makedirs(out_dir, exist_ok=True)
    cfg_path = os.path.join(os.path.dirname(out_dir), "cell.yaml")
    with open(cfg_path, "w") as fh:
        yaml.safe_dump(cfg, fh)
    argv, sys.argv = sys.argv, [
        "e2e_trainer.py", "-config", cfg_path, "-dataPath", data_dir,
        "-outputPath", out_dir, "-task", task]
    try:
        e2e_trainer.main()
    except SystemExit as exc:
        return int(exc.code or 0)
    finally:
        sys.argv = argv
    return 0


# ----------------------------------------------------------------------
# reduction: the window's end-to-end metrics
# ----------------------------------------------------------------------
def window_metrics(run: Run) -> dict:
    fences = run.fences[run.window_open:run.window_close + 1]
    t_open, t_close = fences[0]["ts"], fences[-1]["ts"]
    in_window = fences[1:]
    per_round = [(b["ts"] - a["ts"]) / b["rounds"]
                 for a, b in zip(fences, in_window)]
    rounds = sum(f["rounds"] for f in in_window)
    # fence i belongs to dispatch i: the engine drains in dispatch order
    first = run.window_open + 1
    clients = sum(d["clients"] for d in
                  run.dispatches[first:run.window_close + 1])
    losses = [v for f in in_window for v in f["losses"]]
    return {
        "t_open": t_open, "t_close": t_close,
        "window_s": t_close - t_open, "dispatches": len(in_window),
        "rounds": rounds, "clients": clients,
        "per_round_s": per_round,
        "nonfinite_losses": int(np.sum(~np.isfinite(losses))),
        "round_count_gap": abs(rounds - sum(
            d["rounds"] for d in
            run.dispatches[first:run.window_close + 1])),
        "setup_s": t_open - run.t_start,
    }


def end_to_end(win: dict, cell_name: str) -> dict:
    per_round = np.asarray(win["per_round_s"])
    metrics = {
        "clients_per_s": {"value": win["clients"] / win["window_s"],
                          "unit": "clients/s"},
        "round_s_p50": {"value": float(np.percentile(per_round, 50)),
                        "unit": "s"},
        "round_s_p90": {"value": float(np.percentile(per_round, 90)),
                        "unit": "s"},
        "setup_s": {"value": win["setup_s"], "unit": "s"},
    }
    for name in kept_to_other_cells("end_to_end", cell_name):
        metrics.pop(name, None)
    return metrics


def kept_to_other_cells(group: str, cell_name: str) -> set:
    """The metrics of ``BENCHMARK.json``'s ``group`` whose ``workloads``
    key does not list this cell: a metric that the file keeps to some
    cells is reported there only."""
    listed = read_json(os.path.join(REPO, "BENCHMARK.json"))[group]
    return {entry["name"] for entry in listed
            if cell_name not in entry.get("workloads", [cell_name])}


def device_report() -> dict:
    """The device as jax reports it.  ``memory_peak_bytes`` is the peak on
    the fullest chip: the allocator's peak in use plus, where the backend
    keeps it apart (the TPU runtime reserves a compiled program's scratch
    memory outside ``bytes_in_use``), its peak reservation.  The two
    parts stand beside the sum under keys of their own."""
    import jax
    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    say({"memory_stats": stats[0]})
    fullest = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0) +
                  s.get("peak_bytes_reserved", 0))
    in_use = int(fullest.get("peak_bytes_in_use", 0))
    reserved = int(fullest.get("peak_bytes_reserved", 0))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": in_use + reserved,
            "memory_peak_in_use_bytes": in_use,
            "memory_peak_reserved_bytes": reserved}


def read_spans(out_dir: str) -> list:
    """The program's own host spans (``telemetry/spans.py``), epoch clock."""
    path = os.path.join(out_dir, "models", "telemetry", "events.jsonl")
    spans = []
    if os.path.exists(path):
        with open(path) as fh:
            for line in fh:
                rec = json.loads(line)
                if rec.get("kind") == "span":
                    spans.append(rec)
    return spans


def closed(result: dict, verdicts: list) -> dict:
    """The result with every number compared beside its limit as its last
    key, and the same as the last lines of standard error."""
    for v in verdicts:
        print(f"compared {v['name']} {v['value']!r} limit {v['limit']!r} "
              f"{'ok' if v['ok'] else 'NOT OK'}", file=sys.stderr, flush=True)
    return {**result, "compared": verdicts}


# ----------------------------------------------------------------------
def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: str = BENCH_DIR, control: str | None = None,
             t_start: float | None = None, readings: bool = False) -> dict:
    """One run of one cell; returns the result line's object.  The caller
    (``run.py``) has already checked the platform and the chips.
    ``readings``: stop after the first timed dispatch and return the
    compared numbers alone (what a limit is set from), no metrics."""
    from . import check, trace_reduce

    t_start = time.time() if t_start is None else t_start
    cell = load_cell(root, name)
    doc = cell["config_doc"]
    control_doc = (read_json(os.path.join(root, "controls",
                                          f"{control}.json"))
                   if control else None)
    cfg = build_config(cell, trace, control_doc)

    from msrflute_tpu.utils.backend import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    compiles = compile_log()
    parts = Parts(compiles)
    parts["before_run_cell_s"] = time.time() - t_start

    model = find_module(root, "reference", doc["reference"]["model"])
    with parts.part("weights_s"):
        weights = model.init(
            np.random.default_rng(np.random.SeedSequence([int(seed), 1])),
            cfg["model_config"])

    work = tempfile.mkdtemp(prefix="bench_")
    try:
        data_dir = os.path.join(work, "data")
        with parts.part("data_gen_s"):
            load_generator(root, doc["data"]).write_splits(
                data_dir, seed, doc["data"])
        say({"cell": name, "seed": int(seed), "compilation_cache": cache_dir,
             "data_gen_s": parts["data_gen_s"], "control": control})

        run = Run(cell, seconds, trace, work, weights, t_start, parts,
                  readings)
        out_dir = os.path.join(work, "out")
        with run.watching():
            status = run_cli(cfg, doc["task"], data_dir, out_dir)
        if run.window_close is None or status != os.EX_TEMPFAIL:
            raise RuntimeError(
                f"the trainer ended (status {status}) before the window "
                f"closed: {len(run.fences)} fences")
        t0 = time.time()
        # the drain, the last saves and the trainer's exit
        parts["after_window_s"] = t0 - run.fences[run.window_close]["ts"]
        device = device_report()  # the program's peak, before the reference
        if not readings:
            win = window_metrics(run)
            in_window = compiles.between(win["t_open"], win["t_close"])
            parts["setup_s"] = win["setup_s"]
            parts["window_s"] = win["window_s"]
        spans = read_spans(out_dir) if trace else []
        run.server = None  # the program's state is freed before the reference
        parts["read_back_s"] = time.time() - t0

        t0 = time.time()
        fedround = load_module(os.path.join(BENCH_DIR, "reference",
                                            "fedround.py"))
        reference = dict(
            forward=model.forward, loss=getattr(model, "loss", None),
            sample_count=getattr(model, "sample_count", None),
            model_config=cfg["model_config"],
            params=weights, strategy=doc["reference"]["strategy"],
            block=int(doc["reference"].get("block", 1)))
        # round 0 at `highest`, for the check program (traced under
        # `highest` above); every round of the first dispatch at the
        # backend's default precision, which is what the configurations
        # state and the timed program runs at
        with parts.part("reference_check_s"), compared_never_timed():
            ref_check = fedround.run_rounds(
                rounds=run.first_rounds[:1], precision="highest",
                **reference)[0]
        # at the compiler's own effort: what the backend's default
        # precision computes is the compiler's choice and moves with the
        # effort (the ResNet's rounds at -1.0 read 0.23-0.26 in
        # `timed_later_loss_gap` against the timed program's: PR 35)
        with parts.part("reference_timed_s"):
            refs_timed = fedround.run_rounds(
                rounds=run.first_rounds, precision=None, **reference)
        reference_s = time.time() - t0
        for call, results in (("reference_check", [ref_check]),
                              ("reference_timed", refs_timed)):
            for lap in results[0]["seconds"]:
                parts[f"{call}_{lap}_s"] = sum(
                    r["seconds"][lap] for r in results)

        leaf_gaps = {} if readings else None
        with parts.part("compare_s"):
            numbers = check.compare(
                init_params=weights, ref_check=ref_check,
                refs_timed=refs_timed, rounds=run.first_rounds,
                check_stats=run.check["stats"],
                check_params=run.check["new_params"],
                timed_first=run.fences[0],
                timed_first_params=run.first_dispatch_params,
                dp=doc["reference"].get("dp"),
                leaf_kinds=doc["reference"].get("leaf_kinds"),
                leaf_gaps=leaf_gaps)
        if not readings:
            numbers += [
                ("window_compiles", float(len(in_window))),
                ("nonfinite_losses", float(win["nonfinite_losses"])),
                ("round_count_gap", float(win["round_count_gap"])),
            ]
        verdicts = check.judge(numbers, doc["check_limits"])
        for v in verdicts:
            say({"compared": v["name"], "value": v["value"],
                 "limit": v["limit"], "ok": v["ok"]})
        correct = all(v["ok"] for v in verdicts)

        if readings:
            say({"reference_s": reference_s,
                 "first_fence_s": run.fences[0]["ts"] - t_start,
                 "leaf_gaps": leaf_gaps})
            return closed({"correct": bool(correct), "readings": True,
                           "device": device}, verdicts)
        say({"window_s": win["window_s"], "dispatches": win["dispatches"],
             "rounds": win["rounds"],
             "round_samples": len(win["per_round_s"]),
             "reference_s": reference_s,
             "window_compile_names": [c.name for c in in_window],
             "compile_requests": len(compiles.compiles),
             "cache_hits": len(compiles.hits),
             "cache_misses": len(compiles.misses),
             # [program, effort]: on a warm cache none at effort 0.0,
             # the timed programs'
             "cache_missed": [[c.name, c.effort] for c in compiles.compiles
                              if c.missed],
             "first_fence_s": run.fences[0]["ts"] - t_start})

        result = {
            "correct": bool(correct),
            "attempted": int(win["clients"]),
            # every client update of a round whose loss is not finite
            "failed": int(win["nonfinite_losses"] *
                          win["clients"] / max(win["rounds"], 1)),
            "device": device,
        }
        if not trace:
            result["metrics"] = end_to_end(win, name)
            return closed(result, verdicts)

        with parts.part("trace_reduce_s"):
            reduced = trace_reduce.reduce_profile(run.profile, spans)
        say({"trace": reduced["summary"]})
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
        ctx = {
            "cell": cell, "config": cfg, "spans": spans, "window": win,
            "trace": reduced, "device": device,
            "peaks": read_json(os.path.join(BENCH_DIR, "peaks.json")),
            "model": model, "fedround": fedround, "weights": weights,
            "first_inputs": run.first_rounds[0],
        }
        metrics = {}
        elsewhere = kept_to_other_cells("per_layer", name)
        # a reader may run a program of its own (a model reference's
        # operation count routes one batch): counted, never timed
        with parts.part("layer_metrics_s"), compared_never_timed():
            for metric, reader in load_layer_metrics(root).items():
                if metric in elsewhere:
                    continue
                value = reader.read(ctx)
                if value is not None and math.isfinite(value):
                    metrics[metric] = {"value": float(value),
                                       "unit": reader.UNIT}
        result["metrics"] = metrics
        return closed(result, verdicts)
    finally:
        with parts.part("cleanup_s"):
            shutil.rmtree(work, ignore_errors=True)
        say({"parts_s": parts})  # as far as the run got
