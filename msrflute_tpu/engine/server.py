"""Server round loop — the host-side controller.

Parity target: reference ``OptimizationServer`` (``core/server.py:48-578``).
Everything data-dependent stays here (sampling, eval cadence, LR plateau
decay, checkpointing, logging, timing); everything numeric is inside the
jitted :class:`~msrflute_tpu.engine.round.RoundEngine` program.  Feature map:

- per-round client sampling, incl. ``"lo:hi"`` random count
  (``core/server.py:284-302``)                          -> :meth:`_sample`
- model "broadcast"/collection                          -> RoundEngine
- per-client stats + strategy processing
  (``core/server.py:337-427``)                          -> RoundEngine
- periodic val/test + best tracking (``:448-462``)      -> :meth:`_maybe_eval`
- client-LR decay on val plateau (``:464-469``)         -> ``lr_weight``
- checkpoint/backup/fallback (``:471-475,530-578``)     -> CheckpointManager
- status log (``:477-490``)                             -> ``status_log.json``
- timing stats (``:492-521``)                           -> ``run_stats``
- initial val/test before training (``:236``)           -> ``initial_val``
"""

from __future__ import annotations

import functools
import logging
import os
import time
from collections import deque
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import FLUTEConfig, parse_clients_per_round
from ..data.batching import pack_eval_batches, pack_round_batches, steps_for
from ..data.dataset import BaseDataset
from ..models.base import BaseTask
from ..optim import PlateauTracker, make_lr_schedule
from ..parallel.mesh import CLIENTS_AXIS, make_mesh, pad_to_mesh
from ..resilience import PreemptionHandler, make_chaos
from ..traffic import STALE_HIST_BINS, make_traffic
from ..resilience.integrity import DurableIOLadder, RetryPolicy
from ..strategies import select_strategy
from ..telemetry import NULL_SPAN, emit_event, make_telemetry
from ..telemetry.rollup import host_rss_bytes
from ..utils.logging import flush_metrics, log_metric, print_rank
from ..utils.metrics import Metric, MetricsDict
from ..utils.strict import strict_transfer_scope
from .checkpoint import CheckpointManager
from .evaluation import build_eval_fn, evaluate
from .round import RoundEngine, ServerState


class OptimizationServer:
    """Single-controller federated optimization loop."""

    def __init__(self, task: BaseTask, config: FLUTEConfig,
                 train_dataset: BaseDataset,
                 val_dataset: Optional[BaseDataset] = None,
                 test_dataset: Optional[BaseDataset] = None,
                 server_train_dataset: Optional[BaseDataset] = None,
                 model_dir: str = "./models", mesh=None,
                 seed: int = 0):
        self.task = task
        self.config = config
        self.train_dataset = train_dataset
        self.val_dataset = val_dataset
        self.test_dataset = test_dataset
        self.mesh = mesh if mesh is not None else make_mesh()

        sc = config.server_config
        dp = config.dp_config
        #: universal overlap (PR 6): device-resident strategy carry state
        #: — consulted by strategy selection, the host-orchestrated
        #: predicate, and the RL construction below
        self._fused_carry = bool(sc.get("fused_carry", False))
        strategy_cls = self._select_strategy(config)
        if sc.get("robust"):
            # fluteshield (server_config.robust): a stack aggregator
            # (trimmed_mean / median) swaps in the stack-combining
            # RobustFedAvg; screening-only configs keep the plain
            # strategy.  Non-FedAvg strategies are refused loudly — a
            # robust block that silently aggregated unscreened payloads
            # is the quiet failure this layer exists to prevent.
            from ..strategies.robust import select_robust_strategy
            self.strategy = select_robust_strategy(config, dp, strategy_cls)
        else:
            self.strategy = strategy_cls(config, dp)
        # universal overlap (server_config.fused_carry): strategies whose
        # cross-round state moved into device-resident carry tables
        # (SCAFFOLD controls, EF residuals, personalization heads/alphas)
        # size those tables to the client pool; a no-op for strategies
        # without carry state
        fused_carry = self._fused_carry
        if fused_carry:
            self.strategy.carry_clients = len(train_dataset)
        # fleet mode (server_config.fleet): population size becomes a
        # free variable — O(cohort) cohort draws and, for device-carry
        # strategies, a fixed-capacity page pool replacing the
        # [N, n_params] resident carry tables (engine/paging.py).
        # Parsed BEFORE the engine builds its programs: carry_rows must
        # be set before init_state sizes the tables, and the engine
        # compiles the slot operand in when paging is on.
        _fl = sc.get("fleet") or {}
        self._fleet_cfg = _fl if (_fl and _fl.get("enable", True)) else None
        self._fleet_paged = bool(
            self._fleet_cfg is not None and
            getattr(self.strategy, "device_carry", False))
        if self._fleet_cfg is not None:
            if sc.get("scaffold_device_controls") or \
                    sc.get("ef_device_residuals"):
                raise ValueError(
                    "server_config.fleet does not compose with "
                    "scaffold_device_controls / ef_device_residuals — "
                    "those keep a FULL [N, n_params] table in HBM, the "
                    "exact residency fleet paging exists to replace; "
                    "use fused_carry + fleet instead")
        if self._fleet_paged:
            from ..config import cohort_upper_bound
            cohort_hi = min(cohort_upper_bound(
                sc.get("num_clients_per_iteration", 10)),
                len(train_dataset))
            pad = pad_to_mesh(cohort_hi, self.mesh)
            depth = max(int(sc.get("pipeline_depth", 1) or 0), 0)
            rps = max(int(sc.get("rounds_per_step", 1) or 1), 1)
            # default pool: (in-flight chunks + the one being prepared)
            # cohorts' worth of rows with 2x headroom for cross-round
            # revisits, pow2-quantized; never more rows than clients
            from ..data.batching import pow2_ceil
            mesh_shards = self.mesh.shape[CLIENTS_AXIS]
            if rps > 1 and mesh_shards > 1:
                # a client resampled in a LATER round of one fused
                # chunk can land on a different shard; its carry row
                # would have to cross shards mid-program — exactly the
                # collective the sharded pool exists to avoid.  Single-
                # round chunks migrate between dispatches instead (the
                # pager force-completes the in-flight writeback), so
                # pipeline_depth still provides the overlap.
                raise ValueError(
                    "fleet paged carry on a multi-device clients mesh "
                    f"({mesh_shards} shards) requires rounds_per_step: "
                    f"1 (got {rps}) — a mid-chunk resample onto another "
                    "shard would need a cross-shard carry collective; "
                    "use pipeline_depth for overlap instead")
            auto = pow2_ceil(max(pad * rps * (depth + 1) * 2, pad + 1))
            slots = int(self._fleet_cfg.get("page_pool_slots") or auto)
            slots = min(max(slots, pad), len(train_dataset))
            # mesh-sharded pool: the slot axis splits over CLIENTS_AXIS
            # into contiguous per-shard blocks (per-device HBM =
            # slots / mesh_size rows), so the pool must be a mesh
            # multiple — quantize UP (a pool slightly past N just means
            # some slots never allocate).  The same helper re-derives
            # the geometry at mesh-elastic resume, so construction and
            # resume can never disagree on the quantization rule.
            from ..parallel.sharding import quantize_pool_slots
            slots = quantize_pool_slots(slots, self.mesh)
            # in-flight floor: with depth-N pipelining, (depth+1) chunks
            # of rps cohorts each can pin rows simultaneously — a pool
            # below that would deadlock allocation mid-run; refuse at
            # construction instead (capped at N: once every client is
            # resident no allocation ever happens)
            required = min(pad * rps * (depth + 1), len(train_dataset))
            if slots < required:
                raise ValueError(
                    f"server_config.fleet.page_pool_slots={slots} is "
                    f"below the in-flight floor {required} "
                    f"(= padded cohort {pad} x rounds_per_step {rps} x "
                    f"(pipeline_depth {depth} + 1), capped at the "
                    "population) — raise page_pool_slots or lower "
                    "pipeline_depth")
            self.strategy.carry_rows = slots
        self.engine = RoundEngine(task, config, self.strategy, self.mesh)
        #: fluteshield screening policy (None = firewall path); the ONE
        #: live Shield belongs to the engine — the server reads its
        #: counters/describe() for telemetry + the bench contract
        self.shield = self.engine.shield
        # Host-orchestrated round paths (RL, SCAFFOLD/EF host rounds,
        # personalization's overridden sampling) build their payloads
        # outside the fused round program — the ONE predicate both the
        # fluteshield and the chaos guards below key off.  fused_carry
        # lifts these strategy by strategy: a carry-mode SCAFFOLD/EF run
        # clears its host_rounds/ef_rounds flag at construction, fused RL
        # rides the round program (rl/fused.py), and a server subclass
        # whose ``_sample`` hook degrades to the base sampler under
        # fused_carry declares it with ``fused_carry_sample``
        # (personalization).
        self._sample_hooked = (
            type(self)._sample is not OptimizationServer._sample and
            not (fused_carry and
                 getattr(type(self), "fused_carry_sample", False)))
        host_orchestrated = (
            (sc.get("wantRL", False) and not fused_carry) or
            getattr(self.strategy, "host_rounds", False) or
            getattr(self.strategy, "ef_rounds", False) or
            self._sample_hooked)
        if self.shield is not None:
            if host_orchestrated:
                raise ValueError(
                    "server_config.robust requires the fused round path "
                    "— wantRL, strategy: scaffold / ef_quant, and "
                    "personalization orchestrate rounds host-side and "
                    "would aggregate unscreened payloads; drop the "
                    "robust block for this configuration")

        # ---- resilience: chaos schedule + graceful preemption --------
        # server_config.chaos (resilience/chaos.py): seeded deterministic
        # fault injection.  Client faults (dropout/straggling) ride the
        # fused round program as data operands, so they need the fused
        # path — the host-orchestrated rounds (RL, SCAFFOLD, EF) and
        # personalization's model-dependent sampling build their payloads
        # elsewhere and would silently ignore them.
        self.chaos = make_chaos(sc)
        if self.chaos is not None and (self.chaos.has_client_faults or
                                       self.chaos.has_corruption):
            if host_orchestrated:
                raise ValueError(
                    "server_config.chaos dropout_rate/straggler_rate/"
                    "corrupt_* rates require the fused round path — "
                    "wantRL, strategy: scaffold / ef_quant, and "
                    "personalization orchestrate rounds host-side and "
                    "would ignore the injected faults; zero those rates "
                    "(IO faults and preempt_at_round still apply) or "
                    "drop the feature")
        if self.chaos is not None and self.chaos.has_infra_faults and \
                not self._fleet_paged:
            raise ValueError(
                "server_config.chaos.infra requires fleet paged carry — "
                "the infra fault streams target the fleet host services "
                "(row-store spill/read, the fleet-prefetch daemon, the "
                "writeback fetch, the round marker), which only exist "
                "under server_config.fleet with a fused_carry "
                "device-carry strategy (scaffold / ef_quant / "
                "personalized); zero the infra rates or enable fleet "
                "paging")

        # ---- fluteflow: event-driven arrival plane -------------------
        # server_config.traffic (traffic/): clients become available per
        # a seeded trace and aggregation FIRES when the buffer fills —
        # the schedule replaces boundary sampling (the base _sample
        # consults it), so every plane that assumes "cohort drawn at the
        # round boundary" must either compose or refuse loudly here.
        self.traffic = make_traffic(sc, len(train_dataset))
        #: next fire the base _sample will serve; re-anchored to the
        #: resumed round at train() entry (the timeline is a pure
        #: function of the seed, so fast_forward is a cache warm-up)
        self._traffic_round = 0
        if self.traffic is not None:
            if host_orchestrated:
                raise ValueError(
                    "server_config.traffic requires the fused round "
                    "path — wantRL, strategy: scaffold / ef_quant, and "
                    "personalization orchestrate rounds host-side and "
                    "would keep boundary sampling, silently ignoring "
                    "the arrival plane; drop the traffic block for "
                    "this configuration")
            ncpi = sc.get("num_clients_per_iteration", 10)
            if not isinstance(ncpi, int) or \
                    self.traffic.buffer_size != int(ncpi):
                raise ValueError(
                    f"server_config.traffic.buffer_size "
                    f"({self.traffic.buffer_size}) must equal a FIXED "
                    f"num_clients_per_iteration (got {ncpi!r}) — the "
                    "fused program's [K, S, B] grid is compiled for "
                    "exactly K client slots, so the buffer IS the "
                    "cohort (the FedBuff buffer == K mapping)")
            if (self._fleet_cfg is not None and
                    str(self._fleet_cfg.get("sampling", "uniform"))
                    != "uniform"):
                raise ValueError(
                    "server_config.traffic and fleet.sampling != "
                    "'uniform' are two cohort-selection planes — the "
                    "arrival schedule decides WHO trains, so a "
                    "weighted/floyd fleet draw would be silently "
                    "ignored; use fleet.sampling: uniform or drop the "
                    "traffic block")
            _sa = sc.get("secure_agg") or {}
            if _sa and _sa.get("enable", True):
                _min_surv = int(_sa.get("min_survivors", 0) or 0)
                if _min_surv > self.traffic.buffer_size:
                    raise ValueError(
                        f"secure_agg.min_survivors ({_min_surv}) "
                        f"exceeds traffic.buffer_size "
                        f"({self.traffic.buffer_size}) — a buffered "
                        "fire delivers exactly buffer_size clients, so "
                        "every round would abort below the liveness "
                        "floor; lower min_survivors or raise "
                        "buffer_size")
            if self.engine.traffic_staleness:
                _mgb_t = sc.get("megabatch") or {}
                if _mgb_t and _mgb_t.get("enable", True):
                    raise ValueError(
                        "server_config.megabatch cannot compose with "
                        "traced staleness (traffic.mode: buffered + a "
                        "staleness-aware strategy): megabatch_passes "
                        "replays the strategy's in-jit staleness draw "
                        "per lane and would diverge from the trace's "
                        "true per-client staleness; drop megabatch or "
                        "run traffic.mode: sync")
        #: convergence-tier gate surface (traffic.target_accuracy): the
        #: first round whose val accuracy reaches the configured target
        #: — None until reached, and stays None when no target is set or
        #: the run never gets there.  bench.py records it per protocol
        #: and per traffic_ab arm; `scope trend` gates it alongside
        #: secs_per_round.
        self.rounds_to_target_accuracy: Optional[int] = None
        _tgt = (sc.get("traffic") or {}).get("target_accuracy")
        self.target_accuracy = (float(_tgt) if _tgt is not None else None)
        #: SIGTERM/SIGINT -> drain in-flight round -> emergency
        #: checkpoint -> resumable exit (resilience/preemption.py); the
        #: loop polls `requested` at chunk boundaries
        self.preemption = PreemptionHandler()
        self.preempted = False

        # ---- overlapped host/device round pipeline -------------------
        # pipeline_depth (schema knob, default 1): with depth >= 1 the
        # host drains round k's tail (stats decode, metric logging,
        # privacy processing, checkpoint submit) AFTER dispatching round
        # k+1, so the TPU never idles behind host bookkeeping.  Depth N
        # keeps a ring of up to N dispatched-but-undrained chunks in
        # flight (schema-validated against MAX_PIPELINE_DEPTH — the old
        # silent min(depth, 1) clamp is gone).  Depth 0 restores the
        # serial loop.  Paths that feed host results back into the NEXT
        # dispatch (host-orchestrated RL/SCAFFOLD/EF — i.e. without
        # fused_carry — server replay, the adaptive leakage threshold,
        # a live ``_sample`` hook) force serial — computed here, up
        # front, because the checkpoint-async default below depends on
        # it.
        self.pipeline_depth = max(int(sc.get("pipeline_depth", 1) or 0), 0)
        pm_cfg = config.privacy_metrics_config
        wants_adaptive = bool(
            pm_cfg is not None and pm_cfg.get("apply_metrics", False)
            and pm_cfg.get("adaptive_leakage_threshold"))
        self._pipeline_capable = (
            not (sc.get("wantRL", False) and not fused_carry) and
            not getattr(self.strategy, "host_rounds", False) and
            not getattr(self.strategy, "ef_rounds", False) and
            not (sc.server_replay_config is not None and
                 server_train_dataset is not None) and
            not wants_adaptive and
            not self._sample_hooked)
        # pipelined loops route the per-round `latest` save through the
        # async writer by default so serialization never blocks the next
        # dispatch; an explicit `checkpoint_async:` in the config wins.
        # NOTE the documented skew window (docs/RUNBOOK.md): under async
        # saves, status_log.json can run one round ahead of the on-disk
        # latest_model after a hard crash.
        ckpt_async = sc.get("checkpoint_async")
        if ckpt_async is None:
            ckpt_async = (self.pipeline_depth > 0 and
                          self._pipeline_capable and
                          str(sc.get("checkpoint_backend",
                                     "msgpack")) == "msgpack")
        self.ckpt = CheckpointManager(
            model_dir, backup_freq=sc.get("model_backup_freq", 100),
            backend=str(sc.get("checkpoint_backend", "msgpack")),
            async_latest=bool(ckpt_async),
            retry=RetryPolicy.from_config(sc.get("checkpoint_retry")),
            io_fault=(self.chaos.io_fault_hook if self.chaos is not None
                      else None))

        # ---- flutearmor: ONE durable-IO ladder for every host service
        # (resilience/integrity.py).  The same checkpoint_retry policy
        # that governs checkpoint saves now governs row-store spill/read,
        # the fleet round marker, the writeback fetch, and the rollup
        # writer — with per-surface escalators and the documented
        # degradation table; chaos.infra (when configured) supplies the
        # seeded per-surface fault hooks, so retries redraw fresh
        # decisions exactly like the checkpoint IO stream
        _infra = self.chaos.infra if self.chaos is not None else None
        _hooks = {}
        if _infra is not None:
            _hooks = {"store_write": _infra.hook("store_write"),
                      "store_read": _infra.hook("store_read"),
                      # the round marker is store-family durable IO: it
                      # shares the spill stream (one service, one tag)
                      "marker": _infra.hook("store_write"),
                      "writeback": _infra.hook("writeback"),
                      "writer": _infra.hook("writer")}
        self.ladder = DurableIOLadder(
            policy=RetryPolicy.from_config(sc.get("checkpoint_retry")),
            fault_hooks=_hooks)

        # ---- flutescope telemetry (server_config.telemetry) ----------
        # None when the block is absent/disabled — the default, and the
        # zero-cost contract: every instrumentation point below is one
        # is-None check, no spans, no tracer, no watchdog state
        # (tests/test_telemetry_contract.py).  When on, all host-side
        # consumption reads only values the loop ALREADY fetched (the
        # packed stats, wall clocks), so strict transfer mode and the
        # one-fetch-per-round guard hold unchanged.
        self.scope = make_telemetry(sc.get("telemetry"), model_dir)
        # flag-gated profiling (reference server/client do_profiling flags,
        # core/schema.py:84,233) — emits a TensorBoard-readable XLA trace.
        # An alias of telemetry.profile_rounds: the one RoundProfiler
        # is given the second chunk's rounds (or the only chunk's) when
        # the loop starts; without a telemetry scope the server keeps a
        # bare one, writing to <model_dir>/profile as it always did
        self._do_profiling = bool(
            sc.get("do_profiling", False) or
            config.client_config.get("do_profiling", False))
        self._profiler = None
        if self.scope is not None:
            self._profiler = self.scope.profiler
        elif self._do_profiling:
            from ..telemetry.profiling import RoundProfiler
            self._profiler = RoundProfiler(
                None, os.path.join(model_dir, "profile"))
        #: (device_kind, peak_flops) of the mesh's chip — the live-MFU
        #: denominator, resolved once (utils/compat.py chip table, CPU
        #: nominal fallback); None when the device-truth layer is off
        self._chip = None
        if self.engine.xla is not None:
            from ..utils.compat import chip_peak_flops
            self._chip = chip_peak_flops(next(iter(self.mesh.devices.flat)))
        #: whether host spans are recorded: the non-blocking readiness
        #: probes (ring / inflight / ready_at_start) are asked only then
        self._tracing = self.scope is not None and \
            self.scope.tracer is not None
        if self.scope is not None:
            # what a dispatch and a stats fetch are made of: the engine
            # opens its child spans through the scope
            self.engine.span_factory = self.scope.span
            self.ckpt.telemetry = self.scope
            self.scope.watchdog.on_mark = self._watchdog_mark
            # flight-record context (ISSUE 13): the persisted forensic
            # snapshot embeds the run's scorecard, built at persist time
            self.scope.set_flight_context(self.build_scorecard)
            # a SIGTERM must make the trace/metrics durable BEFORE the
            # drain starts (the drain itself may wedge); the flight
            # record persists in the same window — if the drain then
            # wedges past the grace period, the black box is on disk
            self.preemption.add_flush_hook(self.scope.flush)
            self.preemption.add_flush_hook(self._flight_on_preempt)
        # every failed durable-IO attempt lands a structured
        # store_io_fault instant event (scope-less runs fall back to the
        # metrics stream), and the rollup writer itself degrades through
        # the ladder: an exhausted window append becomes the
        # rollup_windows_dropped event + counter, never an exception up
        # the host tail
        self.ladder.event = self._ladder_event
        if self.scope is not None and self.scope.rollup is not None:
            self.scope.rollup.ladder = self.ladder
            self.scope.rollup.on_drop = self._rollup_dropped

        # LR machinery: server-side schedule + client plateau decay
        self.initial_lr_client = float(sc.get("initial_lr_client", 0.01))
        self.lr_decay_factor = float(sc.get("lr_decay_factor", 1.0))
        self.lr_weight = 1.0
        self.server_lr_schedule = make_lr_schedule(
            sc.annealing_config, float(sc.optimizer_config.get("lr", 1.0)))
        self.plateau: Optional[PlateauTracker] = None
        if sc.annealing_config is not None and \
                sc.annealing_config.get("type") == "val_loss":
            self.plateau = PlateauTracker(
                sc.annealing_config, float(sc.optimizer_config.get("lr", 1.0)))

        self.best_model_criterion = sc.get("best_model_criterion", "loss")
        self.fall_back_to_best = bool(sc.get("fall_back_to_best_model", False))
        self.best_val: Dict[str, Metric] = {}

        # RL meta-aggregation (reference server_config.wantRL + extensions/RL)
        # — the HOST path (double-aggregate + val A/B + reward, three host
        # round trips).  Under fused_carry the tuner instead rides the
        # round program as device-resident carry (rl/fused.py): the engine
        # owns it and no host RLAggregator is built.
        self.rl = None
        if sc.get("wantRL", False) and not fused_carry:
            from ..rl import RLAggregator
            from ..config import RLConfig
            rl_cfg = sc.RL if sc.RL is not None else RLConfig.from_dict({})
            ncpi = sc.get("num_clients_per_iteration", 10)
            if not isinstance(ncpi, int):
                raise ValueError("wantRL requires a fixed "
                                 "num_clients_per_iteration")
            self.rl = RLAggregator(rl_cfg, ncpi, model_dir, seed=seed)
            self._rl_losses = None

        # privacy-attack metric bookkeeping (reference core/server.py:319-325)
        pm = config.privacy_metrics_config
        self.max_allowed_leakage: Optional[float] = None
        self.adaptive_leakage: Optional[float] = None
        if pm is not None and pm.get("apply_metrics", False):
            self.max_allowed_leakage = pm.get("max_allowed_leakage")
            adaptive = pm.get("adaptive_leakage_threshold")
            if adaptive:
                self.adaptive_leakage = float(adaptive)

        # static round-program geometry
        cc = config.client_config
        self.batch_size = int(cc.data_config.train.get("batch_size", 32))
        self.desired_max_samples = cc.get("desired_max_samples") or \
            cc.data_config.train.get("desired_max_samples")
        # np.max, not builtin max: the fleet path hands num_samples in
        # as a 10^6-entry int32 array, and builtin max would iterate it
        # element-by-element in the interpreter
        max_client_samples = int(np.max(np.asarray(
            train_dataset.num_samples)))
        self.max_steps = steps_for(max_client_samples, self.batch_size,
                                   self.desired_max_samples)
        # per-chunk step bucketing: size each fused chunk's [K, S, B] grid
        # to ITS sampled clients instead of the dataset-wide worst case —
        # padded steps are exact no-ops, so the math is unchanged (tested
        # bit-equal), but small-client rounds stop paying max-client FLOPs
        # and memory.  S rounds up to a power of two so jit retraces at
        # most log2(max_steps) distinct programs.
        self.step_bucketing = bool(cc.get("step_bucketing", True))
        # per-chunk LENGTH bucketing (token tasks): crop the [K,S,B,L]
        # grids' all-pad tail columns to a power-of-two bucket — the
        # static-shape answer to the reference DynamicBatchSampler's
        # padding-efficiency packing (utils/data_utils.py:42-119).  Math
        # identical (position masks come from the ids); host-packed path
        # only (the device pool stores full-length rows).
        self.length_bucketing = bool(
            cc.data_config.train.get("length_bucketing", True))
        self._length_bucket_stats = None
        # cohort shape-bucketing (server_config.cohort_bucketing): stop
        # padding every client to the slowest one.  The round's sampled
        # clients partition into a small config-bounded set of
        # power-of-two step buckets; each bucket packs its own compact
        # [K_b, S_b, B, ...] grid and the engine dispatches one collect
        # program per bucket + one on-device finalize per round
        # (engine/round.py).  Boundaries derive from the POPULATION's
        # step-need histogram once at init (greedy-merged to
        # max_buckets), or come from an explicit `boundaries:` list —
        # either way the S set is static, so compiled grid variants stay
        # bounded and the PR 7 recompile sentinel guards closure.
        self.cohort_bucketing = None
        self._step_needs = None
        _cb = sc.get("cohort_bucketing") or {}
        if _cb and _cb.get("enable", True):
            if host_orchestrated:
                raise ValueError(
                    "server_config.cohort_bucketing requires the fused "
                    "round path — wantRL (host), strategy: scaffold / "
                    "ef_quant (host rounds), and personalization's "
                    "overridden sampling orchestrate rounds host-side "
                    "and would silently run unbucketed; drop the block "
                    "or lift the strategy with fused_carry")
            from ..data.batching import bucket_boundaries
            from ..data.fleet import steps_for_array
            # one vectorized metadata pass over the population (fleet
            # scale: a 10^6-user pool must not pay an O(N) python loop
            # at server init)
            needs = steps_for_array(train_dataset.num_samples,
                                    self.batch_size,
                                    self.desired_max_samples)
            max_need = int(needs.max()) if needs.size else 1
            _mb = _cb.get("max_buckets")
            max_buckets = 4 if _mb is None else int(_mb)
            user_bounds = _cb.get("boundaries")
            if user_bounds:
                bounds = [int(b) for b in user_bounds]
                if any(b < 1 for b in bounds) or \
                        any(y <= x for x, y in zip(bounds, bounds[1:])):
                    raise ValueError(
                        "cohort_bucketing.boundaries must be strictly "
                        f"increasing positive ints, got {bounds}")
                # coverage: the TOP bucket must fit the biggest client's
                # step need or its data would silently truncate; user
                # boundaries above that only waste padded steps
                covering = [b for b in bounds if b >= max_need]
                top = min(covering[0] if covering else max_need,
                          self.max_steps)
                top = max(top, max_need)
                bounds = [b for b in bounds if b < top] + [top]
            else:
                bounds = bucket_boundaries(needs, max_buckets,
                                           self.max_steps)
            if len(bounds) > max_buckets:
                raise ValueError(
                    f"cohort_bucketing: {len(bounds)} boundaries exceed "
                    f"max_buckets={max_buckets} — raise max_buckets or "
                    "shorten the boundaries list")
            # static per-bucket capacities: every bucket grid dispatches
            # every round at its fixed K_b (occupied or not), so the
            # compiled shape set is exactly one collect program per
            # bucket + one finalize — closed by construction; overflow
            # spills up, top-bucket overflow (rare) enlarges that grid
            # and is exactly what the recompile sentinel exists to see
            from ..config import cohort_upper_bound
            from ..data.batching import bucket_capacities
            cohort_hi = min(cohort_upper_bound(
                sc.get("num_clients_per_iteration", 10)),
                len(train_dataset))
            caps = bucket_capacities(
                needs, bounds, cohort_hi,
                quantum=self.mesh.shape[CLIENTS_AXIS],
                slack=float(_cb.get("slack", 1.5) or 1.5))
            self.cohort_bucketing = {"boundaries": bounds,
                                     "capacities": caps,
                                     "max_buckets": max_buckets}
            self._step_needs = needs
            print_rank(
                f"cohort bucketing on: step buckets {bounds} with "
                f"client capacities {caps} (population max need "
                f"{max_need}, monolithic S {self.max_steps})")

        # cross-client megabatching (server_config.megabatch): static
        # per-bucket LANE counts from the same population histogram the
        # capacities came from — per-round tape planning happens in
        # _pack_bucketed_round, the segment-carrying lane scan in the
        # engine.  The engine __init__ already refused every
        # incompatible config (missing cohort_bucketing, privacy
        # metrics, pallas_apply, fedlabels), so this block only sizes
        # geometry when the cohort block is live.
        self.megabatch = None
        self._mega_slots = 0.0
        self._mega_real = 0.0
        _mgb = sc.get("megabatch") or {}
        if _mgb and _mgb.get("enable", True) and \
                self.cohort_bucketing is not None:
            from ..data.batching import megabatch_lanes
            _mgb_E = max(int(cc.get("num_epochs", 1) or 1), 1)
            mgb_lanes = megabatch_lanes(
                self._step_needs, bounds, cohort_hi, _mgb_E,
                quantum=self.mesh.shape[CLIENTS_AXIS],
                slack=float(_mgb.get("slack", 1.25) or 1.25),
                lanes=_mgb.get("lanes"), caps=caps)
            self.megabatch = {
                "lanes": mgb_lanes, "epochs": _mgb_E,
                "min_gain": float(_mgb.get("min_gain", 0.1) or 0.0),
            }
            print_rank(
                f"megabatch on: per-bucket lanes {mgb_lanes} over step "
                f"buckets {bounds} (tape depth = {_mgb_E} x S_b, "
                f"min_gain {self.megabatch['min_gain']})")

        # device-resident dataset (data_config.train.device_resident): the
        # whole sample pool lives in HBM; rounds ship [K,S,B] int32 indices
        # and the row gather runs inside the compiled round program.
        # Requires the dataset to fit in memory (build_sample_pool).
        self._pool_offsets = None
        if bool(cc.data_config.train.get("device_resident", False)):
            if self.rl is not None or \
                    getattr(self.strategy, "host_rounds", False) or \
                    getattr(self.strategy, "ef_rounds", False):
                # RL / SCAFFOLD / EF rounds go through the host payload
                # path, which never consults the pool — uploading the
                # dataset to HBM would cost memory for zero benefit,
                # silently
                raise ValueError(
                    "data_config.train.device_resident does not apply to "
                    "host-orchestrated rounds (wantRL / strategy: "
                    "scaffold / strategy: ef_quant) — drop the flag for "
                    "this configuration")
            from ..data.batching import build_sample_pool
            pool_np, self._pool_offsets = build_sample_pool(train_dataset)
            self.engine.attach_pool(pool_np)
            del pool_np

        # server replay training (reference core/server.py:429-442): after
        # aggregation, train on server-held data for a few iterations
        self.server_replay = None
        if sc.server_replay_config is not None and \
                server_train_dataset is not None:
            if getattr(self.strategy, "owns_server_update", False):
                raise ValueError(
                    f"{type(self.strategy).__name__} maintains coupled "
                    "parameter sequences; server replay would mutate params "
                    "behind its back — disable server_replay_config")
            self.server_replay = {
                "dataset": server_train_dataset,
                "iterations": int(sc.server_replay_config.get(
                    "server_iterations", 1)),
                "opt_cfg": sc.server_replay_config.optimizer_config,
                # regex allowlist of layers to update during replay
                # (reference set_component_wise_lr, core/trainer.py:725-751)
                "updatable_names": sc.server_replay_config.get(
                    "updatable_names"),
            }

        # quantization threshold annealing (reference core/server.py:294-298)
        self.quant_thresh = cc.get("quant_thresh") or             config.model_config.get("quant_threshold")
        self.quant_anneal = float(cc.get("quant_anneal", 1.0) or 1.0)


        self._eval_fn = build_eval_fn(task, self.mesh,
                                      self.engine.partition_mode)
        if self.engine.xla is not None:
            # device-truth capture for the eval program too: its
            # FLOPs/HBM row joins the scorecard's entry-point table and
            # an eval-grid shape churn trips the same recompile sentinel
            self._eval_fn = self.engine.xla.wrap("eval_step",
                                                 self._eval_fn)
        self._eval_batches_cache: Dict[str, Any] = {}
        self._per_user_fns: Dict[str, Any] = {}
        self._np_rng = np.random.default_rng(seed)
        # device-side randomness: a CONSTANT base key + a host-side use
        # counter; every consumer takes fold_in(base, n) via _next_rng().
        # The counter (not the key) is what resume persists — restoring
        # it re-anchors every later stream bit-exactly WITHOUT fetching
        # key material from the device (which would add a host transfer
        # per round to the pipelined loop's single-fetch contract).
        self._rng = jax.random.PRNGKey(seed)
        self._rng_uses = 0
        self.run_stats: Dict[str, list] = {
            "secsPerRound": [], "secsPerRoundHousekeeping": [],
            "secsPerRoundHostTail": [], "hostToDeviceBytesPerRound": [],
            # live MFU (device-truth layer: compiled FLOPs / round
            # wall-clock / chip peak) — populated only when
            # telemetry.xla captured the round program's cost
            "mfuPerRound": [],
            # real samples / padded grid slots per packed chunk — the
            # cohort-bucketing win, measured on EVERY run (monolithic
            # too, so the bench A/B and scope diff can compare)
            "paddingEfficiency": []}
        #: run-total padding-efficiency accumulators (slots-weighted —
        #: see _record_padding_efficiency)
        self._pad_real = 0.0
        self._pad_slots = 0
        #: chunks whose host tail overlapped the next chunk's device
        #: execution (observability + the equivalence tests' proof that
        #: the pipelined run actually pipelined)
        self.pipelined_chunks = 0

        self.state = self.engine.init_state(self._rng)
        pretrained = config.model_config.get("pretrained_model_path")
        if pretrained:
            from .checkpoint import load_pretrained_params
            params = load_pretrained_params(pretrained, self.state.params,
                                            data_path=config.data_path)
            # warm-started params, fresh optimizer/strategy state, round 0
            # (reference loads the model before training, e2e_trainer.py:104);
            # keep each leaf on the sharding init_state chose for it
            params = jax.tree.map(
                lambda host, old: jax.device_put(
                    jnp.asarray(host, old.dtype), old.sharding),
                params, self.state.params)
            # strategy state re-derives from the WARM params (e.g. FedAC's
            # w_ag sequence must start at the pretrained point, not the
            # discarded random init)
            self.state = ServerState(params, self.state.opt_state,
                                     self.strategy.init_state(params), 0)
            print_rank(f"warm-started from pretrained model {pretrained}")
        resumed = False
        self._status_ring: list = []
        if sc.get("resume_from_checkpoint", False):
            restored = self.ckpt.load(self.state)
            if restored is not None and self._fleet_paged:
                restored = self._paired_fleet_anchor(restored, model_dir)
            if restored is not None:
                self.state = self._place_restored(restored, self.state)
                resumed = True
                status = self._paired_status(self.ckpt.read_status(),
                                             int(self.state.round))
                # continue the per-round anchor ring from the resumed
                # round; entries beyond it belong to the dead trajectory
                # and get rewritten by the replay
                self._status_ring = [
                    e for e in status.get("status_ring", [])
                    if int(e[0]) <= int(self.state.round)]
                self.lr_weight = float(status.get("weight", 1.0))
                # re-anchor the RNG streams (client sampling order + the
                # device-key counter) so the post-resume trajectory is
                # bit-identical to an uninterrupted run — the core of the
                # preemption contract (tests/test_preempt_resume.py)
                self._restore_rng(status)
                # plateau-LR tracker + best-val metrics live only in
                # memory; restore them so the post-resume LR schedule and
                # best-checkpoint decisions re-anchor too
                if self.plateau is not None and "plateau" in status:
                    pl = status["plateau"]
                    self.plateau.lr = float(pl.get("lr", self.plateau.lr))
                    self.plateau.best = pl.get("best")
                    self.plateau.bad_rounds = int(pl.get("bad_rounds", 0))
                hib = status.get("best_val_hib", {})
                for key, value in status.items():
                    if key.startswith("best_val_") and key != "best_val_hib" \
                            and isinstance(value, (int, float)):
                        name = key[len("best_val_"):]
                        self.best_val[name] = Metric(
                            float(value), bool(hib.get(name, name != "loss")))
                print_rank(f"resumed from checkpoint at round {self.state.round}")
                # fast-forward the quantization-threshold annealing to the
                # resumed round: the schedule is a pure geometric series
                # (thresh_R = thresh_0 * anneal^R), but the running value
                # lives only in memory — without this, a resume restarts
                # the anneal from the config value and the post-resume
                # trajectory diverges from an uninterrupted run (both the
                # fused path's self.quant_thresh and the EF strategy's own
                # copy, strategies/ef_quant.py::next_threshold)
                if self.state.round > 0 and self.quant_anneal != 1.0:
                    ff = self.quant_anneal ** self.state.round
                    if self.quant_thresh is not None:
                        self.quant_thresh = float(self.quant_thresh) * ff
                    if getattr(self.strategy, "ef_rounds", False):
                        self.strategy.quant_thresh *= ff

        # SCAFFOLD control variates (strategies/scaffold.py): host-side
        # store under the model dir.  Controls are reloaded ONLY when the
        # model checkpoint itself resumed — params and controls belong to
        # the same trajectory; a fresh run wipes any previous run's files.
        self.scaffold_store = None
        if getattr(self.strategy, "host_rounds", False):
            from ..strategies.scaffold import ControlStore
            n_params = sum(int(np.prod(l.shape))
                           for l in jax.tree.leaves(self.state.params))
            self.scaffold_store = ControlStore(
                n_params, store_dir=os.path.join(model_dir, "scaffold"),
                resume=resumed)
            if resumed and self.scaffold_store.round() != self.state.round:
                # control writes are synchronous but the model checkpoint
                # may be async: a crash can leave controls ahead of the
                # restored params.  Mismatched trajectories must not mix —
                # restart control estimation from zero.
                print_rank(
                    f"SCAFFOLD controls were at round "
                    f"{self.scaffold_store.round()} but the checkpoint "
                    f"resumed at {self.state.round}; resetting controls")
                self.scaffold_store.reset()
        # error-feedback quantization residuals (strategies/ef_quant.py):
        # same durable per-client row-store discipline as the SCAFFOLD
        # controls — residuals belong to the checkpoint's trajectory
        self.ef_store = None
        if getattr(self.strategy, "ef_rounds", False):
            from ..strategies.ef_quant import ResidualStore
            n_params = sum(int(np.prod(l.shape))
                           for l in jax.tree.leaves(self.state.params))
            self.ef_store = ResidualStore(
                n_params, store_dir=os.path.join(model_dir, "ef_residuals"),
                resume=resumed)
            if resumed and self.ef_store.round() != self.state.round:
                # residual writes are synchronous but the checkpoint may
                # land later (async orbax): mismatched trajectories reset
                # (same marker semantics as the SCAFFOLD controls)
                print_rank(
                    f"EF residuals were at round {self.ef_store.round()} "
                    f"but the checkpoint resumed at {self.state.round}; "
                    "resetting residuals")
                self.ef_store.reset()

        # device-resident control table (scaffold_device_controls): keep
        # the whole [N, n_params] table in HBM; gather offsets and scatter
        # the option-II update in-program so no model-sized per-round
        # transfer crosses the host boundary (strategies/scaffold.py
        # DeviceControlTable).  Built AFTER the resume/reset decision so
        # the table warms up from exactly the controls the run keeps.
        self.scaffold_device = None
        if sc.get("scaffold_device_controls", False):
            if self.scaffold_store is None:
                raise ValueError(
                    "server_config.scaffold_device_controls requires "
                    "strategy: scaffold — with "
                    f"{type(self.strategy).__name__} there are no "
                    "controls to keep on device; drop the flag")
            from ..strategies.scaffold import DeviceControlTable
            self.scaffold_device = DeviceControlTable(
                self.scaffold_store, len(train_dataset), self.mesh)
            gb = 4.0 * self.scaffold_device.n_rows * \
                self.scaffold_store.n_params / 2**30
            print_rank(f"SCAFFOLD device control table: "
                       f"{self.scaffold_device.n_rows} x "
                       f"{self.scaffold_store.n_params} ({gb:.2f} GiB HBM)")

        # device-resident EF residual table (ef_device_residuals): same
        # transfer-vs-HBM tradeoff as the SCAFFOLD table — the per-round
        # [K, n_params] residual matrix stops crossing the host boundary
        # in either direction (strategies/ef_quant.py DeviceResidualTable).
        # Built AFTER the resume/reset decision so it warms from exactly
        # the residuals the run keeps.
        self.ef_device = None
        if sc.get("ef_device_residuals", False):
            if self.ef_store is None:
                raise ValueError(
                    "server_config.ef_device_residuals requires "
                    "strategy: ef_quant — with "
                    f"{type(self.strategy).__name__} there are no "
                    "residuals to keep on device; drop the flag")
            from ..strategies.ef_quant import DeviceResidualTable
            self.ef_device = DeviceResidualTable(
                self.ef_store, len(train_dataset), self.mesh)
            gb = 4.0 * self.ef_device.n_rows * \
                self.ef_store.n_params / 2**30
            print_rank(f"EF device residual table: "
                       f"{self.ef_device.n_rows} x "
                       f"{self.ef_store.n_params} ({gb:.2f} GiB HBM)")

        # fleet paged carry (server_config.fleet + fused_carry): the
        # page pool + host backing store behind the carry tables.
        # Built AFTER the resume decision so the durable row store and
        # the restored params stay on one trajectory (the ControlStore
        # marker discipline) — a marker/round mismatch resets the rows.
        self.fleet_pager = None
        if self._fleet_paged:
            from .paging import CarryPager
            if resumed:
                # the restored tables came off the checkpoint as host
                # arrays: first re-derive the slot geometry for THIS
                # mesh (mesh-elastic resume — a checkpoint saved on M
                # shards may restore [P_old] tables), then re-lay them
                # out with the slot axis sharded so the donated round
                # program sees the SAME layout a fresh init builds (no
                # resharding copy, no donation churn)
                self.state = ServerState(
                    self.state.params, self.state.opt_state,
                    self.engine.shard_carry_state(
                        self._elastic_carry_tables(
                            self.state.strategy_state)),
                    self.state.round)
            self.fleet_pager = CarryPager(
                self.strategy, self.state.strategy_state,
                slots=int(self.strategy.carry_rows), mesh=self.mesh,
                store_dir=os.path.join(model_dir, "fleet_carry"),
                host_cache_rows=int(
                    self._fleet_cfg.get("host_cache_rows", 8192) or 8192),
                resume=resumed,
                partition_mode=self.engine.partition_mode,
                prefetch=bool(self._fleet_cfg.get("prefetch", True)),
                ladder=self.ladder,
                faults=(self.chaos.infra if self.chaos is not None
                        else None))
            # the prefetch worker spans its host IO on its own thread
            # track — the trace then SHOWS the paging stage overlapping
            # the device window instead of on the critical path
            self.fleet_pager.scope = self.scope
            if resumed:
                marker = self.fleet_pager.round()
                if marker is None or int(marker) < int(self.state.round):
                    # unreachable when the anchor pairing above chose
                    # the slot, but direct dir surgery / legacy stores
                    # still get the one-trajectory safety net
                    print_rank(
                        f"fleet carry rows were at round {marker} but "
                        f"the checkpoint resumed at {self.state.round}; "
                        "resetting carry rows (one-trajectory rule)")
                    self.fleet_pager.reset()
                else:
                    # prune the dead trajectory's newer row generations
                    # (a marker AHEAD of the anchor is fine: those
                    # generations are exactly what adoption removes)
                    self.fleet_pager.adopt_round(int(self.state.round))
                    self.fleet_pager.mark_durable(
                        int(self.state.round) - 1)
            mb = (self.fleet_pager.n_slots *
                  self.fleet_pager.hbm_row_bytes()) / 2**20
            print_rank(
                f"fleet paged carry: {self.fleet_pager.n_slots} pool "
                f"slots x {sorted(self.strategy.carry_tables)} "
                f"({mb:.1f} MiB HBM total, "
                f"{mb / self.fleet_pager.mesh_shards:.1f} MiB/device "
                f"over {self.fleet_pager.mesh_shards} shards) over "
                f"{len(train_dataset)} clients")

    # ------------------------------------------------------------------
    def _select_strategy(self, config) -> type:
        """The strategy class this server will construct.  Subclasses
        whose behavior moved into a device-carry strategy under
        ``fused_carry`` override this (PersonalizationServer swaps in
        PersonalizedFedAvg); the base server keeps the registry lookup."""
        return select_strategy(config.strategy)

    # ------------------------------------------------------------------
    def _tspan(self, name: str, **args):
        """One flutescope span — the shared no-op context when telemetry
        is off (the off path costs one attribute read + None check)."""
        return self.scope.span(name, **args) if self.scope is not None \
            else NULL_SPAN

    def _watchdog_mark(self, kind: str, fields: Dict[str, Any]) -> None:
        """Watchdog ``mark`` action: persist the finding to the status
        log so a post-mortem sees it without the metrics stream."""
        self.ckpt.update_status({f"watchdog_{kind}": dict(fields)})

    def _ladder_event(self, kind: str, **fields: Any) -> None:
        """The durable-IO ladder's structured-event sink (scope or the
        bare metrics stream — emit_event handles both)."""
        emit_event(self.scope, kind, **fields)

    def _rollup_dropped(self, rec: Dict[str, Any]) -> None:
        """Rollup-writer exhaustion callback: the degradation table's
        telemetry leg — count it, surface it, keep training."""
        dropped = (self.scope.rollup.windows_dropped
                   if self.scope is not None and
                   self.scope.rollup is not None else 1)
        emit_event(self.scope, "rollup_windows_dropped",
                   windows_dropped=int(dropped),
                   window=rec.get("window"))

    def _place_restored(self, restored: Any, template: Any) -> Any:
        """Re-place a checkpoint-restored state on the shardings
        ``init_state`` chose (the pretrained-path idiom): restore hands
        back HOST numpy leaves, and dispatching those raw commits a
        second input layout — the first post-resume chunk would compile
        a warmup variant that differs from steady state (a spurious
        recompile on every resume).  Leaves whose SHAPE changed (a
        mesh-elastic resume's slot-sized carry tables) stay host-side:
        the fleet path rebuilds and re-shards them explicitly.  Only
        MESH shardings are re-placed: a template leaf sitting on a
        SingleDeviceSharding is an UNCOMMITTED jnp-op result whose
        placement was incidental (jit moves it freely), and committing
        the restored copy there via device_put would pin it to one
        device next to committed mesh-sharded params — an
        incompatible-devices dispatch error.  Those leaves come back as
        uncommitted host numpy, the layout the fresh init dispatches."""
        from jax.sharding import SingleDeviceSharding
        def leaf(host, old):
            sh = getattr(old, "sharding", None)
            if sh is None or isinstance(sh, SingleDeviceSharding) or \
                    np.shape(host) != tuple(old.shape):
                return np.asarray(jax.device_get(host))
            return jax.device_put(jnp.asarray(host, old.dtype), sh)
        from .round import ServerState
        return ServerState(
            params=jax.tree.map(leaf, restored.params, template.params),
            opt_state=jax.tree.map(leaf, restored.opt_state,
                                   template.opt_state),
            strategy_state=jax.tree.map(leaf, restored.strategy_state,
                                        template.strategy_state),
            round=restored.round)

    def _paired_fleet_anchor(self, restored: Any, model_dir: str) -> Any:
        """Crash-consistent resume anchor under fleet paging
        (flutearmor crash-point contract): the carry marker commits
        AFTER the model checkpoint, so a hard kill inside a round's
        commit window can leave ``latest_model`` ahead of the durable
        row set (pipelined loops save each chunk's latest at the NEXT
        dispatch, widening the window to the ring depth).  Bit-identical
        resume requires params and carry from the SAME round, so the
        anchor is the round the MARKER proves durable: keep latest when
        it matches (or trails — newer row generations prune away), fall
        back to the ``.prev`` slot when THAT matches, and otherwise
        cold-start — the seeded run replays from round 0 to the same
        bits, trading wall clock for correctness."""
        from .paging import read_marker
        marker = read_marker(os.path.join(model_dir, "fleet_carry"))
        durable = int(marker) if marker is not None else 0
        latest_round = int(restored.round)
        if durable >= latest_round:
            return restored
        from .checkpoint import LATEST_PREV
        prev = self.ckpt.load(self.state, LATEST_PREV)
        if prev is not None and int(prev.round) == durable:
            print_rank(
                f"fleet carry rows are durable through round {durable} "
                f"but latest_model is at {latest_round} (hard stop "
                "inside the commit window); resuming from the previous "
                "slot so params and carry stay on one trajectory")
            return prev
        print_rank(
            f"fleet carry rows are durable through round {durable} with "
            f"no matching checkpoint slot (latest {latest_round}); "
            "cold-starting — the seeded replay reproduces the run "
            "bit-for-bit")
        return None

    def _paired_status(self, status: Dict[str, Any],
                       round_no: int) -> Dict[str, Any]:
        """The status snapshot PAIRED with the resumed round: the
        status log is written before the round's checkpoint commits
        (and an async save can land later still), so after a hard kill
        the flat fields may belong to a nearby round.  The per-round
        anchor ring keeps the last few snapshots; re-anchoring from the
        checkpoint's own entry keeps the replayed sampling trail — and
        the LR/plateau/best-val trajectory — bit-identical.  Logs
        without a ring (or a ring that rolled past the anchor) fall
        back to the flat fields, the historical behaviour."""
        for entry in reversed(status.get("status_ring", [])):
            if int(entry[0]) == int(round_no):
                merged = dict(status)
                merged.update(entry[1])
                return merged
        return status

    def _elastic_carry_tables(self, strategy_state: Any) -> Any:
        """Mesh-elastic resume (flutearmor leg 4): a fleet checkpoint
        saved on M shards restores carry tables sized for the OLD
        mesh's quantized pool; this run's pool (``strategy.carry_rows``,
        re-quantized for the NEW mesh at construction) may differ.
        Slot-sized tables rebuild at the new capacity from the carry
        defaults — sound because resumed slot maps start EMPTY and the
        host row store (shard-agnostic, keyed by global client id) is
        the authoritative row source: every next touch pages the true
        row in, so per-client math never sees the rebuilt defaults.
        The sampling trail replays via the regular RNG re-anchoring —
        final params stay bit-identical to the uninterrupted run
        (tests/test_fleet_mesh.py)."""
        new_slots = int(self.strategy.carry_rows)
        defaults = dict(self.strategy.carry_row_defaults())
        rebuilt = {}
        old_slots = None
        for k in self.strategy.carry_tables:
            leaf = strategy_state[k]
            rows = int(leaf.shape[0])
            if rows == new_slots:
                continue
            old_slots = rows
            rebuilt[k] = np.full(
                (new_slots,) + tuple(int(d) for d in leaf.shape[1:]),
                defaults.get(k, 0.0), dtype=np.dtype(str(leaf.dtype)))
        if not rebuilt:
            return strategy_state
        emit_event(self.scope, "elastic_resume",
                   from_slots=int(old_slots), to_slots=new_slots,
                   mesh_shards=int(self.mesh.shape[CLIENTS_AXIS]),
                   tables=sorted(rebuilt))
        print_rank(
            f"mesh-elastic resume: carry pool re-quantized "
            f"{old_slots} -> {new_slots} slots for the "
            f"{int(self.mesh.shape[CLIENTS_AXIS])}-shard mesh; rows "
            "reload from the host store on first touch")
        new_state = dict(strategy_state)
        new_state.update(rebuilt)
        return new_state

    def _flight_on_preempt(self) -> None:
        """Preemption flush hook: persist the flight record as part of
        the pre-drain durability window (runs OUTSIDE signal context,
        at the round loop's poll — the deferred-flush discipline)."""
        self.scope.record_flight(
            f"preemption: {self.preemption.reason or 'requested'}")

    # ------------------------------------------------------------------
    def _next_rng(self) -> jax.Array:
        """The run's next device RNG stream: ``fold_in(base, n)`` with a
        host-side monotone counter.  Deterministic in EVENT ORDER (which
        the config fixes), and resumable by persisting the single int —
        see ``_rng_snapshot``."""
        key = jax.random.fold_in(self._rng, self._rng_uses)
        self._rng_uses += 1
        return key

    def _rng_snapshot(self) -> Dict[str, Any]:
        """Host-RNG resume anchor: the numpy bit-generator state (client
        sampling + packing shuffles) and the device-key use counter.
        MUST be captured after all randomness attributable to the
        checkpointed rounds is drawn and before any later round draws —
        the caller picks the point (dispatch time when lookahead packing
        overlaps, housekeeping time otherwise)."""
        import copy
        return {
            "np_rng_state": copy.deepcopy(self._np_rng.bit_generator.state),
            "rng_uses": int(self._rng_uses),
        }

    def _restore_rng(self, status: Dict[str, Any]) -> None:
        """Re-anchor both RNG streams from a status-log snapshot (absent
        in pre-resilience status logs -> streams restart, matching the
        old resume behavior)."""
        if "np_rng_state" in status:
            self._np_rng.bit_generator.state = status["np_rng_state"]
        if "rng_uses" in status:
            self._rng_uses = int(status["rng_uses"])

    # ------------------------------------------------------------------
    def _sample(self) -> list:
        if self.traffic is not None:
            # fluteflow: the arrival plane decides WHO trains — the
            # cohort is the fire's buffer contents, replayed from the
            # seeded timeline (deterministic in fire order, so serial ==
            # pipelined == prefetched == resumed).  The numpy sampling
            # trail is untouched: a traffic run is a different trail by
            # construction, like a fleet sampling mode.
            r = self._traffic_round
            self._traffic_round = r + 1
            fire = self.traffic.fire(r)
            emit_event(self.scope, "buffer_fired", round=r,
                       tick=int(fire["tick"]),
                       wait_ticks=int(fire["wait_ticks"]),
                       stale_max=int(fire["staleness"].max(initial=0)),
                       stale_sum=int(fire["staleness"].sum()))
            return [int(c) for c in fire["cohort"]]
        sc = self.config.server_config
        n = parse_clients_per_round(sc.get("num_clients_per_iteration", 10),
                                    self._np_rng)
        n = min(n, len(self.train_dataset))
        fleet_mode = (str(self._fleet_cfg.get("sampling", "uniform"))
                      if self._fleet_cfg is not None else "uniform")
        if fleet_mode != "uniform":
            # fleet cohort draw (data/fleet.py): explicit Floyd /
            # weighted-reservoir sampling.  NOTE the rng-trail contract
            # (docs/config_extensions.md): these modes draw a NEW
            # sampling trail — like changing the seed — while staying
            # deterministic and resume-stable within it.  The default
            # `uniform` mode keeps the numpy draw below, so plain fleet
            # runs stay trail- (and bit-) identical to non-fleet runs.
            from ..data.fleet import sample_cohort
            return sample_cohort(
                self._np_rng, len(self.train_dataset), n,
                mode=fleet_mode,
                num_samples=self.train_dataset.num_samples)
        # random.sample equivalent (core/server.py:300-302).  Already
        # O(cohort) at any population size: numpy's Generator.choice
        # with replace=False uses Floyd's algorithm (time and memory
        # scale with `size`, not the population — pinned by
        # tests/test_fleet.py::test_default_cohort_draw_is_o_cohort),
        # so the default path keeps its historical rng trail even at
        # 10^6+ clients.
        return list(self._np_rng.choice(len(self.train_dataset), size=n,
                                        replace=False))

    # ------------------------------------------------------------------
    def run(self) -> ServerState:
        return self.train()

    def train(self) -> ServerState:
        # graceful-preemption window: SIGTERM/SIGINT during the loop flip
        # the handler's flag (polled at chunk boundaries) instead of
        # killing the process mid-round; previous dispositions are
        # restored on the way out
        self.preempted = False
        self.preemption.reset()  # a past preemption must not latch forever
        self.preemption.install()
        if self.traffic is not None:
            # a resumed run replays the identical fire sequence: the
            # timeline is a pure function of the traffic seed, so this
            # is a cache warm-up, not a state restore
            self._traffic_round = int(self.state.round)
            self.traffic.fast_forward(self._traffic_round)
        if self.scope is not None:
            # stall monitor (ISSUE 13): a named daemon thread polling
            # the round-completion heartbeat — spawned only when
            # telemetry.watchdog.stall_action is not "off"
            self.scope.watchdog.start_stall_monitor()
        try:
            # strict transfer mode (MSRFLUTE_STRICT_TRANSFERS=1,
            # fluteguard's runtime half): the whole round loop — fused,
            # pipelined, and the host-orchestrated RL/SCAFFOLD/EF paths —
            # runs with implicit device->host transfers disallowed; the
            # explicit device_get fetches (packed stats, eval, host
            # tails) are the only sanctioned crossings.  No-op without
            # the env flag.
            with strict_transfer_scope():
                return self._train_loop()
        except BaseException as exc:
            # a mid-loop abort (WatchdogAbort, checkpoint escalation,
            # Ctrl-C) skips _train_loop's normal tail: await in-flight
            # async checkpoint saves so the resume anchor is not missing
            # rounds — best-effort, never masking the original abort
            try:
                self._run_pending_tail(deferred=False)
                self.ckpt.wait()
            except Exception:
                pass
            if self.scope is not None:
                # the flight record IS the abnormal exit's deliverable:
                # last-N events + live rollup window + scorecard,
                # persisted atomically before the stack unwinds further
                try:
                    self.scope.record_flight(
                        f"exception: {type(exc).__name__}",
                        detail=str(exc))
                except Exception:
                    pass
            raise
        finally:
            if self.scope is not None:
                self.scope.watchdog.stop_stall_monitor()
                if self.scope.rollup is not None:
                    # the trailing partial window still holds up to
                    # window-1 rounds of trend data — flush it so the
                    # on-disk rollup stream covers the whole run
                    try:
                        self.scope.rollup.flush_window(partial=True)
                    except Exception:
                        pass
            if self._profiler is not None:
                # an aborted run leaves no profiler window open
                self._profiler.finish()
            if self.scope is not None:
                # the trace of an ABORTED run is exactly the trace the
                # operator needs: materialize trace.json whatever path
                # exited the loop
                try:
                    # compile/recompile events buffered after the last
                    # drain (e.g. an eval compile) land in the streams,
                    # THEN the trace flushes, THEN the scorecard is
                    # built (its overlap numbers read the flushed
                    # trace).  An aborted run keeps its scorecard too —
                    # that is the run `tools/scope diff` most needs.
                    self._drain_xla_events()
                    self.scope.flush()
                    self.scope.write_scorecard(self.build_scorecard())
                except Exception:
                    pass
            self.preemption.uninstall()

    def _train_loop(self) -> ServerState:
        sc = self.config.server_config
        max_iteration = int(sc.get("max_iteration", 100))
        # single source of truth for "is this the final round" decisions
        # made later in _round_housekeeping (scaffold flush cadence)
        self._max_iteration = max_iteration
        val_freq = int(sc.get("val_freq", 20) or 20)
        rec_freq = int(sc.get("rec_freq", 20) or 20)

        if self.state.round == 0 and sc.get("initial_val", True):
            self._maybe_eval("val", self.state.round, force=True)
        if self.state.round == 0 and sc.get("initial_rec", False):
            self._maybe_eval("test", self.state.round, force=True)

        # TPU-native knob (no reference equivalent): how many rounds to fuse
        # into one scanned device program.  1 == FLUTE-style per-round
        # dispatch; larger values amortize host<->device latency.  Chunks
        # never cross an eval boundary, so plateau/LR/fallback semantics are
        # unchanged.
        rounds_per_step = max(int(sc.get("rounds_per_step", 1) or 1), 1)

        if self.rl is not None:
            rounds_per_step = 1  # RL needs val feedback every round
        if self.scaffold_store is not None:
            # control gather/update is per-round host work (like the
            # reference's per-round protocol exchange); no chunk fusion
            rounds_per_step = 1
        if self.server_replay is not None and rounds_per_step > 1:
            # reference runs replay after EVERY round (core/server.py:429);
            # fusing rounds would cut the replay cadence
            print_rank("server replay forces rounds_per_step=1")
            rounds_per_step = 1

        def chunk_R(r0: int) -> int:
            until_val = (val_freq - (r0 % val_freq)
                         if self.val_dataset is not None else max_iteration)
            until_rec = (rec_freq - (r0 % rec_freq)
                         if self.test_dataset is not None else max_iteration)
            return min(rounds_per_step, max_iteration - r0,
                       until_val, until_rec)

        if self._do_profiling and self._profiler.window is None and \
                self.state.round < max_iteration:
            # do_profiling = profile_rounds over the second chunk
            # (post-compile) when there will be more than one, else over
            # the only one
            lo = self.state.round
            if max_iteration - lo > rounds_per_step:
                lo += chunk_R(lo)
            self._profiler.window = (lo, lo + chunk_R(lo))

        def device_fence() -> None:
            # the profiler stops only once the window's last chunk has
            # run (the newest state is the last program's output)
            jax.block_until_ready(self.state.params)

        def pack_chunk(R: int, round0: int) -> list:
            # with look-ahead packing this is the NEXT chunk's round0
            with self._tspan("pack", rounds=R, chunk=round0):
                return _pack_chunk_inner(R)

        def _pack_chunk_inner(R: int) -> list:
            # sample the whole chunk first so every round pads to a common
            # client count (ranged num_clients_per_iteration draws differ)
            chunk_samples = [self._sample() for _ in range(R)]
            if self.cohort_bucketing is not None:
                # nested layout: batches[r] is round r's list of
                # per-bucket grids (ascending bucket order)
                batches = [self._pack_bucketed_round(sampled)
                           for sampled in chunk_samples]
                flat = [b for row in batches for b in row]
                self._maybe_length_bucket(flat)
                self._record_padding_efficiency(flat)
                return batches
            pad_to = pad_to_mesh(max(len(s) for s in chunk_samples),
                                 self.mesh)
            steps = self._chunk_steps(chunk_samples)
            if self._pool_offsets is not None:
                from ..data.batching import pack_round_indices
                batches = [pack_round_indices(
                    self.train_dataset, self._pool_offsets, sampled,
                    self.batch_size, steps, rng=self._np_rng,
                    pad_clients_to=pad_to,
                    desired_max_samples=self.desired_max_samples)
                    for sampled in chunk_samples]
                self._record_padding_efficiency(batches)
                return batches
            batches = [pack_round_batches(
                self.train_dataset, sampled, self.batch_size, steps,
                rng=self._np_rng, pad_clients_to=pad_to,
                desired_max_samples=self.desired_max_samples)
                for sampled in chunk_samples]
            self._maybe_length_bucket(batches)
            self._record_padding_efficiency(batches)
            return batches

        # prefetch: with fused chunks, the NEXT chunk's host-side sampling
        # and packing happen right after this chunk's async dispatch, so the
        # numpy work overlaps device execution instead of serializing with
        # it.  Disabled when anything host-side runs between chunks that
        # could interact with sampling/packing order (RL, server replay —
        # both force rounds_per_step=1 anyway — and subclasses that hook
        # ``_sample`` against the live global model, e.g. personalization).
        prefetch_ok = (rounds_per_step > 1 and self.rl is None and
                       self.server_replay is None and
                       type(self)._sample is OptimizationServer._sample)
        prefetched = None  # (R, batches) for the upcoming round_no

        # pipelined mode subsumes prefetch: packing ALREADY overlaps the
        # device because the whole host tail is deferred past dispatch
        pipelined = self.pipeline_depth > 0 and self._pipeline_ok()
        if pipelined:
            prefetch_ok = False
        # fleet row prefetch: stage the NEXT chunk's missing carry rows
        # (host-store IO) on the pager's worker thread while this
        # chunk executes, so the page-in's host half leaves the
        # critical path.  Needs lookahead packing — the same sampling-
        # order discipline prefetch_ok already guards (the rng draw
        # order is unchanged: cohorts are data-independent lookahead).
        fleet_prefetch = (self.fleet_pager is not None and
                          self.fleet_pager.prefetch_enabled and
                          self.rl is None and self.server_replay is None
                          and not self._sample_hooked)
        lookahead_pack = prefetch_ok or (pipelined and fleet_prefetch)
        # the ring of dispatched-but-undrained chunks, oldest first: up to
        # ``pipeline_depth`` stay in flight; each dispatch drains the
        # oldest once the ring is full, so with depth N the host tail of
        # chunk k overlaps the device execution of chunks k+1..k+N
        pending: deque = deque()
        self._last_fence = 0.0

        round_no = self.state.round
        start_round = round_no
        while round_no < max_iteration:
            # preemption poll (chunk granularity): a SIGTERM between
            # chunks, or the chaos drill's preempt_at_round, stops BEFORE
            # dispatching new device work; the in-flight pending chunk is
            # drained after the loop so its rounds are kept, checkpointed,
            # and the exit is resumable.  The drill fires only when this
            # run CROSSES the threshold from below — a resumed run that
            # starts at/past it (the RUNBOOK drill relaunches with the
            # same config) trains on instead of re-preempting forever.
            if (self.chaos is not None and
                    self.chaos.preempt_at_round is not None and
                    start_round < self.chaos.preempt_at_round <= round_no
                    and not self.preemption.requested):
                self.preemption.request(
                    f"chaos preempt_at_round="
                    f"{self.chaos.preempt_at_round}")
            if self.preemption.requested:
                # a signal-context request deferred its observability
                # flush (file IO is unsafe in a handler); run it here,
                # outside signal context, BEFORE the drain starts
                self.preemption.flush_now()
                break
            tic = time.time()
            R = chunk_R(round_no)
            if self._profiler is not None:
                # opt-in jax.profiler window (telemetry.profile_rounds,
                # or do_profiling): chunk boundaries are the only safe
                # start/stop points; the chunk's round RANGE decides, so
                # a window inside a fused chunk still captures (the
                # whole chunk)
                self._profiler.observe(round_no, rounds=R,
                                       fence=device_fence)

            # host-orchestrated per-round paths (RL re-weighting, SCAFFOLD
            # controls) share the normal round bookkeeping tail
            host_round = (self._run_rl_round if self.rl is not None else
                          self._run_scaffold_round
                          if self.scaffold_store is not None else
                          self._run_ef_round
                          if self.ef_store is not None else None)
            if host_round is not None:
                with self._tspan("host_round", round=round_no):
                    host_round(round_no)
                if self.server_replay is not None:
                    # the reference runs replay after EVERY round
                    # (core/server.py:429)
                    self._run_server_replay()
                round_no += 1
                self.run_stats["secsPerRound"].append(time.time() - tic)
                self._round_housekeeping(round_no, val_freq, rec_freq)
                continue

            client_lr = self.initial_lr_client * self.lr_weight
            server_lrs = [(self.plateau.lr if self.plateau is not None
                           else self.server_lr_schedule(r))
                          for r in range(round_no, round_no + R)]
            if prefetched is not None and prefetched[0] == R:
                batches = prefetched[1]
            else:
                batches = pack_chunk(R, round_no)
            prefetched = None
            self._record_staged_bytes(batches, R)

            chunk_rng = self._next_rng()
            quant_thresholds = None
            if self.quant_thresh is not None:
                # per-round annealed thresholds (core/server.py:294-298),
                # each logged at its own round like the reference
                quant_thresholds = []
                for j in range(R):
                    self.quant_thresh *= self.quant_anneal
                    quant_thresholds.append(self.quant_thresh)
                    log_metric("Quantization Thresh.", self.quant_thresh,
                               step=round_no + j)

            for ch in pending:
                # submit each pending chunk's `latest` checkpoint BEFORE
                # this dispatch donates its state buffers: the async
                # writer enqueues device-side copies that execute in
                # stream order, ahead of the donating program (only the
                # newest ring entry can still be unsaved)
                if not ch["latest_saved"]:
                    # single-slot writer: this waits for the save still
                    # in flight, so it is a span of its own.  Nothing in
                    # it may wait for the chunk's own program: the host
                    # staging of the next chunk, below, is what the ring
                    # hides behind that program.
                    with self._tspan("ckpt_presubmit",
                                     round=ch["round0"] + ch["R"],
                                     rounds=ch["R"],
                                     chunk=ch["round0"]) as span:
                        launched = self.ckpt.save_latest(ch["state"])
                        if span is not None and launched:
                            span.update(launched)
                    ch["latest_saved"] = True
            if self.fleet_pager is not None:
                # fleet paging: map the chunk's cohorts onto pool slots
                # and page missing rows in (one fixed-shape donated
                # scatter, sequenced after the save_latest copies above
                # and before this dispatch) — batches gain their
                # carry_slots vectors here
                with self._tspan("fleet_page", round0=round_no,
                                 rounds=R, chunk=round_no):
                    new_sstate = self.fleet_pager.prepare_chunk(
                        batches, self.state.strategy_state)
                    if new_sstate is not self.state.strategy_state:
                        self.state = ServerState(
                            self.state.params, self.state.opt_state,
                            new_sstate, self.state.round)
            chaos_vecs = None
            if self.engine.chaos_client_faults or \
                    self.engine.chaos_corruption or \
                    self.engine.traffic_staleness:
                # deterministic per-round fault vectors (seeded on the
                # round index, resilience/chaos.py) — data operands of
                # the compiled program, so no recompile ever.  Each
                # entry carries (drop, keep_steps) and/or the
                # adversarial corruption modes and/or the arrival
                # plane's traced staleness, matching what the engine
                # compiled in (the _chaos_host arity check).
                chaos_vecs = []
                for j in range(R):
                    if self.cohort_bucketing is not None:
                        # nested per-bucket entries: each bucket grid
                        # draws its own salted sub-stream, so the
                        # schedule stays a pure function of (seed,
                        # round, bucket, slot) — serial == pipelined ==
                        # resumed, whatever the bucket layout
                        per_bucket = []
                        for bi, batch in enumerate(batches[j]):
                            entry = ()
                            if self.engine.chaos_client_faults:
                                entry += self.chaos.client_faults(
                                    round_no + j, batch.sample_mask,
                                    salt=bi + 1)
                            if self.engine.chaos_corruption:
                                entry += (self.chaos.corrupt_modes(
                                    round_no + j,
                                    batch.sample_mask.shape[0],
                                    salt=bi + 1),)
                            if self.engine.traffic_staleness:
                                # staleness keys on CLIENT id, not the
                                # bucket slot: the fire's lookup table
                                # realigns to however the packer split
                                # the cohort (padding slots map to 0)
                                entry += (self.traffic.staleness_vector(
                                    round_no + j, batch.client_ids),)
                            per_bucket.append(entry)
                        chaos_vecs.append(per_bucket)
                        continue
                    entry = ()
                    if self.engine.chaos_client_faults:
                        entry += self.chaos.client_faults(
                            round_no + j, batches[j].sample_mask)
                    if self.engine.chaos_corruption:
                        entry += (self.chaos.corrupt_modes(
                            round_no + j,
                            batches[j].sample_mask.shape[0]),)
                    if self.engine.traffic_staleness:
                        entry += (self.traffic.staleness_vector(
                            round_no + j, batches[j].client_ids),)
                    chaos_vecs.append(entry)
            # the device window span opens at dispatch and is ended by
            # whoever drains this chunk — the explicit begin/end API
            # exists exactly for this overlap (round k's window stays
            # open while the host packs/dispatches k+1)
            device_span = (self.scope.begin("round_device",
                                            round0=round_no, rounds=R,
                                            chunk=round_no)
                           if self.scope is not None else None)
            # read BEFORE any work, and only when spans are recorded:
            # how many chunks the ring holds and how many of them the
            # device has not finished (a question, not a fence) — 0
            # in flight means the device had nothing left to run when
            # the host began preparing this chunk
            probe = ({"ring": len(pending),
                      "inflight": sum(not ch["stats"].is_ready()
                                      for ch in pending)}
                     if self._tracing else {})
            with self._tspan("dispatch", round0=round_no, rounds=R,
                             chunk=round_no, **probe):
                if self.cohort_bucketing is not None:
                    self.state, packed = \
                        self.engine.dispatch_bucketed_rounds(
                            self.state, batches, [client_lr] * R,
                            server_lrs, chunk_rng,
                            leakage_threshold=self.max_allowed_leakage,
                            quant_thresholds=quant_thresholds,
                            chaos_vecs=chaos_vecs)
                else:
                    self.state, packed = self.engine.dispatch_rounds(
                        self.state, batches, [client_lr] * R, server_lrs,
                        chunk_rng,
                        leakage_threshold=self.max_allowed_leakage,
                        quant_thresholds=quant_thresholds,
                        chaos_vecs=chaos_vecs)
            chunk = {
                "span": device_span,
                "round0": round_no, "R": R, "state": self.state,
                "stats": packed, "batches": batches,
                "client_lr": client_lr, "server_lrs": server_lrs,
                "tic": tic, "latest_saved": False,
                # resume anchor: with lookahead packing (pipeline /
                # prefetch) the NEXT chunk's sampling happens before this
                # chunk's housekeeping, so the rng state belonging to
                # this chunk's checkpoint must be captured NOW; the plain
                # serial loop snapshots at housekeeping time instead
                # (after any server-replay randomness for these rounds)
                "rng_snapshot": (self._rng_snapshot()
                                 if (pipelined or prefetch_ok) else None),
                # adaptive-DP observability: stash a device-side copy of
                # the post-chunk clip NOW — the next dispatch donates the
                # strategy_state buffers this scalar lives in
                "dp_clip": (jnp.copy(self.state.strategy_state["dp_clip"])
                            if isinstance(self.state.strategy_state, dict)
                            and "dp_clip" in self.state.strategy_state
                            else None),
                # device-truth snapshot: which compiled entry point this
                # chunk dispatched through and what it costs (compile-
                # time facts; the drain pairs them with the measured
                # wall clock for the live MFU).  Snapshotted NOW — by
                # drain time, a newer pipelined dispatch may have
                # overwritten last_dispatch.
                "xla_dispatch": (dict(self.engine.xla.last_dispatch)
                                 if self.engine.xla is not None and
                                 self.engine.xla.last_dispatch is not None
                                 else None),
            }
            if self.fleet_pager is not None:
                # dispatch the writeback gather NOW (async, reads this
                # chunk's output tables before any later program donates
                # them — the dp_clip stash discipline); the drain
                # completes it with one explicit fetch
                chunk["fleet_wb"] = self.fleet_pager.queue_writeback(
                    self.state.strategy_state, round_no=round_no + R)
            # dispatch is async: pack the next chunk NOW, while the device
            # executes this one (reading the stats below is what blocks)
            if lookahead_pack and round_no + R < max_iteration:
                next_R = chunk_R(round_no + R)
                prefetched = (next_R, pack_chunk(next_R, round_no + R))
                if fleet_prefetch:
                    # hand the packed cohort to the fleet-prefetch
                    # worker: missing carry rows stage off-thread while
                    # the device executes, so the next prepare_chunk's
                    # page-in assembly is a staging-buffer copy
                    self.fleet_pager.prefetch_chunk(prefetched[1])
            # the evaluation round before this chunk held its durable
            # tail back: the device has its next program now, and the
            # wait for the writer falls beside it
            self._run_pending_tail(deferred=True)
            # ... and a `latest` that it handed to the writer on hold
            # starts its transfers
            self.ckpt.release()
            round_no += R

            while len(pending) >= self.pipeline_depth and pending:
                # ring full: drain the OLDEST chunk's host tail while the
                # device executes the newer ones (incl. the chunk just
                # dispatched) — the pipeline.  Depth 1 reproduces the
                # original one-deep behavior exactly.
                self._drain_chunk(pending.popleft(), val_freq, rec_freq)
                self.pipelined_chunks += 1
            # the tail at an eval/housekeeping boundary can change LRs,
            # params (fall-back), and sampling-relevant state for the
            # NEXT round, so the whole ring must drain before dispatching
            # past it; the final chunk always drains here too
            boundary = (round_no >= max_iteration or
                        round_no % val_freq == 0 or
                        (round_no % rec_freq == 0 and
                         self.test_dataset is not None))
            if pipelined and not boundary:
                pending.append(chunk)
            else:
                while pending:
                    self._drain_chunk(pending.popleft(), val_freq,
                                      rec_freq)
                    self.pipelined_chunks += 1
                self._drain_chunk(chunk, val_freq, rec_freq)
        # no next chunk (the last one, a preemption, max_iteration): the
        # held-back tail runs now, and train() returns with it durable
        self._run_pending_tail(deferred=False)
        while pending:
            # preemption landed with chunks in flight: the device work is
            # already done, so drain the ring in dispatch order — each
            # chunk's housekeeping writes the per-round `latest`
            # checkpoint, making those rounds part of the resume anchor
            # instead of lost work.  (Nothing speculative beyond the ring
            # is ever dispatched.)  The drain window is a first-class
            # span: checkpoint stalls inside a preemption grace period
            # are exactly what a trace reader needs to see.
            ch = pending.popleft()
            with self._tspan("preempt_drain", round0=ch["round0"],
                             rounds=ch["R"], chunk=ch["round0"]):
                self._drain_chunk(ch, val_freq, rec_freq)
            self.pipelined_chunks += 1
        self.ckpt.wait()  # async checkpoint saves must be durable on return
        if self.preemption.requested and round_no < max_iteration:
            # resumable exit: every completed round is checkpointed and
            # durable; status_log carries the rng anchors written by the
            # last housekeeping.  e2e_trainer turns this flag into
            # os.EX_TEMPFAIL so schedulers re-queue the job.
            self.preempted = True
            # covers a signal that landed after the loop's last poll
            # (e.g. during the final drain): idempotent no-op otherwise
            self.preemption.flush_now()
            self.ckpt.update_status(
                {"preempted": self.preemption.reason or "requested"})
            emit_event(self.scope, "preempted_exit", round=round_no,
                       reason=self.preemption.reason or "requested")
            print_rank(
                f"preempted at round {round_no}/{max_iteration} "
                f"({self.preemption.reason}); checkpoint durable — resume "
                "with server_config.resume_from_checkpoint: true",
                loglevel=logging.WARNING)
        elif "preempted" in self.ckpt.read_status():
            # a resumed run that COMPLETED: clear the stale marker so the
            # final status log doesn't read as an interrupted run
            self.ckpt.update_status({"preempted": None})
        self._log_timing()
        flush_metrics()
        if self._profiler is not None:
            # a window still open (the run ended inside it) stops here
            self._profiler.finish(fence=device_fence)
        if self.scope is not None:
            # make trace.json complete/loadable; the tracer stays open
            # so a later train() on the same server appends to the same
            # trace
            self.scope.flush()
        return self.state

    # ------------------------------------------------------------------
    def _pipeline_ok(self) -> bool:
        """Whether the overlapped host/device loop may run: everything the
        host tail feeds back into the NEXT dispatch (RL rewards, SCAFFOLD/
        EF stores, replay training, the adaptive leakage threshold,
        personalization's model-dependent sampling) forces serial."""
        return self._pipeline_capable and self.rl is None and \
            self.scaffold_store is None and self.ef_store is None and \
            self.server_replay is None and self.adaptive_leakage is None

    # ------------------------------------------------------------------
    def _drain_chunk(self, chunk: Dict[str, Any], val_freq: int,
                     rec_freq: int) -> None:
        """Consume one dispatched chunk's results: fetch the packed stats
        (the honest end-of-chunk fence — ONE transfer per dtype group),
        emit the per-round metrics, process privacy stats, dump norms, and
        run the round housekeeping.  In the pipelined loop this runs while
        the device executes the NEXT chunk; in serial mode it runs
        immediately after dispatch (identical side-effect order either
        way, which the pipeline equivalence tests pin)."""
        R = chunk["R"]
        round0 = chunk["round0"]
        with self._tspan("stats_fetch", round0=round0, rounds=R,
                         chunk=round0) as span:
            if span is not None:
                # traced runs split the fence from the transfer: was
                # the chunk already done (a question), the wait until it
                # is, then the device_get + unpack (`stats_d2h`, opened
                # inside fetch).  The untraced path is the one call.
                span["ready_at_start"] = chunk["stats"].is_ready()
                with self._tspan("fence_wait", rounds=R):
                    chunk["stats"].wait()
            stats = chunk["stats"].fetch()
        if self.scope is not None:
            # the fetch is the honest end-of-chunk fence: the device
            # window that opened at dispatch closes here
            self.scope.end(chunk.get("span"))
        toc = time.time()
        # serial chunks: prep-to-fence (chunk tic follows the previous
        # fence).  Pipelined chunks: fence-to-fence — this chunk's prep
        # started BEFORE the previous chunk's fence, so tic-based timing
        # would double-count the overlapped span.
        self.run_stats["secsPerRound"].append(
            (toc - max(chunk["tic"], self._last_fence)) / R)
        self._last_fence = toc

        if self.fleet_pager is not None and chunk.get("fleet_wb"):
            # fleet paging drain half: ONE explicit fetch of this
            # chunk's updated carry rows, written through to the host
            # store; the chunk's slots unpin and become evictable.
            # Runs BEFORE the host tail so housekeeping/eval at this
            # boundary read current rows.
            with self._tspan("fleet_writeback", round0=round0,
                             rounds=R, chunk=round0):
                self.fleet_pager.complete_writeback(chunk["fleet_wb"])

        with self._tspan("host_tail", round0=round0, rounds=R,
                         chunk=round0) as span:
            self._drain_host_tail(chunk, stats, val_freq, rec_freq)
            if span is not None:
                # what the model counted in its forward passes
                # (BaseTask.counter_names: an expert layer's load), summed
                # over the chunk's rounds; it came with the packed stats
                for key in stats:
                    if key.startswith("ctr_"):
                        span[key[4:]] = float(np.sum(stats[key]))
        self.run_stats["secsPerRoundHostTail"].append(
            (time.time() - toc) / R)
        if self.scope is not None:
            mfu_before = len(self.run_stats["mfuPerRound"])
            self._drain_device_truth(chunk, round0, R)
            # this chunk's live MFU, iff the device-truth tail computed
            # one just now — the rollup's per-round mfu column
            chunk_mfu = (self.run_stats["mfuPerRound"][-1]
                         if len(self.run_stats["mfuPerRound"]) > mfu_before
                         else None)
            # one host RSS reading per chunk (a /proc line — pure host
            # IO, zero device access) feeds the rss_leak detector and
            # the rollup gauge
            rss = host_rss_bytes()
            xla_snap = (self.engine.xla.snapshot()
                        if self.engine.xla is not None else
                        {"recompiles": int(self.engine.recompile_count)})
            # fleet + dataset-cache gauges: host counters the loop
            # already owns (zero device access), published per chunk
            # through the host-side bus and handed to the rollup window
            # so `scope watch`/`scope health` see paging pressure live
            fleet_gauges = {}
            if self.fleet_pager is not None:
                pd = self.fleet_pager.describe()
                for key in ("hits", "misses", "evictions", "resident"):
                    fleet_gauges[f"fleet_page_{key}"] = pd[key]
                    self.scope.devbus_host(f"fleet_page_{key}", pd[key],
                                           step=round0 + R - 1)
                # transfer-plane accounting (mesh-sharded pool): this
                # chunk's page-in/writeback bytes off the completed
                # handle, plus the cumulative per-device split and the
                # prefetch hit rate — what `scope diff/trend --gate`
                # watches for a replication regression (per-device
                # bytes snapping back to the total)
                wb = chunk.get("fleet_wb") or {}
                self.scope.devbus_host(
                    "fleet_page_in_bytes",
                    wb.get("page_in_bytes", 0), step=round0 + R - 1)
                self.scope.devbus_host(
                    "fleet_writeback_bytes",
                    wb.get("writeback_bytes", 0), step=round0 + R - 1)
                if pd["prefetch_hit_rate"] is not None:
                    # None = prefetch never engaged this run (serial /
                    # sample-hooked / prefetch-off): no coverage to
                    # report, nothing for the diff gate to read
                    self.scope.devbus_host(
                        "fleet_prefetch_hit_rate",
                        pd["prefetch_hit_rate"], step=round0 + R - 1)
                for key in ("page_in_bytes", "page_in_bytes_per_device",
                            "writeback_bytes",
                            "writeback_bytes_per_device",
                            "prefetch_hit_rate", "migrations",
                            "forced_drains"):
                    if pd[key] is not None:
                        fleet_gauges[f"fleet_{key}"] = pd[key]
            cache_stats_fn = getattr(self.train_dataset, "cache_stats",
                                     None)
            if cache_stats_fn is not None:
                cs = cache_stats_fn()
                for key in ("hits", "misses", "evictions", "resident"):
                    fleet_gauges[f"lazy_cache_{key}"] = cs[key]
                    self.scope.devbus_host(f"lazy_cache_{key}", cs[key],
                                           step=round0 + R - 1)
            mgb_util = (self.megabatch_utilization
                        if self.megabatch is not None else None)
            if mgb_util is not None:
                # live tape occupancy for `scope watch`/rollups; absent
                # (not 0.0) until a bucket actually attached a tape
                fleet_gauges["megabatch_utilization"] = mgb_util
                self.scope.devbus_host("megabatch_utilization",
                                       mgb_util, step=round0 + R - 1)
            if fleet_gauges and self.scope.rollup is not None:
                self.scope.rollup.update_gauges(fleet_gauges)
            # watchdogs run over values this tail ALREADY holds: the
            # fetched per-round losses, the wall clock, the checkpoint
            # escalator's consecutive-failure count.  A configured
            # `abort` raises WatchdogAbort out of the round loop.
            secs = self.run_stats["secsPerRound"][-1]
            for j in range(R):
                n = max(float(stats["client_count"][j]), 1.0)
                quarantine_frac = None
                if "shield_nonfinite" in stats:
                    # quarantined / live cohort (client_count is the
                    # POST-screen count, so the cohort adds them back) —
                    # the quarantine_rate detector's "a few bad clients
                    # vs the model itself diverging" signal
                    q = (float(stats["shield_nonfinite"][j]) +
                         float(stats["shield_norm_outlier"][j]))
                    quarantine_frac = q / max(
                        q + float(stats["client_count"][j]), 1.0)
                self.scope.watchdog.observe_round(
                    round0 + j,
                    train_loss=float(stats["train_loss_sum"][j]) / n,
                    round_secs=secs,
                    ckpt_failures=self.ckpt.escalator.consecutive,
                    quarantine_frac=quarantine_frac,
                    # always-on engine counter (compiled variants beyond
                    # the first per entry point) — feeds recompile_storm
                    recompiles=self.engine.recompile_count,
                    host_rss_bytes=rss)
                # endurance rollup (ISSUE 13): the same already-held
                # host values, windowed — zero new transfers
                self.scope.rollup_observe(
                    round0 + j, secs,
                    clients=float(stats["client_count"][j]),
                    mfu=chunk_mfu, rss_bytes=rss,
                    xla_snapshot=xla_snap)

    def _drain_host_tail(self, chunk: Dict[str, Any], stats,
                         val_freq: int, rec_freq: int) -> None:
        """The decode/log/housekeeping half of :meth:`_drain_chunk`
        (split out so the whole region is one ``host_tail`` span)."""
        R = chunk["R"]
        round0 = chunk["round0"]
        # per-round logging (reference core/server.py:362-395 + AzureML)
        for j in range(R):
            r = round0 + j
            n_clients = max(float(stats["client_count"][j]), 1.0)
            log_metric("Training loss",
                       float(stats["train_loss_sum"][j]) / n_clients, step=r)
            log_metric("LR for agg. opt.", chunk["server_lrs"][j], step=r)
            log_metric("Client learning rate", chunk["client_lr"], step=r)
            log_metric("Agg. grad norm",
                       float(stats["agg_grad_norm"][j]), step=r)
        if self.scope is not None:
            # bus-published device scalars: decoded from the SAME packed
            # fetch as everything above (zero extra transfers)
            self.scope.consume_devbus(stats, round0, R)
        if self.chaos is not None and "chaos_dropped" in stats:
            # injected-fault observability: counters computed inside the
            # round program, fetched through the SAME packed single
            # transfer as every other stat (no extra host syncs)
            counters = self.chaos.counters
            for j in range(R):
                r = round0 + j
                dropped = float(stats["chaos_dropped"][j])
                straggled = float(stats["chaos_straggled"][j])
                lost = float(stats["chaos_steps_lost"][j])
                counters["dropped"] += dropped
                counters["straggled"] += straggled
                counters["steps_lost"] += lost
                log_metric("Chaos dropped clients", dropped, step=r)
                log_metric("Chaos stragglers", straggled, step=r)
                log_metric("Chaos steps lost", lost, step=r)
                if dropped or straggled or lost:
                    # structured fault record (metrics stream + trace
                    # instant), not just greppable metric lines
                    emit_event(self.scope, "chaos_faults", round=r,
                               dropped=dropped, straggled=straggled,
                               steps_lost=lost)
        if self.chaos is not None and "chaos_nan_injected" in stats:
            # adversarial corruption counters (fluteshield's attack
            # half): same packed-transfer discipline as the fault
            # counters above
            counters = self.chaos.counters
            for j in range(R):
                r = round0 + j
                nans = float(stats["chaos_nan_injected"][j])
                scaled = float(stats["chaos_scaled"][j])
                flipped = float(stats["chaos_sign_flipped"][j])
                counters["nan_injected"] += nans
                counters["scaled"] += scaled
                counters["sign_flipped"] += flipped
                log_metric("Chaos NaN-injected clients", nans, step=r)
                log_metric("Chaos scaled clients", scaled, step=r)
                log_metric("Chaos sign-flipped clients", flipped, step=r)
                if nans or scaled or flipped:
                    emit_event(self.scope, "chaos_corruption", round=r,
                               nan_injected=nans, scaled=scaled,
                               sign_flipped=flipped)
        if self.traffic is not None and "traffic_stale_sum" in stats:
            # arrival-plane observability: the on-device staleness
            # histogram rides the SAME packed transfer as every other
            # stat; the schedule's host-side rollups are the replay
            # oracle these counters are cross-checked against
            # (tests/test_traffic.py)
            for j in range(R):
                r = round0 + j
                stale_sum = float(stats["traffic_stale_sum"][j])
                hist = [float(stats[f"traffic_stale_{b}"][j])
                        for b in range(STALE_HIST_BINS)]
                log_metric("Traffic staleness sum", stale_sum, step=r)
                emit_event(self.scope, "traffic_staleness", round=r,
                           stale_sum=stale_sum, hist=hist)
        if self.shield is not None and "shield_nonfinite" in stats:
            # fluteshield quarantine observability: per-cause counters
            # computed inside the round program, fetched through the
            # SAME packed single transfer as every other stat
            counters = self.shield.counters
            for j in range(R):
                r = round0 + j
                nonfinite = float(stats["shield_nonfinite"][j])
                outlier = float(stats["shield_norm_outlier"][j])
                counters["quarantined_nonfinite"] += nonfinite
                counters["quarantined_norm_outlier"] += outlier
                log_metric("Quarantined clients (non-finite)", nonfinite,
                           step=r)
                log_metric("Quarantined clients (norm outlier)", outlier,
                           step=r)
                if nonfinite or outlier:
                    emit_event(self.scope, "quarantine", round=r,
                               nonfinite=nonfinite, norm_outlier=outlier)
        if getattr(self.strategy, "wants_cohort", False) and \
                "secagg_recovered_dropout" in stats:
            # secure-agg mask-recovery observability: per-cause recovery
            # counts and the liveness-floor abort flag computed inside
            # the round program, fetched through the SAME packed single
            # transfer as every other stat
            counters = self.strategy.counters
            for j in range(R):
                r = round0 + j
                rec_drop = float(stats["secagg_recovered_dropout"][j])
                rec_quar = float(stats["secagg_recovered_quarantine"][j])
                counters["recovered_dropout"] += rec_drop
                counters["recovered_quarantine"] += rec_quar
                log_metric("SecAgg recovered (dropout)", rec_drop, step=r)
                log_metric("SecAgg recovered (quarantine)", rec_quar,
                           step=r)
                if rec_drop or rec_quar:
                    emit_event(self.scope, "secagg_recovered", round=r,
                               dropout=rec_drop, quarantine=rec_quar)
                if "secagg_abort" in stats:
                    aborted = float(stats["secagg_abort"][j])
                    if aborted:
                        counters["aborted_rounds"] += aborted
                        log_metric("SecAgg aborted round", aborted,
                                   step=r)
                        emit_event(self.scope, "secagg_abort", round=r,
                                   aborted=aborted)
        self._process_privacy_stats(
            stats, round0,
            client_mask=self._chunk_client_masks(chunk["batches"]))
        if chunk["dp_clip"] is not None:
            # adaptive DP clipping observability (arXiv:1905.03871); the
            # post-chunk value is the clip the NEXT round applies, so it
            # logs at that round's step.  Explicit fetch: float() on the
            # device scalar was an implicit sync (strict transfer mode)
            log_metric("DP clip norm",
                       float(jax.device_get(chunk["dp_clip"])),
                       step=round0 + R)
        if self.engine.dump_norm_stats and "norm" in stats:
            self._dump_norm_stats(stats, chunk["batches"])
        if self.server_replay is not None:
            self._run_server_replay()
        self._round_housekeeping(round0 + R, val_freq, rec_freq,
                                 skip_latest=chunk["latest_saved"],
                                 rng_snapshot=chunk.get("rng_snapshot"),
                                 chunk=round0)

    # ------------------------------------------------------------------
    # flutescope device-truth (telemetry/xla.py): the host-tail half.
    # Compile-time facts (FLOPs, HBM bytes, recompile findings) pair
    # with the wall clocks the loop ALREADY measures — no device access,
    # no new transfers, clean under strict mode by construction.
    # ------------------------------------------------------------------
    def _drain_xla_events(self) -> None:
        """Emit the introspector's buffered compile/recompile events as
        structured records (metrics stream + trace instants), plus the
        attention dispatch gate's fallback records
        (ops/pallas_attention.py — buffered at plan time, host-side)
        and the convolutions' ``conv_taps`` records (ops/conv.py —
        buffered at trace time)."""
        if self.scope is None:
            return
        from ..ops.conv import drain_conv_events
        from ..ops.pallas_attention import drain_attention_events
        for ev in drain_attention_events() + drain_conv_events():
            self.scope.event(ev.pop("kind"), **ev)
        # megabatch dispatch-gate fallbacks (engine-buffered: the
        # server's analytic slots gate and the aot_cost shootout both
        # push here) — same loud-fallback surface as the attention gate
        for ev in self.engine.drain_megabatch_events():
            self.scope.event(ev.pop("kind"), **ev)
        reg = self.engine.xla
        if reg is None:
            return
        for ev in reg.drain_events():
            self.scope.event(ev.pop("kind"), **ev)

    def _drain_device_truth(self, chunk: Dict[str, Any], round0: int,
                            R: int) -> None:
        """Per-chunk device-truth tail: drain compile events, then the
        live MFU — the chunk's compiled FLOPs (snapshotted at dispatch)
        over the measured per-round wall clock and the chip's peak —
        and the program's HBM footprint, published through the host-side
        bus (metric lines + trace counters; zero device reads)."""
        self._drain_xla_events()
        disp = chunk.get("xla_dispatch")
        if not disp or not disp.get("flops") or self._chip is None:
            return
        from ..telemetry.xla import mfu as _mfu
        flops_per_round = float(disp["flops"]) / max(
            int(disp.get("rounds") or R), 1)
        secs = self.run_stats["secsPerRound"][-1]
        value = _mfu(flops_per_round, secs, peak_flops=self._chip[1])
        if value is not None:
            self.run_stats["mfuPerRound"].append(value)
            self.scope.devbus_host("mfu", value, step=round0 + R - 1)
        hbm = disp.get("hbm_bytes")
        if hbm:
            self.scope.devbus_host("hbm_program_gb", hbm / 2 ** 30,
                                   step=round0 + R - 1)

    def build_scorecard(self) -> Dict[str, Any]:
        """The run's compact regression surface
        (``telemetry/scorecard.json``): the metrics ``tools/scope diff``
        thresholds and the endurance harness gates on.  Every value is
        something the run already measured — wall clocks, the overlap
        geometry from the flushed trace, the device-truth layer's
        compile-time numbers, watchdog findings."""
        rs = self.run_stats

        def p50(values):
            return (round(float(np.percentile(values, 50)), 6)
                    if values else None)

        card: Dict[str, Any] = {
            "rounds": int(self.state.round),
            "pipeline_depth": int(self.pipeline_depth),
            "pipelined_chunks": int(self.pipelined_chunks),
            "round_secs_p50": p50(rs["secsPerRound"]),
            "host_tail_secs_p50": p50(rs["secsPerRoundHostTail"]),
            "staged_bytes_per_round_p50": p50(
                rs["hostToDeviceBytesPerRound"]),
            # run-total real samples / padded grid slots (slots- i.e.
            # FLOPs-weighted, NOT a per-chunk mean — cheap chunks must
            # not mask waste on expensive ones)
            "padding_efficiency": (
                round(self.padding_efficiency, 6)
                if self.padding_efficiency is not None else None),
            # six significant digits, not six decimals: a small model
            # on a busy host reads 1e-7, which is not 0
            "mfu_p50": (float(f"{np.percentile(rs['mfuPerRound'], 50):.6g}")
                        if rs["mfuPerRound"] else None),
            "puts_per_dispatch": int(self.engine.last_dispatch_puts),
            "compiles": len(self.engine.compile_log),
            "recompiles": int(self.engine.recompile_count),
        }
        fires: Dict[str, int] = {}
        if self.scope is not None:
            for finding in self.scope.watchdog.findings:
                kind = str(finding.get("kind", "?"))
                fires[kind] = fires.get(kind, 0) + 1
        card["watchdog_fires"] = fires
        if self.scope is not None and self.scope.tracer is not None:
            # the Tracer's 1M-event cap used to drop silently past the
            # in-trace flag; endurance gates need the drop COUNT on the
            # regression surface (ISSUE 13 satellite)
            card["trace_events_dropped"] = int(self.scope.tracer.dropped)
        if self.scope is not None and self.scope.rollup is not None:
            card["rollup_windows"] = int(
                self.scope.rollup.windows_flushed)
            # the degradation table's telemetry ledger: windows lost to
            # writer exhaustion (always present when rollups are on —
            # 0 is the healthy reading the drill gates against)
            card["rollup_windows_dropped"] = int(
                self.scope.rollup.windows_dropped)
        if self.chaos is not None and self.chaos.infra is not None:
            # seeded infra-fault ledger (chaos.infra): per-surface
            # injected-fault counts — a drill run is impossible to
            # confuse with a clean one on the regression surface
            card["infra_faults"] = {
                k: float(v)
                for k, v in sorted(self.chaos.infra.counters.items())}
        if self.fleet_pager is not None:
            # paging pressure joins the regression surface: a hit-rate
            # collapse or an eviction storm is a fleet-sizing regression
            # `scope diff`/`scope health` should see
            card["fleet"] = self.fleet_pager.describe()
            # flat copies for the `scope diff --gate` rules (DIFF_RULES
            # reads top-level scorecard keys): per-device transfer
            # bytes are the replication-regression tripwire — a
            # replicated pool multiplies them by mesh_size
            card["fleet_page_in_bytes_per_device"] = \
                card["fleet"]["page_in_bytes_per_device"]
            card["fleet_writeback_bytes_per_device"] = \
                card["fleet"]["writeback_bytes_per_device"]
            if card["fleet"]["prefetch_hit_rate"] is not None:
                # absent (not 0.0) when prefetch never engaged, so the
                # diff gate's lower_abs rule skips instead of flagging
                # a non-prefetching arm as a coverage regression
                card["fleet_prefetch_hit_rate"] = \
                    card["fleet"]["prefetch_hit_rate"]
        cache_stats_fn = getattr(self.train_dataset, "cache_stats", None)
        if cache_stats_fn is not None:
            card["lazy_cache"] = cache_stats_fn()
        if self.cohort_bucketing is not None:
            card["cohort_bucketing"] = {
                "boundaries": list(self.cohort_bucketing["boundaries"]),
                "max_buckets": int(self.cohort_bucketing["max_buckets"]),
                # compiled-grid closure: distinct (K_b, S_b) collect
                # shapes this run compiled (gated <= max_buckets in the
                # bench A/B; churn past warmup trips the sentinel)
                "bucket_grid_variants":
                    len(self.engine.bucket_shapes_seen),
            }
        if self.megabatch is not None:
            util = self.megabatch_utilization
            card["megabatch"] = {
                "lanes": [int(l) for l in self.megabatch["lanes"]],
                "utilization": (round(util, 6)
                                if util is not None else None),
                # dispatch gate's chosen arm per compiled bucket shape
                # ("mega" | "vmap") — the regression surface for a
                # silently-fallen-back bucket
                "gate_arms": {f"K{k}_S{s}": arm for (k, s), arm in
                              sorted(self.engine._mega_gate.items())},
            }
            # flat copy for the `scope diff --gate` lower_frac rule
            card["megabatch_utilization"] = \
                card["megabatch"]["utilization"]
        if self.traffic is not None:
            # arrival-plane rollups (traffic/schedule.py): the trace
            # identity plus the host replay oracle's counters — enough
            # to make a traffic run impossible to confuse with a
            # boundary-sampled baseline in `scope diff`
            card["traffic"] = {
                **self.traffic.describe(),
                "arrival_rate": round(self.traffic.arrival_rate(), 6),
                "mean_buffer_occupancy": round(
                    self.traffic.mean_buffer_occupancy(), 6),
                "stale_hist": [int(c) for c in self.traffic.stale_hist],
                "counters": {k: float(v) for k, v in
                             self.traffic.counters.items()},
                "target_accuracy": self.target_accuracy,
                "rounds_to_target_accuracy":
                    self.rounds_to_target_accuracy,
            }
        reg = self.engine.xla
        if reg is not None:
            card["entry_points"] = reg.summary()
            card["hbm_peak_bytes"] = reg.hbm_peak_bytes()
            if self._chip is not None:
                card["chip"] = {"kind": self._chip[0],
                                "peak_flops": self._chip[1]}
        # overlap geometry from the flushed trace — via the ONE reader
        # (scope_cli.summarize), so the scorecard and `tools/scope`
        # can never disagree about the efficiency number
        try:
            from ..telemetry.scope_cli import summarize
            overlap = summarize(self.ckpt.model_dir).get("overlap") or {}
            card["overlap_efficiency_pct"] = overlap.get("efficiency_pct")
            if "by_depth" in overlap:
                card["host_tail_by_depth_s"] = overlap["by_depth"]
            if "max_rounds_in_flight" in overlap:
                card["max_rounds_in_flight"] = \
                    overlap["max_rounds_in_flight"]
        except Exception:
            card["overlap_efficiency_pct"] = None
        return card

    # ------------------------------------------------------------------
    def _record_staged_bytes(self, batches: list, rounds: int) -> None:
        """Host->device payload per round (the design's whole communication
        story: pool mode ships int32 indices, host packing ships feature
        bytes) — the TPU-native counterpart of the reference's per-client
        ``communicationCosts`` timing (``core/server.py:317,353``);
        reported by ``_log_timing``.  Called from the fused path AND the
        host-orchestrated (RL/SCAFFOLD) rounds, which also ship a packed
        batch.  Bucketed chunks pass the nested per-round bucket lists;
        the bytes sum over every grid either way."""
        flat = [b for entry in batches
                for b in (entry if isinstance(entry, list) else [entry])]
        chunk_bytes = sum(
            sum(a.nbytes for a in
                (getattr(b, "arrays", None) or
                 {"__idx__": b.indices}).values())
            + b.sample_mask.nbytes for b in flat)
        self.run_stats["hostToDeviceBytesPerRound"].append(
            chunk_bytes / max(rounds, 1))

    # ------------------------------------------------------------------
    def _maybe_length_bucket(self, batches: list) -> None:
        """Crop the chunk's token grids to their real-length bucket (see
        ``data.batching.seq_length_bucket``); logs the padding-efficiency
        ratio like the reference's DynamicBatchSampler meter."""
        keys = getattr(self.task, "seq_pad_keys", ())
        if not self.length_bucketing or not keys:
            return
        from ..data.batching import seq_length_bucket
        stats = seq_length_bucket(batches, keys)
        if stats is not None and stats["cropped"]:
            self._length_bucket_stats = stats
            print_rank(
                f"length bucket L={stats['bucket']}/{stats['full_len']} "
                f"pad-eff {stats['tokens_real'] / max(stats['tokens_grid_after'], 1):.3f}"
                f" (was {stats['tokens_real'] / max(stats['tokens_grid_before'], 1):.3f})",
                loglevel=logging.DEBUG)

    # ------------------------------------------------------------------
    def _pack_bucketed_round(self, sampled: list) -> list:
        """One round's cohort as per-bucket compact grids
        (``server_config.cohort_bucketing``): deterministic assignment
        of each sampled client to the smallest step bucket covering its
        need, one ``[K_b, S_b, B, ...]`` grid per occupied bucket with
        ``K_b`` pow2-quantized (then mesh-padded) so the compiled grid
        variant set stays small and closed."""
        from ..data.batching import assign_step_buckets
        needs = [int(self._step_needs[i]) for i in sampled]
        caps = self.cohort_bucketing["capacities"]
        bounds = self.cohort_bucketing["boundaries"]
        assignment = assign_step_buckets(needs, bounds, capacities=caps)
        # pre-draw every sampled client's shuffle permutation in COHORT
        # order — the exact rng calls the monolithic pack would make —
        # so bucketing changes only grid SHAPES, never which samples a
        # client trains on or any later round's sampling stream
        orders = {int(ci): self._np_rng.permutation(
                      int(self.train_dataset.num_samples[ci]))
                  for ci in sampled}
        out = []
        for bi, ((s_b, positions), cap) in enumerate(
                zip(assignment.items(), caps)):
            ids = [sampled[p] for p in positions]
            cap = int(cap)
            # TOP-bucket overflow (sampling variance beyond the slack)
            # splits into EXTRA GRIDS OF THE SAME COMPILED SHAPE — the
            # collect-variant set stays exactly one program per bucket,
            # deterministically; only the finalize (one more partial in
            # its signature) retraces, once per new grid count
            groups = ([ids] if len(ids) <= cap else
                      [ids[i:i + cap] for i in range(0, len(ids), cap)])
            tapes = None
            if self.megabatch is not None and ids:
                from ..data.batching import plan_megabatch
                L = int(self.megabatch["lanes"][bi])
                E = int(self.megabatch["epochs"])
                plan = plan_megabatch(
                    [needs[p] for p in positions], E, L, int(s_b),
                    self.mesh.shape[CLIENTS_AXIS], cap)
                # analytic slots gate: per lane-scan step the tape
                # trains L lanes for depth=E*S steps vs the per-client
                # grid's cap rows for S steps x E epochs — compute
                # ratio reduces to groups*L vs groups*cap.  The tape
                # must win by min_gain or the bucket falls back LOUDLY
                # to the vmap arm (buffered megabatch_fallback event,
                # the flash-vs-dense discipline)
                gain = 1.0 + float(self.megabatch["min_gain"])
                if len(plan) * L * gain <= len(groups) * cap:
                    # planned row order (shard-local blocks, -1 holes)
                    # replaces the plain cohort split; the hole-aware
                    # packers keep grid rows aligned to the tape's
                    # segment ids
                    groups = [[ids[j] if j >= 0 else -1 for j in rows]
                              for rows, _ in plan]
                    tapes = [t for _, t in plan]
                else:
                    self.engine.push_megabatch_event({
                        "kind": "megabatch_fallback", "reason": "slots",
                        "bucket_steps": int(s_b), "clients": len(ids),
                        "lanes": L, "tape_groups": len(plan),
                        "grid_groups": len(groups)})
            for gi, g in enumerate(groups):
                if self._pool_offsets is not None:
                    from ..data.batching import pack_round_indices
                    b = pack_round_indices(
                        self.train_dataset, self._pool_offsets, g,
                        self.batch_size, s_b, rng=self._np_rng,
                        pad_clients_to=cap, orders=orders,
                        desired_max_samples=self.desired_max_samples)
                else:
                    b = pack_round_batches(
                        self.train_dataset, g, self.batch_size, s_b,
                        rng=self._np_rng, pad_clients_to=cap,
                        orders=orders,
                        desired_max_samples=self.desired_max_samples)
                if tapes is not None:
                    t = tapes[gi]
                    b.mega = t
                    self._mega_slots += float(
                        t.lanes * t.depth * self.batch_size)
                    self._mega_real += float(
                        t.entries * self.batch_size)
                out.append(b)
        return out

    def _record_padding_efficiency(self, batches_flat: list) -> None:
        """Real samples / padded grid slots of one packed chunk — the
        meter the cohort-bucketing win is gated on (scorecard +
        ``tools/scope diff`` + bench A/B).  The per-chunk ratio joins
        ``run_stats`` for observability; the GATED number is the
        run-total ratio (:attr:`padding_efficiency`) — slots-weighted,
        i.e. FLOPs-weighted, so cheap small-cohort chunks cannot mask
        waste on the expensive ones.

        Megabatch grids count their TAPE slots (``lanes * depth * B``,
        per-epoch-normalized to match the grid convention) instead of
        the ``K*S*B`` grid the tape re-reads — the lane scan's compute
        is the tape, so the meter keeps meaning "real samples / sample
        slots the round actually paid for"."""
        from ..data.batching import grid_slots, padding_efficiency
        if self.megabatch is None:
            self.run_stats["paddingEfficiency"].append(
                padding_efficiency(batches_flat))
            self._pad_slots += grid_slots(batches_flat)
            self._pad_real += float(sum(np.sum(b.num_samples)
                                        for b in batches_flat))
            return
        E = max(int(self.megabatch["epochs"]), 1)
        slots = 0.0
        for b in batches_flat:
            t = getattr(b, "mega", None)
            if t is None:
                slots += grid_slots([b])
            else:
                slots += (float(t.lanes * t.depth)
                          * int(b.sample_mask.shape[2]) / E)
        real = float(sum(np.sum(b.num_samples) for b in batches_flat))
        self.run_stats["paddingEfficiency"].append(
            real / max(slots, 1.0))
        self._pad_slots += slots
        self._pad_real += real

    @property
    def padding_efficiency(self) -> Optional[float]:
        """Run-total real samples / padded grid slots (1.0 = zero
        padding waste); None before any chunk packed."""
        if not self._pad_slots:
            return None
        return self._pad_real / self._pad_slots

    @property
    def megabatch_utilization(self) -> Optional[float]:
        """Run-total real tape entries / super-batch slots (1.0 = every
        lane-scan step trains a real client batch; idle tape padding is
        the complement).  None before any bucket attached a tape —
        distinct from 0.0, so diff gates skip non-megabatch arms."""
        if not self._mega_slots:
            return None
        return self._mega_real / self._mega_slots

    # ------------------------------------------------------------------
    def _chunk_steps(self, chunk_samples: list) -> int:
        """Step grid for one fused chunk: the dataset-wide ``max_steps``
        worst case, or (``step_bucketing``, default) the chunk's own max
        rounded up to a power of two — bounded retraces, identical math."""
        if not self.step_bucketing:
            return self.max_steps
        need = max(steps_for(self.train_dataset.num_samples[i],
                             self.batch_size, self.desired_max_samples)
                   for sampled in chunk_samples for i in sampled)
        pow2 = 1 << max(need - 1, 0).bit_length()
        return min(self.max_steps, pow2)

    def _run_server_replay(self) -> None:
        """Replay training on server-held data after aggregation
        (reference ``core/server.py:429-442``)."""
        if not hasattr(self, "_replay_fn"):
            from ..data.dataset import ArraysDataset
            from .client_update import ClientHParams, build_client_update
            replay = self.server_replay
            updatable = replay.get("updatable_names")
            # empty list means "freeze everything", which is distinct from
            # None ("no allowlist"): use an explicit None check
            hp = ClientHParams(
                num_epochs=replay["iterations"],
                updatable_layers=(tuple(updatable) if updatable is not None
                                  else None))
            self._replay_update = build_client_update(
                self.task, replay["opt_cfg"], hp)
            merged = ArraysDataset.concat_users(replay["dataset"])
            n = len(next(iter(merged.values())))
            bs = int(self.config.server_config.data_config.train.get(
                "batch_size", self.batch_size))
            # geometry is static (same jitted program every round); the
            # *contents* are re-packed per round below — the reference
            # re-iterates a shuffling DataLoader each round
            # (core/server.py:429-442), so sample order must not freeze
            self._replay_pack = (ArraysDataset(["server"], [merged]),
                                 bs, steps_for(n, bs))
            lr = float(replay["opt_cfg"].get("lr", 0.01))

            def fn(params, arrays, mask, rng):
                pg, tl, ns, _ = self._replay_update(
                    params, arrays, mask, jnp.asarray(lr, jnp.float32), rng)
                return jax.tree.map(lambda w, g: w - g, params, pg), tl
            self._replay_fn = jax.jit(fn)
        rng = self._next_rng()
        one, bs, steps = self._replay_pack
        batch = pack_round_batches(one, [0], bs, steps, rng=self._np_rng)
        arrays = {k: v[0] for k, v in batch.arrays.items()}
        mask = batch.sample_mask[0]
        new_params, tl = self._replay_fn(self.state.params, arrays, mask, rng)
        self.state = ServerState(new_params, self.state.opt_state,
                                 self.state.strategy_state, self.state.round)
        # explicit fetch: float(tl) was an implicit sync on the in-flight
        # replay program (host-sync lint + strict transfer mode)
        print_rank(f"server replay loss {float(jax.device_get(tl)):.4f}")

    def _dump_norm_stats(self, stats, batches) -> None:
        """Append per-round client grad norms + cosines-vs-aggregate
        (reference ``norm_stats.txt``/``cosines.txt``,
        ``core/server.py:392-395``, ``core/strategies/fedavg.py:149-152``)."""
        import json as _json
        norms = np.asarray(stats["norm"])      # [R, K]
        cosines = np.asarray(stats["cosine"])  # [R, K]
        masks = np.stack([b.client_mask for b in batches]) > 0
        with open(os.path.join(self.ckpt.model_dir, "norm_stats.txt"),
                  "a", encoding="utf-8") as fh:
            for r in range(norms.shape[0]):
                fh.write(_json.dumps(norms[r][masks[r]].tolist()) + "\n")
        with open(os.path.join(self.ckpt.model_dir, "cosines.txt"),
                  "a", encoding="utf-8") as fh:
            for r in range(cosines.shape[0]):
                fh.write(_json.dumps(cosines[r][masks[r]].tolist()) + "\n")

    # ------------------------------------------------------------------
    def _round_housekeeping(self, round_no: int, val_freq: int,
                            rec_freq: int,
                            skip_latest: bool = False,
                            rng_snapshot: Optional[Dict[str, Any]] = None,
                            chunk: Optional[int] = None) -> None:
        """Eval cadence, LR plateau decay, fallback, checkpoint, status log
        (reference ``core/server.py:448-490``).  ``skip_latest``: the
        pipelined loop already submitted this round's ``latest`` save
        before the next dispatch donated the state buffers.
        ``rng_snapshot``: the resume anchor captured at dispatch time when
        lookahead packing overlaps (see ``_rng_snapshot``); None means
        "capture now" (plain serial loop, host-orchestrated rounds).
        ``chunk``: the drained chunk's id, from the chunk loop, which
        runs a held-back durable tail after its next launch
        (:meth:`_run_pending_tail`); None (host-orchestrated rounds)
        runs the tail at once."""
        with self._tspan("housekeeping", round=round_no):
            self._round_housekeeping_inner(round_no, val_freq, rec_freq,
                                           skip_latest, rng_snapshot, chunk)

    def _round_housekeeping_inner(self, round_no: int, val_freq: int,
                                  rec_freq: int, skip_latest: bool,
                                  rng_snapshot: Optional[Dict[str, Any]],
                                  chunk: Optional[int]) -> None:
        housekeeping_tic = time.time()
        improved = False
        if round_no % val_freq == 0:
            improved = self._maybe_eval("val", round_no, hold_best=True)
            # client-LR decay on val plateau (core/server.py:464-469)
            if not improved and self.lr_decay_factor != 1.0:
                self.lr_weight *= float(self.lr_decay_factor)
                print_rank(f"decayed client lr weight to {self.lr_weight}")
            if self.plateau is not None and "loss" in self._last_val and \
                    np.isfinite(self._last_val["loss"].value):
                # non-finite val loss: skip the plateau step rather than
                # corrupt its best/bad_rounds history (NaN compares
                # False against everything — the tracker would count a
                # permanent plateau and decay the LR to the floor)
                self.plateau.step(self._last_val["loss"].value)
            if self.fall_back_to_best and not improved:
                self._fall_back()
        if round_no % rec_freq == 0 and self.test_dataset is not None:
            self._maybe_eval("test", round_no)
        # only now, and held until the tail waits for it: the writer
        # asks for the whole snapshot's transfers at once, and whatever
        # is queued behind them waits for all 1.9 GB (on the chip: the
        # test evaluation's fetch, 0.25 -> 0.8 s, or the next dispatch's
        # inputs, 0.4 s of an idle device)
        saved_state, best_file = self._save_bettered(hold=True)

        status_update = {
            "i": round_no,
            "weight": self.lr_weight,
            # rng resume anchors: numpy bit-generator state + device-key
            # use counter, captured at the point all randomness for
            # rounds <= round_no (and none beyond) has been drawn
            **(rng_snapshot if rng_snapshot is not None
               else self._rng_snapshot()),
            **{f"best_val_{k}": m.value for k, m in self.best_val.items()},
        }
        if self.best_val:
            status_update["best_val_hib"] = {
                k: bool(m.higher_is_better)
                for k, m in self.best_val.items()}
        if self.plateau is not None:
            status_update["plateau"] = {
                "lr": self.plateau.lr, "best": self.plateau.best,
                "bad_rounds": self.plateau.bad_rounds}
        self._status_ring.append([int(round_no), dict(status_update)])
        del self._status_ring[:-16]
        status_update["status_ring"] = list(self._status_ring)
        # the state this round's evaluation gave out as the best model
        # (no fall-back replaced it since): the same bytes, so `latest`
        # is a link to that file
        link = best_file if saved_state is self.state and \
            not skip_latest else None
        # a `latest` of its own on the chunk loop (no evaluation, or
        # one that found nothing better) is held like the best model's
        # snapshot: copied on the device here, before the next dispatch
        # donates the buffers, and fetched once that dispatch is launched
        # (the loop says `release`).  Fetched at once, its 1.8 GB of
        # transfers kept the next dispatch's inputs waiting: on the chip
        # such a period took 7.2-7.4 s where an improving one took 6.4
        hold = chunk is not None and link is None and not skip_latest
        tail = functools.partial(self._durable_tail, round_no,
                                 status_update, link, skip_latest, chunk,
                                 hold)
        if chunk is not None and link is not None and \
                self.ckpt.async_latest:
            # the file is still with the writer, and nothing the tail
            # writes needs this state's buffers (`latest` is a link): the
            # chunk loop launches the next dispatch first, and the wait
            # for the disk falls where the host waits for the device
            self._pending_tail = tail
        else:
            tail(deferred=False)
        # one buffered-metrics flush per chunk instead of one per metric
        # line — the jsonl stream stays observable at round granularity
        # while the host tail stops paying a syscall per scalar
        flush_metrics()
        if self.scope is not None:
            # keep the on-disk trace fresh for long runs (throttled:
            # the rewrite is O(events), paid at most every
            # Tracer.FLUSH_INTERVAL_SECS)
            self.scope.flush_throttled()
            # endurance rollups flush on the same cadence: at most one
            # appended record per rollup_window rounds, then the window
            # state resets — host memory stays O(window) for any run
            # length (ISSUE 13)
            self.scope.rollup_housekeeping()
        self.run_stats["secsPerRoundHousekeeping"].append(
            time.time() - housekeeping_tic)

    def _run_pending_tail(self, deferred: bool) -> None:
        """Run the durable tail that the last evaluation round held back,
        if it did; ``deferred``: the next dispatch has been launched."""
        tail, self._pending_tail = self._pending_tail, None
        if tail is not None:
            tail(deferred=deferred)

    def _durable_tail(self, round_no: int, status_update: Dict[str, Any],
                      link: Optional[str], skip_latest: bool,
                      chunk: Optional[int], hold: bool,
                      deferred: bool) -> None:
        """Everything that writes a NAME for round ``round_no``'s state,
        on the training thread and in the one order a hard kill may cut
        anywhere: the best-model file and its sidecar (the writer's,
        waited for here) -> the status log -> ``latest`` -> backups ->
        the paired stores' markers.  ``link``: the best-model file that
        this round's ``latest`` is a link to; ``hold``: a ``latest``
        of its own stays on the device until the chunk loop has launched
        its next dispatch."""
        self.ckpt.land_best()
        # the status write leads the round's durable sequence (status ->
        # rows/marker -> checkpoint), and the ring keeps one snapshot
        # per recent round: whatever slot a crash leaves loadable, the
        # anchors for exactly that round are already durable
        # (flutearmor crash-point contract — _paired_status)
        self.ckpt.update_status(status_update)
        with self._tspan("ckpt_submit", round=round_no, deferred=deferred,
                         **({} if chunk is None else {"chunk": chunk})):
            if not skip_latest:
                self.ckpt.save_latest(self.state, same_as=link, hold=hold)
            self.ckpt.backup(self.state, round_no,
                             best_names=tuple(self.best_val))
        if self.scaffold_store is not None:
            # commit the control-round marker only once the paired model
            # checkpoint is DURABLE (async orbax saves land out of band):
            # clean restarts then keep accumulated controls; a crash inside
            # the round window leaves the -1 sentinel and resets safely.
            # The wait() (a real stall under orbax OR checkpoint_async —
            # and load-bearing in both) deliberately serializes the async
            # save for SCAFFOLD rounds: committing the marker lazily
            # against the previous durable slot would let the control files
            # run one round ahead of the marker — the silent controls/params
            # mismatch this marker exists to prevent — and scaffold rounds
            # are host-transfer-bound anyway
            self.ckpt.wait()
            if self.scaffold_device is not None:
                # write the dirty HBM rows through to the durable store
                # before the marker claims they exist.  Flush cadence
                # (scaffold_flush_freq, default 1) bounds the per-round
                # [D, n_params] fetch: at freq > 1 the rounds in between
                # fetch only logging scalars and the marker stays at the
                # -1 sentinel — so a stop inside the window makes resume
                # reset ALL controls (marker mismatch semantics), not just
                # the unflushed tail.  That is the transfer-bound
                # deployment's tradeoff (controls are estimates and
                # re-warm), not the default.
                flush_freq = int(self.config.server_config.get(
                    "scaffold_flush_freq", 1) or 1)
                # the iteration count train() stashed at entry — a second
                # sc.get() here could desync and either flush every round
                # or never fire the final-round flush
                final = round_no >= self._max_iteration
                if flush_freq <= 1 or round_no % flush_freq == 0 or final:
                    self.scaffold_device.flush()
                    self.scaffold_store.set_round(int(self.state.round))
            else:
                self.scaffold_store.set_round(int(self.state.round))
        if self.ef_store is not None:
            # same durable-pairing rule as the SCAFFOLD marker above
            self.ckpt.wait()
            if self.ef_device is not None:
                # mirror the scaffold_flush_freq tradeoff: between flushes
                # the marker stays at the -1 sentinel, so a stop inside
                # the window resets ALL residuals on resume (graceful —
                # EF degrades to memoryless for one participation)
                flush_freq = int(self.config.server_config.get(
                    "ef_flush_freq", 1) or 1)
                final = round_no >= self._max_iteration
                if flush_freq <= 1 or round_no % flush_freq == 0 or final:
                    self.ef_device.flush()
                    self.ef_store.set_round(int(self.state.round))
            else:
                self.ef_store.set_round(int(self.state.round))
        if self.fleet_pager is not None:
            # fleet paged-carry durability: the host store already holds
            # every drained row (writeback-on-drain); spill the dirty
            # ones to disk and commit the round marker only once the
            # paired model checkpoint is durable — the ControlStore
            # pairing rule.  Unlike the control stores, a hard stop
            # inside this window stays bit-identically resumable: spills
            # are generation-versioned, so resume rolls the rows back to
            # whatever slot matches the marker (_paired_fleet_anchor).
            # fleet.spill_freq > 1 amortizes the disk IO; a stop inside
            # THAT window resets rows on resume (marker behind anchor),
            # the same tradeoff as scaffold_flush_freq.
            spill_freq = int(self._fleet_cfg.get("spill_freq", 1) or 1)
            final = round_no >= self._max_iteration
            if spill_freq <= 1 or round_no % spill_freq == 0 or final:
                self.ckpt.wait()
                self.fleet_pager.flush()
                # the marker commits the DRAINED round (the pipelined
                # loop's self.state can already belong to a newer
                # dispatched chunk whose rows are not on the host yet)
                self.fleet_pager.set_round(int(round_no))
                # every checkpoint through round_no is durable after
                # the wait() above; row generations superseded at or
                # below round_no - 1 become garbage (the - 1 keeps the
                # generation a corruption fallback to .prev would need)
                self.fleet_pager.mark_durable(int(round_no) - 1)

    # ------------------------------------------------------------------
    def _val_acc(self) -> float:
        """Validation accuracy (falls back to -loss) for RL rewards."""
        metrics = evaluate(self.task, self._eval_fn, self.state.params,
                           self._packed_eval_batches("val"), self.mesh,
                           self.engine.partition_mode)
        self.engine._note_compiles("eval_step", self._eval_fn)
        if "acc" in metrics:
            return float(metrics["acc"].value)
        return -float(metrics["loss"].value)

    def _host_round_setup(self, round_no: int):
        """Shared prologue of the host-orchestrated round paths (RL,
        SCAFFOLD): LRs, client sampling, packed batch (with the same
        per-round step bucketing the fused path uses), round rng."""
        client_lr = self.initial_lr_client * self.lr_weight
        server_lr = (self.plateau.lr if self.plateau is not None
                     else self.server_lr_schedule(round_no))
        sampled = self._sample()
        batch = pack_round_batches(
            self.train_dataset, sampled, self.batch_size,
            self._chunk_steps([sampled]), rng=self._np_rng,
            pad_clients_to=pad_to_mesh(len(sampled), self.mesh),
            desired_max_samples=self.desired_max_samples)
        self._maybe_length_bucket([batch])
        self._record_staged_bytes([batch], 1)
        self._record_padding_efficiency([batch])
        rng = self._next_rng()
        return client_lr, server_lr, batch, rng

    def _run_scaffold_round(self, round_no: int) -> None:
        """One SCAFFOLD round (``strategies/scaffold.py``): gather per-client
        control offsets ``c - c_i``, run the drift-corrected payload program,
        aggregate with sample-count weights, then update the controls
        host-side from the per-client pseudo-gradients (option II)."""
        client_lr, server_lr, batch, rng = self._host_round_setup(round_no)

        offsets = (self.scaffold_device.offsets(batch.client_ids)
                   if self.scaffold_device is not None else
                   self.scaffold_store.offsets(batch.client_ids))
        pgs, ws, tls, stats = self.engine.client_payloads(
            self.state, batch, client_lr, rng, grad_offsets=offsets,
            leakage_threshold=self.max_allowed_leakage)
        self.state = self.engine.apply_custom_weights(self.state, pgs, ws,
                                                      server_lr)

        # ONE bundled fetch for everything that exists at collect time
        # (weights + stats + losses); c_norm is PRODUCED by the control
        # update below, so it cannot ride this bundle
        ws_np, stats_np, tls_np = jax.device_get((ws, stats, tls))
        ws_np = np.asarray(ws_np)
        epochs = int(self.config.client_config.get("num_epochs", 1) or 1)
        # real local steps per client: steps with >= 1 real sample, per epoch
        steps = (batch.sample_mask.sum(axis=2) > 0).sum(axis=1) * epochs
        # invalidate the marker while the control files mutate: a crash
        # mid-update must read as a mismatch on resume, not as round N
        self.scaffold_store.set_round(-1)
        if self.scaffold_device is not None:
            # ---- in-program control update: the [K, n_params] payload
            # stack never visits the host; flush() writes the durable
            # copies when the marker commits ----
            c_norm = self.scaffold_device.update(
                batch.client_ids, steps, pgs, ws, ws_np, client_lr,
                total_clients=len(self.train_dataset))
            # the device branch's `‖c‖` only exists after the update —
            # a post-bundle scalar fetch is the price of keeping the
            # [K, n_params] control math on device
            # flint: disable=transfer-budget c_norm is produced by the control update, after the tail bundle
            c_norm = jax.device_get(c_norm)
        else:
            # ---- host-side control update (exact per-client math) ----
            # flint: disable=transfer-budget host-control branch only; bundling pgs would fetch [K, n_params] on the device branch too
            pgs_np = jax.device_get(pgs)
            k = len(batch.client_ids)
            # [K, n_params] in ravel_pytree order: tree.leaves order, each
            # leaf C-order — one concatenate, no per-client round-trips
            pgs_flat = np.concatenate(
                [np.asarray(leaf).reshape(k, -1)
                 for leaf in jax.tree.leaves(pgs_np)], axis=1)
            self.strategy.update_controls(
                self.scaffold_store, batch.client_ids, steps, pgs_flat,
                client_lr, total_clients=len(self.train_dataset),
                weights=ws_np)
            c_norm = float(np.linalg.norm(self.scaffold_store.c))

        # the tail below reads only the bundled fetch from collect time.
        # The -1 sentinel stays in place until _round_housekeeping
        # commits the marker AFTER the paired model checkpoint is
        # durable — resume keeps the controls whenever a matching
        # checkpoint exists and resets only on a crash inside the round
        # window
        self._process_privacy_stats(stats_np, round_no,
                                    client_mask=batch.client_mask)
        tls_np = np.asarray(tls_np)
        n_real = max(float((batch.client_ids >= 0).sum()), 1.0)
        log_metric("Training loss",
                   float(tls_np.sum() / n_real), step=round_no)
        log_metric("Aggregated weights", float(ws_np.sum()), step=round_no)
        log_metric("Control norm (server c)", float(c_norm),
                   step=round_no)  # latest-checkpoint save: housekeeping
        if self.scope is not None:
            # host-side bus publish of the already-fetched c_norm (the
            # device branch's post-update scalar fetch, or the host
            # branch's python float) — a counter sample, no new transfer
            self.scope.devbus_host("scaffold_c_norm", float(c_norm),
                                   step=round_no)

    # ------------------------------------------------------------------
    def _run_ef_round(self, round_no: int) -> None:
        """One error-feedback quantized round (``strategies/ef_quant.py``):
        collect per-client payloads (post local-DP transform), fold in the
        stored residuals, quantize, aggregate the quantized payloads with
        the strategy weights, and persist ``corrected - q`` per client."""
        client_lr, server_lr, batch, rng = self._host_round_setup(round_no)
        # the residual store keeps ONE row per client: a duplicate id in a
        # round batch would aggregate both quantized payloads but keep only
        # the last slot's residual, silently losing the other occurrence's
        # compression error.  Sampling is without replacement, so this is
        # a contract check, not a code path.
        real_ids = np.asarray(batch.client_ids)
        real_ids = real_ids[real_ids >= 0]
        if len(np.unique(real_ids)) != len(real_ids):
            raise ValueError(
                "ef_quant round batch contains duplicate client ids "
                f"({sorted(real_ids.tolist())}); per-client EF residuals "
                "require without-replacement sampling")
        pgs, ws, tls, stats = self.engine.client_payloads(
            self.state, batch, client_lr, rng,
            leakage_threshold=self.max_allowed_leakage)

        # per-round threshold annealing (the fused path's quant_anneal
        # semantics, logged at the same metric name)
        thresh = self.strategy.next_threshold()
        if self.strategy.quant_anneal != 1.0:
            log_metric("Quantization Thresh.", thresh, step=round_no)
        if self.scope is not None:
            # host-side bus publish: the annealed threshold is a host
            # float (no device value involved)
            self.scope.devbus_host("ef_quant_thresh", float(thresh),
                                   step=round_no)
        leaves = jax.tree.leaves(pgs)
        treedef = jax.tree.structure(pgs)
        shapes = [l.shape[1:] for l in leaves]
        sizes = [int(np.prod(sh)) for sh in shapes]
        if not hasattr(self, "_ef_step_fn"):
            strategy = self.strategy

            def step(leaves_in, residuals, thresh):
                flat = jnp.concatenate(
                    [l.reshape(l.shape[0], -1) for l in leaves_in], axis=1)
                q, new_res = strategy.ef_step(flat, residuals, thresh)
                outs, off = [], 0
                for sh, n in zip(shapes, sizes):
                    outs.append(q[:, off:off + n].reshape((-1,) + sh))
                    off += n
                return outs, new_res

            self._ef_step_fn = jax.jit(step)
        residuals = (self.ef_device.rows(batch.client_ids)
                     if self.ef_device is not None else
                     self.ef_store.rows(batch.client_ids))
        # invalidate the marker while residual files mutate: a crash
        # inside the round window must read as a mismatch on resume
        self.ef_store.set_round(-1)
        q_leaves, new_res = self._ef_step_fn(
            leaves, residuals, jnp.asarray(thresh, jnp.float32))
        q_tree = jax.tree.unflatten(treedef, q_leaves)
        self.state = self.engine.apply_custom_weights(self.state, q_tree,
                                                      ws, server_lr)

        # ONE bundled fetch for the EF tail (weights + stats + losses —
        # the same single-transfer discipline as the scaffold round)
        ws_np, stats_np, tls_np = jax.device_get((ws, stats, tls))
        ws_np = np.asarray(ws_np)
        if self.ef_device is not None:
            # new_res and ws stay on device; the scatter gates on
            # participation (id >= 0, w > 0) in-program
            self.ef_device.update(batch.client_ids, new_res, ws, ws_np)
        else:
            # dropped clients (w == 0) contributed nothing: their residual
            # must not absorb this round's uncompressed payload
            keep = (np.asarray(batch.client_ids) >= 0) & (ws_np > 0)
            # flint: disable=transfer-budget host-store branch only; bundling new_res would fetch the [K, n_params] residual stack on the device branch too
            new_res_np = np.asarray(jax.device_get(new_res))
            self.ef_store.update(batch.client_ids, new_res_np, keep)

        self._process_privacy_stats(stats_np, round_no,
                                    client_mask=batch.client_mask)
        tls_np = np.asarray(tls_np)
        n_real = max(float((batch.client_ids >= 0).sum()), 1.0)
        log_metric("Training loss",
                   float(tls_np.sum() / n_real), step=round_no)
        log_metric("Aggregated weights", float(ws_np.sum()), step=round_no)

    # ------------------------------------------------------------------
    def _run_rl_round(self, round_no: int) -> None:
        """One RL-assisted round (reference ``core/strategies/dga.py:286-406``):
        collect per-client payloads once, aggregate with both the strategy
        weights and the RL-estimated weights, keep whichever validates
        better, reward the policy, train the DQN."""
        client_lr, server_lr, batch, rng = self._host_round_setup(round_no)

        pgs, ws, _tls, stats = self.engine.client_payloads(
            self.state, batch, client_lr, rng,
            leakage_threshold=self.max_allowed_leakage)
        # ONE fetch for everything the RL head reads — per-field
        # device_get of stats members paid a transfer per stat
        ws_np, stats_np = jax.device_get((ws, stats))
        ws_np = np.asarray(ws_np)
        k = int((batch.client_ids >= 0).sum())
        state_vec = np.concatenate([
            ws_np[:k],
            np.asarray(stats_np["mag"])[:k],
            np.asarray(stats_np["mean"])[:k],
            np.asarray(stats_np["var_corrected"])[:k]])

        # candidate A: strategy weights; candidate B: RL weights
        baseline_state = self.engine.apply_custom_weights(
            self.state, pgs, ws, server_lr)
        action = self.rl.forward(state_vec)
        rl_w = self.rl.weights_from_action(action)
        rl_w_full = np.zeros_like(ws_np)
        rl_w_full[:k] = rl_w[:k] if len(rl_w) >= k else \
            np.pad(rl_w, (0, k - len(rl_w)))
        rl_state = self.engine.apply_custom_weights(
            self.state, pgs, rl_w_full, server_lr)

        self.state = baseline_state
        baseline_acc = self._val_acc()
        self.state = rl_state
        rl_acc = self._val_acc()

        reward, keep_rl = self.rl.compute_reward(
            baseline_acc, rl_acc,
            bool(self.config.lookup("server_config.RL.marginal_update_RL",
                                    True)))
        self.state = rl_state if keep_rl else baseline_state
        log_metric("RL Rewards", reward, step=round_no)
        log_metric("Val acc (baseline vs RL)",
                   {"baseline": baseline_acc, "rl": rl_acc}, step=round_no)
        # attack metrics + adaptive leakage threshold, same as the fused
        # and scaffold paths — without this the adaptive threshold could
        # never update and the leakage-based dropping would stay inert
        self._process_privacy_stats(stats_np, round_no,
                                    client_mask=batch.client_mask)
        self.rl.train(state_vec, action, reward)
        self.rl.save()
        log_metric("RL Running Loss", self.rl.running_loss, step=round_no)

    # ------------------------------------------------------------------
    def _chunk_client_masks(self, batches) -> np.ndarray:
        """``[R, K]`` live-client mask of one chunk for the privacy-stat
        distribution.  Bucketed chunks concatenate each round's bucket
        masks in ascending-bucket order — the SAME layout the finalize
        program concatenates its per-client vectors in — then zero-pad
        rounds to the chunk max exactly like
        :meth:`~msrflute_tpu.engine.round.BucketedStats.fetch`."""
        rows = []
        for entry in batches:
            if isinstance(entry, list):
                rows.append(np.concatenate(
                    [b.client_mask for b in entry]))
            else:
                rows.append(np.asarray(entry.client_mask))
        width = max(r.shape[0] for r in rows)
        return np.stack([
            r if r.shape[0] == width
            else np.concatenate([r, np.zeros(width - r.shape[0],
                                             r.dtype)])
            for r in rows])

    def _process_privacy_stats(self, stats, round_no: int,
                               client_mask=None) -> None:
        """Log attack metrics + adapt the leakage threshold (reference
        ``core/server.py:390-409``: the new threshold is the configured
        quantile of this chunk's per-client leakage values).  ``client_mask``
        [R, K] excludes mesh-padding lanes from the distribution."""
        if "privacy_dropped" not in stats:
            return
        real = (np.asarray(client_mask).ravel() > 0 if client_mask is not None
                else None)

        def _select(key):
            vals = np.asarray(stats[key]).ravel()
            if real is not None and real.shape == vals.shape:
                vals = vals[real]
            return vals[np.isfinite(vals)]

        log_metric("Dropped clients", float(_select("privacy_dropped").sum()),
                   step=round_no)
        for key, name in (("privacy_overlap", "Extracted indices percentage"),
                          ("privacy_leakage", "Practical epsilon (Max leakage)"),
                          ("privacy_above_rank", "Words percentage above rank")):
            if key in stats:
                finite = _select(key)
                if finite.size:
                    log_metric(name, float(finite.max()), step=round_no)
        if self.adaptive_leakage is not None and "privacy_leakage" in stats:
            values = np.sort(_select("privacy_leakage"))
            if values.size:
                idx = min(int(self.adaptive_leakage * values.size),
                          values.size - 1)
                self.max_allowed_leakage = float(values[idx])
                print_rank(f"updated leakage threshold to "
                           f"{self.max_allowed_leakage}")

    # ------------------------------------------------------------------
    _last_val: MetricsDict = {}
    #: (state, metric names) a validation found better, until saved
    _bettered: Tuple[Any, Tuple[str, ...]] = (None, ())
    #: an evaluation round's durable tail, held back until the chunk loop
    #: has launched the next dispatch (:meth:`_run_pending_tail`)
    _pending_tail = None

    def _split_cfg(self, split: str):
        dc = self.config.server_config.data_config
        return dc.val if split == "val" else dc.test

    def _packed_eval_batches(self, split: str):
        """Packed ``[T, B, ...]`` eval grid for a split — cached AS STAGED
        DEVICE ARRAYS: eval data is static across rounds, so both the host
        packing and the host->device transfer happen once per split; every
        later eval's ``device_put`` on the already-placed arrays is a
        no-op (the RL path evaluates twice per round, and on a remote-
        attached chip the re-transfer would otherwise dominate eval)."""
        with self._tspan("eval_pack", split=split,
                         cached=split in self._eval_batches_cache):
            return self._packed_eval_batches_inner(split)

    def _packed_eval_batches_inner(self, split: str):
        batches = self._eval_batches_cache.get(split)
        if batches is None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            dataset = self.val_dataset if split == "val" else self.test_dataset
            bs = int(self._split_cfg(split).get("batch_size",
                                                self.batch_size))
            batches = pack_eval_batches(
                dataset, bs,
                pad_steps_to_multiple_of=self.mesh.shape[CLIENTS_AXIS])
            spec = (P(CLIENTS_AXIS) if self.engine.partition_mode ==
                    "shard_map" else P())
            sharding = NamedSharding(self.mesh, spec)
            # flint: disable=put-loop eval batches staged once and cached across evals
            batches = {k: jax.device_put(v, sharding)
                       for k, v in batches.items()}
            self._eval_batches_cache[split] = batches
        return batches

    def _maybe_eval(self, split: str, round_no: int, force: bool = False,
                    hold_best: bool = False) -> bool:
        """``hold_best``: a validation that improved leaves its
        best-model save to the caller's :meth:`_save_bettered`."""
        dataset = self.val_dataset if split == "val" else self.test_dataset
        if dataset is None or len(dataset) == 0:
            return False
        with self._tspan("eval", split=split, round=round_no):
            metrics = evaluate(self.task, self._eval_fn, self.state.params,
                               self._packed_eval_batches(split), self.mesh,
                               self.engine.partition_mode,
                               telemetry=self.scope)
        # eval compiles join the always-on compile log (and so the
        # recompile counter the storm watchdog + scorecard gate on) —
        # an eval-grid shape churn must not hide from the sentinel
        self.engine._note_compiles("eval_step", self._eval_fn)
        for name, metric in metrics.items():
            log_metric(f"{split.capitalize()} {name}", metric.value, step=round_no)
        if self._split_cfg(split).get("wantLogits", False):
            self._dump_predictions(split, round_no)
        if self._split_cfg(split).get("per_user_stats", False):
            self._log_per_user_stats(split, round_no, dataset)

        improved = False
        if split == "val":
            self._last_val = metrics
            bettered = []
            for name, metric in metrics.items():
                if not np.isfinite(metric.value):
                    # eval-side non-finite guard, host half: a NaN/Inf
                    # metric must never enter best_val (it would poison
                    # every later is_better_than comparison and the
                    # fall-back-to-best target) — today's value simply
                    # doesn't compete
                    emit_event(self.scope, "eval_nonfinite_skipped",
                               split=split, metric=name, round=round_no,
                               value=str(metric.value))
                    continue
                prev = self.best_val.get(name)
                if prev is None or metric.is_better_than(prev):
                    self.best_val[name] = metric
                    bettered.append(name)
                    if name == self.best_model_criterion:
                        improved = True
            if bettered:
                # one file per metric, one fetch and one write for all,
                # on the disk before the status log names the new
                # best_val (_durable_tail waits for it)
                self._bettered = (self.state, tuple(bettered))
                if not hold_best:
                    self._save_bettered()
            # convergence-tier crossing (traffic.target_accuracy): the
            # FIRST val eval at/above the target pins the round — the
            # rounds_to_target_accuracy bench.py records and `scope
            # trend` gates alongside secs_per_round
            if self.target_accuracy is not None and \
                    self.rounds_to_target_accuracy is None:
                acc = metrics.get("acc")
                if acc is not None and np.isfinite(acc.value) and \
                        float(acc.value) >= self.target_accuracy:
                    self.rounds_to_target_accuracy = int(round_no)
                    emit_event(self.scope, "target_accuracy_reached",
                               round=round_no, acc=float(acc.value),
                               target=self.target_accuracy)
        return improved

    def _save_bettered(self, hold: bool = False
                       ) -> Tuple[Any, Optional[str]]:
        """The best-model save of the state the last validation found
        better, if it has not gone out yet: (that state, its file).
        ``hold``: the round's durable tail follows (``save_best``)."""
        state, names = self._bettered
        self._bettered = (None, ())
        return state, (self.ckpt.save_best(state, *names, hold=hold)
                       if names else None)

    def _log_per_user_stats(self, split: str, round_no: int,
                            dataset) -> None:
        """Per-user accuracy dispersion when the split's data_config sets
        ``per_user_stats`` — the fairness observability the aggregate
        metric hides (and what q-FFL/AFL-style strategies optimize):
        worst / p10 / p50 / p90 / std of per-user accuracy, plus the
        evaluated-user count.  Classification-style tasks only: needs
        ``task.apply`` producing per-sample class logits AND ``y`` labels
        in the eval grid (BERT MLM has ``apply`` but no ``y``; sequence
        tasks have neither) — anything else warns and skips."""
        batches = self._packed_eval_batches(split)
        if not hasattr(self.task, "apply") or "y" not in batches:
            print_rank(f"per_user_stats set for {split} but task "
                       f"{type(self.task).__name__} is not "
                       "classification-style (needs apply() + y labels); "
                       "skipping", loglevel=logging.WARNING)
            return
        from .evaluation import build_per_user_eval_fn, per_user_accuracy
        if split not in self._per_user_fns:
            self._per_user_fns[split] = build_per_user_eval_fn(
                self.task, self.mesh, len(dataset),
                self.engine.partition_mode)
        accs = per_user_accuracy(self._per_user_fns[split],
                                 self.state.params, batches,
                                 self.mesh, self.engine.partition_mode)
        accs = accs[~np.isnan(accs)]
        if accs.size == 0:
            return
        cap = split.capitalize()
        log_metric(f"{cap} acc (worst user)", float(accs.min()),
                   step=round_no)
        for pct in (10, 50, 90):
            log_metric(f"{cap} acc (user p{pct})",
                       float(np.percentile(accs, pct)), step=round_no)
        log_metric(f"{cap} acc (user std)", float(accs.std()),
                   step=round_no)
        log_metric(f"{cap} acc (users evaluated)", int(accs.size),
                   step=round_no)

    def _dump_predictions(self, split: str, round_no: int,
                          topk: int = 3) -> None:
        """Per-sample prediction dump when the split's data_config sets
        ``wantLogits`` (reference ``core/client.py:156`` +
        ``nlg_gru/model.py:113-130``: eval returns output payloads).
        One JSON line per real sample -> ``predictions_<split>_r<N>.jsonl``.

        Deliberate cost: this is a SECOND forward over the eval grid, kept
        separate from the metric eval (whose contract is psum'd scalar
        sums, not per-sample payloads) — it only runs on wantLogits evals.
        """
        import json as _json

        task = self.task
        seq_fn = getattr(task, "topk_predictions", None)
        cls_fn = getattr(task, "predict", None)
        if seq_fn is None and cls_fn is None:
            print_rank(f"wantLogits set for {split} but task "
                       f"{type(task).__name__} exposes neither "
                       "topk_predictions nor predict — no dump written",
                       loglevel=logging.WARNING)
            return
        batches = self._packed_eval_batches(split)
        if not hasattr(self, "_pred_fns"):
            self._pred_fns = {}
        fn = self._pred_fns.get(split)
        if fn is None:
            if seq_fn is not None:
                fn = jax.jit(lambda p, b: seq_fn(p, b, topk))
            else:
                fn = jax.jit(cls_fn)
            self._pred_fns[split] = fn

        path = os.path.join(self.ckpt.model_dir,
                            f"predictions_{split}_r{round_no}.jsonl")
        T = batches["sample_mask"].shape[0]
        # the cache holds staged DEVICE arrays; pull the two bookkeeping
        # grids to host in ONE fetch instead of one transfer per grid
        # (and none per step)
        mask_np, uids_np = jax.device_get(
            (batches["sample_mask"], batches["user_idx"]))
        mask_np = np.asarray(mask_np) > 0
        uids_np = np.asarray(uids_np)
        # tmp + os.replace: the dump streams one row per sample, so a
        # crash mid-loop would otherwise leave a silently-truncated
        # predictions file at the advertised path
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            for t in range(T):
                mask = mask_np[t]
                if not mask.any():
                    continue  # mesh-padding step: skip the forward entirely
                batch = {k: v[t] for k, v in batches.items()
                         if k != "user_idx"}
                out = jax.device_get(fn(self.state.params, batch))
                uids = uids_np[t]
                for i in np.flatnonzero(mask):
                    if seq_fn is not None:
                        top_p, top_ids, labels = out
                        row = {"user": int(uids[i]),
                               "topk_ids": top_ids[i].tolist(),
                               "topk_probs": np.round(
                                   top_p[i], 6).tolist(),
                               "labels": labels[i].tolist()}
                    else:
                        logits, pred, labels = out
                        row = {"user": int(uids[i]),
                               "pred": int(pred[i]),
                               "label": int(labels[i]),
                               "logits": np.round(logits[i], 6).tolist()}
                    fh.write(_json.dumps(row) + "\n")
        os.replace(tmp, path)
        print_rank(f"wrote {split} predictions to {path}")

    def _fall_back(self) -> None:
        """Reload the best checkpoint, preserving current LR weight
        (reference ``core/server.py:561-578``)."""
        restored = self.ckpt.load_best(self.state, self.best_model_criterion)
        if restored is not None:
            self.state = ServerState(restored.params, restored.opt_state,
                                     restored.strategy_state, self.state.round)
            print_rank("fell back to previous best model")
            if self.scaffold_store is not None:
                # controls accumulated since that checkpoint belong to the
                # abandoned trajectory; restart control estimation from
                # zero (the paper's init) rather than bias the restored
                # params with stale drift corrections
                if self.scaffold_device is not None:
                    self.scaffold_device.reset()  # also resets the store
                else:
                    self.scaffold_store.reset()
                print_rank("reset SCAFFOLD controls after fallback")
            if self.ef_store is not None:
                # residuals accumulated since that checkpoint carry the
                # abandoned trajectory's compression error
                if self.ef_device is not None:
                    self.ef_device.reset()  # also resets the store
                else:
                    self.ef_store.reset()
                print_rank("reset EF residuals after fallback")

    def _log_timing(self) -> None:
        """Timing summary (reference ``run_stats``, ``core/server.py:492-521``)
        — percentiles as well as means: tail rounds are what a wall-clock
        budget actually pays for."""
        for key, values in self.run_stats.items():
            if values:
                log_metric(f"{key} (mean)", float(np.mean(values)))
                log_metric(f"{key} (p50)", float(np.percentile(values, 50)))
                log_metric(f"{key} (p95)", float(np.percentile(values, 95)))


def select_server(server_type: str):
    """Reference ``select_server`` (``core/server.py:581-597``):
    ``personalization`` -> PersonalizationServer, else OptimizationServer."""
    if (server_type or "").lower() == "personalization":
        from .personalization import PersonalizationServer
        return PersonalizationServer
    return OptimizationServer
