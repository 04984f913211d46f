"""Config schema validation for msrflute_tpu.

Parity target: reference ``core/schema.py`` (a 299-line cerberus schema dict
loaded with ``eval`` at ``core/config.py:766-769``).  We validate the same
classes of constraint with a small hand-rolled checker:

- required sections and keys;
- enum values (optimizer types per ``core/schema.py:90``, annealing types
  per ``utils/utils.py:151-186``, strategies per
  ``core/strategies/__init__.py:9-23``);
- **unknown-key detection**: cerberus rejects keys outside the schema; we do
  the same for every structured section, with a did-you-mean suggestion, so
  a typo'd ``initial_lr_clients:`` fails loudly instead of silently falling
  back to the default.  Free-form surfaces (``model_config`` plugin params,
  ``semisupervision``, ``augment``, ``mesh_config``) stay open by design.
- an **applied-defaults report** (:func:`applied_defaults`) mirroring the
  reference's printout of the diff between the user config and the config
  with defaults applied (``core/config.py:771-779``).

Raises :class:`SchemaError` with every violation collected, like cerberus
reports all errors at once.  ``strict=False`` (or env
``MSRFLUTE_ALLOW_UNKNOWN=1``) downgrades unknown-key errors to warnings for
forward-compat with configs written for newer versions.
"""

from __future__ import annotations

import difflib
import os
import warnings
from typing import Any, Dict, Iterable, List, Optional

ALLOWED_OPTIMIZERS = [
    # reference core/schema.py:90
    "sgd", "adam", "adamax", "lars", "LarsSGD", "lamb", "adamW",
    # accepted aliases
    "adamw", "larssgd",
    # net-new: FedYogi server optimizer (arXiv:2003.00295)
    "yogi",
]

ALLOWED_ANNEALING = [
    # reference utils/utils.py:151-186
    "step_lr", "multi_step_lr", "rampup-keep-expdecay-keep", "val_loss",
    # alias
    "constant",
]

ALLOWED_STRATEGIES = [
    # reference core/strategies/__init__.py:9-23
    "dga", "DGA", "fedavg", "FedAvg", "fedprox", "FedProx",
    "fedlabels", "FedLabels", "fedac", "FedAC", "scaffold", "Scaffold",
    # net-new: q-FFL fairness weighting (arXiv:1905.10497)
    "qffl", "QFFL",
    # net-new: secure aggregation simulation (Bonawitz et al., CCS'17)
    "secure_agg", "secagg", "SecureAgg",
    # net-new: error-feedback quantization (arXiv:1901.09847)
    "ef_quant", "efquant", "EFQuant",
    # net-new: buffered async aggregation (arXiv:2106.06639)
    "fedbuff", "FedBuff",
]

ALLOWED_SERVER_TYPES = [
    # reference core/server.py:581-597
    "optimization", "model_optimization", "personalization",
]

# ----------------------------------------------------------------------
# known keys per structured section.  Sources: the dataclass fields in
# config.py plus every documented TPU-native extension key the engine
# consumes (grep ``.get("<key>")`` over msrflute_tpu/).
# ----------------------------------------------------------------------
OPTIMIZER_KEYS = {
    "type", "lr", "momentum", "nesterov", "weight_decay", "amsgrad", "eps",
    "betas", "dampening",
}

ANNEALING_KEYS = {
    "type", "step_interval", "step_size", "gamma", "milestones", "patience",
    "factor", "peak_lr", "floor_lr", "rampup_steps", "hold_steps",
    "decay_steps",
}

DATASET_KEYS = {
    # reference per-split blocks
    "batch_size", "loader_type", "list_of_train_data", "test_data",
    "val_data", "train_data", "train_data_server", "vocab_dict",
    "pin_memory", "num_workers", "prefetch_factor", "desired_max_samples",
    "max_batch_size", "max_num_words", "max_seq_length",
    "min_words_per_utt", "num_frames", "max_samples_per_user",
    "max_grad_norm", "utterance_mvn", "unsorted_batch",
    # TPU-native extensions
    "device_resident", "lazy", "lazy_cache_users", "augment", "wantLogits",
    "step_bucketing", "length_bucketing", "per_user_stats",
}

DATACONFIG_KEYS = {"train", "val", "test", "num_clients"}

DP_KEYS = {
    "enable_local_dp", "enable_global_dp", "eps", "delta", "max_grad",
    "max_weight", "min_weight", "weight_scaler", "global_sigma",
    # reference extras (extensions/privacy/__init__.py)
    "enable_prod", "max_bound", "min_bound",
    # TPU-native: quantile-tracking adaptive clipping (arXiv:1905.03871)
    "adaptive_clipping",
}

ADAPTIVE_CLIP_KEYS = {
    "target_quantile", "clip_lr", "initial_clip", "count_sigma",
}

PRIVACY_METRICS_KEYS = {
    "apply_metrics", "apply_indices_extraction", "allowed_word_rank",
    "apply_leakage_metric", "max_leakage", "max_allowed_leakage",
    "adaptive_leakage_threshold", "is_leakage_weighted",
    "attacker_optimizer_config", "max_allowed_overlap",
}

SERVER_REPLAY_KEYS = {"server_iterations", "optimizer_config", "data_config"}

CHAOS_KEYS = {
    "enable", "seed", "dropout_rate", "straggler_rate",
    "straggler_inflation", "ckpt_io_error_rate", "preempt_at_round",
    # adversarial update-corruption streams (fluteshield's attack half,
    # resilience/chaos.py corrupt_modes)
    "corrupt_nan_rate", "corrupt_scale_rate", "corrupt_sign_flip_rate",
    "corrupt_scale_factor", "corrupt_sign_flip_scale",
    # flutearmor's infrastructure fault plane (nested mapping,
    # CHAOS_INFRA_KEYS / resilience/chaos.py InfraFaults)
    "infra",
}

#: ``server_config.chaos.infra`` — seeded host-service fault streams
#: (flutearmor): each knob arms one surface's call-indexed stream
CHAOS_INFRA_KEYS = {
    "store_write_error_rate", "store_read_error_rate",
    "prefetch_error_rate", "prefetch_delay_rate", "prefetch_delay_s",
    "writer_error_rate", "writeback_error_rate",
}

ROBUST_KEYS = {
    "enable", "screen_nonfinite", "norm_multiplier", "aggregator",
    "trim_fraction",
}

# mirrors strategies/secure_agg.py SECURE_AGG_KEYS (schema_drift keeps
# the docs table in sync): a misspelled masking knob silently running
# the defaults is the quiet failure this schema exists to prevent
SECURE_AGG_KEYS = {
    "frac_bits", "clip", "seed", "graph", "min_survivors",
}

SECURE_AGG_FIELD_SPECS = {
    "frac_bits": ("int", 1, 24),
    "clip": ("number", None, None),
    "seed": ("int", None, None),
    "min_survivors": ("int", 0, None),
}

COHORT_BUCKETING_KEYS = {
    "enable", "max_buckets", "boundaries", "slack",
}

COHORT_BUCKETING_FIELD_SPECS = {
    "enable": ("bool", None, None),
    # distinct compiled bucket grids the run may hold (1 == monolithic
    # shape discipline); the recompile sentinel + bench A/B gate closure
    "max_buckets": ("int", 1, None),
    # per-bucket capacity headroom over the expected cohort mix: lower
    # = tighter grids (better padding efficiency) but more spill-up and
    # occasional extra top-bucket grids; < 1 would under-provision the
    # EXPECTED occupancy and spill every round
    "slack": ("num", 1.0, None),
    # `boundaries` (explicit step-bucket S values) keeps a bespoke check
    # in validate(): a strictly-increasing positive-int LIST is a shape
    # the scalar spec table cannot express
}

MEGABATCH_KEYS = {
    "enable", "lanes", "slack", "min_gain", "autotune",
}

MEGABATCH_FIELD_SPECS = {
    "enable": ("bool", None, None),
    # explicit lane count applied to EVERY bucket's super-batch tape
    # (power users / A-Bs); absent = auto-sized per bucket from the
    # population's expected tape occupancy
    "lanes": ("int", 1, None),
    # lane-capacity headroom over the expected per-round tape entries:
    # lower = tighter tapes (better utilization) but more same-shape
    # overflow grids when sampling runs hot
    "slack": ("num", 1.0, None),
    # analytic-gate margin: the megabatch arm must price at least this
    # fraction cheaper (in padded sample slots) than per-client vmap
    # before a bucket repacks — covers the per-step gather/reset
    # overhead the slot count cannot see
    "min_gain": ("num", 0.0, None),
    # price both arms with telemetry.xla aot cost analyses at first
    # dispatch (when the xla introspector is on) instead of trusting
    # the slot heuristic; the loser falls back loudly
    # (`megabatch_fallback` instant event)
    "autotune": ("bool", None, None),
}

FLEET_KEYS = {
    "enable", "page_pool_slots", "host_cache_rows", "spill_freq",
    "sampling", "prefetch",
}

#: fleet cohort-draw vocabulary (data/fleet.py sample_cohort):
#: `uniform` = numpy Generator.choice (O(cohort) via Floyd's algorithm,
#: trail-identical to the non-fleet path); `floyd` = the explicit Floyd
#: implementation; `by_samples` = sample-count-weighted reservoir —
#: the latter two start new rng trails
ALLOWED_FLEET_SAMPLING = ["uniform", "floyd", "by_samples"]

FLEET_FIELD_SPECS = {
    "enable": ("bool", None, None),
    # device page-pool rows per carry table (HBM = slots x row bytes,
    # independent of population); must cover (pipeline_depth + 1)
    # in-flight cohorts or dispatch refuses — default auto-sizes from
    # the cohort geometry
    "page_pool_slots": ("int", 1, None),
    # host RAM rows before LRU spill-through to the durable .npz store
    "host_cache_rows": ("int", 1, None),
    # rounds between durable spill + round-marker commits (the
    # scaffold_flush_freq tradeoff: > 1 amortizes disk IO, a stop
    # inside the window resets carry rows on resume)
    "spill_freq": ("int", 1, None),
    # stage the next chunk's missing carry rows on the fleet-prefetch
    # worker thread while the current chunk executes (bit-identical to
    # the cold path; default on — off only for the prefetch A/B)
    "prefetch": ("bool", None, None),
    # `sampling` keeps a bespoke enum check in validate()
}

MEGAKERNEL_KEYS = {
    "pallas_apply",
}

MEGAKERNEL_FIELD_SPECS = {
    # opt-in pallas fused SGD apply over the flattened param vector
    # (plain-SGD client optimizers only; TPU-targeted)
    "pallas_apply": ("bool", None, None),
}

# mirrors traffic/schedule.py _SCHEDULE_KEYS + the trace knobs consumed
# by traffic/traces.py make_trace (schema_drift keeps the docs table in
# sync): a misspelled arrival knob silently running the Poisson defaults
# is the quiet failure this schema exists to prevent
TRAFFIC_KEYS = {
    "enable", "mode", "seed", "buffer_size", "duration_lo",
    "duration_hi", "max_idle_ticks", "target_accuracy",
    # trace selection + per-trace knobs (traffic/traces.py)
    "trace", "rate", "period", "depth", "burst_rate", "burst_every",
    "burst_len", "classes",
}

#: arrival-plane mode vocabulary (traffic/schedule.py TRAFFIC_MODES):
#: `buffered` = FedBuff-style async firing with true traced staleness;
#: `sync` = the barrier baseline (stale deliveries discarded, counted)
ALLOWED_TRAFFIC_MODES = ["sync", "buffered"]

#: trace catalogue (traffic/traces.py TRACE_NAMES)
ALLOWED_TRAFFIC_TRACES = ["poisson", "diurnal", "bursty",
                          "device_classes"]

TRAFFIC_FIELD_SPECS = {
    "enable": ("bool", None, None),
    "seed": ("int", None, None),
    # arrivals needed to fire a round — must equal the run's (fixed)
    # num_clients_per_iteration: the fused [K, S, B] grid is compiled
    # for exactly K client slots, so the buffer IS the cohort (the
    # server refuses a mismatch at construction)
    "buffer_size": ("int", 1, None),
    # training-duration draw bounds, in ticks (per-class duration_scale
    # multiplies on top for device_classes)
    "duration_lo": ("int", 1, None),
    "duration_hi": ("int", 1, None),
    # starvation tripwire: ticks without a fire before the schedule
    # raises instead of spinning forever on an undersubscribed trace
    "max_idle_ticks": ("int", 1, None),
    # bench.py rounds_to_target_accuracy threshold (traffic_ab arm)
    "target_accuracy": ("num", 0.0, 1.0),
    # mean arrivals per tick across the population (trace-specific
    # baseline; bursty's off-burst floor)
    "rate": ("num", 0.0, None),
    # diurnal / device_classes cycle length, ticks
    "period": ("int", 1, None),
    # diurnal modulation depth: 0 = flat, 1 = full swing through zero
    "depth": ("num", 0.0, None),
    # bursty flash-crowd knobs: in-burst rate + burst geometry
    "burst_rate": ("num", 0.0, None),
    "burst_every": ("int", 1, None),
    "burst_len": ("int", 1, None),
    # `mode`/`trace` keep enum checks in validate(); `classes` (a list
    # of per-class mappings) keeps a bespoke check — the scalar spec
    # table cannot express it
}

PRECISION_KEYS = {
    "enable", "params", "compute", "stats",
}

#: precision-policy dtype vocabulary (engine/client_update.py): each
#: entry defaults to float32, the bit-identity spelling of "absent"
ALLOWED_PRECISION_DTYPES = ["float32", "bfloat16", "float16"]

#: robust aggregator vocabulary (mirrors robust.shield.AGGREGATORS)
ALLOWED_ROBUST_AGGREGATORS = ["mean", "trimmed_mean", "median"]

ROBUST_FIELD_SPECS = {
    "enable": ("bool", None, None),
    "screen_nonfinite": ("bool", None, None),
    # scales the cohort's median payload norm; 0 disables the norm
    # screen.  The (0, 1) gap is rejected by a bespoke check in
    # validate() — the inclusive range table cannot express {0} ∪ [1,∞)
    "norm_multiplier": ("num", 0.0, None),
    # per-side trim; == 0.5 (nothing left to average) is rejected by a
    # bespoke check in validate() — the range table is inclusive
    "trim_fraction": ("num", 0.0, 0.5),
}

CHECKPOINT_RETRY_KEYS = {
    "retries", "backoff_base_s", "backoff_max_s", "jitter",
    "escalation_threshold",
}

TELEMETRY_KEYS = {
    "enable", "trace", "devbus", "profile_rounds", "watchdog",
    "xla", "scorecard",
    # endurance layer (ISSUE 13): windowed rollups, flight recorder,
    # size-capped log rotation
    "rollup", "rollup_window", "flight", "flight_events", "max_log_mb",
}

WATCHDOG_KEYS = {
    "nan_loss", "round_time_action", "round_time_factor",
    "round_time_window", "ckpt_failure_action", "ckpt_failure_streak",
    "quarantine_rate_action", "quarantine_rate_threshold",
    "recompile_storm_action", "recompile_storm_threshold",
    "recompile_storm_warmup_rounds",
    # longitudinal detectors (ISSUE 13)
    "stall_action", "stall_factor", "stall_poll_secs",
    "stall_grace_secs", "rss_leak_action", "rss_leak_window",
    "rss_leak_mb_per_round", "throughput_drift_action",
    "throughput_drift_window", "throughput_drift_factor",
}

TELEMETRY_FIELD_SPECS = {
    "enable": ("bool", None, None),
    "trace": ("bool", None, None),
    "devbus": ("bool", None, None),
    # device-truth layer (telemetry/xla.py): compiled cost/memory
    # capture + recompile sentinel + live MFU
    "xla": ("bool", None, None),
    # compact per-run regression surface (telemetry/scorecard.json)
    "scorecard": ("bool", None, None),
    # endurance rollups (telemetry/rollup.py): one rollups.jsonl record
    # per rollup_window rounds, O(window) host memory
    "rollup": ("bool", None, None),
    "rollup_window": ("int", 1, None),
    # flight recorder: ring of the last flight_events structured events
    # persisted as flight.json on abort/preemption/exception
    "flight": ("bool", None, None),
    "flight_events": ("int", 8, None),
    # size-capped metrics.jsonl/events.jsonl rotation (MB; 0 = off)
    "max_log_mb": ("num", 0, None),
    # profile_rounds keeps a bespoke check in validate(): int | "lo:hi"
    # | [lo, hi] is a union type the scalar spec table cannot express
}

WATCHDOG_FIELD_SPECS = {
    # a slowdown factor < 1 would flag every round faster than median
    "round_time_factor": ("num", 1.0, None),
    "round_time_window": ("int", 4, None),
    "ckpt_failure_streak": ("int", 1, None),
    # fluteshield: fraction of the live cohort quarantined in one round
    "quarantine_rate_threshold": ("num", 0.0, 1.0),
    # recompile sentinel storm: fire after this many recompile events
    # past the warmup rounds (a steady-state loop recompiles ZERO times)
    "recompile_storm_threshold": ("int", 1, None),
    "recompile_storm_warmup_rounds": ("int", 0, None),
    # stall: no round-completion heartbeat within
    # max(stall_factor x trailing-median round time, stall_grace_secs)
    "stall_factor": ("num", 1.0, None),
    "stall_poll_secs": ("num", 0.01, None),
    "stall_grace_secs": ("num", 0.0, None),
    # rss_leak: least-squares host-RSS slope over a trailing window
    "rss_leak_window": ("int", 4, None),
    "rss_leak_mb_per_round": ("num", 0.0, None),
    # throughput_drift: trailing-median secs/round vs the anchor window
    "throughput_drift_window": ("int", 4, None),
    "throughput_drift_factor": ("num", 1.0, None),
}

#: watchdog detector actions (telemetry/watchdog.py ACTIONS)
ALLOWED_WATCHDOG_ACTIONS = ["off", "log", "mark", "abort"]

#: documented upper bound on ``server_config.pipeline_depth`` (the ring
#: of in-flight dispatched-but-undrained round chunks): each slot holds
#: a full set of staged round inputs + a packed-stats output buffer in
#: HBM, and past the point where the host tail is fully hidden extra
#: depth only adds memory and preemption-drain latency.  Validation
#: REFUSES larger values (the PR-1 silent clamp is gone).
MAX_PIPELINE_DEPTH = 8

CHAOS_FIELD_SPECS = {
    "enable": ("bool", None, None),
    "seed": ("int", 0, None),
    "dropout_rate": ("num", 0.0, 1.0),
    "straggler_rate": ("num", 0.0, 1.0),
    # divides the steps a straggler completes before the round barrier
    "straggler_inflation": ("num", 1.0, None),
    "ckpt_io_error_rate": ("num", 0.0, 1.0),
    "preempt_at_round": ("int", 0, None),
    "corrupt_nan_rate": ("num", 0.0, 1.0),
    "corrupt_scale_rate": ("num", 0.0, 1.0),
    "corrupt_sign_flip_rate": ("num", 0.0, 1.0),
    # the multiplier a scaling attacker applies (also useful < 1 to
    # rehearse shrink attacks); strictly positive
    "corrupt_scale_factor": ("num", 0.0, None),
    "corrupt_sign_flip_scale": ("num", 0.0, None),
}

CHAOS_INFRA_FIELD_SPECS = {
    "store_write_error_rate": ("num", 0.0, 1.0),
    "store_read_error_rate": ("num", 0.0, 1.0),
    "prefetch_error_rate": ("num", 0.0, 1.0),
    "prefetch_delay_rate": ("num", 0.0, 1.0),
    # seconds a delayed prefetch staging stalls (superseded-generation
    # drill); any non-negative duration
    "prefetch_delay_s": ("num", 0.0, None),
    "writer_error_rate": ("num", 0.0, 1.0),
    "writeback_error_rate": ("num", 0.0, 1.0),
}

CHECKPOINT_RETRY_FIELD_SPECS = {
    "retries": ("int", 1, None),
    "backoff_base_s": ("num", 0, None),
    "backoff_max_s": ("num", 0, None),
    "jitter": ("num", 0, 1.0),
    "escalation_threshold": ("int", 1, None),
}

RL_KEYS = {
    "marginal_update_RL", "RL_path", "RL_path_global", "model_descriptor_RL",
    "network_params", "initial_epsilon", "final_epsilon", "epsilon_gamma",
    "max_replay_memory_size", "minibatch_size", "gamma", "optimizer_config",
    "annealing_config", "wantLSTM", "runningAvg_param", "resume_from_checkpoint",
}

SERVER_KEYS = {
    "type", "max_iteration", "num_clients_per_iteration", "initial_lr_client",
    "lr_decay_factor", "val_freq", "rec_freq", "initial_val", "initial_rec",
    "best_model_criterion", "fall_back_to_best_model", "model_backup_freq",
    "resume_from_checkpoint", "send_dicts", "max_grad_norm", "do_profiling",
    "wantRL", "aggregate_median", "softmax_beta", "initial_lr",
    "weight_train_loss", "stale_prob", "num_skip_decoding", "data_config",
    "optimizer_config", "annealing_config", "server_replay_config", "RL",
    "nbest_task_scheduler", "best_model_metric",
    # TPU-native extensions
    # pipeline_depth: overlapped host/device round pipeline (0 = serial
    # loop, 1 = default: drain round k's host tail — stats decode, metric
    # logging, privacy processing, checkpoint submit — while the device
    # executes round k+1).  Bit-identical params/metrics either way
    # (tests/test_server_pipeline.py); host-orchestrated paths (wantRL,
    # scaffold/ef strategies, server replay, personalization) and the
    # adaptive leakage threshold fall back to serial automatically.  Set
    # 0 to debug host-tail timing or to keep the per-round `latest`
    # checkpoint synchronous (pipelined mode defaults checkpoint_async on,
    # which widens the crash window: after a hard crash status_log.json
    # may be one round ahead of latest_model — see docs/RUNBOOK.md).
    "pipeline_depth",
    # fused_carry: universal overlap (PR 6) — move cross-round strategy
    # state (SCAFFOLD controls, EF residuals, personalization
    # heads/alphas, the RL weight tuner) into device-resident carry
    # operands of the fused round program so those strategies run
    # pipelined instead of host-orchestrated serial; see
    # docs/config_extensions.md for the per-strategy tradeoffs
    "fused_carry",
    "rounds_per_step", "clients_per_chunk", "checkpoint_backend",
    "checkpoint_async", "compilation_cache_dir", "secure_agg", "fedbuff",
    "dump_norm_stats", "scaffold_device_controls", "scaffold_flush_freq",
    "ef_device_residuals", "ef_flush_freq",
    # resilience: seeded deterministic fault injection (dropout/straggler
    # faults fold into the fused round program; IO faults exercise the
    # checkpoint retry/fallback machinery; preempt_at_round drives the
    # kill/resume drill) and the checkpoint retry/backoff/escalation
    # policy — see docs/config_extensions.md and docs/RUNBOOK.md
    "chaos", "checkpoint_retry",
    # fluteflow: event-driven arrival plane (traffic/) — seeded traffic
    # traces decide WHO trains and WHEN aggregation fires (buffered
    # async with true traced staleness, or the sync barrier baseline);
    # see docs/config_extensions.md
    "traffic",
    # flutescope telemetry: round spans + Perfetto trace export, the
    # packed-stats device-metric bus, opt-in jax.profiler round windows,
    # and the NaN/round-time/checkpoint watchdogs — default off, zero
    # overhead when absent (docs/observability.md)
    "telemetry",
    # fluteshield screened aggregation: on-device NaN/Inf + norm-outlier
    # quarantine and Byzantine-robust aggregators (trimmed mean /
    # median) — default off; disabled is bit-identical to pre-fluteshield
    # behavior (docs/config_extensions.md)
    "robust",
    # cohort shape-bucketing: partition each round's cohort into a
    # config-bounded set of power-of-two step buckets and dispatch one
    # compact [K_b, S_b, B] grid per bucket + an on-device finalize,
    # instead of padding every client to the slowest one — default off;
    # per-client updates stay bit-identical to the monolithic grid
    # (docs/config_extensions.md, RUNBOOK "Tuning cohort buckets")
    "cohort_bucketing",
    # cross-client megabatching: within each step bucket, repack many
    # small clients' batches into device-saturating super-batch lanes
    # (a segment-carrying scan replaces the per-client vmap when the
    # per-bucket dispatch gate prices it cheaper) — default off;
    # requires cohort_bucketing (docs/config_extensions.md, RUNBOOK
    # "Closing the MFU gap")
    "megabatch",
    # megakernel local SGD: the opt-in pallas fused SGD apply
    # (docs/config_extensions.md)
    "megakernel",
    # fleet mode: million-client populations — O(cohort) cohort draws
    # (Floyd / weighted reservoir) and, with fused_carry, a fixed-
    # capacity device page pool + durable host backing store replacing
    # the [N, n_params] resident carry tables — default off; see
    # docs/config_extensions.md and RUNBOOK "Running a fleet-scale
    # population"
    "fleet",
    # precision policy: params/compute/stats dtypes for the client
    # inner loop — absent is the bit-identical f32 path; compute:
    # bfloat16 keeps f32 master params + f32 stats accumulators
    # (docs/config_extensions.md, RUNBOOK "Choosing a precision policy")
    "precision",
    "semisupervision", "updatable_names",
    "fedac_eta", "fedac_gamma", "fedac_alpha", "fedac_beta",
    "qffl_q",
    "personalization_init", "personalization_interp",
}

CLIENT_KEYS = {
    "type", "meta_learning", "copying_train_data", "do_profiling",
    "ignore_subtask", "num_skip_decoding", "desired_max_samples",
    "max_grad_norm", "freeze_layer", "data_config", "optimizer_config",
    "annealing_config", "fedprox_mu", "convex_model_interp",
    "meta_optimizer_config", "ss_config",
    # TPU-native extensions
    "num_epochs", "step_bucketing", "quant_thresh", "quant_threshold",
    "quant_bits", "quant_anneal", "updatable_layers",
    "semisupervision",
}

TOP_KEYS = {
    "model_config", "dp_config", "privacy_metrics_config", "strategy",
    "server_config", "client_config", "mesh_config", "task", "data_path",
    "output_path", "experiment",
}

# sections whose contents are free-form by design (plugin surfaces)
_FREEFORM = "model_config", "semisupervision", "augment", "mesh_config", \
    "nbest_task_scheduler", "ss_config", "experiment"

# ----------------------------------------------------------------------
# per-field type/range rules (the cerberus per-field ``type``/``min``/
# ``max`` declarations, reference core/schema.py): spec is
# ("bool" | "int" | "num", lo, hi) with inclusive bounds, None = open.
# Only fields with an unambiguous scalar contract are listed — fields
# with union types (num_clients_per_iteration int|"lo:hi") keep their
# bespoke checks in validate().
# ----------------------------------------------------------------------
SERVER_FIELD_SPECS = {
    "initial_lr_client": ("num", 0, None),
    "lr_decay_factor": ("num", 0, None),
    "softmax_beta": ("num", 0, None),
    "stale_prob": ("num", 0.0, 1.0),
    "initial_lr": ("num", 0, None),
    "max_grad_norm": ("num", 0, None),
    "initial_val": ("bool", None, None),
    "initial_rec": ("bool", None, None),
    "wantRL": ("bool", None, None),
    "fall_back_to_best_model": ("bool", None, None),
    "send_dicts": ("bool", None, None),
    "do_profiling": ("bool", None, None),
    "resume_from_checkpoint": ("bool", None, None),
    "scaffold_device_controls": ("bool", None, None),
    "dump_norm_stats": ("bool", None, None),
    "pipeline_depth": ("int", 0, None),
    "fused_carry": ("bool", None, None),
    "rounds_per_step": ("int", 1, None),
    "clients_per_chunk": ("int", 1, None),
    "model_backup_freq": ("int", 1, None),
    "scaffold_flush_freq": ("int", 1, None),
    "ef_device_residuals": ("bool", None, None),
    "ef_flush_freq": ("int", 1, None),
    "qffl_q": ("num", 0, None),
}

CLIENT_FIELD_SPECS = {
    "fedprox_mu": ("num", 0, None),
    "max_grad_norm": ("num", 0, None),
    "quant_anneal": ("num", 0, 1.0),
    # quantile of |g| (jnp.quantile q arg, ops/quantization.py): [0, 1]
    "quant_thresh": ("num", 0, 1.0),
    "convex_model_interp": ("num", 0.0, 1.0),
    "num_epochs": ("int", 1, None),
    "desired_max_samples": ("int", 0, None),
    "quant_bits": ("int", 1, 32),
    "copying_train_data": ("bool", None, None),
    "do_profiling": ("bool", None, None),
    "ignore_subtask": ("bool", None, None),
    "step_bucketing": ("bool", None, None),
}

DATASET_FIELD_SPECS = {
    "batch_size": ("int", 1, None),
    "desired_max_samples": ("int", 0, None),
    "num_workers": ("int", 0, None),
    "prefetch_factor": ("int", 1, None),
    "max_seq_length": ("int", 1, None),
    "max_num_words": ("int", 1, None),
    "max_samples_per_user": ("int", 1, None),
    "lazy_cache_users": ("int", 1, None),
    "device_resident": ("bool", None, None),
    "lazy": ("bool", None, None),
    "wantLogits": ("bool", None, None),
    "pin_memory": ("bool", None, None),
    "unsorted_batch": ("bool", None, None),
    "step_bucketing": ("bool", None, None),
    "length_bucketing": ("bool", None, None),
    "per_user_stats": ("bool", None, None),
}

OPTIMIZER_FIELD_SPECS = {
    "lr": ("num", 0, None),
    "momentum": ("num", 0, 1.0),
    "weight_decay": ("num", 0, None),
    "dampening": ("num", 0, 1.0),
    "eps": ("num", 0, None),
    "nesterov": ("bool", None, None),
    "amsgrad": ("bool", None, None),
}

ANNEALING_FIELD_SPECS = {
    "gamma": ("num", 0, None),
    "step_size": ("int", 1, None),
    "patience": ("int", 0, None),
    "factor": ("num", 0, None),
    "peak_lr": ("num", 0, None),
    "floor_lr": ("num", 0, None),
    "rampup_steps": ("int", 0, None),
    "hold_steps": ("int", 0, None),
    "decay_steps": ("int", 1, None),
}

DP_FIELD_SPECS = {
    # eps < 0 is the documented clip-only sentinel
    # (privacy/__init__.py::apply_local_dp) — numeric but unbounded
    "eps": ("num", None, None),
    "delta": ("num", 0.0, 1.0),
    "max_grad": ("num", 0, None),
    "max_weight": ("num", 0, None),
    "min_weight": ("num", 0, None),
    "weight_scaler": ("num", 0, None),
    "global_sigma": ("num", 0, None),
    "enable_local_dp": ("bool", None, None),
    "enable_global_dp": ("bool", None, None),
    "enable_prod": ("bool", None, None),
}


class SchemaError(ValueError):
    def __init__(self, errors: List[str]):
        self.errors = errors
        super().__init__("config schema violations:\n  " + "\n  ".join(errors))


def _check_enum(errors: List[str], raw: Dict[str, Any], path: str, key: str,
                allowed: List[str]) -> None:
    val = raw.get(key)
    if val is not None and val not in allowed:
        errors.append(f"{path}.{key}: {val!r} not in {allowed}")


def _check_unknown(errors: List[str], raw: Any, path: str,
                   known: Iterable[str]) -> None:
    """Flag keys outside ``known`` with a did-you-mean suggestion (the
    cerberus ``unknown field`` behavior, reference ``core/schema.py``)."""
    if not isinstance(raw, dict):
        return
    known = set(known)
    for key in raw:
        if key in known or key in _FREEFORM:
            continue
        hint = difflib.get_close_matches(str(key), known, n=1, cutoff=0.6)
        suggest = f" (did you mean {hint[0]!r}?)" if hint else ""
        errors.append(f"{path}.{key}: unknown key{suggest}")


def _check_fields(errors: List[str], raw: Any, path: str,
                  specs: Dict[str, tuple]) -> None:
    """Per-field type + inclusive-range checks (the cerberus ``type`` /
    ``min`` / ``max`` rules).  ``None`` values skip — optionality is the
    dataclass default's job, not the schema's."""
    if not isinstance(raw, dict):
        return
    for key, (kind, lo, hi) in specs.items():
        val = raw.get(key)
        if val is None:
            continue
        if kind == "bool":
            if not isinstance(val, bool):
                errors.append(f"{path}.{key}: must be a boolean, got "
                              f"{type(val).__name__}")
            continue
        # bool is an int subclass: a stray `true` must not pass as 1
        if isinstance(val, bool) or not isinstance(
                val, int if kind == "int" else (int, float)):
            want = "an integer" if kind == "int" else "a number"
            errors.append(f"{path}.{key}: must be {want}, got "
                          f"{type(val).__name__}")
            continue
        if (lo is not None or hi is not None) and val != val:
            # NaN compares False against any bound — reject it explicitly
            # or `stale_prob: .nan` would sail through a [0, 1] range
            errors.append(f"{path}.{key}: must be a finite number, got NaN")
            continue
        if lo is not None and val < lo:
            errors.append(f"{path}.{key}: must be >= {lo}, got {val}")
        if hi is not None and val > hi:
            errors.append(f"{path}.{key}: must be <= {hi}, got {val}")


def _check_optimizer(errors: List[str], raw: Any, path: str,
                     unknown: Optional[List[str]] = None) -> None:
    if not isinstance(raw, dict):
        return
    _check_enum(errors, raw, path, "type", ALLOWED_OPTIMIZERS)
    _check_unknown(unknown if unknown is not None else errors, raw, path,
                   OPTIMIZER_KEYS)
    _check_fields(errors, raw, path, OPTIMIZER_FIELD_SPECS)


def _check_annealing(errors: List[str], raw: Any, path: str,
                     unknown: Optional[List[str]] = None) -> None:
    if not isinstance(raw, dict):
        return
    _check_enum(errors, raw, path, "type", ALLOWED_ANNEALING)
    _check_unknown(unknown if unknown is not None else errors, raw, path,
                   ANNEALING_KEYS)
    _check_fields(errors, raw, path, ANNEALING_FIELD_SPECS)


def _check_data_config(errors: List[str], raw: Any, path: str) -> None:
    if not isinstance(raw, dict):
        return
    _check_unknown(errors, raw, path, DATACONFIG_KEYS)
    for split in ("train", "val", "test"):
        blk = raw.get(split)
        if isinstance(blk, dict):
            _check_unknown(errors, blk, f"{path}.{split}", DATASET_KEYS)


def _check_data_fields(errors: List[str], raw: Any, path: str) -> None:
    """Type/range rules for the per-split dataset blocks (always hard
    errors, unlike the unknown-key pass which can be downgraded)."""
    if not isinstance(raw, dict):
        return
    for split in ("train", "val", "test"):
        blk = raw.get(split)
        if isinstance(blk, dict):
            _check_fields(errors, blk, f"{path}.{split}",
                          DATASET_FIELD_SPECS)


def validate(raw: Dict[str, Any], strict: Optional[bool] = None) -> None:
    """Validate a raw (YAML-loaded) config dict in place.

    Required sections follow reference ``core/schema.py``: ``model_config``
    and ``server_config`` are required; everything else optional with
    defaults supplied by the dataclass tree.  Unknown keys in structured
    sections are errors (``strict=True``, the default) or warnings
    (``strict=False`` / env ``MSRFLUTE_ALLOW_UNKNOWN=1``).
    """
    if strict is None:
        strict = not os.environ.get("MSRFLUTE_ALLOW_UNKNOWN")
    errors: List[str] = []
    unknown: List[str] = []

    if "model_config" not in raw:
        errors.append("model_config: required section missing")
    elif not isinstance(raw["model_config"], dict):
        errors.append("model_config: must be a mapping")
    elif "model_type" not in raw["model_config"]:
        errors.append("model_config.model_type: required key missing")

    if "server_config" not in raw:
        errors.append("server_config: required section missing")

    strategy = raw.get("strategy")
    if strategy is not None and strategy not in ALLOWED_STRATEGIES:
        errors.append(f"strategy: {strategy!r} not in {ALLOWED_STRATEGIES}")
    # cross-field: secure_agg options without the strategy would be
    # SILENTLY ignored — the user believes masking is on when per-client
    # payloads flow unmasked (the exact quiet failure this schema exists
    # to prevent)
    sc_raw = raw.get("server_config")
    if isinstance(sc_raw, dict) and sc_raw.get("secure_agg") is not None \
            and str(strategy or "fedavg").lower() not in (
                "secure_agg", "secagg", "secureagg"):
        errors.append(
            "server_config.secure_agg is set but strategy is "
            f"{strategy!r} — only strategy: secure_agg reads it; "
            "payloads would flow UNMASKED")
    # same quiet-failure rule for fedbuff: its options under another
    # strategy would leave the run fully synchronous while the user
    # believes they are simulating async staleness
    if isinstance(sc_raw, dict) and sc_raw.get("fedbuff") is not None \
            and str(strategy or "fedavg").lower() != "fedbuff":
        errors.append(
            "server_config.fedbuff is set but strategy is "
            f"{strategy!r} — only strategy: fedbuff reads it; the run "
            "would be fully synchronous")

    _check_unknown(unknown, raw, "config", TOP_KEYS)

    sc = raw.get("server_config")
    if isinstance(sc, dict):
        _check_enum(errors, sc, "server_config", "type", ALLOWED_SERVER_TYPES)
        _check_enum(errors, sc, "server_config", "personalization_init",
                    ["global", "random", "initial"])
        _check_enum(errors, sc, "server_config", "personalization_interp",
                    ["probs", "logprobs"])
        _check_unknown(unknown, sc, "server_config", SERVER_KEYS)
        _check_optimizer(errors, sc.get("optimizer_config"), "server_config.optimizer_config", unknown)
        _check_annealing(errors, sc.get("annealing_config"), "server_config.annealing_config", unknown)
        _check_data_config(unknown, sc.get("data_config"), "server_config.data_config")
        _check_fields(errors, sc, "server_config", SERVER_FIELD_SPECS)
        _check_data_fields(errors, sc.get("data_config"),
                           "server_config.data_config")
        replay = sc.get("server_replay_config")
        if isinstance(replay, dict):
            _check_unknown(unknown, replay, "server_config.server_replay_config",
                           SERVER_REPLAY_KEYS)
            _check_optimizer(errors, replay.get("optimizer_config"),
                             "server_config.server_replay_config.optimizer_config",
                             unknown)
        rl = sc.get("RL")
        if isinstance(rl, dict):
            _check_unknown(unknown, rl, "server_config.RL", RL_KEYS)
        chaos = sc.get("chaos")
        if isinstance(chaos, dict):
            _check_unknown(unknown, chaos, "server_config.chaos",
                           CHAOS_KEYS)
            _check_fields(errors, chaos, "server_config.chaos",
                          CHAOS_FIELD_SPECS)
            # the spec table's ranges are inclusive; ChaosSchedule
            # requires these strictly positive, and the validation layer
            # must not bless a config the constructor will refuse
            for key in ("corrupt_scale_factor", "corrupt_sign_flip_scale"):
                val = chaos.get(key)
                if isinstance(val, (int, float)) and \
                        not isinstance(val, bool) and float(val) == 0.0:
                    errors.append(
                        f"server_config.chaos.{key}: must be > 0")
            infra = chaos.get("infra")
            if infra is not None and not isinstance(infra, dict):
                errors.append(
                    "server_config.chaos.infra: must be a mapping of "
                    "infrastructure fault rates (see "
                    "docs/config_extensions.md), got "
                    f"{type(infra).__name__}")
            if isinstance(infra, dict):
                _check_unknown(unknown, infra,
                               "server_config.chaos.infra",
                               CHAOS_INFRA_KEYS)
                _check_fields(errors, infra,
                              "server_config.chaos.infra",
                              CHAOS_INFRA_FIELD_SPECS)
        robust = sc.get("robust")
        if robust is not None and not isinstance(robust, dict):
            errors.append(
                "server_config.robust: must be a mapping (see "
                "docs/config_extensions.md), got "
                f"{type(robust).__name__}")
        if isinstance(robust, dict):
            _check_unknown(unknown, robust, "server_config.robust",
                           ROBUST_KEYS)
            _check_fields(errors, robust, "server_config.robust",
                          ROBUST_FIELD_SPECS)
            _check_enum(errors, robust, "server_config.robust",
                        "aggregator", ALLOWED_ROBUST_AGGREGATORS)
            # valid domain is {0} ∪ [1, inf) — a union the inclusive
            # spec table cannot express; Shield.__init__ enforces the
            # same invariant, this keeps config load from blessing a
            # value server construction will refuse
            nm = robust.get("norm_multiplier")
            if isinstance(nm, (int, float)) and not isinstance(nm, bool) \
                    and 0.0 < float(nm) < 1.0:
                errors.append(
                    "server_config.robust.norm_multiplier: must be >= 1 "
                    "(it scales the cohort's median payload norm; < 1 "
                    "would quarantine the median client itself) or 0 to "
                    "disable the norm screen")
            # the range table is inclusive but Shield requires < 0.5
            tf = robust.get("trim_fraction")
            if isinstance(tf, (int, float)) and not isinstance(tf, bool) \
                    and float(tf) == 0.5:
                errors.append(
                    "server_config.robust.trim_fraction: must be < 0.5 "
                    "— trimming half or more from each side leaves "
                    "nothing to average")
            # quiet-failure rule (the secure_agg/fedbuff discipline): a
            # robust block under a strategy whose combine it cannot
            # screen means the user believes the cohort is defended
            # while poisoned payloads aggregate untouched
            if robust.get("enable", True) and \
                    str(strategy or "fedavg").lower() not in (
                        "fedavg", "fedprox",
                        "secure_agg", "secagg", "secureagg"):
                errors.append(
                    "server_config.robust is set but strategy is "
                    f"{strategy!r} — screened aggregation plugs into the "
                    "fedavg/fedprox combine (or secure_agg's submitted-"
                    "norm screening); payloads would aggregate "
                    "UNSCREENED")
            if robust.get("enable", True) and \
                    str(robust.get("aggregator", "mean")) in (
                        "trimmed_mean", "median") and \
                    str(strategy or "fedavg").lower() in (
                        "secure_agg", "secagg", "secureagg"):
                errors.append(
                    "server_config.robust.aggregator: "
                    f"{robust.get('aggregator')!r} sorts per-client "
                    "payload coordinates, but secure_agg submissions "
                    "are masked int32 group elements — use aggregator: "
                    "mean (submitted-norm screening still applies)")
        sa = sc.get("secure_agg")
        if isinstance(sa, dict):
            _check_unknown(unknown, sa, "server_config.secure_agg",
                           SECURE_AGG_KEYS)
            _check_fields(errors, sa, "server_config.secure_agg",
                          SECURE_AGG_FIELD_SPECS)
            graph = sa.get("graph")
            if graph is not None and str(graph).lower() not in ("full",
                                                                "log"):
                errors.append(
                    "server_config.secure_agg.graph: must be 'full' or "
                    f"'log', got {graph!r}")
            clip = sa.get("clip")
            if isinstance(clip, (int, float)) and \
                    not isinstance(clip, bool) and float(clip) <= 0.0:
                errors.append(
                    "server_config.secure_agg.clip: must be > 0")
        cb = sc.get("cohort_bucketing")
        if cb is not None and not isinstance(cb, dict):
            errors.append(
                "server_config.cohort_bucketing: must be a mapping (see "
                "docs/config_extensions.md), got "
                f"{type(cb).__name__}")
        if isinstance(cb, dict):
            _check_unknown(unknown, cb, "server_config.cohort_bucketing",
                           COHORT_BUCKETING_KEYS)
            _check_fields(errors, cb, "server_config.cohort_bucketing",
                          COHORT_BUCKETING_FIELD_SPECS)
            bounds = cb.get("boundaries")
            if bounds is not None:
                # bespoke: a strictly-increasing positive-int list — a
                # non-increasing list would assign clients to a bucket
                # too small for their data (silent truncation), which
                # the server also refuses; validation must not bless it
                if not isinstance(bounds, (list, tuple)) or not bounds:
                    errors.append(
                        "server_config.cohort_bucketing.boundaries: "
                        "must be a non-empty list of step counts")
                elif any(isinstance(b, bool) or not isinstance(b, int)
                         or b < 1 for b in bounds):
                    errors.append(
                        "server_config.cohort_bucketing.boundaries: "
                        "every boundary must be a positive integer, "
                        f"got {list(bounds)!r}")
                elif any(y <= x for x, y in zip(bounds, bounds[1:])):
                    errors.append(
                        "server_config.cohort_bucketing.boundaries: "
                        f"must be strictly increasing, got "
                        f"{list(bounds)!r}")
                mb = cb.get("max_buckets")
                if isinstance(mb, int) and not isinstance(mb, bool) and \
                        isinstance(bounds, (list, tuple)) and \
                        len(bounds) > mb:
                    errors.append(
                        "server_config.cohort_bucketing: "
                        f"{len(bounds)} boundaries exceed "
                        f"max_buckets={mb}")
        fl = sc.get("fleet")
        if fl is not None and not isinstance(fl, dict):
            errors.append(
                "server_config.fleet: must be a mapping (see "
                "docs/config_extensions.md), got "
                f"{type(fl).__name__}")
        if isinstance(fl, dict):
            _check_unknown(unknown, fl, "server_config.fleet",
                           FLEET_KEYS)
            _check_fields(errors, fl, "server_config.fleet",
                          FLEET_FIELD_SPECS)
            _check_enum(errors, fl, "server_config.fleet", "sampling",
                        ALLOWED_FLEET_SAMPLING)
        mgb = sc.get("megabatch")
        if mgb is not None and not isinstance(mgb, dict):
            errors.append(
                "server_config.megabatch: must be a mapping (see "
                "docs/config_extensions.md), got "
                f"{type(mgb).__name__}")
        if isinstance(mgb, dict):
            _check_unknown(unknown, mgb, "server_config.megabatch",
                           MEGABATCH_KEYS)
            _check_fields(errors, mgb, "server_config.megabatch",
                          MEGABATCH_FIELD_SPECS)
            _cb_blk = sc.get("cohort_bucketing") or {}
            _cb_on = bool(_cb_blk) and (not isinstance(_cb_blk, dict)
                                        or _cb_blk.get("enable", True))
            if mgb.get("enable", True) and not _cb_on:
                # decidable at config load (the quiet-failure rule):
                # the tape geometry is a per-bucket quantity, so an
                # unbucketed run has nothing to repack
                errors.append(
                    "server_config.megabatch requires "
                    "server_config.cohort_bucketing — the super-batch "
                    "tape repacks per-bucket grids; add the "
                    "cohort_bucketing block or drop megabatch")
            if mgb.get("enable", True) and \
                    str(strategy or "fedavg").lower() == "fedlabels":
                # also decidable at config load: fedlabels' dual
                # sup/unsup training loop steps outside the
                # client_update contract the lane scan reproduces
                errors.append(
                    "server_config.megabatch is set but strategy is "
                    "'fedlabels' — its dual sup/unsup loop steps "
                    "outside the client_update contract the lane scan "
                    "reproduces; drop megabatch or change strategy")
        mk = sc.get("megakernel")
        if mk is not None and not isinstance(mk, dict):
            errors.append(
                "server_config.megakernel: must be a mapping (see "
                "docs/config_extensions.md), got "
                f"{type(mk).__name__}")
        if isinstance(mk, dict):
            _check_unknown(unknown, mk, "server_config.megakernel",
                           MEGAKERNEL_KEYS)
            _check_fields(errors, mk, "server_config.megakernel",
                          MEGAKERNEL_FIELD_SPECS)
        traffic = sc.get("traffic")
        if traffic is not None and not isinstance(traffic, dict):
            errors.append(
                "server_config.traffic: must be a mapping (see "
                "docs/config_extensions.md), got "
                f"{type(traffic).__name__}")
        if isinstance(traffic, dict):
            _check_unknown(unknown, traffic, "server_config.traffic",
                           TRAFFIC_KEYS)
            _check_fields(errors, traffic, "server_config.traffic",
                          TRAFFIC_FIELD_SPECS)
            _check_enum(errors, traffic, "server_config.traffic",
                        "mode", ALLOWED_TRAFFIC_MODES)
            _check_enum(errors, traffic, "server_config.traffic",
                        "trace", ALLOWED_TRAFFIC_TRACES)
            lo, hi = traffic.get("duration_lo"), traffic.get("duration_hi")
            if isinstance(lo, int) and isinstance(hi, int) and hi < lo:
                errors.append(
                    "server_config.traffic: duration_hi "
                    f"({hi}) < duration_lo ({lo})")
            classes = traffic.get("classes")
            if classes is not None and (
                    not isinstance(classes, (list, tuple)) or
                    not all(isinstance(c, dict) for c in classes)):
                errors.append(
                    "server_config.traffic.classes: expected a list of "
                    "per-class mappings (fraction/rate/window/phase/"
                    f"duration_scale), got {classes!r}")
            if traffic.get("enable", True):
                # decidable at config load (the quiet-failure rule):
                # the liveness floor can never be met when it exceeds
                # the fire size — every round would abort
                _sa_blk = sc.get("secure_agg") or {}
                if isinstance(_sa_blk, dict) and \
                        _sa_blk.get("enable", True):
                    ms = _sa_blk.get("min_survivors")
                    bs = traffic.get("buffer_size",
                                     sc.get("num_clients_per_iteration"))
                    if isinstance(ms, int) and isinstance(bs, int) and \
                            ms > bs:
                        errors.append(
                            "server_config.secure_agg.min_survivors "
                            f"({ms}) exceeds traffic.buffer_size ({bs}) "
                            "— a buffered fire delivers exactly "
                            "buffer_size clients, so every round would "
                            "abort below the liveness floor")
        prec = sc.get("precision")
        if prec is not None and not isinstance(prec, dict):
            errors.append(
                "server_config.precision: must be a mapping (see "
                "docs/config_extensions.md), got "
                f"{type(prec).__name__}")
        if isinstance(prec, dict):
            _check_unknown(unknown, prec, "server_config.precision",
                           PRECISION_KEYS)
            for key in ("params", "compute", "stats"):
                _check_enum(errors, prec, "server_config.precision", key,
                            ALLOWED_PRECISION_DTYPES)
            en = prec.get("enable")
            if en is not None and not isinstance(en, bool):
                errors.append(
                    "server_config.precision.enable: expected bool, got "
                    f"{en!r}")
        ckpt_retry = sc.get("checkpoint_retry")
        if isinstance(ckpt_retry, dict):
            _check_unknown(unknown, ckpt_retry,
                           "server_config.checkpoint_retry",
                           CHECKPOINT_RETRY_KEYS)
            _check_fields(errors, ckpt_retry,
                          "server_config.checkpoint_retry",
                          CHECKPOINT_RETRY_FIELD_SPECS)
        telemetry = sc.get("telemetry")
        if telemetry is not None and not isinstance(telemetry, dict):
            errors.append(
                "server_config.telemetry: must be a mapping "
                f"(see docs/observability.md), got "
                f"{type(telemetry).__name__}")
        if isinstance(telemetry, dict):
            _check_unknown(unknown, telemetry, "server_config.telemetry",
                           TELEMETRY_KEYS)
            _check_fields(errors, telemetry, "server_config.telemetry",
                          TELEMETRY_FIELD_SPECS)
            if telemetry.get("profile_rounds") is not None:
                # union type (int | "lo:hi" | [lo, hi]) — reuse the one
                # parser the profiler itself runs, so config load and
                # round `lo` can never disagree about validity
                from .telemetry.profiling import parse_profile_rounds
                try:
                    parse_profile_rounds(telemetry["profile_rounds"])
                except (ValueError, TypeError) as exc:
                    errors.append(
                        f"server_config.telemetry.profile_rounds: {exc}")
            wd = telemetry.get("watchdog")
            if wd is not None and not isinstance(wd, dict):
                # a bare string like `watchdog: abort` would otherwise
                # sail through here and die cryptically in
                # Watchdog.__init__ at server construction
                errors.append(
                    "server_config.telemetry.watchdog: must be a mapping "
                    f"of detector knobs, got {type(wd).__name__}")
            if isinstance(wd, dict):
                _check_unknown(unknown, wd,
                               "server_config.telemetry.watchdog",
                               WATCHDOG_KEYS)
                _check_fields(errors, wd,
                              "server_config.telemetry.watchdog",
                              WATCHDOG_FIELD_SPECS)
                for key in ("nan_loss", "round_time_action",
                            "ckpt_failure_action",
                            "quarantine_rate_action",
                            "recompile_storm_action", "stall_action",
                            "rss_leak_action",
                            "throughput_drift_action"):
                    _check_enum(errors, wd,
                                "server_config.telemetry.watchdog", key,
                                ALLOWED_WATCHDOG_ACTIONS)
        # pipeline_depth keeps a bespoke upper bound the inclusive range
        # table cannot document: the donated ring costs HBM per slot and
        # the old engine-side min(depth, 1) clamp silently ignored the
        # config — refusal with the bound beats clamping
        pd = sc.get("pipeline_depth")
        if isinstance(pd, int) and not isinstance(pd, bool) and \
                pd > MAX_PIPELINE_DEPTH:
            errors.append(
                f"server_config.pipeline_depth: {pd} exceeds the "
                f"supported maximum {MAX_PIPELINE_DEPTH} — each depth "
                "slot keeps a full round chunk's staged inputs and "
                "packed stats resident in device memory, and depth past "
                "the host-tail/device-round ratio buys nothing; lower "
                "it (see docs/RUNBOOK.md pipeline tuning)")
        ncpi = sc.get("num_clients_per_iteration")
        if ncpi is not None and not isinstance(ncpi, int):
            if not (isinstance(ncpi, str) and ":" in ncpi):
                errors.append(
                    "server_config.num_clients_per_iteration: must be int or 'lo:hi'")
        for key in ("max_iteration", "val_freq", "rec_freq"):
            val = sc.get(key)
            if val is not None and (not isinstance(val, int) or val < 0):
                errors.append(f"server_config.{key}: must be a non-negative int")

    cc = raw.get("client_config")
    if isinstance(cc, dict):
        _check_unknown(unknown, cc, "client_config", CLIENT_KEYS)
        _check_optimizer(errors, cc.get("optimizer_config"), "client_config.optimizer_config", unknown)
        if cc.get("annealing_config") is not None:
            _check_annealing(errors, cc.get("annealing_config"), "client_config.annealing_config", unknown)
        _check_data_config(unknown, cc.get("data_config"), "client_config.data_config")
        _check_fields(errors, cc, "client_config", CLIENT_FIELD_SPECS)
        _check_data_fields(errors, cc.get("data_config"),
                           "client_config.data_config")

    dp = raw.get("dp_config")
    if isinstance(dp, dict):
        _check_unknown(unknown, dp, "dp_config", DP_KEYS)
        ac = dp.get("adaptive_clipping")
        if isinstance(ac, dict):
            _check_unknown(unknown, ac, "dp_config.adaptive_clipping",
                           ADAPTIVE_CLIP_KEYS)
        _check_fields(errors, dp, "dp_config", DP_FIELD_SPECS)

    pm = raw.get("privacy_metrics_config")
    if isinstance(pm, dict):
        _check_unknown(unknown, pm, "privacy_metrics_config",
                       PRIVACY_METRICS_KEYS)
        _check_optimizer(errors, pm.get("attacker_optimizer_config"),
                         "privacy_metrics_config.attacker_optimizer_config",
                         unknown)

    if unknown:
        if strict:
            errors.extend(unknown)
        else:
            warnings.warn("config has unknown keys (MSRFLUTE_ALLOW_UNKNOWN "
                          "set; would be errors otherwise):\n  "
                          + "\n  ".join(unknown), stacklevel=2)
    if errors:
        raise SchemaError(errors)


# ----------------------------------------------------------------------
# applied-defaults report (reference core/config.py:771-779 prints the
# diff between the user YAML and the config with defaults applied)
# ----------------------------------------------------------------------
def applied_defaults(raw: Dict[str, Any], cfg: Any,
                     _path: str = "") -> Dict[str, Any]:
    """Return ``{dotted.path: default}`` for every structured field the user
    did NOT set, i.e. the defaults the framework filled in.  ``cfg`` is the
    built dataclass tree; ``raw`` the original YAML dict."""
    import dataclasses

    out: Dict[str, Any] = {}
    if not dataclasses.is_dataclass(cfg):
        return out
    raw = raw if isinstance(raw, dict) else {}
    for f in dataclasses.fields(cfg):
        if f.name == "extra":
            continue
        val = getattr(cfg, f.name)
        path = f"{_path}.{f.name}" if _path else f.name
        if dataclasses.is_dataclass(val):
            out.update(applied_defaults(raw.get(f.name), val, path))
        elif f.name not in raw and val is not None:
            out[path] = val
    return out
