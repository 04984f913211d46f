"""Typed configuration tree for msrflute_tpu.

Parity target: reference ``core/config.py`` (dataclass tree with
``MutableMapping`` dict-compat and dotted ``lookup``, ``core/config.py:39-79``)
plus ``core/schema.py`` (cerberus schema).  We keep FLUTE's six top-level
sections and key vocabulary (``doc/sphinx/scenarios.rst:137-145``) so that
reference YAML configs translate mechanically:

    model_config, dp_config, privacy_metrics_config, strategy,
    server_config, client_config

Differences from the reference, by design:

- Validation is a hand-rolled schema (:mod:`msrflute_tpu.schema`) rather than
  cerberus — the reference loads its schema with ``eval(open(...))``
  (``core/config.py:766-769``); we use an importable module.
- Unknown keys are preserved in an ``extra`` mapping on each section instead
  of being dropped, because task plugins read free-form model parameters.
"""

from __future__ import annotations

import copy
import dataclasses
import os
from collections.abc import MutableMapping
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import yaml


class Config(MutableMapping):
    """Dict-compatible config base with dotted-path ``lookup``.

    Mirrors the ergonomics of reference ``core/config.py:39-79``: sections
    behave both as attributes and as mapping items, and
    ``cfg.lookup('server_config.optimizer_config.lr')`` resolves nested keys,
    returning ``default`` when any component is missing.
    """

    def lookup(self, path: str, default: Any = None) -> Any:
        node: Any = self
        for part in path.split("."):
            if node is None:
                return default
            if isinstance(node, MutableMapping) or dataclasses.is_dataclass(node):
                try:
                    node = node[part] if isinstance(node, MutableMapping) else getattr(node, part)
                except (KeyError, AttributeError):
                    return default
            elif isinstance(node, dict):
                node = node.get(part, default)
            else:
                node = getattr(node, part, None)
                if node is None:
                    return default
        return default if node is None else node

    # MutableMapping protocol over dataclass fields + extras ------------
    def _field_names(self) -> List[str]:
        return [f.name for f in dataclasses.fields(self)]  # type: ignore[arg-type]

    def __getitem__(self, key: str) -> Any:
        if key in self._field_names():
            return getattr(self, key)
        extra = getattr(self, "extra", None)
        if extra is not None and key in extra:
            return extra[key]
        raise KeyError(key)

    def __setitem__(self, key: str, value: Any) -> None:
        if key in self._field_names():
            setattr(self, key, value)
        else:
            getattr(self, "extra")[key] = value

    def __delitem__(self, key: str) -> None:
        if key in self._field_names():
            setattr(self, key, None)
        else:
            del getattr(self, "extra")[key]

    def __iter__(self):
        for name in self._field_names():
            if name != "extra" and getattr(self, name) is not None:
                yield name
        for key in getattr(self, "extra", {}):
            yield key

    def __len__(self) -> int:
        return sum(1 for _ in self)

    def get(self, key: str, default: Any = None) -> Any:
        try:
            value = self[key]
        except KeyError:
            return default
        return default if value is None else value

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for key in self:
            value = self[key]
            out[key] = value.to_dict() if isinstance(value, Config) else copy.deepcopy(value)
        return out


def _take(raw: Dict[str, Any], known: List[str]) -> Dict[str, Any]:
    """Split ``raw`` into kwargs for known fields; the rest goes to extra."""
    kwargs = {k: raw[k] for k in known if k in raw}
    kwargs["extra"] = {k: copy.deepcopy(v) for k, v in raw.items() if k not in known}
    return kwargs


@dataclass
class OptimizerConfig(Config):
    """Optimizer settings (reference ``core/config.py`` OptimizerConfig;
    allowed types from ``core/schema.py:90``)."""

    type: str = "sgd"
    lr: float = 0.01
    momentum: float = 0.0
    nesterov: bool = False
    weight_decay: float = 0.0
    amsgrad: bool = False
    eps: float = 1e-8
    betas: Optional[List[float]] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, raw: Optional[Dict[str, Any]]) -> "OptimizerConfig":
        if raw is None:
            return cls()
        return cls(**_take(dict(raw), [
            "type", "lr", "momentum", "nesterov", "weight_decay", "amsgrad",
            "eps", "betas"]))


@dataclass
class AnnealingConfig(Config):
    """LR-annealing settings (reference ``utils/utils.py:151-224``)."""

    type: str = "step_lr"
    step_interval: str = "epoch"
    step_size: int = 1
    gamma: float = 1.0
    milestones: Optional[List[int]] = None
    # val_loss / ReduceLROnPlateau mode:
    patience: int = 10
    factor: float = 0.1
    # rampup-keep-expdecay-keep schedule:
    peak_lr: Optional[float] = None
    floor_lr: Optional[float] = None
    rampup_steps: int = 0
    hold_steps: int = 0
    decay_steps: int = 1
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, raw: Optional[Dict[str, Any]]) -> "AnnealingConfig":
        if raw is None:
            return cls()
        return cls(**_take(dict(raw), [
            "type", "step_interval", "step_size", "gamma", "milestones",
            "patience", "factor", "peak_lr", "floor_lr", "rampup_steps",
            "hold_steps", "decay_steps"]))


@dataclass
class DatasetConfig(Config):
    """One split's data settings (reference DataConfig per-split blocks)."""

    batch_size: int = 32
    loader_type: str = "auto"
    list_of_train_data: Optional[str] = None
    test_data: Optional[str] = None
    val_data: Optional[str] = None
    train_data: Optional[str] = None
    train_data_server: Optional[str] = None
    vocab_dict: Optional[str] = None
    pin_memory: bool = True
    num_workers: int = 0
    desired_max_samples: Optional[int] = None
    max_batch_size: Optional[int] = None
    max_num_words: Optional[int] = None
    max_seq_length: Optional[int] = None
    min_words_per_utt: Optional[int] = None
    num_frames: Optional[int] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, raw: Optional[Dict[str, Any]]) -> "DatasetConfig":
        if raw is None:
            return cls()
        return cls(**_take(dict(raw), [
            "batch_size", "loader_type", "list_of_train_data", "test_data",
            "val_data", "train_data", "train_data_server", "vocab_dict",
            "pin_memory", "num_workers", "desired_max_samples",
            "max_batch_size", "max_num_words", "max_seq_length",
            "min_words_per_utt", "num_frames"]))


@dataclass
class DataConfig(Config):
    """train/val/test dataset triple (reference DataConfig)."""

    train: DatasetConfig = field(default_factory=DatasetConfig)
    val: DatasetConfig = field(default_factory=DatasetConfig)
    test: DatasetConfig = field(default_factory=DatasetConfig)
    num_clients: Optional[int] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, raw: Optional[Dict[str, Any]]) -> "DataConfig":
        if raw is None:
            return cls()
        raw = dict(raw)
        return cls(
            train=DatasetConfig.from_dict(raw.pop("train", None)),
            val=DatasetConfig.from_dict(raw.pop("val", None)),
            test=DatasetConfig.from_dict(raw.pop("test", None)),
            num_clients=raw.pop("num_clients", None),
            extra=raw,
        )


@dataclass
class ModelConfig(Config):
    """Model selection + free-form model params (reference ModelConfig).

    ``model_type`` names a class in the task plugin's ``model.py``
    (reference ``doc/sphinx/scenarios.rst:96-106``); here it names an entry
    in :mod:`msrflute_tpu.models.registry` or a plugin module.
    """

    model_type: str = "LR"
    model_folder: Optional[str] = None
    pretrained_model_path: Optional[str] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, raw: Optional[Dict[str, Any]]) -> "ModelConfig":
        if raw is None:
            return cls()
        return cls(**_take(dict(raw), [
            "model_type", "model_folder", "pretrained_model_path"]))


@dataclass
class DPConfig(Config):
    """Differential-privacy settings (reference ``core/schema.py`` dp_config
    block; consumed by ``extensions/privacy/__init__.py:128-201``)."""

    enable_local_dp: bool = False
    enable_global_dp: bool = False
    eps: float = -1.0            # local epsilon; eps < 0 => clip-only mode
    delta: float = 1e-7
    max_grad: float = 1.0        # L2 clip bound for the flattened update
    max_weight: float = 100.0    # aggregation-weight clip ceiling
    min_weight: float = 0.0
    weight_scaler: float = 1.0   # scale applied to weight before noising
    global_sigma: float = 0.0    # server-side noise multiplier
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, raw: Optional[Dict[str, Any]]) -> "DPConfig":
        if raw is None:
            return cls()
        return cls(**_take(dict(raw), [
            "enable_local_dp", "enable_global_dp", "eps", "delta", "max_grad",
            "max_weight", "min_weight", "weight_scaler", "global_sigma"]))


@dataclass
class PrivacyMetricsConfig(Config):
    """Privacy-attack metric settings (reference privacy_metrics_config,
    consumed at ``core/client.py:466-508``)."""

    apply_metrics: bool = False
    apply_indices_extraction: bool = False
    allowed_word_rank: int = 9000
    apply_leakage_metric: bool = False
    max_leakage: float = 30.0
    max_allowed_leakage: float = 3.0
    adaptive_leakage_threshold: float = 0.0
    is_leakage_weighted: bool = False
    attacker_optimizer_config: OptimizerConfig = field(default_factory=OptimizerConfig)
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, raw: Optional[Dict[str, Any]]) -> "PrivacyMetricsConfig":
        if raw is None:
            return cls()
        raw = dict(raw)
        att = OptimizerConfig.from_dict(raw.pop("attacker_optimizer_config", None))
        out = cls(**_take(raw, [
            "apply_metrics", "apply_indices_extraction", "allowed_word_rank",
            "apply_leakage_metric", "max_leakage", "max_allowed_leakage",
            "adaptive_leakage_threshold", "is_leakage_weighted"]))
        out.attacker_optimizer_config = att
        return out


@dataclass
class ServerReplayConfig(Config):
    """Server-side replay training (reference ServerReplayConfig,
    ``core/server.py:429-442``)."""

    server_iterations: int = 1
    optimizer_config: OptimizerConfig = field(default_factory=OptimizerConfig)
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, raw: Optional[Dict[str, Any]]) -> Optional["ServerReplayConfig"]:
        if raw is None:
            return None
        raw = dict(raw)
        opt = OptimizerConfig.from_dict(raw.pop("optimizer_config", None))
        out = cls(**_take(raw, ["server_iterations"]))
        out.optimizer_config = opt
        return out


@dataclass
class RLConfig(Config):
    """RL meta-aggregator settings (reference RLConfig, ``extensions/RL``)."""

    marginal_update_RL: bool = True
    RL_path: Optional[str] = None
    RL_path_global: bool = True
    model_descriptor_RL: str = "marginalUpdate"
    network_params: Optional[List[int]] = None
    initial_epsilon: float = 0.5
    final_epsilon: float = 0.0001
    epsilon_gamma: float = 0.90
    max_replay_memory_size: int = 1000
    minibatch_size: int = 16
    gamma: float = 0.99
    optimizer_config: OptimizerConfig = field(default_factory=OptimizerConfig)
    annealing_config: AnnealingConfig = field(default_factory=AnnealingConfig)
    wantLSTM: bool = False
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, raw: Optional[Dict[str, Any]]) -> Optional["RLConfig"]:
        if raw is None:
            return None
        raw = dict(raw)
        opt = OptimizerConfig.from_dict(raw.pop("optimizer_config", None))
        ann = AnnealingConfig.from_dict(raw.pop("annealing_config", None))
        out = cls(**_take(raw, [
            "marginal_update_RL", "RL_path", "RL_path_global",
            "model_descriptor_RL", "network_params", "initial_epsilon",
            "final_epsilon", "epsilon_gamma", "max_replay_memory_size",
            "minibatch_size", "gamma", "wantLSTM"]))
        out.optimizer_config = opt
        out.annealing_config = ann
        return out


@dataclass
class ServerConfig(Config):
    """Server round-loop settings (reference ServerConfig,
    ``core/server.py:48-181``)."""

    type: str = "optimization"
    max_iteration: int = 100
    num_clients_per_iteration: Any = 10   # int or "lo:hi" random range (core/server.py:284-291)
    initial_lr_client: float = 0.01
    lr_decay_factor: float = 1.0
    val_freq: int = 20
    rec_freq: int = 20
    initial_val: bool = True
    initial_rec: bool = False
    best_model_criterion: str = "loss"
    fall_back_to_best_model: bool = False
    model_backup_freq: int = 100
    resume_from_checkpoint: bool = False
    send_dicts: bool = False
    max_grad_norm: Optional[float] = None
    do_profiling: bool = False
    wantRL: bool = False
    aggregate_median: Optional[str] = None   # 'softmax' => DGA weighting
    softmax_beta: float = 1.0
    initial_lr: float = 0.0
    weight_train_loss: str = "train_loss"
    stale_prob: float = 0.0
    num_skip_decoding: int = -1
    data_config: DataConfig = field(default_factory=DataConfig)
    optimizer_config: OptimizerConfig = field(default_factory=OptimizerConfig)
    annealing_config: AnnealingConfig = field(default_factory=AnnealingConfig)
    server_replay_config: Optional[ServerReplayConfig] = None
    RL: Optional[RLConfig] = None
    nbest_task_scheduler: Optional[Dict[str, Any]] = None
    # TPU-native resilience extensions (no reference equivalent):
    # seeded deterministic fault injection (resilience/chaos.py) and the
    # checkpoint retry/backoff/escalation policy
    # (resilience/integrity.py::RetryPolicy) — both free-form dicts whose
    # keys the schema validates (schema.CHAOS_KEYS /
    # CHECKPOINT_RETRY_KEYS)
    chaos: Optional[Dict[str, Any]] = None
    checkpoint_retry: Optional[Dict[str, Any]] = None
    # flutescope telemetry (telemetry/): spans + trace export, the
    # device-metric bus, opt-in jax.profiler windows, and watchdogs —
    # free-form dict validated by schema.TELEMETRY_KEYS /
    # WATCHDOG_KEYS; absent (the default) means telemetry fully off
    telemetry: Optional[Dict[str, Any]] = None
    # fluteshield screened aggregation (robust/): on-device NaN/Inf +
    # norm-outlier quarantine and Byzantine-robust aggregator variants
    # (strategies/robust.py) — free-form dict validated by
    # schema.ROBUST_KEYS; absent (the default) is the firewall path:
    # the exact pre-fluteshield round program
    robust: Optional[Dict[str, Any]] = None
    # cohort shape-bucketing (engine/round.py + data/batching.py):
    # partition each round's cohort into power-of-two step buckets and
    # dispatch one compact grid per bucket instead of padding every
    # client to the slowest one — free-form dict validated by
    # schema.COHORT_BUCKETING_KEYS; absent (the default) keeps the
    # monolithic [K, S, B] round program
    cohort_bucketing: Optional[Dict[str, Any]] = None
    # megakernel local SGD (engine/client_update.py): the opt-in pallas
    # fused SGD apply — free-form dict validated by
    # schema.MEGAKERNEL_KEYS
    megakernel: Optional[Dict[str, Any]] = None
    # precision policy (engine/client_update.py): params/compute/stats
    # dtypes for the client inner loop — free-form dict validated by
    # schema.PRECISION_KEYS; absent (the default) is the bit-identical
    # f32 path
    precision: Optional[Dict[str, Any]] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, raw: Optional[Dict[str, Any]]) -> "ServerConfig":
        if raw is None:
            return cls()
        raw = dict(raw)
        data = DataConfig.from_dict(raw.pop("data_config", None))
        opt = OptimizerConfig.from_dict(raw.pop("optimizer_config", None))
        ann = AnnealingConfig.from_dict(raw.pop("annealing_config", None))
        replay = ServerReplayConfig.from_dict(raw.pop("server_replay_config", None))
        rl = RLConfig.from_dict(raw.pop("RL", None))
        out = cls(**_take(raw, [
            "type", "max_iteration", "num_clients_per_iteration",
            "initial_lr_client", "lr_decay_factor", "val_freq", "rec_freq",
            "initial_val", "initial_rec", "best_model_criterion",
            "fall_back_to_best_model", "model_backup_freq",
            "resume_from_checkpoint", "send_dicts", "max_grad_norm",
            "do_profiling", "wantRL", "aggregate_median", "softmax_beta",
            "initial_lr", "weight_train_loss", "stale_prob",
            "num_skip_decoding", "nbest_task_scheduler", "chaos",
            "checkpoint_retry", "telemetry", "robust",
            "cohort_bucketing", "megakernel", "precision"]))
        out.data_config = data
        out.optimizer_config = opt
        out.annealing_config = ann
        out.server_replay_config = replay
        out.RL = rl
        return out


@dataclass
class ClientConfig(Config):
    """Client-side settings (reference ClientConfig,
    ``core/client.py:226-511``)."""

    type: str = "optimization"
    meta_learning: str = "basic"
    copying_train_data: bool = False
    do_profiling: bool = False
    ignore_subtask: bool = False
    num_skip_decoding: int = -1
    desired_max_samples: Optional[int] = None
    max_grad_norm: Optional[float] = None
    # per-layer LR freezing (reference core/client.py:306-307)
    freeze_layer: Optional[List[str]] = None
    data_config: DataConfig = field(default_factory=DataConfig)
    optimizer_config: OptimizerConfig = field(default_factory=OptimizerConfig)
    annealing_config: Optional[AnnealingConfig] = None
    # FedProx proximal term mu (reference core/trainer.py:416-501)
    fedprox_mu: float = 0.0
    # personalization (reference core/client.py:387-443, experiments/cv)
    convex_model_interp: Optional[float] = None
    meta_optimizer_config: Optional[OptimizerConfig] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, raw: Optional[Dict[str, Any]]) -> "ClientConfig":
        if raw is None:
            return cls()
        raw = dict(raw)
        data = DataConfig.from_dict(raw.pop("data_config", None))
        opt = OptimizerConfig.from_dict(raw.pop("optimizer_config", None))
        ann_raw = raw.pop("annealing_config", None)
        meta_raw = raw.pop("meta_optimizer_config", None)
        out = cls(**_take(raw, [
            "type", "meta_learning", "copying_train_data", "do_profiling",
            "ignore_subtask", "num_skip_decoding", "desired_max_samples",
            "max_grad_norm", "freeze_layer", "fedprox_mu",
            "convex_model_interp"]))
        out.data_config = data
        out.optimizer_config = opt
        out.annealing_config = AnnealingConfig.from_dict(ann_raw) if ann_raw else None
        out.meta_optimizer_config = OptimizerConfig.from_dict(meta_raw) if meta_raw else None
        return out


@dataclass
class FLUTEConfig(Config):
    """Top-level config (reference FLUTEConfig, ``core/config.py:713-796``).

    Six sections, same vocabulary as the reference
    (``doc/sphinx/scenarios.rst:137-145``).
    """

    model_config: ModelConfig = field(default_factory=ModelConfig)
    dp_config: Optional[DPConfig] = None
    privacy_metrics_config: Optional[PrivacyMetricsConfig] = None
    strategy: str = "fedavg"
    server_config: ServerConfig = field(default_factory=ServerConfig)
    client_config: ClientConfig = field(default_factory=ClientConfig)
    # engine-level (TPU-native additions; no reference equivalent)
    mesh_config: Dict[str, Any] = field(default_factory=dict)
    task: Optional[str] = None
    data_path: Optional[str] = None
    output_path: Optional[str] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, raw: Dict[str, Any], validate_schema: bool = True) -> "FLUTEConfig":
        from . import schema

        raw = copy.deepcopy(raw)
        if validate_schema:
            schema.validate(raw)
        dp_raw = raw.pop("dp_config", None)
        pm_raw = raw.pop("privacy_metrics_config", None)
        out = cls(
            model_config=ModelConfig.from_dict(raw.pop("model_config", None)),
            dp_config=DPConfig.from_dict(dp_raw) if dp_raw is not None else None,
            privacy_metrics_config=(PrivacyMetricsConfig.from_dict(pm_raw)
                                    if pm_raw is not None else None),
            strategy=raw.pop("strategy", "fedavg"),
            server_config=ServerConfig.from_dict(raw.pop("server_config", None)),
            client_config=ClientConfig.from_dict(raw.pop("client_config", None)),
            mesh_config=raw.pop("mesh_config", {}) or {},
            task=raw.pop("task", None),
            data_path=raw.pop("data_path", None),
            output_path=raw.pop("output_path", None),
            extra=raw,
        )
        return out

    @classmethod
    def from_yaml(cls, path: str, **kw: Any) -> "FLUTEConfig":
        with open(path, "r") as fh:
            return cls.from_dict(yaml.safe_load(fh), **kw)

    def validate(self, data_path: Optional[str] = None) -> "FLUTEConfig":
        """Join data paths into the config (reference
        ``core/config.py:736-760`` joins ``data_path`` onto the per-split
        file names) and normalize derived fields."""
        data_path = data_path or self.data_path
        if data_path:
            for section in (self.server_config.data_config, self.client_config.data_config):
                for split in (section.train, section.val, section.test):
                    for attr in ("list_of_train_data", "test_data", "val_data",
                                 "train_data", "train_data_server", "vocab_dict"):
                        val = getattr(split, attr)
                        if val and not os.path.isabs(val):
                            setattr(split, attr, os.path.join(data_path, val))
            vocab = self.model_config.get("vocab_dict")
            if vocab and not os.path.isabs(vocab):
                self.model_config["vocab_dict"] = os.path.join(data_path, vocab)
        return self


def parse_clients_per_round(spec: Any, rng) -> int:
    """Resolve ``num_clients_per_iteration``: an int, or ``"lo:hi"`` meaning
    a per-round uniform random count (reference ``core/server.py:284-291``)."""
    if isinstance(spec, int):
        return spec
    if isinstance(spec, str) and ":" in spec:
        lo, hi = (int(x) for x in spec.split(":"))
        return int(rng.integers(lo, hi + 1))
    return int(spec)


def cohort_upper_bound(spec: Any) -> int:
    """The largest cohort ``num_clients_per_iteration`` can draw — the
    rng-free companion of :func:`parse_clients_per_round` (one parser
    for the ``"lo:hi"`` spec; capacity/pool sizing must never desync
    from the draw's format)."""
    if isinstance(spec, str) and ":" in spec:
        return int(spec.split(":")[1])
    return int(spec)
