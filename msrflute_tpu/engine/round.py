"""The federated round as one jitted SPMD program.

Parity target: the whole middle of the reference stack —
``federated.Server.dispatch_clients/process_clients``
(``core/federated.py:281-424``), the Worker recv loop
(``core/federated.py:482-632``), and the server-side aggregation half of
``OptimizationServer.train`` (``core/server.py:337-427``).

TPU-native redesign (SURVEY.md §5.8): no message protocol, no work queue.
One compiled ``round_step``:

    shard_map over mesh 'clients' axis:
        vmap(client_update) over the shard's clients        # local SGD
        per-client strategy weight + payload transform      # DP/quant/freeze
        weighted local sums -> psum over 'clients'          # "collection"
    strategy.combine (+ staleness buffer, global DP)        # aggregation
    server optax step on the aggregate pseudo-gradient      # ModelUpdater

The per-round model "broadcast" (reference ``core/federated.py:330-335``,
K-1 unicasts) is just the replicated ``params`` operand — XLA keeps it
resident on every chip; the "harvest" poll loop (``core/federated.py:216-229``)
is a single ``psum`` riding ICI.  Greedy work-stealing is replaced by static
client sharding; imbalance is absorbed by masked padding, which costs FLOPs
on padded samples instead of latency on stragglers — the right trade on MXUs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..config import FLUTEConfig
from ..data.batching import RoundBatch
from ..models.base import BaseTask
from ..optim import make_optimizer
from ..parallel.mesh import CLIENTS_AXIS, MODEL_AXIS, make_mesh
from ..resilience import chaos as chaos_modes
from ..traffic.schedule import STALE_HIST_BINS
from ..robust import make_shield
from ..strategies.base import BaseStrategy
from ..telemetry import (NULL_SPAN, devbus_config_enabled,
                         xla_config_enabled)
from ..telemetry import compiles as compile_spans
from ..telemetry import xla as xla_telemetry
from ..telemetry.devbus import DeviceMetricBus
from ..utils.flatpack import AxisPacker, FlatPacker, ScalarStager
from .client_update import (ClientHParams, build_client_update,
                            build_mega_update, _clip_by_global_norm)


def _round_scoped(fn: Callable) -> Callable:
    """``fn`` traced under the catalogue scope ``round_aggregate``
    (docs/observability.md, "Named scopes"): the one place where every
    round-program builder gets it.  The clients' local steps open
    ``client_steps`` inside it (``engine/client_update.py``) and a
    device operation counts under the innermost catalogue scope of its
    path, so what reads as ``round_aggregate`` is the round outside the
    local steps: the pseudo-gradient and its statistics, clipping, DP
    noise, quantisation (``quant_select`` nested), the strategy's
    weights, the weighted sum and its ``psum``, the server optimizer's
    step and the packed stats.  Trace-time metadata only: the compiled
    arithmetic, and so the compile cache's key, does not change."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope("round_aggregate"):
            return fn(*args, **kwargs)
    return scoped


@dataclass
class PackedStats:
    """Lazy handle to one chunk's round stats, packed on device.

    The round program returns its ~dozen per-round scalars / per-client
    vectors as ONE 1-D buffer per distinct dtype (``utils/flatpack.py``),
    so the host pays one ``device_get`` per dtype group per chunk instead
    of one per stat (the per-buffer dispatch overhead measured by
    ``tools/dispatch_cost_probe.py``).  Nothing is fetched until
    :meth:`fetch` — the server's pipelined loop holds this handle while
    the device executes the next chunk and drains it afterwards.
    """

    vecs: Dict[str, jax.Array]  #: {dtype_str: 1-D (or [R, n]) device buffer}
    packer: FlatPacker          #: single-round slot table
    rounds: int                 #: R rounds in this chunk
    stacked: bool               #: True if ``vecs`` carry a leading [R] axis
    #: the engine's span factory when tracing is on (``stats_d2h``)
    span: Optional[Callable] = None
    #: hands the host buffers this chunk's inputs were staged in back to
    #: the engine, at the fence: the program's outputs being there is
    #: the proof that it has read its inputs (``jax.device_put`` returns
    #: before the transfer, and on the CPU backend the device array may
    #: be the numpy memory itself)
    release: Optional[Callable[[], None]] = None

    def is_ready(self) -> bool:
        """Whether the chunk's program has produced the stats: a
        question, not a fence (asked only when tracing is on)."""
        return all(v.is_ready() for v in self.vecs.values())

    def wait(self) -> None:
        """Block until the stats are there (the traced run's
        ``fence_wait``; :meth:`fetch` blocks by itself otherwise)."""
        jax.block_until_ready(self.vecs)
        self._fenced()

    def _fenced(self) -> None:
        release, self.release = self.release, None
        if release is not None:
            release()

    def fetch(self) -> Dict[str, np.ndarray]:
        """Fetch + decode: ONE host transfer per dtype group (the honest
        end-of-chunk fence), then pure numpy views.  Leaves come back
        with a leading ``[R]`` round axis like ``run_rounds`` always
        returned."""
        with (self.span("stats_d2h", rounds=self.rounds)
              if self.span is not None else NULL_SPAN):
            return self._fetch()

    def _fetch(self) -> Dict[str, np.ndarray]:
        host = jax.device_get(self.vecs)
        self._fenced()
        if self.stacked:
            return self.packer.unpack_np_stacked(host)
        tree = self.packer.unpack_np(host)
        return {k: np.asarray(v)[None] for k, v in tree.items()}


class StagingPool:
    """The staged dispatch's host buffers, kept and written again.

    A fresh ``[R, K, total]`` array a dispatch is mostly page faults: its
    pages are first touched as they are filled and unmapped after the
    launch (two 307 MB arrays a dispatch where five ResNet rounds are
    fused).  Here a buffer is taken for a dispatch, handed back at the
    fence of the chunk whose program read it (``PackedStats.release``),
    and written again by a later dispatch of the same shapes.  With none
    free, :meth:`take` allocates, as every dispatch did before:
    correctness never depends on the pool.  It holds only the shapes of
    the dispatch that took last (a shorter last chunk's or another
    cohort padding's buffers are dropped), and of each at most ``keep``.
    """

    def __init__(self, keep: int):
        self.keep = int(keep)
        self._free: Dict[tuple, list] = {}

    def take(self, shapes: Dict[str, Tuple[int, ...]]
             ) -> Tuple[Dict[str, np.ndarray], bool]:
        """``({dtype: buffer}, reused)`` for one dispatch: ``reused`` is
        False when any of them had to be allocated."""
        self._free = {(dt, shape): self._free.get((dt, shape), [])
                      for dt, shape in shapes.items()}
        bufs, reused = {}, True
        for dt, shape in shapes.items():
            free = self._free[dt, shape]
            if free:
                bufs[dt] = free.pop()
            else:
                bufs[dt] = np.empty(shape, jnp.dtype(dt))
                reused = False
        return bufs, reused

    def give(self, bufs: Dict[str, np.ndarray]) -> None:
        """Back from a fenced chunk; kept if a dispatch may want it."""
        for dt, buf in bufs.items():
            free = self._free.get((dt, buf.shape))
            if free is not None and len(free) < self.keep:
                free.append(buf)


@dataclass
class BucketedStats:
    """Lazy handle to a bucketed chunk's per-round packed stats.

    Cohort bucketing dispatches each round as N collect programs plus a
    finalize whose packed stats ride the same one-buffer-per-dtype
    contract as :class:`PackedStats` — but rounds of one chunk may have
    different cohort-vector lengths (per-client privacy stats are laid
    out as the concatenation of that round's buckets), so the chunk's
    stats cannot ride one stacked buffer.  ``fetch`` pulls every round's
    buffers in ONE ``device_get`` call (still one packed buffer per
    dtype group per round — the invariant), then stacks host-side:
    scalars to ``[R]``, per-client vectors zero-padded to the chunk max
    (their mask is the batches' client_mask, padded identically by the
    server)."""

    rounds_stats: list  #: one PackedStats per round, dispatch order
    #: the engine's span factory when tracing is on (``stats_d2h``)
    span: Optional[Callable] = None

    @property
    def rounds(self) -> int:
        return len(self.rounds_stats)

    def is_ready(self) -> bool:
        return all(ps.is_ready() for ps in self.rounds_stats)

    def wait(self) -> None:
        jax.block_until_ready([ps.vecs for ps in self.rounds_stats])

    def fetch(self) -> Dict[str, np.ndarray]:
        with (self.span("stats_d2h", rounds=self.rounds)
              if self.span is not None else NULL_SPAN):
            return self._fetch()

    def _fetch(self) -> Dict[str, np.ndarray]:
        host = jax.device_get([ps.vecs for ps in self.rounds_stats])
        decoded = [ps.packer.unpack_np(h)
                   for ps, h in zip(self.rounds_stats, host)]
        out: Dict[str, np.ndarray] = {}
        for key in decoded[0]:
            vals = [np.asarray(d[key]) for d in decoded]
            if vals[0].ndim == 0:
                out[key] = np.asarray(vals)
                continue
            width = max(v.shape[0] for v in vals)
            out[key] = np.stack([
                v if v.shape[0] == width else np.concatenate(
                    [v, np.zeros((width - v.shape[0],) + v.shape[1:],
                                 v.dtype)])
                for v in vals])
        return out


@dataclass
class ServerState:
    """Replicated server-side state threaded through rounds
    (the analogue of the reference's global model + ModelUpdater optimizer +
    strategy buffers)."""

    params: Any
    opt_state: Any
    strategy_state: Any
    round: int = 0


def gather_pool(pool, arrays, sample_mask):
    """Device-resident mode: ``arrays`` carries pool indices; gather the
    feature rows in-program (one XLA gather per key, HBM-local — no host
    bytes moved).  Padding slots index row 0, so zero the gathered rows
    with the sample mask: padding then holds zeros exactly like host
    packing (pool-vs-host bit-identity by construction, not by every
    task loss masking perfectly — tests/test_device_pool.py)."""
    idx = arrays["__idx__"]
    m = sample_mask
    return {
        k: pool[k][idx]
        * m.reshape(m.shape + (1,) * (pool[k].ndim - 1)).astype(pool[k].dtype)
        for k in pool}


def _stat_sums(tls, nss, stats, cm) -> Dict[str, Any]:
    """The loss / sample / live-client / gradient-stat sums over a
    client axis whose live mask is ``cm``."""
    return {
        "train_loss_sum": jnp.sum(tls),
        "num_samples_sum": jnp.sum(nss),
        "client_count": jnp.sum(cm),
        "stats_mean_sum": jnp.sum(stats["mean"] * cm),
        "stats_mag_sum": jnp.sum(stats["mag"] * cm),
        "stats_var_sum": jnp.sum(stats["var_corrected"] * cm),
        "stats_norm_sum": jnp.sum(stats["norm"] * cm),
    }


def _round_stats(collected, part_sums, agg) -> Dict[str, Any]:
    """The round's scalar stats from its collected sums: what every
    round program packs, before its own counters join."""
    default_part = part_sums.get("default") or \
        next(iter(part_sums.values()))
    count = collected["client_count"]
    # the floor of the count is taken once a mean, as the programs have
    # always traced it (hoisting it would change every cell's HLO)
    return {
        "train_loss_sum": collected["train_loss_sum"],
        "num_samples_sum": collected["num_samples_sum"],
        "client_count": count,
        "weight_sum": default_part["weight_sum"],
        "weight_sum_raw": default_part["weight_sum_raw"],
        "grad_mean": collected["stats_mean_sum"] / jnp.maximum(count, 1.0),
        "grad_mag": collected["stats_mag_sum"] / jnp.maximum(count, 1.0),
        "grad_var": collected["stats_var_sum"] / jnp.maximum(count, 1.0),
        "grad_norm": collected["stats_norm_sum"] / jnp.maximum(count, 1.0),
        "agg_grad_norm": optax.global_norm(agg),
    }


class RoundEngine:
    """Compiles and runs the per-round SPMD program."""

    def __init__(self, task: BaseTask, config: FLUTEConfig,
                 strategy: BaseStrategy, mesh: Optional[Mesh] = None):
        self.task = task
        self.config = config
        self.strategy = strategy
        strategy.task = task  # strategies may need model apply()/loss()
        self.mesh = mesh if mesh is not None else make_mesh()

        cc = config.client_config
        sc = config.server_config
        freeze = cc.get("freeze_layer") or []
        if isinstance(freeze, str):
            freeze = [freeze]
        # the opt-in pallas fused SGD apply
        # (server_config.megakernel.pallas_apply)
        pallas_apply = bool(
            (sc.get("megakernel") or {}).get("pallas_apply", False))
        if pallas_apply and jax.default_backend() != "tpu":
            # the round runs client_update inside shard_map over virtual
            # CPU devices off-TPU, where interpret-mode pallas kernels
            # deadlock (the documented reason ops/pallas_attention.py
            # defaults to dense there) — refuse loudly instead of
            # hanging the first round
            raise ValueError(
                "megakernel.pallas_apply requires a TPU backend: the "
                "interpret-mode kernel cannot run inside the shard_map'd "
                "round on CPU — drop the flag or run on TPU")
        # precision policy (server_config.precision): params/compute/
        # stats dtypes for the client inner loop.  Absent — or every
        # entry "float32" — compiles the exact f32 legacy trace (the
        # bit-identity default); `compute: bfloat16` runs the forward/
        # backward in bf16 while master params and packed-stats
        # accumulators stay f32.
        _prec_raw = sc.get("precision") or {}
        _prec_on = bool(_prec_raw) and bool(_prec_raw.get("enable", True))
        self.precision = ({k: str(_prec_raw[k])
                           for k in ("params", "compute", "stats")
                           if _prec_raw.get(k) is not None}
                          if _prec_on else {})
        self.hparams = ClientHParams(
            max_grad_norm=cc.get("max_grad_norm"),
            fedprox_mu=float(cc.get("fedprox_mu", 0.0) or 0.0),
            num_epochs=int(cc.get("num_epochs", 1) or 1),
            freeze_layers=tuple(freeze),
            pallas_apply=pallas_apply,
            param_dtype=self.precision.get("params"),
            compute_dtype=self.precision.get("compute"),
            stats_dtype=self.precision.get("stats"),
        )
        self.client_update = build_client_update(
            task, cc.optimizer_config, self.hparams)
        self.server_tx = make_optimizer(sc.optimizer_config)
        self.server_max_grad_norm = sc.get("max_grad_norm")
        self.stale_prob = float(getattr(strategy, "stale_prob", 0.0) or 0.0)
        if self.stale_prob > 0.0 and not strategy.supports_staleness:
            raise ValueError(
                f"{type(strategy).__name__} does not support stale_prob > 0")
        if sc.get("wantRL", False) and not strategy.supports_rl:
            raise ValueError(
                f"{type(strategy).__name__} does not support wantRL")
        if getattr(strategy, "owns_server_update", False):
            opt_type = str(sc.optimizer_config.get("type", "sgd")).lower()
            if opt_type != "sgd":
                raise ValueError(
                    f"{type(strategy).__name__} applies its own coupled "
                    f"server update; server optimizer_config type="
                    f"{opt_type!r} would be silently ignored — use sgd "
                    "(the lr still scales the update)")
        self.dump_norm_stats = bool(config.get("dump_norm_stats",
                                               sc.get("dump_norm_stats",
                                                      False)))
        # scan-over-client-chunks: bound HBM at large K.  vmap over all K
        # clients materializes K x (activations + payload tree) at once —
        # measured OOM at K=1024 on a 16G v5e (bench_scale.json); chunking
        # scans vmap(chunk) accumulating the weighted sums, so memory is
        # O(chunk) while the psum'd result is identical up to f32
        # reassociation (tests/test_client_chunking.py).
        cpc = sc.get("clients_per_chunk")
        self.clients_per_chunk = int(cpc) if cpc else None
        if self.clients_per_chunk and self.dump_norm_stats:
            raise ValueError(
                "clients_per_chunk is incompatible with dump_norm_stats: "
                "per-client cosines need every payload against the final "
                "aggregate, which chunked accumulation never materializes — "
                "disable one of them")

        # device-resident carry state (universal overlap): the strategy
        # keeps its cross-round per-client tables (SCAFFOLD controls, EF
        # residuals, personalization heads/alphas) INSIDE strategy_state,
        # gathers its rows per client in-program and scatters the update
        # back via apply_carry — the round-k -> k+1 data dependency lives
        # on device, so these strategies pipeline like FedAvg.  The
        # server flips the flag (enable_device_carry) before building the
        # engine when server_config.fused_carry is set.
        self.device_carry = bool(getattr(strategy, "device_carry", False))
        if self.device_carry and self.clients_per_chunk:
            raise ValueError(
                "fused_carry is incompatible with clients_per_chunk: the "
                "carry scatter needs every client's update row, which "
                "chunked accumulation never materializes — disable one")
        # fleet paged carry (server_config.fleet + fused_carry): the
        # carry tables are a fixed-capacity page pool (engine/paging.py)
        # and the round program takes ONE extra per-round data operand —
        # carry_slots [K] int32, the host-remapped pool slot per lane —
        # which the carry gather/scatter indexes INSTEAD of client_ids.
        # Per-client rng streams keep folding on the true client id, so
        # per-client math is bit-identical to resident tables.  Static
        # at engine build: without the fleet block the program is byte-
        # for-byte the PR 6 trace (carry_slots IS client_ids in-trace).
        _fleet_raw = sc.get("fleet") or {}
        self.carry_paged = bool(
            self.device_carry and _fleet_raw and
            _fleet_raw.get("enable", True))
        # mesh-sharded page pool: the tables' slot axis splits over
        # CLIENTS_AXIS into contiguous per-shard blocks (the same split
        # shard_map applies to the cohort grids), so the in-program
        # carry gather/scatter is shard-local — the engine converts the
        # GLOBAL carry_slots operand to shard-local indices inside the
        # shard_map body using this block width.
        self._carry_shard_slots = 0
        if self.carry_paged:
            rows = int(getattr(strategy, "carry_rows", 0) or 0)
            shards = int(self.mesh.shape[CLIENTS_AXIS])
            if rows <= 0 or rows % shards:
                raise ValueError(
                    f"fleet paged carry: page pool of {rows} slots does "
                    f"not split over the {shards}-shard clients mesh "
                    "axis — the server quantizes page_pool_slots to a "
                    "mesh multiple before building the engine")
            self._carry_shard_slots = rows // shards

        # fused RL (server_config.wantRL + fused_carry): the DQN
        # aggregation-weight tuner lives in strategy_state (rl/fused.py)
        # and re-weights the gathered payload stack in-program; the
        # reward is the round-over-round train-loss delta (delayed one
        # round) instead of the host path's val-accuracy comparison —
        # the documented tradeoff that buys full overlap.
        self.rl_fused = bool(sc.get("wantRL", False) and
                             sc.get("fused_carry", False))
        self._rl = None
        if self.rl_fused:
            if not strategy.supports_rl:
                raise ValueError(
                    f"{type(strategy).__name__} does not support wantRL")
            if self.device_carry:
                raise ValueError(
                    "fused RL does not compose with a device-carry "
                    "strategy (scaffold/ef_quant/personalization): the "
                    "RL re-weighting assumes the plain single-payload "
                    "flow — drop wantRL or use fedavg/dga")
            if strategy.stateful or \
                    getattr(strategy, "adaptive_clip", None) is not None:
                raise ValueError(
                    "fused RL requires a stateless strategy combine "
                    "(no adaptive_clipping / strategy state): the RL "
                    "weights replace the combine entirely")
            if getattr(strategy, "wants_cohort", False) or \
                    strategy.unit_weight_parts:
                raise ValueError(
                    "fused RL does not compose with masked multi-part "
                    "payloads (secure_agg/fedlabels): re-weighting would "
                    "break mask cancellation")
            if self.clients_per_chunk:
                raise ValueError(
                    "fused RL (wantRL) is incompatible with "
                    "clients_per_chunk: re-weighting needs the full "
                    "payload stack")
            if float(getattr(strategy, "stale_prob", 0.0) or 0.0) > 0.0:
                raise ValueError("fused RL does not support stale_prob")
            from ..config import RLConfig
            from ..rl.fused import FusedRL
            rl_cfg = sc.RL if getattr(sc, "RL", None) is not None \
                else RLConfig.from_dict({})
            if bool(rl_cfg.get("wantLSTM", False)):
                raise ValueError(
                    "fused RL does not support wantLSTM — the state-"
                    "window recurrence is host-side; drop fused_carry "
                    "for LSTM RL runs")
            ncpi = sc.get("num_clients_per_iteration", 10)
            if not isinstance(ncpi, int):
                raise ValueError(
                    "wantRL requires a fixed num_clients_per_iteration")
            from ..parallel.mesh import pad_to_mesh
            self._rl = FusedRL(rl_cfg, pad_to_mesh(int(ncpi), self.mesh))

        #: the staged dispatch's jitted programs (_build_staged_fn), by
        #: rounds per dispatch and packer signatures
        self._staged_cache: Dict[Any, Callable] = {}
        #: dispatch-cost observability (bench extras + the tier-1
        #: regression guard): host->device put calls and bytes of the
        #: most recent dispatch
        self.last_dispatch_puts = 0
        self.last_staged_bytes = 0
        #: host staging buffers: what the ring can have in flight plus
        #: the one being filled, and one to spare
        self._staging = StagingPool(
            max(int(sc.get("pipeline_depth", 1) or 0), 0) + 2)
        #: the server's span factory (``Telemetry.span``) when tracing
        #: is on: what a dispatch is made of (``stage_host``, ``h2d``,
        #: ``launch``) and the stats transfer (``stats_d2h``) become
        #: child spans.  None: every site is one check, nothing else.
        self.span_factory: Optional[Callable] = None

        # deterministic chaos client faults (server_config.chaos): when the
        # schedule injects dropout/straggling, the round program takes two
        # extra per-round data operands — drop [K] and keep_steps [K] —
        # and folds them into client_mask / sample_mask IN-program, so the
        # faults cost no recompile and the injected-fault counters ride
        # the packed-stats single-transfer path (resilience/chaos.py).
        # Static at engine build: a chaos-free config compiles the exact
        # program it always did.  Read straight from the config block —
        # the ONE live ChaosSchedule (counters, IO-fault stream) belongs
        # to the server; a second instance here would silently diverge.
        _chaos_raw = sc.get("chaos") or {}
        _chaos_on = bool(_chaos_raw and _chaos_raw.get("enable", True))
        self.chaos_client_faults = bool(
            _chaos_on and
            (float(_chaos_raw.get("dropout_rate", 0.0) or 0.0) > 0.0 or
             float(_chaos_raw.get("straggler_rate", 0.0) or 0.0) > 0.0))
        # adversarial corruption streams (fluteshield's attack half):
        # when any corrupt_* rate is non-zero the program takes ONE more
        # per-round data operand — mode [K] int32 — and applies the
        # NaN/scale/sign-flip transform to the default payload inside
        # the vmap'd client body.  Same static-at-build discipline as
        # the fault flag above: zero rates compile the exact program a
        # corruption-free config always had.
        self.chaos_corruption = bool(
            _chaos_on and
            any(float(_chaos_raw.get(k, 0.0) or 0.0) > 0.0
                for k in ("corrupt_nan_rate", "corrupt_scale_rate",
                          "corrupt_sign_flip_rate")))
        self._corrupt_scale = float(
            _chaos_raw.get("corrupt_scale_factor", 10.0) or 10.0)
        self._corrupt_flip_scale = float(
            _chaos_raw.get("corrupt_sign_flip_scale", 1.0) or 1.0)

        # fluteflow traced staleness (server_config.traffic, buffered
        # mode, with a strategy that declares supports_traced_staleness
        # — FedBuff): the round program takes ONE more per-round data
        # operand — staleness [K] int32, the TRUE broadcast-version gap
        # the arrival plane measured — threaded on the exact rails the
        # chaos vectors ride (appended after corrupt_mode in every
        # positional order), so traffic costs no recompile and the
        # per-staleness histogram counters ride the packed-stats single
        # transfer.  Static at engine build: a traffic-free config (or
        # sync mode, or a staleness-blind strategy) compiles the exact
        # program it always did.
        _traffic_raw = sc.get("traffic") or {}
        _traffic_on = bool(_traffic_raw and
                           _traffic_raw.get("enable", True))
        self.traffic_staleness = bool(
            _traffic_on and
            str(_traffic_raw.get("mode", "buffered")) == "buffered" and
            getattr(strategy, "supports_traced_staleness", False))
        if self.traffic_staleness and self.clients_per_chunk:
            raise ValueError(
                "server_config.traffic traced staleness cannot compose "
                "with clients_per_chunk: the chunk scan's operand tuple "
                "is fixed per chunk — disable one of them")

        # fluteshield screened aggregation (server_config.robust): the
        # quarantine mask is computed INSIDE the round program from the
        # per-client payloads (robust/shield.py) and folds into
        # client_mask/weights as data — no recompile, counters ride the
        # packed-stats single transfer.  None (no block / enable: false)
        # is the firewall path: the exact pre-fluteshield program.
        self.shield = make_shield(sc)
        if self.shield is not None:
            from ..strategies.fedavg import FedAvg
            from ..strategies.robust import RobustFedAvg
            from ..strategies.secure_agg import SecureAgg
            # exact-class check: QFFL/FedBuff/... subclass FedAvg but
            # combine through their own payload parts, which quarantine
            # zeroing would silently corrupt — isinstance would admit
            # them.  SecureAgg is admitted by name: its masked path
            # screens on submitted norms (Shield.screen_masked) and a
            # quarantined client feeds the pairwise-mask cancellation as
            # one more dropout cause (tests/test_secagg_compose.py)
            if type(strategy) not in (FedAvg, RobustFedAvg, SecureAgg):
                raise ValueError(
                    "server_config.robust requires strategy: fedavg/"
                    f"fedprox/secure_agg — {type(strategy).__name__} "
                    "aggregates through its own payload parts and would "
                    "bypass the screening")
            if isinstance(strategy, SecureAgg) and self.shield.wants_stack:
                raise ValueError(
                    f"robust.aggregator={self.shield.aggregator!r} sorts "
                    "per-client payload coordinates, but secure_agg "
                    "submissions are masked int32 group elements — only "
                    "the SUM is meaningful.  Use aggregator: mean (norm "
                    "screening still applies, on submitted norms)")
            if self.clients_per_chunk:
                raise ValueError(
                    "server_config.robust is incompatible with "
                    "clients_per_chunk: median-of-norms screening (and "
                    "the trimmed-mean/median payload stack) needs every "
                    "client's payload against the full cohort, which "
                    "chunked accumulation never materializes — disable "
                    "one of them")
            if getattr(strategy, "adaptive_clip", None) is not None:
                # screening zeroes only the default payload part; the
                # adaptive-clip quantile aggregates per-client below-clip
                # votes that quarantine cannot retract, so the clip would
                # drift off the population actually being aggregated
                raise ValueError(
                    "server_config.robust is incompatible with "
                    "dp_config.adaptive_clipping: quarantined clients' "
                    "below-clip votes would still steer the clip "
                    "quantile — use a fixed max_grad or drop the robust "
                    "block")
            if self.shield.wants_stack and \
                    not getattr(strategy, "wants_client_stack", False):
                raise ValueError(
                    f"robust.aggregator={self.shield.aggregator!r} needs "
                    "the stack-combining RobustFedAvg strategy "
                    "(strategies/robust.py); the server wires this — "
                    "constructing RoundEngine directly, pass it yourself")

        # cohort shape-bucketing (server_config.cohort_bucketing): the
        # round's sampled clients partition into a small set of
        # power-of-two step buckets; each bucket dispatches a COMPACT
        # [K_b, S_b, B, ...] collect program (the same per-client math
        # as the fused round — masked padding steps are no-op-pinned,
        # so per-client updates are bit-identical), and a finalize
        # program combines the per-bucket partials into the weighted
        # aggregate ON DEVICE in deterministic bucket order.  One packed
        # stats fetch per round and zero implicit host syncs, unchanged.
        _cb_raw = sc.get("cohort_bucketing") or {}
        self.cohort_bucketing = bool(_cb_raw and _cb_raw.get("enable", True))
        # an EXPLICIT max_buckets: 0 must reach the < 1 refusal below,
        # not silently coerce to the default (bench injects blocks past
        # schema validation)
        _mb = _cb_raw.get("max_buckets")
        self.bucket_max = 4 if _mb is None else int(_mb)
        if self.cohort_bucketing:
            if self.bucket_max < 1:
                raise ValueError("cohort_bucketing.max_buckets must be >= 1")
            if self.clients_per_chunk:
                raise ValueError(
                    "cohort_bucketing is incompatible with "
                    "clients_per_chunk: the chunk scan assumes one grid "
                    "shape per round — pick one HBM/FLOP bounding scheme")
            if self.dump_norm_stats:
                raise ValueError(
                    "cohort_bucketing is incompatible with "
                    "dump_norm_stats: per-client cosines need every "
                    "payload against the final aggregate inside ONE "
                    "program — disable one of them")
            if self.rl_fused:
                raise ValueError(
                    "cohort_bucketing does not compose with fused RL: "
                    "the DQN re-weighting assumes the single-grid payload "
                    "stack — drop wantRL or cohort_bucketing")
            # NOTE: wants_cohort strategies (secure_agg) now compose —
            # each bucket runs its own pairwise-mask graph over the
            # bucket's sampled sub-cohort and the finalize cancels
            # residual masks per bucket before decoding; the int32
            # telescoping is exact either way, so bucketed == monolithic
            # bit-identical (tests/test_secagg_compose.py)
            if self.shield is not None and \
                    float(getattr(strategy, "stale_prob", 0.0) or 0.0) > 0:
                raise ValueError(
                    "cohort_bucketing + robust screening does not "
                    "support stale_prob > 0")
        # cross-client megabatching (server_config.megabatch): within a
        # step bucket, many SMALL clients' step sequences concatenate
        # into super-batch LANES read off a [lanes, depth] pointer tape
        # (data/batching.plan_megabatch), and the collect program runs
        # the segment-carrying lane scan (client_update.
        # build_mega_update) instead of one vmap lane per client — same
        # per-client math, folded on true client ids, with a cheap
        # fake-update vmap pass replaying the strategy's weight/
        # transform/carry logic on the harvested rows.  The dispatch
        # gate prices megabatch vs per-client vmap PER BUCKET (like the
        # attention flash/dense gate) and falls back loudly via the
        # buffered ``megabatch_fallback`` event.
        _mgb_raw = sc.get("megabatch") or {}
        self.megabatch = bool(_mgb_raw and _mgb_raw.get("enable", True))
        self.megabatch_min_gain = float(
            _mgb_raw.get("min_gain", 0.1) or 0.0)
        self.megabatch_autotune = bool(_mgb_raw.get("autotune", True))
        self.mega_update = None
        if self.megabatch:
            if not self.cohort_bucketing:
                raise ValueError(
                    "megabatch requires cohort_bucketing: the super-"
                    "batch tape repacks the per-bucket step grids — add "
                    "the cohort_bucketing block or drop megabatch")
            _pm = getattr(config, "privacy_metrics_config", None)
            if _pm is not None and _pm.get("apply_metrics", False):
                raise ValueError(
                    "megabatch is incompatible with privacy_metrics_"
                    "config.apply_metrics: the attack metrics replay "
                    "each client's own batches against its payload, "
                    "which the fused lane scan no longer materializes "
                    "per client — disable one of them")
            if not getattr(strategy, "supports_megabatch", True):
                raise ValueError(
                    f"megabatch does not compose with "
                    f"{type(strategy).__name__}: its training loop "
                    "steps outside the client_update contract the lane "
                    "scan reproduces (fedlabels' dual sup/unsup "
                    "passes) — drop megabatch")
            if self.hparams.pallas_apply:
                raise ValueError(
                    "megabatch is incompatible with megakernel."
                    "pallas_apply: the flat fused kernel has no "
                    "segment-reset lane — drop one of them")
            self.mega_update = build_mega_update(
                task, cc.optimizer_config, self.hparams)
        #: per-(K_b, S_b) dispatch-gate verdicts ("mega"/"vmap") — the
        #: server reports the chosen arm per bucket on the scorecard
        self._mega_gate: Dict[Any, str] = {}
        #: buffered megabatch_fallback event records (the attention
        #: gate's _PENDING_EVENTS discipline), drained by the server
        self._mega_events: list = []

        #: staged per-bucket collect programs, keyed by grid geometry +
        #: packer signatures — one compiled variant per distinct
        #: (K_b, S_b) shape, which the recompile sentinel watches
        self._bucket_collect_cache: Dict[Any, Callable] = {}
        self._bucket_collect_core: Dict[bool, Callable] = {}
        self._bucket_finalize = None
        #: distinct (K_b, S_b) collect grids this run compiled — the
        #: scorecard/bench closure metric gated against max_buckets
        self.bucket_shapes_seen: set = set()

        # flutescope device-metric bus (server_config.telemetry.devbus):
        # engine/strategy code publishes per-round device scalars at
        # TRACE time; round_step drains them into round_stats just
        # before the flatpack pack, so every published value rides the
        # existing single per-dtype-group transfer — zero new
        # device_gets.  Static at engine build like the chaos flag: a
        # telemetry-free config compiles the exact program it always
        # did.  Strategies publish through their `devbus` attribute.
        self.devbus = DeviceMetricBus(
            devbus_config_enabled(sc.get("telemetry")))
        strategy.devbus = self.devbus

        # flutescope device-truth (server_config.telemetry.xla): wrap
        # each jitted entry point in an AOT-cached _InstrumentedFn so
        # every compile is observed with its cost/memory analysis and
        # the recompile sentinel sees signature churn (telemetry/
        # xla.py).  None when telemetry/xla is off — the zero-cost
        # contract: no introspection objects, the plain jit callables,
        # identical dispatch path.
        self.xla = (xla_telemetry.XlaIntrospector()
                    if xla_config_enabled(sc.get("telemetry")) else None)
        #: entry-point names in compile order — ALWAYS on (a list append
        #: per compiled program variant, read from the jit caches; no
        #: introspection objects).  `recompile_count` derives from it,
        #: so bench.py can report recompiles without telemetry enabled.
        self.compile_log: list = []
        self._compile_seen: Dict[Any, int] = {}

        self._client_sharding = NamedSharding(self.mesh, P(CLIENTS_AXIS))
        self._replicated = NamedSharding(self.mesh, P())
        #: device-resident sample pool (build_sample_pool); when set, round
        #: inputs are [K,S,B] indices and the gather runs in-program
        self._pool = None
        # partition mode: explicit shard_map collectives (default), or
        # GSPMD sharding propagation (required for a model axis > 1)
        mesh_cfg = config.mesh_config or {}
        default_mode = ("gspmd" if self.mesh.shape.get(MODEL_AXIS, 1) > 1
                        else "shard_map")
        self.partition_mode = mesh_cfg.get("partition", default_mode)
        #: {geometry key: FlatPacker} — slot tables for decoding the
        #: packed stats buffers, recorded when the round program traces
        self._stats_packers: Dict[Any, FlatPacker] = {}
        self._round_step_core = self._build_round_step()

    # ------------------------------------------------------------------
    def _instrument(self, name: str, jitted: Callable,
                    rounds: int = 1) -> Callable:
        """Route one jitted entry point through the device-truth layer
        (cost/memory capture + recompile sentinel) when it is on; the
        plain jit callable otherwise."""
        if self.xla is None:
            return jitted
        return self.xla.wrap(name, jitted, rounds=rounds)

    @staticmethod
    def _roofline_secs(cost) -> float:
        """Roofline score of one compiled arm (``max(flops/peak,
        bytes/bw)``) — the same one-number verdict the attention
        flash/dense gate compares (ops/pallas_attention.py)."""
        from ..ops.pallas_attention import _roofline_secs
        return _roofline_secs(cost)

    def push_megabatch_event(self, rec: Dict[str, Any]) -> None:
        """Buffer one ``megabatch_fallback`` dispatch-gate record
        (mirrors the attention gate's pending-events discipline; capped
        so an undrained session cannot grow it unboundedly).  The
        server's host tail drains + emits them into the structured-event
        stream (docs/observability.md)."""
        if len(self._mega_events) < 64:
            self._mega_events.append(dict(rec))

    def drain_megabatch_events(self) -> list:
        """Hand the buffered megabatch gate events to the caller (the
        server's host tail, which owns emitting them)."""
        out, self._mega_events = self._mega_events, []
        return out

    @staticmethod
    def _launch(fn: Callable, *args):
        """Call one jitted entry point.  With a tracer attached, a
        program's first launch is followed, once the call is issued, by
        its scope map (``telemetry/compiles.py``: which compiled
        operation belongs to which ``jax.named_scope``); with tracing
        off both hooks return at once."""
        before = compile_spans.programs_before(fn)
        out = fn(*args)
        compile_spans.program_scopes(fn, before, args)
        return out

    def _span(self, name: str, **args):
        """One child span of the server's ``dispatch`` — the shared
        no-op context unless the server handed over a factory."""
        return self.span_factory(name, **args) \
            if self.span_factory is not None else NULL_SPAN

    def _note_compiles(self, name: str, fn: Callable) -> None:
        """Append one ``compile_log`` entry per NEW compiled variant of
        ``fn`` since the last note — read from the wrapper's AOT cache
        or the pjit dispatch cache, so the count is the truth of what
        XLA compiled, not a guess from our own cache keys."""
        if hasattr(fn, "cache_len"):          # _InstrumentedFn
            n = int(fn.cache_len)
        elif hasattr(fn, "_cache_size"):      # pjit function
            try:
                n = int(fn._cache_size())
            except Exception:
                return
        else:
            return
        key = (name, id(fn))
        prev = self._compile_seen.get(key, 0)
        for _ in range(n - prev):
            self.compile_log.append(name)
        self._compile_seen[key] = max(prev, n)

    @property
    def recompile_count(self) -> int:
        """Compiled program variants beyond the first per entry point —
        the always-on recompile counter (the sentinel's event stream,
        with operand diffs, additionally exists when telemetry/xla is
        on)."""
        return len(self.compile_log) - len(set(self.compile_log))

    # ------------------------------------------------------------------
    def init_state(self, rng: jax.Array, params: Any = None) -> ServerState:
        if params is None:
            params = self.task.init_params(rng)
        if self.partition_mode == "gspmd" and \
                self.mesh.shape.get(MODEL_AXIS, 1) > 1:
            from ..parallel.sharding import infer_model_sharding
            shardings = infer_model_sharding(params, self.mesh)
            params = jax.tree.map(jax.device_put, params, shardings)
            opt_state = jax.jit(self.server_tx.init)(params)
        else:
            params = jax.device_put(params, self._replicated)
            opt_state = jax.jit(self.server_tx.init,
                                out_shardings=self._replicated)(params)
        strategy_state = self.strategy.init_state(params)
        if self.carry_paged:
            strategy_state = self.shard_carry_state(strategy_state)
        if self.rl_fused:
            # the DQN tuner's carry (net params, optimizer state, replay
            # ring, epsilon, delayed-reward anchors) rides strategy_state
            # so it is donated, scanned, and checkpointed exactly like
            # any strategy state
            strategy_state = {"base": strategy_state,
                              "rl": self._rl.init_state(rng)}
        return ServerState(
            params=params,
            opt_state=opt_state,
            strategy_state=strategy_state,
            round=0,
        )

    # ------------------------------------------------------------------
    def shard_carry_state(self, strategy_state: Any) -> Any:
        """Lay the paged carry tables out with the slot axis SHARDED
        over the clients mesh axis (the fleet transfer plane's HBM
        divisor: per-device pool bytes = total / mesh_size) and the
        rest of the state replicated.  Applied at init and again after
        a checkpoint restore, so the donated round program always sees
        one stable layout (no resharding copies, no donation-layout
        churn the recompile sentinel would flag)."""
        if not isinstance(strategy_state, dict):
            raise ValueError(
                "fleet paged carry requires a dict strategy_state with "
                f"the carry tables as keys — got "
                f"{type(strategy_state).__name__}")
        from ..parallel.sharding import slot_pool_sharding
        pool_spec = slot_pool_sharding(self.mesh)
        carry_keys = set(self.strategy.carry_tables)
        # flint: disable=put-loop one-time layout at init/resume, not per-round dispatch
        return {k: jax.device_put(v, pool_spec if k in carry_keys
                                  else self._replicated)
                for k, v in strategy_state.items()}

    # ------------------------------------------------------------------
    def attach_pool(self, pool_arrays: Dict[str, np.ndarray]) -> None:
        """Upload the flat sample pool (``build_sample_pool``) to every
        device ONCE and switch the round program to device-resident mode:
        per-round inputs shrink from gathered feature rows to ``[K,S,B]``
        int32 indices, and the row gather becomes part of the compiled
        program.  The dataloading analogue of keeping params resident —
        the reference re-ships client data from host per round
        (``core/client.py:101-124``); on a remote-attached chip that
        transfer dominates small-model rounds."""
        # flint: disable=put-loop one-time pool upload at attach, not per-round dispatch
        self._pool = {k: jax.device_put(np.asarray(v), self._replicated)
                      for k, v in pool_arrays.items()}
        self._staged_cache = {}
        self._stats_packers = {}
        self._bucket_collect_cache = {}
        self._bucket_collect_core = {}
        self._bucket_finalize = None
        self._round_step_core = self._build_round_step()

    # ------------------------------------------------------------------
    # what the round builders share: the monolithic round
    # (_build_round_step) and the bucketed one (_get_bucket_collect_core
    # + _get_bucket_finalize) trace these same bodies.  The compile-time
    # flags come from self; none of them branches on who calls.
    # ------------------------------------------------------------------
    def _gather_axis(self, x):
        """Shard-local ``[K_local]`` -> full replicated ``[K]`` cohort
        (the median vote, the robust payload stack and the carry scatter
        need every client, not this shard's slice); under GSPMD the
        arrays are global already."""
        if self.partition_mode == "shard_map":
            return jax.lax.all_gather(x, CLIENTS_AXIS, axis=0, tiled=True)
        return x

    def _per_client_fn(self, update_for: Callable, params, strategy_state,
                       client_lr, round_idx, leakage_threshold,
                       quant_threshold, rng, cohort_ids, cohort_mask
                       ) -> Callable:
        """The per-client step that every round builder ``vmap``s (or,
        at ``clients_per_chunk: 1``, calls on one client): local training
        through the strategy, chaos corruption, the secure-aggregation
        mask, the live-client mask and the stale coin.

        ``update_for(rows)`` gives the client-update function from the
        client's trailing rows: the engine's own everywhere but in the
        megabatch collect, whose rows are what its lane scan has already
        trained and whose function hands them back."""
        strategy = self.strategy
        stale_prob = self.stale_prob
        carry_paged = self.carry_paged
        device_carry = self.device_carry
        chaos_corruption = self.chaos_corruption
        traffic_staleness = self.traffic_staleness
        wants_cohort = bool(getattr(strategy, "wants_cohort", False))

        def per_client(arr_c, mask_c, cm_c, cid_c, *rest):
            # Deterministic independent stream per (round, client):
            # jax.random.fold_in discipline (SURVEY.md §7 hard parts).
            # rng folds on the TRUE client id even under fleet paging —
            # only the carry table index is remapped — so a client's
            # whole local update is independent of which grid slot or
            # bucket it landed in: the bit-identity anchor
            rest = list(rest)
            slot_c = rest.pop(0) if carry_paged else cid_c
            corrupt_c = rest.pop(0) if chaos_corruption else None
            stale_c = rest.pop(0) if traffic_staleness else None
            rng_c = jax.random.fold_in(rng, cid_c)
            update_fn = update_for(tuple(rest))
            # traced staleness (fluteflow): the arrival plane's TRUE
            # broadcast-version gap replaces the strategy's in-jit
            # staleness model — passed only when the engine compiled the
            # operand in, so staleness-blind strategies keep their exact
            # call signature
            stale_kw = {"staleness": stale_c} if traffic_staleness else {}
            carry_row = None
            if device_carry:
                # carry strategies gather their own table rows from
                # strategy_state by row id (the client id for resident
                # tables, the page-pool SLOT id under fleet paging) and
                # return the per-client carry update row alongside the
                # payload
                parts, tl, ns, stats, carry_row = \
                    strategy.client_step_carry(
                        update_fn, params, arr_c, mask_c, client_lr,
                        rng_c, client_id=slot_c, live_mask=cm_c,
                        round_idx=round_idx,
                        leakage_threshold=leakage_threshold,
                        quant_threshold=quant_threshold,
                        strategy_state=strategy_state, **stale_kw)
            else:
                parts, tl, ns, stats = strategy.client_step(
                    update_fn, params, arr_c, mask_c, client_lr, rng_c,
                    round_idx=round_idx,
                    leakage_threshold=leakage_threshold,
                    quant_threshold=quant_threshold,
                    strategy_state=strategy_state, **stale_kw)
            if chaos_corruption:
                # adversarial chaos (resilience/chaos.py corrupt modes,
                # already gated on the live client_mask): the DEFAULT
                # payload this client would transmit is what gets
                # corrupted — local training, stats, and the claimed
                # weight stay honest-looking, exactly the threat
                # fluteshield screens for
                pg0, w0 = parts["default"]
                mult = jnp.where(
                    corrupt_c == chaos_modes.CORRUPT_SCALE,
                    self._corrupt_scale,
                    jnp.where(corrupt_c == chaos_modes.CORRUPT_SIGN_FLIP,
                              -self._corrupt_flip_scale, 1.0))
                bad = corrupt_c == chaos_modes.CORRUPT_NAN
                pg0 = jax.tree.map(
                    lambda g: (jnp.where(
                        bad, jnp.asarray(jnp.nan, g.dtype),
                        g * mult.astype(g.dtype))
                        if jnp.issubdtype(g.dtype, jnp.floating)
                        else g), pg0)
                parts = dict(parts)
                parts["default"] = (pg0, w0)
            sub_norm = jnp.zeros(())
            if wants_cohort:
                # secure aggregation: encode + pairwise-mask the
                # POST-corruption payload toward the SAMPLED cohort
                # (cohort_ids/cohort_mask, replicated: the round's, or
                # the bucket's sub-cohort — bucket placement changes a
                # client's mask graph, never its encoding, and masks
                # cancel exactly); the returned sub_norm is the
                # submitted-norm scalar a verified-aggregation server
                # would see — the shield's masked screening votes on it
                parts, sub_norm = strategy.mask_parts(
                    parts, cid_c, cm_c, cohort_ids, cohort_mask, round_idx)
            parts = {name: (tree, w * cm_c)
                     for name, (tree, w) in parts.items()}
            if stale_prob > 0.0:
                coin = jax.random.bernoulli(
                    jax.random.fold_in(rng_c, 3), stale_prob)
                stale = coin.astype(jnp.float32) * cm_c
            else:
                stale = jnp.zeros(())
            # carry_row is None (a leafless pytree — vmap passes it
            # through) unless the strategy runs in device-carry mode
            return (parts, tl * cm_c, ns * cm_c, stats, stale, carry_row,
                    sub_norm)

        return per_client

    def _shard_sums(self, parts, tls, nss, stats, stale, cm_k,
                    deferred_tree: Optional[str]) -> Dict[str, Any]:
        """One shard's (or chunk's) weighted sums over its clients: per
        payload part the weighted tree and its weight sums, then the
        loss / sample / stat sums.

        ``deferred_tree`` is where the two builders have drifted, each
        kept as it traces (ROADMAP debt *bucketed-round-drift*): the
        bucket collect sums the deferred clients' tree always, between
        the weight sums (``"inline"``); the monolithic round after them
        (``"last"``), and only where a client can be deferred or outside
        the chunk scan, which would carry the tree as an accumulator of
        its own (``None`` there; outside the scan XLA drops an unused
        one).  The model counters (``ctr_*``) are summed by the
        monolithic round alone, at its call."""
        strategy = self.strategy
        local = {"parts": {}}
        for name, (trees, ws) in parts.items():
            w_now = ws * (1.0 - stale)
            w_def = ws * stale
            wsum = lambda w, t: jax.tree.map(
                lambda g: jnp.tensordot(w, g, axes=[[0], [0]]), t)
            if name in strategy.unit_weight_parts:
                # masked payloads: every PRESENT slot enters with
                # coefficient exactly 1 (else pairwise masks cannot
                # cancel); the tensordot runs in the tree's own dtype so
                # int32 modular arithmetic wraps instead of promoting to
                # float
                gsum = jax.tree.map(
                    lambda g: jnp.tensordot(
                        cm_k.astype(g.dtype), g, axes=[[0], [0]]), trees)
                local["parts"][name] = {
                    "grad_sum": gsum,
                    "weight_sum": jnp.sum(w_now),
                    "grad_sum_def": jax.tree.map(jnp.zeros_like, gsum),
                    "weight_sum_def": jnp.sum(w_def),
                    "weight_sum_raw": jnp.sum(ws),
                }
                continue
            sums = {"grad_sum": wsum(w_now, trees),
                    "weight_sum": jnp.sum(w_now)}
            if deferred_tree == "inline":
                sums["grad_sum_def"] = wsum(w_def, trees)
            sums["weight_sum_def"] = jnp.sum(w_def)
            sums["weight_sum_raw"] = jnp.sum(ws)
            if deferred_tree == "last":
                sums["grad_sum_def"] = wsum(w_def, trees)
            local["parts"][name] = sums
        local.update(_stat_sums(tls, nss, stats, cm_k))
        return local

    @property
    def _carry_split(self) -> bool:
        """Whether the paged carry tables ride a slot-axis-sharded
        operand of their own (fleet paging under ``shard_map``)."""
        return self.carry_paged and self.partition_mode == "shard_map"

    def _trailing_operands(self) -> tuple:
        """The optional operands that trail a collect's fixed ones, in
        their one positional order, as ``(name, present, spec)``: each
        builder reads its ``in_specs`` and its unpacking from this, so
        which slot means what is written once (with corruption off and
        the pool on, the pool must not land in ``corrupt_mode``)."""
        cspec, rspec = P(CLIENTS_AXIS), P()
        # mesh-sharded page pool (fleet paging x shard_map): the carry
        # tables enter the shard_map as their OWN operand with a
        # P(CLIENTS_AXIS) slot-axis spec (the rest of strategy_state
        # stays replicated), and the GLOBAL carry_slots convert to
        # shard-local indices in-body — the gather/scatter is local to
        # the shard that computes the lane, no cross-shard collective.
        # GSPMD mode keeps global ids and lets the partitioner place
        # the (still slot-axis-sharded) tables.
        return (("carry_tables", self._carry_split, cspec),
                ("carry_slots", self.carry_paged, cspec),
                ("corrupt_mode", self.chaos_corruption, cspec),
                ("staleness", self.traffic_staleness, cspec),
                ("pool", self._pool is not None, rspec))

    def _unpack_trailing(self, trailing: tuple, strategy_state, rest):
        """Inside the ``shard_map``: the trailing positional operands
        back to ``shard_body``'s keywords.  Returns the strategy state
        (with this shard's table block under a sharded page pool) and
        the keyword dict."""
        rest = list(rest)
        kw = {name: rest.pop(0) if on else None for name, on, _ in trailing}
        tables = kw.pop("carry_tables")
        if tables is not None:
            # sharded pool: this shard's table block rejoins the
            # replicated state, and the global slot ids drop to
            # block-local (padding stays -1) — the allocator guaranteed
            # every lane's slot lives on this shard
            strategy_state = {**strategy_state, **tables}
            off = jax.lax.axis_index(CLIENTS_AXIS) * self._carry_shard_slots
            slots = kw["carry_slots"]
            kw["carry_slots"] = jnp.where(slots >= 0, slots - off, -1)
        return strategy_state, kw

    def _fold_faults(self, strategy_state, sample_mask, client_mask,
                     client_ids, extra_args):
        """Ahead of the collect: fold the round's (or the bucket's)
        fault and staleness operands into its masks, count them, and
        line the collect's trailing operands up in
        :meth:`_trailing_operands`' order.  Returns ``(sample_mask,
        client_mask, carry_slots, collect_state, trailing_args,
        stats)``; the counters in ``stats`` leave through the same
        packed single-transfer buffer as every other stat (per bucket
        they sum additively in finalize).

        Chaos client faults (extra data operands, present only when the
        engine was built with them): dropout multiplies into
        client_mask — downstream everything (strategy weights, psum
        denominators, stats) renormalizes exactly like mesh padding —
        and straggling truncates sample_mask's step grid, so a
        straggler's PARTIAL local work still aggregates
        (CLIP/FedBuff-style partial participation)."""
        stats = {}
        f32 = jnp.float32
        n_used = 0
        if self.carry_paged:
            # fleet paging: the host-remapped pool slot per lane — the
            # carry gather/scatter index; everything else keeps using
            # the true client ids
            carry_slots = extra_args[0]
            n_used = 1
        else:
            carry_slots = client_ids
        if self.chaos_client_faults:
            chaos_drop, chaos_keep = \
                extra_args[n_used], extra_args[n_used + 1]
            n_used += 2
            step_live = (jnp.sum(sample_mask, axis=-1) > 0)      # [K, S]
            real_steps = jnp.sum(step_live, axis=-1)             # [K]
            keep_f = (jnp.arange(sample_mask.shape[-2])[None, :]
                      < chaos_keep[:, None]).astype(f32)         # [K, S]
            live_cm = client_mask * (1.0 - chaos_drop)
            stats = {
                "chaos_dropped": jnp.sum(client_mask * chaos_drop),
                "chaos_straggled": jnp.sum(
                    live_cm * (chaos_keep < real_steps)),
                "chaos_steps_lost": jnp.sum(
                    step_live.astype(f32) * (1.0 - keep_f)
                    * live_cm[:, None]),
            }
            sample_mask = sample_mask * keep_f[..., None].astype(
                sample_mask.dtype)
            client_mask = live_cm
        corrupt_args = ()
        if self.chaos_corruption:
            # adversarial corruption modes (one more per-round data
            # operand): gated on the LIVE mask — a dropped client never
            # transmits, and a padding slot's zero payload must not be
            # NaN'd into the sum (0-weight x NaN is still NaN through a
            # tensordot)
            corrupt_mode = extra_args[n_used]
            n_used += 1
            corrupt_mode = jnp.where(client_mask > 0, corrupt_mode, 0)
            stats.update({
                name: jnp.sum((corrupt_mode == mode).astype(f32))
                for name, mode in (
                    ("chaos_nan_injected", chaos_modes.CORRUPT_NAN),
                    ("chaos_scaled", chaos_modes.CORRUPT_SCALE),
                    ("chaos_sign_flipped", chaos_modes.CORRUPT_SIGN_FLIP))})
            corrupt_args = (corrupt_mode,)
        stale_args = ()
        if self.traffic_staleness:
            # fluteflow traced staleness (one more per-round data
            # operand): gated on the LIVE mask — padding slots and
            # chaos-dropped clients contribute nothing, so their
            # staleness must not count — and binned into the
            # per-staleness histogram that rides the packed stats (the
            # host replay oracle in traffic/schedule.py is the
            # cross-check).  The strategy consumes the TRUE value; only
            # the histogram clips at its last (overflow) bin.
            stale_vec = extra_args[n_used]
            n_used += 1
            stale_vec = jnp.where(client_mask > 0, stale_vec, 0)
            live = (client_mask > 0).astype(f32)
            binned = jnp.minimum(stale_vec, STALE_HIST_BINS - 1)
            stats.update({
                f"traffic_stale_{b}": jnp.sum(
                    (binned == b).astype(f32) * live)
                for b in range(STALE_HIST_BINS)})
            stats["traffic_stale_sum"] = jnp.sum(
                stale_vec.astype(f32) * live)
            stale_args = (stale_vec,)
        pool_args = tuple(extra_args[n_used:])
        if self._carry_split:
            # the sharded pool tables ride their own cspec operand;
            # everything else in strategy_state stays replicated
            carry_keys = tuple(self.strategy.carry_tables)
            collect_state = {k: v for k, v in strategy_state.items()
                             if k not in carry_keys}
            carry_tab_args = ({k: strategy_state[k] for k in carry_keys},)
        else:
            collect_state = strategy_state
            carry_tab_args = ()
        trailing_args = carry_tab_args + \
            ((carry_slots,) if self.carry_paged else ()) + \
            corrupt_args + stale_args + pool_args
        return (sample_mask, client_mask, carry_slots, collect_state,
                trailing_args, stats)

    # ------------------------------------------------------------------
    def _build_round_step(self) -> Callable:
        strategy = self.strategy
        client_update = self.client_update
        stale_prob = self.stale_prob
        mesh = self.mesh
        cspec = P(CLIENTS_AXIS)
        rspec = P()

        clients_per_chunk = self.clients_per_chunk
        # fluteshield statics: all compile-time branches — a config
        # without robust/corruption traces the exact legacy program
        shield = self.shield
        robust_stack = shield is not None and shield.wants_stack
        chaos_corruption = self.chaos_corruption
        # fluteflow static: the traced-staleness operand threads AFTER
        # corrupt_mode in every positional order below
        traffic_staleness = self.traffic_staleness
        # universal-overlap statics: both compile-time branches — a
        # config without fused_carry traces the exact legacy program
        device_carry = self.device_carry
        carry_paged = self.carry_paged
        rl_fused = self.rl_fused
        fused_rl = self._rl
        trailing = self._trailing_operands()
        gather_axis = self._gather_axis
        # secure-aggregation statics: wants_cohort routes the default
        # payload through the strategy's mask_parts AFTER corruption
        # (the adversary attacks the float payload the client would
        # transmit; the int32 group element is transport, not target)
        # and masked_screen switches fluteshield to submitted-norm
        # voting (the masked stack carries no plaintext norm signal)
        wants_cohort = bool(getattr(strategy, "wants_cohort", False))
        masked_screen = shield is not None and wants_cohort
        # the deferred clients' tree (see _shard_sums)
        deferred_tree = "last" if stale_prob > 0.0 or \
            not clients_per_chunk else None

        def shard_body(params, strategy_state, arrays, sample_mask,
                       client_mask, client_ids, client_lr, round_idx,
                       leakage_threshold, quant_threshold, rng,
                       cohort_ids=None, cohort_mask=None,
                       carry_slots=None, corrupt_mode=None,
                       staleness=None, pool=None):
            per_client = self._per_client_fn(
                lambda rows: client_update, params, strategy_state,
                client_lr, round_idx, leakage_threshold, quant_threshold,
                rng, cohort_ids, cohort_mask)

            def process_chunk(arr_k, sm_k, cm_k, cid_k, *rest_k):
                """One chunk of clients -> (summed locals, per-client
                privacy stats, raw parts, effective client mask).  The
                whole shard is one chunk in the default path."""
                rest_k = list(rest_k)
                slot_k = rest_k.pop(0) if carry_paged else None
                corrupt_k = rest_k.pop(0) if chaos_corruption else None
                stale_k = rest_k.pop(0) if traffic_staleness else None
                if pool is not None:
                    arr_k = gather_pool(pool, arr_k, sm_k)
                vmap_args = (arr_k, sm_k, cm_k, cid_k) + \
                    ((slot_k,) if carry_paged else ()) + \
                    ((corrupt_k,) if chaos_corruption else ()) + \
                    ((stale_k,) if traffic_staleness else ())
                if clients_per_chunk == 1:
                    # one client at a time: the client's program as it
                    # is, with no batch axis (a Pallas call with scalar
                    # prefetch does not batch, and a tree of 10^8
                    # parameters gains nothing from a leading 1)
                    one = per_client(*jax.tree.map(lambda a: a[0],
                                                   vmap_args))
                    parts, tls, nss, stats, stale, carry_rows, sub_norms = \
                        jax.tree.map(lambda a: a[None], one)
                else:
                    parts, tls, nss, stats, stale, carry_rows, \
                        sub_norms = jax.vmap(per_client)(*vmap_args)
                # per-client privacy-attack metrics stay per-client (the
                # server needs the distribution for the adaptive leakage
                # threshold, core/server.py:397-409)
                privacy_per_client = {k: v for k, v in stats.items()
                                      if k.startswith("privacy_")}
                stats = {k: v for k, v in stats.items()
                         if not k.startswith("privacy_")}

                shield_counts = None
                if shield is not None:
                    # fluteshield screening: quarantine from the ACTUAL
                    # would-be-aggregated payloads, then exclude the
                    # quarantined clients from every downstream sum via
                    # jnp.where — a `0 *` multiply would let a NaN leaf
                    # re-poison the very aggregate it was caught in
                    pg_k, w_k = parts["default"]
                    if masked_screen:
                        # masked submissions carry no plaintext norm or
                        # finiteness signal — vote on the per-client
                        # SUBMITTED norms instead (the verified-
                        # aggregation model; robust/shield.py)
                        keep, q_nonfinite, q_norm = shield.screen_masked(
                            sub_norms, tls, w_k, cm_k, gather_axis)
                    else:
                        keep, q_nonfinite, q_norm = shield.screen(
                            pg_k, tls, w_k, cm_k, gather_axis)
                    keep_b = keep > 0
                    pg_k = jax.tree.map(
                        lambda g: jnp.where(
                            keep_b.reshape((-1,) + (1,) * (g.ndim - 1)),
                            g, jnp.zeros_like(g)), pg_k)
                    parts = dict(parts)
                    parts["default"] = (pg_k, jnp.where(keep_b, w_k, 0.0))
                    tls = jnp.where(keep_b, tls, 0.0)
                    nss = jnp.where(keep_b, nss, 0.0)
                    stats = {k: jnp.where(keep_b, v, 0.0)
                             for k, v in stats.items()}
                    # fold into the client mask: counts, stat means, and
                    # aggregation weights renormalize on device exactly
                    # like mesh padding / chaos dropout
                    cm_k = cm_k * keep
                    shield_counts = (jnp.sum(q_nonfinite),
                                     jnp.sum(q_norm))

                local = self._shard_sums(parts, tls, nss, stats, stale, cm_k,
                                         deferred_tree)
                for key in stats:
                    if key.startswith("ctr_"):
                        # what the model counted in its forward passes
                        # (BaseTask.counter_names), over the live clients
                        local[key] = jnp.sum(stats[key] * cm_k)
                if shield_counts is not None:
                    # per-cause quarantine counters: psum'd with the
                    # other locals and packed into the single-transfer
                    # stats buffer — zero new device_gets
                    local["shield_nonfinite"] = shield_counts[0]
                    local["shield_norm_outlier"] = shield_counts[1]
                extras = {}
                if device_carry:
                    extras["carry"] = carry_rows
                if rl_fused:
                    # the RL tuner needs the full per-client payload stack
                    # (to re-weight) and the reference feature layout
                    # (weight, magnitude, mean, variance per client)
                    extras["rl"] = {
                        "stack": parts["default"][0],
                        "w": parts["default"][1],
                        "mag": stats["mag"], "mean": stats["mean"],
                        "var": stats["var_corrected"],
                    }
                return local, privacy_per_client, parts, cm_k, extras

            k_local = sample_mask.shape[0]
            if clients_per_chunk and clients_per_chunk < k_local:
                if k_local % clients_per_chunk != 0:
                    raise ValueError(
                        f"clients_per_chunk={clients_per_chunk} must divide "
                        f"the per-shard client grid ({k_local}); pad "
                        "num_clients_per_iteration or pick a divisor")

                def to_chunks(x):
                    return x.reshape((k_local // clients_per_chunk,
                                      clients_per_chunk) + x.shape[1:])

                xs = jax.tree.map(to_chunks, (arrays, sample_mask,
                                              client_mask, client_ids) +
                                  ((corrupt_mode,) if chaos_corruption
                                   else ()))

                def scan_body(acc, xs_c):
                    local_c, priv_c, _, _, _ = process_chunk(*xs_c)
                    return jax.tree.map(jnp.add, acc, local_c), priv_c

                zero_local = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype),
                    jax.eval_shape(lambda c: process_chunk(*c)[0],
                                   jax.tree.map(lambda x: x[0], xs)))
                local, priv_chunks = jax.lax.scan(scan_body, zero_local, xs)
                # [C, chunk] per-client stats back to the flat [K] layout
                privacy_per_client = jax.tree.map(
                    lambda y: y.reshape((-1,) + y.shape[2:]), priv_chunks)
                parts = None  # never materialized across all K — the point
                cm_eff = None
                extras = {}
            else:
                (local, privacy_per_client, parts, cm_eff,
                 extras) = process_chunk(
                    arrays, sample_mask, client_mask, client_ids,
                    *((carry_slots,) if carry_paged else ()),
                    *((corrupt_mode,) if chaos_corruption else ()),
                    *((staleness,) if traffic_staleness else ()))
            if self.partition_mode == "shard_map":
                # the "harvest": one collective instead of K P2P recvs
                total = jax.lax.psum(local, CLIENTS_AXIS)
            else:
                total = local
            if self.dump_norm_stats and parts and "default" in parts:
                # per-client PAYLOAD norm + cosine vs the aggregate
                # direction (reference norm_stats.txt/cosines.txt dumps over
                # client_parameters_stack — i.e. post-transform payloads —
                # core/server.py:392-395, fedavg.py:149-152); the weighted
                # grad SUM has the aggregate's direction, so cosines match
                # the reference's vs-agg values exactly
                pgs, _ = parts["default"]
                gsum = total["parts"]["default"]["grad_sum"]
                dots = jax.tree.map(
                    lambda g, G: jnp.tensordot(
                        g.reshape(g.shape[0], -1), G.reshape(-1), axes=1),
                    pgs, gsum)
                dot = sum(jax.tree.leaves(dots))
                sqs = jax.tree.map(
                    lambda g: jnp.sum(g.reshape(g.shape[0], -1) ** 2, axis=1),
                    pgs)
                pg_norm = jnp.sqrt(sum(jax.tree.leaves(sqs)))
                gnorm = optax.global_norm(gsum)
                privacy_per_client["norm"] = pg_norm
                privacy_per_client["cosine"] = dot / jnp.maximum(
                    pg_norm * gnorm, 1e-12)
            out = (total, privacy_per_client)
            if robust_stack:
                # the Byzantine-robust combine (coordinate-wise trimmed
                # mean / median, strategies/robust.py) needs the full
                # SCREENED per-client payload stack replicated: the
                # estimator's inherent K x model memory cost, paid in
                # HBM inside the program — nothing crosses to the host
                stack_tree = jax.tree.map(gather_axis,
                                          parts["default"][0])
                stack_keep = gather_axis(cm_eff)
                out += (stack_tree, stack_keep)
            if masked_screen:
                # the post-quarantine survivor mask, replicated: the
                # round step needs it to cancel the residual pairwise
                # masks of (survivor, quarantined) edges and to
                # renormalize the decode over survivors only
                out += (gather_axis(cm_eff),)
            if device_carry:
                # replicated full-cohort carry rows: every shard scatters
                # the identical update, so strategy_state stays replicated
                out += (jax.tree.map(gather_axis, extras["carry"]),)
            if rl_fused:
                # full per-client payload stack + feature vectors for the
                # in-program re-weighting (reference keeps
                # client_parameters_stack for this, dga.py:317-330)
                out += (jax.tree.map(gather_axis, extras["rl"]),)
            return out

        def shard_entry(params, strategy_state, arrays, sample_mask,
                        client_mask, client_ids, client_lr, round_idx,
                        leakage_threshold, quant_threshold, rng,
                        cohort_ids, cohort_mask, *rest):
            # the monolithic round always takes the cohort operands
            # (the bucket collect only under wants_cohort)
            strategy_state, kw = self._unpack_trailing(
                trailing, strategy_state, rest)
            return shard_body(params, strategy_state, arrays, sample_mask,
                              client_mask, client_ids, client_lr,
                              round_idx, leakage_threshold,
                              quant_threshold, rng, cohort_ids,
                              cohort_mask, **kw)

        if self.partition_mode == "shard_map":
            out_specs = (rspec, cspec) + \
                ((rspec, rspec) if robust_stack else ()) + \
                ((rspec,) if masked_screen else ()) + \
                ((rspec,) if device_carry else ()) + \
                ((rspec,) if rl_fused else ())
            sharded_collect = shard_map(
                shard_entry, mesh=mesh,
                in_specs=(rspec, rspec, cspec, cspec, cspec, cspec, rspec,
                          rspec, rspec, rspec, rspec, rspec, rspec) +
                         tuple(spec for _, on, spec in trailing if on),
                out_specs=out_specs, check_vma=False)
        else:
            # GSPMD mode: plain jit — client data stays sharded on the
            # 'clients' axis, params sharded per infer_model_sharding on the
            # 'model' axis; XLA's SPMD partitioner inserts the collectives
            # (enables tensor-parallel BERT, which the reference lacks).
            sharded_collect = shard_entry

        @_round_scoped
        def round_step(params, opt_state, strategy_state, arrays, sample_mask,
                       client_mask, client_ids, client_lr, server_lr,
                       round_idx, leakage_threshold, quant_threshold, rng,
                       *extra_args):
            # the round's SAMPLED cohort mask, captured BEFORE chaos
            # dropout folds in: secure-aggregation clients mask toward
            # the sampled cohort, so the cancellation pass needs both
            # masks to find the (survivor, lost) edges
            sampled_cm = client_mask
            (sample_mask, client_mask, carry_slots, collect_state,
             trailing_args, fault_stats) = self._fold_faults(
                strategy_state, sample_mask, client_mask, client_ids,
                extra_args)
            # strategies may move the broadcast point off the canonical
            # params (e.g. FedAC's momentum-like md point); default identity
            bcast = strategy.broadcast_params(params, strategy_state)
            collect_out = sharded_collect(
                bcast, collect_state, arrays, sample_mask, client_mask,
                client_ids, client_lr, round_idx, leakage_threshold,
                quant_threshold, rng, client_ids, sampled_cm,
                *trailing_args)
            collected, privacy_per_client = collect_out[0], collect_out[1]
            pos = 2
            if robust_stack:
                stack_tree, stack_keep = collect_out[pos:pos + 2]
                pos += 2
            if masked_screen:
                survivors = collect_out[pos]
                pos += 1
            if device_carry:
                carry_full = collect_out[pos]
                pos += 1
            if rl_fused:
                rl_pc = collect_out[pos]
                pos += 1
            part_sums = collected["parts"]
            secagg_stats = {}
            if wants_cohort:
                # secure-aggregation mask recovery: subtract the residual
                # one-sided masks of every (survivor, lost) pair so the
                # int32 sum telescopes back to exactly the survivors'
                # encodings.  Both masks are DATA — no dropout pattern
                # recompiles.  Without a shield the survivor set is the
                # post-chaos live mask; quarantine shrinks it further.
                if not masked_screen:
                    survivors = client_mask
                default = dict(part_sums["default"])
                gsum = strategy.cancel_masks(
                    default["grad_sum"], client_ids, sampled_cm,
                    survivors, round_idx)
                f32 = jnp.float32
                secagg_stats = {
                    "secagg_recovered_dropout": jnp.sum(
                        ((sampled_cm > 0) & (client_mask <= 0))
                        .astype(f32)),
                    "secagg_recovered_quarantine": jnp.sum(
                        ((client_mask > 0) & (survivors <= 0))
                        .astype(f32)),
                }
                min_surv = int(getattr(strategy, "min_survivors", 0) or 0)
                if min_surv > 0:
                    # SecAgg's t-of-K liveness floor: too few survivors
                    # aborts the round on device — the aggregate zeroes,
                    # the server step is a no-op, and the abort flag
                    # rides the packed stats
                    abort = (jnp.sum(survivors) <
                             jnp.asarray(min_surv, survivors.dtype))
                    gsum = jax.tree.map(
                        lambda g: g * (1 - abort.astype(g.dtype)), gsum)
                    secagg_stats["secagg_abort"] = abort.astype(f32)
                default["grad_sum"] = gsum
                part_sums = dict(part_sums)
                part_sums["default"] = default
            deferred = None
            if stale_prob > 0.0:
                default = part_sums["default"]
                deferred = {"grad_sum": default["grad_sum_def"],
                            "weight_sum": default["weight_sum_def"]}
            rl_stats = {}
            if robust_stack:
                # Byzantine-robust combine over the screened stack
                # (strategies/robust.py); strategy state passes through
                # untouched — RobustFedAvg is stateless by construction
                agg = strategy.combine_stack(stack_tree, stack_keep,
                                             jax.random.fold_in(rng, 17))
                new_strategy_state = strategy_state
            elif rl_fused:
                # fused RL replaces the combine: the DQN tuner re-weights
                # the gathered payload stack in-program; its whole carry
                # (net, optimizer, replay ring, epsilon, delayed reward)
                # rides strategy_state["rl"] (rl/fused.py)
                cur_loss = collected["train_loss_sum"] / jnp.maximum(
                    collected["client_count"], 1.0)
                agg, new_rl_state, rl_stats = fused_rl.combine(
                    strategy_state["rl"],
                    {k: rl_pc[k] for k in ("w", "mag", "mean", "var")},
                    rl_pc["stack"], cur_loss, jax.random.fold_in(rng, 29))
                new_strategy_state = {"base": strategy_state["base"],
                                      "rl": new_rl_state}
            else:
                agg, new_strategy_state = strategy.combine_parts(
                    part_sums, deferred, strategy_state,
                    jax.random.fold_in(rng, 17),
                    num_clients=collected["client_count"],
                    global_params=bcast)
            if device_carry:
                # scatter the round's per-client carry rows (SCAFFOLD
                # controls / EF residuals / personalization heads) back
                # into the donated strategy_state tables — the round-k ->
                # k+1 dependency the pipeline needed off the host.
                # carry_slots IS client_ids outside fleet paging.
                new_strategy_state = strategy.apply_carry(
                    new_strategy_state, carry_slots, carry_full,
                    rng=jax.random.fold_in(rng, 31))
            if self.server_max_grad_norm is not None:
                agg = _clip_by_global_norm(agg, float(self.server_max_grad_norm))
            if strategy.owns_server_update:
                # multi-sequence schemes (FedAC) apply their own coupled
                # update; the optax state passes through untouched
                new_params, new_strategy_state = strategy.apply_server_update(
                    params, agg, new_strategy_state, server_lr)
                new_opt_state = opt_state
            else:
                # server optimizer over the aggregate pseudo-gradient
                # (reference ModelUpdater.update_model, core/trainer.py:127-137)
                opt_state.hyperparams["learning_rate"] = server_lr
                updates, new_opt_state = self.server_tx.update(
                    agg, opt_state, params)
                new_params = optax.apply_updates(params, updates)
            round_stats = _round_stats(collected, part_sums, agg)
            round_stats.update({k: v for k, v in collected.items()
                                if k.startswith("ctr_")})
            round_stats.update(fault_stats)
            round_stats.update(secagg_stats)
            round_stats.update(rl_stats)
            if shield is not None:
                # per-cause quarantine counters out through the same
                # packed single transfer as every other stat
                round_stats["shield_nonfinite"] = \
                    collected["shield_nonfinite"]
                round_stats["shield_norm_outlier"] = \
                    collected["shield_norm_outlier"]
            for k, v in privacy_per_client.items():
                round_stats[k] = v
            if self.devbus.enabled:
                # engine's own publisher: relative APPLIED update size
                # ‖Δθ‖/‖θ‖ — the training-health scalar a grad norm
                # alone hides (a huge gradient into huge weights is
                # fine; into tiny ones is a blow-up).  Δθ is the
                # post-optimizer delta (new - old), NOT the aggregate
                # pseudo-gradient: the server lr / momentum transform
                # scales the actual step, and this scalar must report
                # what was applied.  Published like any strategy scalar
                # and drained into the packed stats below.
                applied = jax.tree.map(lambda a, b: a - b,
                                       new_params, params)
                self.devbus.publish(
                    "update_ratio",
                    optax.global_norm(applied)
                    / (optax.global_norm(new_params) + 1e-12))
                round_stats.update(self.devbus.drain())
            # single-transfer stats: pack the whole stats tree into one
            # 1-D buffer per dtype INSIDE the program (pure reshape/concat,
            # XLA fuses it), so the host fetches one buffer per dtype group
            # per round instead of ~a dozen scalars.  The packer (the slot
            # table the host decodes with) is recorded at trace time under
            # a key both sides can compute from the round geometry alone —
            # for one engine the stats tree is a function of K only.
            packer = FlatPacker(round_stats)
            # sample_mask is [K, S, B] here (scan slices the leading round
            # axis off before core runs), so K = shape[-3].  Deliberate
            # trace-time effect: the packer IS this trace's slot table —
            # written once per compile, read only by the host decoder.
            self._stats_packers[("single", sample_mask.shape[-3])] = packer
            return (new_params, new_opt_state, new_strategy_state,
                    packer.pack(round_stats))

        return round_step

    # ------------------------------------------------------------------
    def _multi_core(self, num_rounds: int) -> Callable:
        """The un-jitted ``lax.scan``-over-rounds program body, which the
        staged dispatch wraps in its unpacking jit.

        TPU-first perf feature with no reference equivalent: FLUTE pays a
        full server<->worker protocol exchange per round
        (``core/federated.py:281-424``); even our single-round program pays
        one host dispatch per round, which dominates when the controller is
        far from the chips.  Scanning R rounds inside one program amortizes
        dispatch/transfer to once per R rounds; client sampling stays
        host-side (it is data-independent lookahead), eval boundaries cap R.
        """
        core = self._round_step_core
        chaos_faults = self.chaos_client_faults
        chaos_corruption = self.chaos_corruption
        n_extra = (1 if self.carry_paged else 0) + \
            (2 if chaos_faults else 0) + \
            (1 if chaos_corruption else 0) + \
            (1 if self.traffic_staleness else 0)

        def multi(params, opt_state, strategy_state, arrays, sample_mask,
                  client_mask, client_ids, client_lrs, server_lrs,
                  round_idxs, leakage_threshold, quant_thresholds, rngs,
                  *extra_args):
            # per-round trailing operands — carry slots ([R, K], fleet
            # paging) then chaos drop/keep and/or corrupt modes — scan
            # with the rest of the round inputs; the resident pool
            # stays a carried constant
            chaos_args = extra_args[:n_extra]
            pool_args = extra_args[n_extra:]

            def body(carry, xs):
                p, o, s = carry
                arr, sm, cm, cid, clr, slr, ridx, qt, rng = xs[:9]
                chaos_xs = xs[9:]
                p, o, s, stats = core(p, o, s, arr, sm, cm, cid, clr, slr,
                                      ridx, leakage_threshold, qt, rng,
                                      *chaos_xs, *pool_args)
                return (p, o, s), stats

            xs = (arrays, sample_mask, client_mask, client_ids,
                  client_lrs, server_lrs, round_idxs, quant_thresholds,
                  rngs) + tuple(chaos_args)
            (p, o, s), stats = jax.lax.scan(
                body, (params, opt_state, strategy_state), xs)
            return p, o, s, stats

        return multi

    # ------------------------------------------------------------------
    # RL support: a round variant that also returns per-client payloads so
    # the meta-aggregator can re-weight them (reference keeps
    # client_parameters_stack for this, core/strategies/dga.py:317-330).
    def _build_payload_step(self, with_offsets: bool = False):
        strategy = self.strategy
        client_update = self.client_update
        mesh = self.mesh
        cspec = P(CLIENTS_AXIS)
        rspec = P()

        @_round_scoped
        def shard_body(params, strategy_state, arrays, sample_mask,
                       client_mask, client_ids, client_lr, rng,
                       leakage_threshold, offsets_flat=None):
            def per_client(arr_c, mask_c, cm_c, cid_c, off_c):
                rng_c = jax.random.fold_in(rng, cid_c)
                off_tree = None
                if off_c is not None:
                    from jax.flatten_util import ravel_pytree
                    _, unravel = ravel_pytree(params)
                    off_tree = unravel(off_c)
                parts, tl, ns, stats = strategy.client_step(
                    client_update, params, arr_c, mask_c, client_lr, rng_c,
                    leakage_threshold=leakage_threshold,
                    strategy_state=strategy_state, grad_offset=off_tree)
                pg, w = parts["default"]
                return pg, w * cm_c, tl * cm_c, stats
            return jax.vmap(per_client, in_axes=(0, 0, 0, 0,
                                                 0 if with_offsets else None))(
                arrays, sample_mask, client_mask, client_ids, offsets_flat)

        fn = shard_map(shard_body, mesh=mesh,
                       in_specs=(rspec, rspec, cspec, cspec, cspec, cspec,
                                 rspec, rspec, rspec) +
                                ((cspec,) if with_offsets else ()),
                       out_specs=cspec, check_vma=False)
        return jax.jit(fn)

    def client_payloads(self, state: ServerState, batch: RoundBatch,
                        client_lr: float, rng: jax.Array,
                        grad_offsets: Optional[np.ndarray] = None,
                        leakage_threshold: Optional[float] = None):
        """Per-client ``(pseudo_grad [K,...], weight [K], train_loss [K],
        stats [K])`` — the payload program behind RL re-weighting
        (reference keeps ``client_parameters_stack``, ``dga.py:317-330``)
        and SCAFFOLD control-variate rounds.

        ``grad_offsets`` (optional ``[K, n_params]`` flat f32 array) is the
        per-client drift correction added to every local step's gradient
        (SCAFFOLD's ``c - c_i``); rows for padding clients must be zero.
        ``leakage_threshold`` enables the same privacy-leakage client
        dropping the fused round applies (``wt=0`` above threshold).
        """
        key = "_payload_step_off" if grad_offsets is not None \
            else "_payload_step"
        if not hasattr(self, key):
            setattr(self, key, self._instrument(
                key.lstrip("_"), self._build_payload_step(
                    with_offsets=grad_offsets is not None)))
        args = [
            state.params, state.strategy_state,
            # flint: disable=put-loop host-orchestrated legacy round path; fused_carry is the staged overlap path
            {k: jax.device_put(v, self._client_sharding)
             for k, v in batch.arrays.items()},
            jax.device_put(batch.sample_mask, self._client_sharding),
            jax.device_put(batch.client_mask, self._client_sharding),
            jax.device_put(batch.client_ids, self._client_sharding),
            jnp.asarray(client_lr, jnp.float32), rng,
            jnp.asarray(leakage_threshold if leakage_threshold is not None
                        else jnp.inf, jnp.float32),
        ]
        if grad_offsets is not None:
            # device arrays (DeviceControlTable.offsets) pass through —
            # np.asarray would round-trip the matrix via the host; numpy
            # goes through a sharded put directly (staging via jnp.asarray
            # would commit the whole [K, n_params] matrix to one device)
            if not isinstance(grad_offsets, jax.Array):
                grad_offsets = np.asarray(grad_offsets, np.float32)
            args.append(jax.device_put(grad_offsets, self._client_sharding))
        fn = getattr(self, key)
        out = self._launch(fn, *args)
        self._note_compiles(key.lstrip("_"), fn)
        return out

    def apply_custom_weights(self, state: ServerState, pgs, weights,
                             server_lr: float) -> ServerState:
        """Aggregate per-client payloads with externally chosen weights and
        take a server step — the RL re-aggregation
        (``sum pg_k * w_k / sum w_k``, reference ``dga.py:317-332``)."""
        if not hasattr(self, "_custom_agg"):
            server_tx = self.server_tx

            def agg_fn(params, opt_state, pgs, weights, server_lr):
                wsum = jnp.maximum(jnp.sum(weights), 1e-12)
                agg = jax.tree.map(
                    lambda g: jnp.tensordot(weights, g, axes=[[0], [0]]) / wsum,
                    pgs)
                if self.server_max_grad_norm is not None:
                    agg = _clip_by_global_norm(
                        agg, float(self.server_max_grad_norm))
                opt_state.hyperparams["learning_rate"] = server_lr
                updates, new_opt = server_tx.update(agg, opt_state, params)
                return optax.apply_updates(params, updates), new_opt

            self._custom_agg = self._instrument("custom_agg",
                                                jax.jit(agg_fn))
        params, opt_state = self._custom_agg(
            state.params, state.opt_state, pgs,
            jax.device_put(jnp.asarray(weights, jnp.float32),
                           self._client_sharding),
            jnp.asarray(server_lr, jnp.float32))
        self._note_compiles("custom_agg", self._custom_agg)
        return ServerState(params, opt_state, state.strategy_state,
                           state.round + 1)

    # ------------------------------------------------------------------
    def _chaos_host(self, chaos_vecs: Optional[list],
                    rounds: int) -> list:
        """Validate + assemble the per-round fault/staleness vectors as
        HOST numpy arrays, one tuple a round with one entry per trailing
        program operand: ``(drop [K], keep_steps [K])`` when client
        faults compiled in, followed by ``(corrupt_mode [K],)`` when
        corruption compiled in, followed by ``(staleness [K],)`` when
        traced staleness compiled in (fluteflow) — or empty tuples when
        the engine compiled without any.  Mismatches are programming
        errors and raise."""
        dtypes = ([np.float32, np.float32] if self.chaos_client_faults
                  else []) + \
                 ([np.int32] if self.chaos_corruption else []) + \
                 ([np.int32] if self.traffic_staleness else [])
        if not dtypes:
            if chaos_vecs:
                raise ValueError(
                    "chaos vectors supplied but the engine was built "
                    "without chaos client faults, corruption, or traced "
                    "staleness (server_config.chaos / traffic)")
            return [()] * rounds
        if not chaos_vecs:
            raise ValueError(
                "engine built with chaos client faults/corruption/"
                "traced staleness: every dispatch needs the per-round "
                "vectors")
        if len(chaos_vecs) != rounds or \
                any(len(entry) != len(dtypes) for entry in chaos_vecs):
            raise ValueError(
                f"chaos vector arity mismatch: engine expects "
                f"{len(dtypes)} per-round vectors for each of {rounds} "
                f"rounds (faults={self.chaos_client_faults}, "
                f"corruption={self.chaos_corruption}, "
                f"staleness={self.traffic_staleness})")
        return [tuple(np.asarray(entry[i], dt)
                      for i, dt in enumerate(dtypes))
                for entry in chaos_vecs]

    # ------------------------------------------------------------------
    # single-buffer input staging: the dispatch half of the flatpack
    # idea.  Everything the host assembles per round — the feature (or
    # index) grids, sample/client masks, client ids, chaos fault vectors,
    # and the lr/round/threshold scalars — crosses the host boundary as
    # ONE buffer per dtype group (clients-axis operands via AxisPacker,
    # replicated scalars via ScalarStager); the inverse runs INSIDE the
    # jitted program as static slices/reshapes XLA fuses away
    # (the staging tests pin the transfer count).  The clients-axis
    # buffers are the engine's own (StagingPool): each round's leaves
    # are written once, straight into their slots of a buffer that an
    # earlier dispatch used, and the buffer comes back at the fence of
    # the chunk whose program read it.
    # ------------------------------------------------------------------
    def _build_staged_fn(self, R: int, ax_packer: AxisPacker,
                         stager: ScalarStager) -> Callable:
        stacked = R > 1
        core = self._multi_core(R) if stacked else self._round_step_core

        carry_paged = self.carry_paged

        def staged(params, opt_state, strategy_state, ax_bufs, sc_bufs,
                   rng, *pool_args):
            ax = ax_packer.unpack(ax_bufs)
            sc = stager.unpack(sc_bufs)
            carry = (ax["carry_slots"],) if carry_paged else ()
            chaos = ax.get("chaos", ())
            if not stacked:
                return core(params, opt_state, strategy_state,
                            ax["arrays"], ax["sample_mask"],
                            ax["client_mask"], ax["client_ids"],
                            sc["client_lr"], sc["server_lr"],
                            sc["round_idx"], sc["leakage"], sc["quant"],
                            rng, *carry, *chaos, *pool_args)
            # one key a round, split inside the trace
            rngs = jax.random.split(rng, R)
            return core(params, opt_state, strategy_state, ax["arrays"],
                        ax["sample_mask"], ax["client_mask"],
                        ax["client_ids"], sc["client_lr"], sc["server_lr"],
                        sc["round_idx"], sc["leakage"], sc["quant"], rngs,
                        *carry, *chaos, *pool_args)

        return jax.jit(staged, donate_argnums=(0, 1, 2))

    def _dispatch_staged(self, state: ServerState, batches: list,
                         client_lrs: list, server_lrs: list,
                         rng: jax.Array,
                         leakage_threshold: Optional[float],
                         quant_thresholds: Optional[list],
                         chaos_vecs: Optional[list]
                         ) -> Tuple[ServerState, PackedStats]:
        """Staged dispatch of ``len(batches)`` rounds: assemble host-side,
        pack per dtype group, one ``device_put`` for the clients-axis
        groups and one for the scalar groups, run the unpacking jit."""
        R = len(batches)
        stacked = R > 1
        with self._span("stage_host", rounds=R) as span:
            ax_packer, stager, trees, sc_bufs, pool_args = \
                self._stage_host(state, batches, client_lrs, server_lrs,
                                 leakage_threshold, quant_thresholds,
                                 chaos_vecs)
            # each round's leaves once into a kept buffer per dtype
            # group, a fresh one where none is free; given back at the
            # chunk's fence (PackedStats.release)
            kept, reused = self._staging.take(ax_packer.buffer_shapes())
            ax_bufs = ax_packer.pack_rounds_into(kept, trees)
            staged_bytes = int(
                sum(b.nbytes for b in ax_bufs.values()) +
                sum(b.nbytes for b in sc_bufs.values()))
            if span is not None:
                span["bytes"] = staged_bytes
                span["reused"] = reused
        ax_sharding = (NamedSharding(self.mesh, P(None, CLIENTS_AXIS))
                       if stacked else self._client_sharding)
        with self._span("h2d", rounds=R, bytes=staged_bytes,
                        puts=len(ax_bufs) + len(sc_bufs)):
            # ONE staging transfer per dtype group: each put runs on the
            # whole per-dtype dict, so the transfer count equals the
            # group count — the dispatch-cost contract the tier-1 guard
            # pins
            ax_dev = jax.device_put(ax_bufs, ax_sharding)
            sc_dev = jax.device_put(sc_bufs, self._replicated)
        self.last_dispatch_puts = len(ax_bufs) + len(sc_bufs)
        self.last_staged_bytes = staged_bytes
        with self._span("launch", rounds=R) as span:
            key = (R, ax_packer.signature, stager.signature)
            fn = self._staged_cache.get(key)
            if fn is None:
                fn = self._instrument(f"staged_r{R}",
                                      self._build_staged_fn(R, ax_packer,
                                                            stager),
                                      rounds=R)
                self._staged_cache[key] = fn
            seen = len(self.compile_log)
            params, opt_state, strategy_state, vecs = self._launch(
                fn, state.params, state.opt_state, state.strategy_state,
                ax_dev, sc_dev, rng, *pool_args)
            self._note_compiles(f"staged_r{R}", fn)
            if span is not None:
                span["compiled"] = len(self.compile_log) > seen
        new_state = ServerState(params, opt_state, strategy_state,
                                state.round + R)
        packer = self._stats_packers[
            ("single", batches[0].sample_mask.shape[0])]
        return new_state, PackedStats(
            vecs, packer, rounds=R, stacked=stacked,
            span=self.span_factory,
            release=(functools.partial(self._staging.give, kept)
                     if kept else None))

    def _stage_host(self, state: ServerState, batches: list,
                    client_lrs: list, server_lrs: list,
                    leakage_threshold: Optional[float],
                    quant_thresholds: Optional[list],
                    chaos_vecs: Optional[list]) -> tuple:
        """The host half of a staged dispatch but for the one copy:
        each round's clients-axis tree (arrays, masks, ids, fault
        vectors: nothing stacked) and the packer of their stack, the
        scalars packed.  Returns ``(ax_packer, stager, round_trees,
        sc_bufs, pool_args)``."""
        R = len(batches)
        stacked = R > 1
        trees = []
        for batch, chaos in zip(batches, self._chaos_host(chaos_vecs, R)):
            tree, pool_args = self._round_tree(batch, chaos)
            trees.append(tree)
        lr_dt, rd_dt = np.float32, np.int32
        if stacked:
            sc_tree = {
                "client_lr": np.asarray(client_lrs, lr_dt),
                "server_lr": np.asarray(server_lrs, lr_dt),
                "round_idx": np.arange(state.round, state.round + R,
                                       dtype=rd_dt),
                "leakage": lr_dt(leakage_threshold
                                 if leakage_threshold is not None
                                 else np.inf),
                "quant": np.asarray(quant_thresholds
                                    if quant_thresholds is not None
                                    else [-1.0] * R, lr_dt),
            }
        else:
            sc_tree = {
                "client_lr": lr_dt(client_lrs[0]),
                "server_lr": lr_dt(server_lrs[0]),
                "round_idx": rd_dt(state.round),
                "leakage": lr_dt(leakage_threshold
                                 if leakage_threshold is not None
                                 else np.inf),
                "quant": lr_dt(quant_thresholds[0]
                               if quant_thresholds is not None else -1.0),
            }
        ax_packer = AxisPacker.for_rounds(trees[0], R)
        stager = ScalarStager(sc_tree)
        return (ax_packer, stager, trees, stager.pack_np(sc_tree),
                pool_args)

    # ------------------------------------------------------------------
    def run_round(self, state: ServerState, batch: RoundBatch,
                  client_lr: float, server_lr: float,
                  rng: jax.Array,
                  leakage_threshold: Optional[float] = None,
                  quant_threshold: Optional[float] = None,
                  chaos_vecs: Optional[list] = None
                  ) -> Tuple[ServerState, PackedStats]:
        """Stage one round's data onto the mesh and execute the program.

        Dispatch is async; the returned :class:`PackedStats` is a lazy
        handle — nothing crosses the host boundary until ``.fetch()``.
        """
        return self._dispatch_staged(
            state, [batch], [client_lr], [server_lr], rng,
            leakage_threshold,
            [quant_threshold] if quant_threshold is not None else None,
            chaos_vecs)

    # ------------------------------------------------------------------
    @staticmethod
    def _batch_slots(batch) -> np.ndarray:
        """The batch's fleet page-pool slot vector; a paged-carry
        dispatch without one is a programming error (the pager sets it
        at prepare time) — fail loudly instead of gathering garbage."""
        slots = getattr(batch, "carry_slots", None)
        if slots is None:
            raise ValueError(
                "fleet paged carry: batch has no carry_slots — the "
                "CarryPager must prepare every chunk before dispatch")
        return np.asarray(slots, np.int32)

    # ------------------------------------------------------------------
    def _host_arrays(self, batch) -> Tuple[Dict[str, np.ndarray], tuple]:
        """The data inputs of one round as HOST numpy arrays, and the
        trailing program operands that go with them.

        Host-packed ``RoundBatch``es carry their gathered feature arrays;
        ``IndexRoundBatch``es carry only the int32 index grid and ride the
        resident pool (``attach_pool``) as a trailing program operand.
        """
        from ..data.batching import IndexRoundBatch
        is_idx = isinstance(batch, IndexRoundBatch)
        if is_idx != (self._pool is not None):
            raise ValueError(
                "round engine pool mode mismatch: "
                f"batch={'indices' if is_idx else 'arrays'} but pool "
                f"{'attached' if self._pool is not None else 'absent'}")
        if is_idx:
            return {"__idx__": batch.indices}, (self._pool,)
        return dict(batch.arrays), ()

    def _round_tree(self, batch, chaos: tuple) -> Tuple[dict, tuple]:
        """One round's clients-axis operands (every leaf ``[K, ...]``) as
        the tree the staged programs unpack, and the trailing program
        operands."""
        arrays_host, pool_args = self._host_arrays(batch)
        tree = {
            "arrays": arrays_host,
            "sample_mask": batch.sample_mask,
            "client_mask": batch.client_mask,
            "client_ids": batch.client_ids,
        }
        if self.carry_paged:
            tree["carry_slots"] = self._batch_slots(batch)
        if chaos:
            tree["chaos"] = chaos
        return tree, pool_args

    # ------------------------------------------------------------------
    def dispatch_rounds(self, state: ServerState, batches: list,
                        client_lrs: list, server_lrs: list,
                        rng: jax.Array,
                        leakage_threshold: Optional[float] = None,
                        quant_thresholds: Optional[list] = None,
                        chaos_vecs: Optional[list] = None
                        ) -> Tuple[ServerState, PackedStats]:
        """Dispatch ``len(batches)`` rounds as ONE device program (the
        single-round program for R==1, a scan otherwise) WITHOUT blocking:
        the returned state is the async program output and the stats are a
        lazy :class:`PackedStats` handle.  This is the dispatch half of
        the server's software-pipelined loop — the host is free to consume
        the previous chunk's results while this one executes."""
        return self._dispatch_staged(
            state, batches, client_lrs, server_lrs, rng,
            leakage_threshold, quant_thresholds, chaos_vecs)

    # ------------------------------------------------------------------
    # cohort shape-bucketing (server_config.cohort_bucketing): one
    # COMPACT [K_b, S_b, B, ...] collect program per step bucket + one
    # finalize program per round that combines the per-bucket partials
    # into the weighted aggregate ON DEVICE.  The per-client math is the
    # fused round's exactly (client rng streams fold on client id, and
    # masked padding steps are no-op-pinned), so per-client updates are
    # bit-identical to the monolithic grid; only the summation
    # association differs, in a DETERMINISTIC left-to-right bucket
    # order.  Compiled-program economics: one collect variant per
    # distinct (K_b, S_b) grid — S_b values come from the config-bounded
    # boundary set and K_b is pow2-quantized by the server — plus one
    # finalize variant per bucket-shape signature; the PR 7 recompile
    # sentinel watches that this set stays closed after warmup.
    # ------------------------------------------------------------------
    def _get_bucket_collect_core(self, mega: bool = False) -> Callable:
        """The un-jitted one-bucket collect body (shared by every staged
        per-shape variant): chaos fold + vmap'd client math + either the
        psum'd partial sums (default) or the gathered per-client stack
        (shield mode, where screening must see the WHOLE cohort and so
        defers to the finalize program).

        ``mega`` builds the MEGABATCH variant: two extra lane-sharded
        tape operands (ptr/seg), the heavy training replaced by the
        segment-carrying lane scan run once per ``megabatch_passes``
        spec, and the vmap'd client body kept — unchanged strategy
        weight/transform/carry/stale/corruption math — but fed a FAKE
        client_update that hands back the lane scan's per-client rows."""
        cached = self._bucket_collect_core.get(mega)
        if cached is not None:
            return cached
        strategy = self.strategy
        client_update = self.client_update
        mega_update = self.mega_update
        mesh = self.mesh
        cspec = P(CLIENTS_AXIS)
        rspec = P()
        defer_screen = self.shield is not None
        chaos_corruption = self.chaos_corruption
        # fluteflow: the traced-staleness operand threads after
        # corrupt_mode per bucket, exactly like the monolithic round
        traffic_staleness = self.traffic_staleness
        device_carry = self.device_carry
        carry_paged = self.carry_paged
        # mesh-sharded page pool: same split as the monolithic round —
        # tables ride a cspec operand, global slots drop to shard-local
        trailing = self._trailing_operands()
        gather_axis = self._gather_axis
        # secure aggregation x bucketing: each bucket runs its OWN
        # pairwise-mask graph over the bucket's sampled sub-cohort (two
        # replicated operands — the bucket's ids and sampled mask);
        # residual-mask cancellation happens per bucket in finalize.
        # The int32 telescoping is exact either way, so the decoded
        # aggregate is bit-identical to the monolithic round's.
        wants_cohort = bool(getattr(strategy, "wants_cohort", False))

        def replay_update(mega_c):
            # fake-update replay: the lane scan already trained this
            # client — hand its harvested rows back through the
            # client_update interface, so the strategy's
            # weight/transform/carry code runs UNCHANGED.  The
            # trace-time call counter maps the strategy's i-th
            # client_update call to its i-th megabatch pass
            # (personalization's global+local double train).
            calls = {"n": 0}

            def update_fn(gp, arr, mask, lr_, r_, grad_offset=None):
                i = calls["n"]
                calls["n"] += 1
                if i >= len(mega_c):
                    raise ValueError(
                        f"{type(strategy).__name__} issued more "
                        "client_update calls than its megabatch_passes "
                        "declared — extend the hook or set "
                        "supports_megabatch = False")
                pg_i, tl_i, ns_i, st_i = mega_c[i]
                return pg_i, tl_i, ns_i, dict(st_i)

            return update_fn

        def shard_body(params, strategy_state, arrays, sample_mask,
                       client_mask, client_ids, client_lr, round_idx,
                       leakage_threshold, quant_threshold, rng,
                       cohort_ids=None, cohort_mask=None,
                       carry_slots=None, corrupt_mode=None,
                       staleness=None, pool=None,
                       ptr=None, seg=None):
            per_client = self._per_client_fn(
                replay_update if mega else (lambda rows: client_update),
                params, strategy_state, client_lr, round_idx,
                leakage_threshold, quant_threshold, rng, cohort_ids,
                cohort_mask)
            if pool is not None:
                arrays = gather_pool(pool, arrays, sample_mask)
            mega_rows = ()
            if mega:
                # one lane scan per strategy pass — the MXU-saturating
                # training; per-client rng still folds on TRUE client
                # ids inside the scan, so slot/bucket placement cannot
                # perturb a client's update
                slots_k = carry_slots if carry_paged else client_ids
                passes = strategy.megabatch_passes(
                    strategy_state=strategy_state, global_params=params,
                    client_ids=client_ids, slots=slots_k, rng=rng)
                mega_rows = tuple(
                    mega_update(params, arrays, sample_mask, client_ids,
                                ptr, seg, client_lr, rng,
                                init_rows=spec.get("init_rows"),
                                offset_rows=spec.get("offset_rows"),
                                rng_salt=spec.get("rng_salt"))
                    for spec in passes)
            vmap_args = (arrays, sample_mask, client_mask, client_ids) + \
                ((carry_slots,) if carry_paged else ()) + \
                ((corrupt_mode,) if chaos_corruption else ()) + \
                ((staleness,) if traffic_staleness else ()) + \
                mega_rows
            parts, tls, nss, stats, stale, carry_rows, sub_norms = \
                jax.vmap(per_client)(*vmap_args)
            privacy_per_client = {k: v for k, v in stats.items()
                                  if k.startswith("privacy_")}
            stats = {k: v for k, v in stats.items()
                     if not k.startswith("privacy_")}

            if defer_screen:
                # shield mode: screening needs the FULL cohort's norms,
                # which spans buckets — ship the per-client stack (the
                # same K x model HBM cost the robust_stack path already
                # pays) replicated to the finalize program; nothing
                # crosses to the host
                pc = {
                    "stack": jax.tree.map(gather_axis,
                                          parts["default"][0]),
                    "w": gather_axis(parts["default"][1]),
                    "tl": gather_axis(tls),
                    "ns": gather_axis(nss),
                    "stats": {k: gather_axis(v) for k, v in stats.items()},
                    "cm": gather_axis(client_mask),
                }
                if wants_cohort:
                    # the finalize's masked screening votes on submitted
                    # norms (the stack itself is masked int32 — no norm
                    # signal there by construction)
                    pc["sub_norm"] = gather_axis(sub_norms)
                return pc, privacy_per_client

            local = self._shard_sums(parts, tls, nss, stats, stale,
                                     client_mask, "inline")
            if self.partition_mode == "shard_map":
                local = jax.lax.psum(local, CLIENTS_AXIS)
            out = (local, privacy_per_client)
            if device_carry:
                out += (jax.tree.map(gather_axis, carry_rows),)
            return out

        def shard_entry(params, strategy_state, arrays, sample_mask,
                        client_mask, client_ids, client_lr, round_idx,
                        leakage_threshold, quant_threshold, rng, *rest):
            rest = list(rest)
            # secure-agg cohort operands: the bucket's ids + sampled
            # mask, REPLICATED (every client derives masks toward the
            # whole bucket, not this shard's slice)
            cohort_ids = rest.pop(0) if wants_cohort else None
            cohort_mask = rest.pop(0) if wants_cohort else None
            # megabatch tape: lane axis shard-blocked like the grids, so
            # each shard's lanes point only at its own grid rows
            ptr = rest.pop(0) if mega else None
            seg = rest.pop(0) if mega else None
            strategy_state, kw = self._unpack_trailing(
                trailing, strategy_state, rest)
            return shard_body(params, strategy_state, arrays, sample_mask,
                              client_mask, client_ids, client_lr,
                              round_idx, leakage_threshold,
                              quant_threshold, rng,
                              cohort_ids=cohort_ids,
                              cohort_mask=cohort_mask, ptr=ptr, seg=seg,
                              **kw)

        if self.partition_mode == "shard_map":
            out_specs = ((rspec, cspec) if defer_screen else
                         (rspec, cspec) +
                         ((rspec,) if device_carry else ()))
            sharded = shard_map(
                shard_entry, mesh=mesh,
                in_specs=(rspec, rspec, cspec, cspec, cspec, cspec, rspec,
                          rspec, rspec, rspec, rspec) +
                         ((rspec, rspec) if wants_cohort else ()) +
                         ((cspec, cspec) if mega else ()) +
                         tuple(spec for _, on, spec in trailing if on),
                out_specs=out_specs, check_vma=False)
        else:
            sharded = shard_entry

        @_round_scoped
        def collect_core(params, strategy_state, arrays, sample_mask,
                         client_mask, client_ids, client_lr, round_idx,
                         leakage_threshold, quant_threshold, rng,
                         *extra_args):
            # the bucket's SAMPLED mask, pre-chaos: secure-agg clients
            # mask toward it; finalize cancels toward the lost slots
            sampled_cm = client_mask
            tape_args = ()
            if mega:
                tape_args = tuple(extra_args[:2])
                extra_args = extra_args[2:]
            (sample_mask, client_mask, carry_slots, collect_state,
             trailing_args, fault_stats) = self._fold_faults(
                strategy_state, sample_mask, client_mask, client_ids,
                extra_args)
            bcast = strategy.broadcast_params(params, strategy_state)
            out = sharded(bcast, collect_state, arrays, sample_mask,
                          client_mask, client_ids, client_lr, round_idx,
                          leakage_threshold, quant_threshold, rng,
                          *((client_ids, sampled_cm) if wants_cohort
                            else ()),
                          *tape_args, *trailing_args)
            if defer_screen:
                result = {"pc": out[0], "privacy": out[1]}
            else:
                result = {"local": out[0], "privacy": out[1]}
                if device_carry:
                    result["carry"] = out[2]
            result["chaos"] = fault_stats
            result["ids"] = client_ids
            if wants_cohort:
                # everything the finalize's per-bucket mask cancellation
                # needs: the bucket's sampled and post-chaos live masks
                # (device arrays — no host sync) and the round index the
                # mask keys derive from
                result["sa"] = {"sampled": sampled_cm,
                                "live": client_mask,
                                "round_idx": round_idx}
            if carry_paged:
                # the finalize's apply_carry scatters by pool slot
                result["slots"] = carry_slots
            # trace-time hygiene: a strategy publish during a COLLECT
            # trace would otherwise be drained by the finalize trace as
            # a leaked tracer; bucket collects drop such publishes (the
            # engine's own update_ratio publish lives in finalize)
            self.devbus.drain()
            return result

        self._bucket_collect_core[mega] = collect_core
        return collect_core

    def _bucket_collect_fn(self, K: int, S: int, ax_packer: AxisPacker,
                           stager: ScalarStager,
                           tape_packer: Optional[AxisPacker] = None
                           ) -> Callable:
        """The staged, jitted collect program for one (K_b, S_b) grid —
        cached per geometry + packer signature.  Entry-point name keys
        on S only: the S set is config-bounded, so a NEW compiled
        variant under one name is exactly the K-quantization churn the
        recompile sentinel should see.  ``tape_packer`` (the megabatch
        ptr/seg tape's own AxisPacker — its lead dim is lanes, not
        clients, so it cannot ride the grid packer) selects the
        megabatch collect core under its own ``megabatch_collect_s{S}``
        entry name — the gate's second arm."""
        mega = tape_packer is not None
        key = (K, S, ax_packer.signature, stager.signature,
               tape_packer.signature if mega else None)
        fn = self._bucket_collect_cache.get(key)
        if fn is not None:
            return fn
        core = self._get_bucket_collect_core(mega=mega)

        carry_paged = self.carry_paged

        def staged(params, strategy_state, ax_bufs, sc_bufs, rng,
                   *rest):
            if mega:
                tp = tape_packer.unpack(rest[0])
                tape = (tp["ptr"], tp["seg"])
                pool_args = rest[1:]
            else:
                tape = ()
                pool_args = rest
            ax = ax_packer.unpack(ax_bufs)
            sc = stager.unpack(sc_bufs)
            carry = (ax["carry_slots"],) if carry_paged else ()
            chaos = ax.get("chaos", ())
            return core(params, strategy_state, ax["arrays"],
                        ax["sample_mask"], ax["client_mask"],
                        ax["client_ids"], sc["client_lr"],
                        sc["round_idx"], sc["leakage"], sc["quant"],
                        rng, *tape, *carry, *chaos, *pool_args)

        name = (f"megabatch_collect_s{S}" if mega
                else f"bucket_collect_s{S}")
        fn = self._instrument(name, jax.jit(staged))
        self._bucket_collect_cache[key] = fn
        self.bucket_shapes_seen.add((K, S))
        return fn

    def _get_bucket_finalize(self) -> Callable:
        """The jitted finalize program: per-bucket partials -> screened/
        combined aggregate -> server step -> ONE packed stats buffer per
        dtype group.  Shapes vary with the round's bucket signature; the
        jit cache (and the sentinel, when on) tracks the variants."""
        if self._bucket_finalize is not None:
            return self._bucket_finalize
        strategy = self.strategy
        shield = self.shield
        robust_stack = shield is not None and shield.wants_stack
        device_carry = self.device_carry
        stale_prob = self.stale_prob
        server_tx = self.server_tx
        wants_cohort = bool(getattr(strategy, "wants_cohort", False))
        min_surv = int(getattr(strategy, "min_survivors", 0) or 0) \
            if wants_cohort else 0

        def cancel_buckets(gsum, outs, survivors_per_bucket):
            """Per-bucket secure-agg mask recovery over the FOLDED sum:
            residuals are additive across buckets (each bucket has its
            own mask graph), so chaining ``cancel_masks`` per bucket
            subtracts exactly the union of (survivor, lost) edge masks.
            Returns the cancelled sum + per-cause recovery counters."""
            f32 = jnp.float32
            rec_drop = jnp.zeros((), f32)
            rec_quar = jnp.zeros((), f32)
            surv_tot = jnp.zeros((), f32)
            for o, surv_b in zip(outs, survivors_per_bucket):
                sa = o["sa"]
                gsum = strategy.cancel_masks(
                    gsum, o["ids"], sa["sampled"], surv_b,
                    sa["round_idx"])
                rec_drop += jnp.sum(
                    ((sa["sampled"] > 0) & (sa["live"] <= 0)).astype(f32))
                rec_quar += jnp.sum(
                    ((sa["live"] > 0) & (surv_b <= 0)).astype(f32))
                surv_tot += jnp.sum((surv_b > 0).astype(f32))
            sa_stats = {"secagg_recovered_dropout": rec_drop,
                        "secagg_recovered_quarantine": rec_quar}
            if min_surv > 0:
                abort = surv_tot < jnp.asarray(min_surv, f32)
                gsum = jax.tree.map(
                    lambda g: g * (1 - abort.astype(g.dtype)), gsum)
                sa_stats["secagg_abort"] = abort.astype(jnp.float32)
            return gsum, sa_stats

        @_round_scoped
        def finalize(params, opt_state, strategy_state, outs, server_lr,
                     rng):
            bcast = strategy.broadcast_params(params, strategy_state)
            shield_counts = None
            sa_stats = {}
            if shield is None:
                # deterministic on-device aggregation order: partial
                # sums fold left-to-right in ascending-bucket order
                total = outs[0]["local"]
                for o in outs[1:]:
                    total = jax.tree.map(jnp.add, total, o["local"])
                part_sums = total["parts"]
                if wants_cohort:
                    # no shield: a bucket's survivors are its post-chaos
                    # live clients
                    default = dict(part_sums["default"])
                    gsum, sa_stats = cancel_buckets(
                        default["grad_sum"], outs,
                        [o["sa"]["live"] for o in outs])
                    default["grad_sum"] = gsum
                    part_sums = dict(part_sums)
                    part_sums["default"] = default
                    total = dict(total)
                    total["parts"] = part_sums
                deferred = None
                if stale_prob > 0.0:
                    default = part_sums["default"]
                    deferred = {"grad_sum": default["grad_sum_def"],
                                "weight_sum": default["weight_sum_def"]}
                agg, new_strategy_state = strategy.combine_parts(
                    part_sums, deferred, strategy_state,
                    jax.random.fold_in(rng, 17),
                    num_clients=total["client_count"],
                    global_params=bcast)
                collected = total
            else:
                # shield mode: assemble the cohort stack (ascending-
                # bucket concatenation), screen against the WHOLE
                # cohort's median norm, zero quarantined clients via
                # jnp.where, then sum/combine — the fused round's
                # screening semantics over the multi-grid cohort
                def cat(*xs):
                    return jnp.concatenate(xs, axis=0)
                stack = jax.tree.map(cat, *[o["pc"]["stack"]
                                            for o in outs])
                w = cat(*[o["pc"]["w"] for o in outs])
                tls = cat(*[o["pc"]["tl"] for o in outs])
                nss = cat(*[o["pc"]["ns"] for o in outs])
                cm = cat(*[o["pc"]["cm"] for o in outs])
                stats = jax.tree.map(cat, *[o["pc"]["stats"]
                                            for o in outs])
                if wants_cohort:
                    # masked stacks carry no plaintext norm signal —
                    # vote on the cat'd submitted norms instead
                    sub_norms = cat(*[o["pc"]["sub_norm"] for o in outs])
                    keep, q_nonfinite, q_norm = shield.screen_masked(
                        sub_norms, tls, w, cm, lambda x: x)
                else:
                    keep, q_nonfinite, q_norm = shield.screen(
                        stack, tls, w, cm, lambda x: x)
                keep_b = keep > 0
                stack = jax.tree.map(
                    lambda g: jnp.where(
                        keep_b.reshape((-1,) + (1,) * (g.ndim - 1)),
                        g, jnp.zeros_like(g)), stack)
                w = jnp.where(keep_b, w, 0.0)
                tls = jnp.where(keep_b, tls, 0.0)
                nss = jnp.where(keep_b, nss, 0.0)
                stats = {k: jnp.where(keep_b, v, 0.0)
                         for k, v in stats.items()}
                cm = cm * keep
                if wants_cohort:
                    # masked payloads sum with coefficient EXACTLY 1 per
                    # surviving slot, in the tree's own int32 dtype (the
                    # fused round's unit-weight discipline — a float
                    # weight would break mask cancellation), then the
                    # per-bucket residual masks toward quarantined and
                    # dropped slots cancel out of the folded sum
                    gsum = jax.tree.map(
                        lambda g: jnp.tensordot(
                            cm.astype(g.dtype), g, axes=[[0], [0]]),
                        stack)
                    sizes = [o["pc"]["cm"].shape[0] for o in outs]
                    surv_buckets = []
                    off = 0
                    for sz in sizes:
                        surv_buckets.append(cm[off:off + sz])
                        off += sz
                    gsum, sa_stats = cancel_buckets(gsum, outs,
                                                    surv_buckets)
                else:
                    gsum = jax.tree.map(
                        lambda g: jnp.tensordot(w, g, axes=[[0], [0]]),
                        stack)
                part_sums = {"default": {
                    "grad_sum": gsum,
                    "weight_sum": jnp.sum(w),
                    "grad_sum_def": jax.tree.map(jnp.zeros_like, gsum),
                    "weight_sum_def": jnp.zeros(()),
                    "weight_sum_raw": jnp.sum(w),
                }}
                collected = _stat_sums(tls, nss, stats, cm)
                if robust_stack:
                    agg = strategy.combine_stack(
                        stack, cm, jax.random.fold_in(rng, 17))
                    new_strategy_state = strategy_state
                else:
                    agg, new_strategy_state = strategy.combine_parts(
                        part_sums, None, strategy_state,
                        jax.random.fold_in(rng, 17),
                        num_clients=collected["client_count"],
                        global_params=bcast)
                shield_counts = (jnp.sum(q_nonfinite), jnp.sum(q_norm))
            if device_carry:
                # per-bucket scatters commute (a client id appears in
                # exactly one bucket), so sequential application equals
                # the monolithic single scatter; under fleet paging the
                # scatter index is the pool slot the pager assigned
                for b, o in enumerate(outs):
                    new_strategy_state = strategy.apply_carry(
                        new_strategy_state,
                        o["slots"] if "slots" in o else o["ids"],
                        o["carry"],
                        rng=jax.random.fold_in(
                            jax.random.fold_in(rng, 31), b))
            if self.server_max_grad_norm is not None:
                agg = _clip_by_global_norm(
                    agg, float(self.server_max_grad_norm))
            if strategy.owns_server_update:
                new_params, new_strategy_state = \
                    strategy.apply_server_update(params, agg,
                                                 new_strategy_state,
                                                 server_lr)
                new_opt_state = opt_state
            else:
                opt_state.hyperparams["learning_rate"] = server_lr
                updates, new_opt_state = server_tx.update(
                    agg, opt_state, params)
                new_params = optax.apply_updates(params, updates)
            round_stats = _round_stats(collected, part_sums, agg)
            chaos_tot = outs[0]["chaos"]
            for o in outs[1:]:
                chaos_tot = jax.tree.map(jnp.add, chaos_tot, o["chaos"])
            round_stats.update(chaos_tot)
            round_stats.update(sa_stats)
            if shield_counts is not None:
                round_stats["shield_nonfinite"] = shield_counts[0]
                round_stats["shield_norm_outlier"] = shield_counts[1]
            privacy = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0),
                *[o["privacy"] for o in outs])
            for k, v in privacy.items():
                round_stats[k] = v
            if self.devbus.enabled:
                applied = jax.tree.map(lambda a, b: a - b,
                                       new_params, params)
                self.devbus.publish(
                    "update_ratio",
                    optax.global_norm(applied)
                    / (optax.global_norm(new_params) + 1e-12))
                round_stats.update(self.devbus.drain())
            packer = FlatPacker(round_stats)
            k_tot = sum(int(o["ids"].shape[0]) for o in outs)
            # flint: disable=jit-purity trace-time slot-table recording is the flatpack contract (one write per compile, host-side reads only)
            self._stats_packers[("bucketed", k_tot)] = packer
            return (new_params, new_opt_state, new_strategy_state,
                    packer.pack(round_stats))

        # donate only the server state (params/opt/strategy) — the
        # per-bucket partials (arg 3) mostly feed reductions XLA cannot
        # alias in place, and an unusable donation warns per compile
        self._bucket_finalize = self._instrument(
            "bucket_finalize",
            jax.jit(finalize, donate_argnums=(0, 1, 2)))
        return self._bucket_finalize

    def dispatch_bucketed_rounds(self, state: ServerState,
                                 rounds_buckets: list,
                                 client_lrs: list, server_lrs: list,
                                 rng: jax.Array,
                                 leakage_threshold: Optional[float] = None,
                                 quant_thresholds: Optional[list] = None,
                                 chaos_vecs: Optional[list] = None
                                 ) -> Tuple[ServerState, BucketedStats]:
        """Dispatch ``len(rounds_buckets)`` bucketed rounds WITHOUT
        blocking.  ``rounds_buckets[r]`` is round r's list of per-bucket
        :class:`~msrflute_tpu.data.batching.RoundBatch` grids (ascending
        bucket order); ``chaos_vecs[r][b]`` the matching per-bucket
        fault-vector entries.  Per round: one staged collect dispatch
        per occupied bucket, then one finalize dispatch producing the
        round's single packed-stats handle — everything device-side, so
        the pipeline ring and strict-transfer contracts hold unchanged."""
        R = len(rounds_buckets)
        # same stream derivation as the monolithic dispatch (split is a
        # pure function), so a bucketed round sees the exact round rng
        # the monolithic program would have — per-client bit-identity
        rngs = [rng] if R == 1 else list(jax.random.split(rng, R))
        finalize = self._get_bucket_finalize()
        per_round: list = []
        cur = state
        puts = staged_bytes = 0
        lr_dt, rd_dt = np.float32, np.int32
        for r, buckets in enumerate(rounds_buckets):
            outs = []
            round_flops = 0.0
            round_hbm = 0
            for b, batch in enumerate(buckets):
                # the three children of `dispatch`, once per bucket
                # program; `rounds` is 1 on a round's first bucket and 0
                # on the others, so that a division by rounds counts
                # each round once
                span_rounds = 1 if b == 0 else 0
                with self._span("stage_host", rounds=span_rounds,
                                bucket=b) as span:
                    entry = (chaos_vecs[r][b] if chaos_vecs is not None
                             else None)
                    axis_tree, pool_args = self._round_tree(
                        batch, self._chaos_host(
                            [entry] if entry is not None else None, 1)[0])
                    sc_tree = {
                        "client_lr": lr_dt(client_lrs[r]),
                        "round_idx": rd_dt(cur.round),
                        "leakage": lr_dt(leakage_threshold
                                         if leakage_threshold is not None
                                         else np.inf),
                        "quant": lr_dt(quant_thresholds[r]
                                       if quant_thresholds is not None
                                       else -1.0),
                    }
                    ax_packer = AxisPacker(axis_tree, lead_ndim=1)
                    stager = ScalarStager(sc_tree)
                    K, S = (int(batch.sample_mask.shape[0]),
                            int(batch.sample_mask.shape[1]))
                    ax_bufs = ax_packer.pack_np(axis_tree)
                    sc_bufs = stager.pack_np(sc_tree)
                    bucket_bytes = int(
                        sum(bf.nbytes for bf in ax_bufs.values()) +
                        sum(bf.nbytes for bf in sc_bufs.values()))
                    if span is not None:
                        span["bytes"] = bucket_bytes
                with self._span("h2d", rounds=span_rounds, bucket=b,
                                bytes=bucket_bytes,
                                puts=len(ax_bufs) + len(sc_bufs)):
                    # flint: disable=put-loop one staged put per dtype group per BUCKET PROGRAM (each loop iteration dispatches its own compiled grid; the leaves are already flatpacked)
                    ax_dev = jax.device_put(ax_bufs, self._client_sharding)
                    # flint: disable=put-loop same — the scalar group's single staged buffer for this bucket's dispatch
                    sc_dev = jax.device_put(sc_bufs, self._replicated)
                puts += len(ax_bufs) + len(sc_bufs)
                staged_bytes += bucket_bytes
                with self._span("launch", rounds=span_rounds,
                                bucket=b) as span:
                    seen = len(self.compile_log)
                    # megabatch dispatch gate: when the server attached a
                    # super-batch tape, pick megabatch vs per-client vmap
                    # PER BUCKET — cached per (K, S) geometry, priced on
                    # the compiled cost model at first sight (both arms
                    # run once; the verdict is deterministic because cost
                    # analyses are static)
                    tape = getattr(batch, "mega", None)
                    fn_mega = tp_dev = None
                    if tape is not None and self.megabatch:
                        tape_tree = {"ptr": tape.ptr, "seg": tape.seg}
                        tape_packer = AxisPacker(tape_tree, lead_ndim=1)
                        fn_mega = self._bucket_collect_fn(
                            K, S, ax_packer, stager, tape_packer=tape_packer)
                        tp_bufs = tape_packer.pack_np(tape_tree)
                        # flint: disable=put-loop the tape's single int32 staged buffer for this bucket's dispatch
                        tp_dev = jax.device_put(tp_bufs, self._client_sharding)
                        puts += len(tp_bufs)
                        staged_bytes += int(sum(bf.nbytes
                                                for bf in tp_bufs.values()))
                    fn = self._bucket_collect_fn(K, S, ax_packer, stager)
                    arm = (self._mega_gate.get((K, S))
                           if fn_mega is not None else "vmap")
                    out = None
                    if fn_mega is not None and arm is None and \
                            self.megabatch_autotune and self.xla is not None:
                        out_v = fn(cur.params, cur.strategy_state, ax_dev,
                                   sc_dev, rngs[r], *pool_args)
                        self._note_compiles(f"bucket_collect_s{S}", fn)
                        cost_v = dict(self.xla.last_dispatch or {})
                        out_m = fn_mega(cur.params, cur.strategy_state,
                                        ax_dev, sc_dev, rngs[r], tp_dev,
                                        *pool_args)
                        self._note_compiles(f"megabatch_collect_s{S}",
                                            fn_mega)
                        cost_m = dict(self.xla.last_dispatch or {})
                        secs_v = self._roofline_secs(cost_v)
                        secs_m = self._roofline_secs(cost_m)
                        if secs_m <= secs_v:
                            arm, out = "mega", out_m
                        else:
                            arm, out = "vmap", out_v
                            self.push_megabatch_event({
                                "kind": "megabatch_fallback",
                                "reason": "aot_cost",
                                "clients": K, "steps": S,
                                "lanes": int(tape.lanes),
                                "depth": int(tape.depth),
                                "mega_secs_est": secs_m,
                                "vmap_secs_est": secs_v,
                            })
                        self._mega_gate[(K, S)] = arm
                        # the live-MFU snapshot must describe the CHOSEN arm
                        self.xla.last_dispatch = (cost_v if arm == "vmap"
                                                  else cost_m)
                    elif fn_mega is not None and arm is None:
                        # no compiled cost model in reach (telemetry.xla off
                        # or autotune disabled): the server's analytic slots
                        # precheck already priced the tape — trust it
                        arm = "mega"
                        self._mega_gate[(K, S)] = arm
                    if out is None:
                        if arm == "mega":
                            out = self._launch(
                                fn_mega, cur.params, cur.strategy_state,
                                ax_dev, sc_dev, rngs[r], tp_dev, *pool_args)
                            self._note_compiles(f"megabatch_collect_s{S}",
                                                fn_mega)
                        else:
                            out = self._launch(
                                fn, cur.params, cur.strategy_state, ax_dev,
                                sc_dev, rngs[r], *pool_args)
                            self._note_compiles(f"bucket_collect_s{S}", fn)
                    if self.xla is not None and \
                            self.xla.last_dispatch is not None:
                        round_flops += float(
                            self.xla.last_dispatch.get("flops") or 0.0)
                        round_hbm = max(round_hbm, int(
                            self.xla.last_dispatch.get("hbm_bytes") or 0))
                    if span is not None:
                        span["compiled"] = len(self.compile_log) > seen
                outs.append(out)
            with self._span("launch", rounds=0, finalize=True) as span:
                seen = len(self.compile_log)
                params, opt_state, strategy_state, vecs = self._launch(
                    finalize, cur.params, cur.opt_state, cur.strategy_state,
                    tuple(outs), jnp.asarray(server_lrs[r], jnp.float32),
                    rngs[r])
                self._note_compiles("bucket_finalize", finalize)
                if span is not None:
                    span["compiled"] = len(self.compile_log) > seen
            if self.xla is not None and \
                    self.xla.last_dispatch is not None:
                round_flops += float(
                    self.xla.last_dispatch.get("flops") or 0.0)
                round_hbm = max(round_hbm, int(
                    self.xla.last_dispatch.get("hbm_bytes") or 0))
                # the live-MFU snapshot must describe the WHOLE bucketed
                # round (collects + finalize), not just whichever
                # program dispatched last
                self.xla.last_dispatch = {
                    "entry": "bucketed_round", "rounds": 1,
                    "flops": round_flops or None,
                    "bytes_accessed": None,
                    "hbm_bytes": round_hbm or None,
                }
            cur = ServerState(params, opt_state, strategy_state,
                              cur.round + 1)
            k_tot = sum(int(batch.sample_mask.shape[0])
                        for batch in buckets)
            packer = self._stats_packers[("bucketed", k_tot)]
            per_round.append(PackedStats(vecs, packer, rounds=1,
                                         stacked=False))
        from ..data.batching import ceil_div
        self.last_dispatch_puts = ceil_div(puts, R)
        self.last_staged_bytes = int(staged_bytes // R)
        return cur, BucketedStats(per_round, span=self.span_factory)

    def run_rounds(self, state: ServerState, batches: list,
                   client_lrs: list, server_lrs: list,
                   rng: jax.Array,
                   leakage_threshold: Optional[float] = None,
                   quant_thresholds: Optional[list] = None,
                   chaos_vecs: Optional[list] = None
                   ) -> Tuple[ServerState, Dict[str, np.ndarray]]:
        """Run ``len(batches)`` rounds in ONE device program (scan) and
        fetch the stats (one transfer per dtype group).

        Returns per-round stats stacked on a leading axis.
        """
        new_state, packed = self.dispatch_rounds(
            state, batches, client_lrs, server_lrs, rng,
            leakage_threshold=leakage_threshold,
            quant_thresholds=quant_thresholds, chaos_vecs=chaos_vecs)
        return new_state, packed.fetch()
