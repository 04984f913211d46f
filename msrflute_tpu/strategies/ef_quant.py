"""Error-feedback quantized aggregation (EF-SGD / EF14-style memory) —
net-new vs the reference.

The reference's quantization (``extensions/quantization/quant.py:9-50``)
is memoryless: what the binning throws away each round is gone, which
biases the aggregate and stalls convergence at aggressive bit widths.
Error feedback is the standard fix (Seide et al. 2014; Karimireddy et
al. 2019 arXiv:1901.09847): each client keeps the residual of its last
compression and folds it into the next payload before compressing —

    corrected_k = pg_k + e_k
    q_k         = Q(corrected_k)          (sent; aggregated as usual)
    e_k'        = corrected_k - q_k       (kept on the client)

so quantization error is delayed, never dropped, and compressed SGD
recovers the uncompressed rate.

Cross-device FL needs the residual to SURVIVE between a client's
participations, so ``e_k`` rides the same durable per-client row store
discipline as SCAFFOLD's control variates: flat f32 rows in
ravel-pytree order, crash-safe files under the model dir, reloaded on
resume only with a matching checkpoint (``engine/server.py``).  The
round runs on the host-orchestrated path (``client_payloads`` -> one
jitted EF step over the ``[K, n_params]`` payload stack ->
``apply_custom_weights``), exactly like SCAFFOLD/RL rounds.

Config::

    strategy: ef_quant
    client_config:
      quant_bits: 4          # 2^bits levels; EF is what makes 2-4 viable
      quant_thresh: 0.0      # |.|-quantile zeroed before binning
      quant_anneal: 1.0      # per-round threshold multiplier (DGA's knob)

Composition: local DP runs inside ``client_payloads``'s per-client
transform BEFORE the EF step, so the noised payload is what gets
compressed — the DP guarantee is unaffected by EF (the residual never
leaves the client).  RL re-weighting and staleness use the fused path
and do not compose with EF rounds.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .fedavg import FedAvg


class ResidualStore:
    """Durable per-client EF residual rows (flat f32, ravel-pytree
    order).  Same file discipline as ``scaffold.ControlStore``: tmp+rename
    writes, unseen clients start at zero, LRU-bounded RAM when a disk
    store exists."""

    _MAX_RESIDENT = 4096

    def __init__(self, n_params: int, store_dir: Optional[str] = None,
                 resume: bool = False):
        self.n_params = int(n_params)
        self.store_dir = store_dir
        self._rows: Dict[int, np.ndarray] = {}
        #: residual rows dropped by store-less eviction (each drop degrades
        #: that client to memoryless quantization for its next round)
        self.dropped_rows = 0
        if store_dir is not None:
            os.makedirs(store_dir, exist_ok=True)
            if not resume:
                for name in os.listdir(store_dir):
                    if name.startswith("residual_"):
                        os.remove(os.path.join(store_dir, name))

    def _path(self, cid: int) -> str:
        return os.path.join(self.store_dir, f"residual_{cid}.npy")

    def _evict(self) -> None:
        # RAM is bounded in BOTH modes.  With a disk store eviction is
        # free (the durable copy is the record).  Without one there is
        # nowhere to spill: evicting DROPS the LRU client's residual —
        # that client quantizes memorylessly next time (the EF guarantee
        # degrades gracefully, never the aggregate's correctness).  The
        # server always runs with a store_dir; store-less mode is the
        # library/test path, where unbounded growth past _MAX_RESIDENT
        # rows of n_params f32 would be the worse failure.
        while len(self._rows) > self._MAX_RESIDENT:
            self._rows.pop(next(iter(self._rows)))
            if self.store_dir is None:
                self.dropped_rows += 1

    def _touch(self, cid: int, row: np.ndarray) -> None:
        # true LRU: re-insert at the tail on every read AND write, like
        # ControlStore — eviction pops the head (least recently used)
        self._rows.pop(cid, None)
        self._rows[cid] = row

    def rows(self, ids) -> np.ndarray:
        """[K, n_params] residual matrix; zeros for unseen/padding."""
        out = np.zeros((len(ids), self.n_params), np.float32)
        for i, cid in enumerate(np.asarray(ids)):
            cid = int(cid)
            if cid < 0:
                continue
            row = self._rows.get(cid)
            if row is None and self.store_dir is not None and \
                    os.path.exists(self._path(cid)):
                row = np.load(self._path(cid)).astype(np.float32)
            if row is not None:
                self._touch(cid, row)
                out[i] = row
        self._evict()
        return out

    def update(self, ids, new_rows: np.ndarray, keep_mask) -> None:
        for i, cid in enumerate(np.asarray(ids)):
            cid = int(cid)
            if cid < 0 or not keep_mask[i]:
                continue
            row = np.asarray(new_rows[i], np.float32)
            self._touch(cid, row)
            if self.store_dir is not None:
                path = self._path(cid)
                tmp = path + ".tmp.npy"
                np.save(tmp, row)
                os.replace(tmp, path)
        self._evict()

    # -- trajectory marker (same crash semantics as ControlStore): -1
    # sentinel while residual files mutate; the server commits the real
    # round only after the paired model checkpoint is durable
    def set_round(self, round_no: int) -> None:
        if self.store_dir is None:
            return
        path = os.path.join(self.store_dir, "residual_round.npy")
        tmp = path + ".tmp.npy"
        np.save(tmp, np.asarray([round_no], np.int64))
        os.replace(tmp, path)

    def round(self):
        if self.store_dir is None:
            return None
        path = os.path.join(self.store_dir, "residual_round.npy")
        if not os.path.exists(path):
            return None
        return int(np.load(path)[0])

    def reset(self) -> None:
        """Zero every residual and the files (fallback / trajectory
        mismatch: accumulated compression error belongs to the abandoned
        params)."""
        self._rows.clear()
        if self.store_dir is not None:
            for name in os.listdir(self.store_dir):
                if name.startswith("residual_"):
                    os.remove(os.path.join(self.store_dir, name))

    def persisted_client_ids(self):
        """Client ids with a durable residual file (device-table warm-up)."""
        if self.store_dir is None:
            return sorted(self._rows)
        ids = []
        for name in os.listdir(self.store_dir):
            if name.startswith("residual_") and name.endswith(".npy"):
                key = name[len("residual_"):-len(".npy")]
                if key.lstrip("-").isdigit():
                    ids.append(int(key))
        return sorted(ids)


class DeviceResidualTable:
    """HBM-resident EF residuals (``server_config.ef_device_residuals``).

    The host ``ResidualStore`` path materializes a dense ``[K, n_params]``
    f32 matrix on the host every EF round and ships it to the device (and
    the new residuals back) — at BERT scale that is GB-class host traffic
    per round, the exact transfer profile the SCAFFOLD
    ``DeviceControlTable`` was built to kill.  This is the same cure on
    the same pattern: the full ``[N_clients, n_params]`` residual table
    lives in HBM sharded over the clients mesh axis; per round

    - ``rows(ids)`` gathers the K sampled residual rows as a
      client-sharded device array that feeds the jitted EF step directly,
    - ``update(...)`` scatters the step's new-residual output (already a
      device array) back in-program with the table buffer donated —
      participation-gated (id >= 0 and aggregation weight > 0) with
      out-of-bounds drop for padding slots,

    so the ROUND PATH no longer stages residuals through the host in
    either direction.  Durability: the wrapped :class:`ResidualStore`
    stays the format of record; dirty rows flush through when the
    residual-round marker commits — and that flush is itself a
    ``[K, n_params]`` fetch + K file writes, so at the default
    ``ef_flush_freq: 1`` roughly half of the host traffic remains.  The
    full transfer win needs ``ef_flush_freq > 1`` (amortizes the flush;
    the rounds in between keep the -1 marker sentinel, so a crash inside
    the window resets ALL residuals on resume — the same
    durability-vs-transfer tradeoff as ``scaffold_flush_freq``).  HBM
    cost is ``4·N·n_params`` bytes — worth it when per-round residual
    transfers dominate, not when the client pool is huge and the model
    small.
    """

    def __init__(self, store: ResidualStore, n_clients: int, mesh):
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import CLIENTS_AXIS

        self.store = store
        self.n_clients = int(n_clients)
        axis = int(mesh.shape[CLIENTS_AXIS])
        # pad rows to shard evenly; padding rows are never gathered
        # (valid ids < N) and scatters to them drop out of bounds
        self.n_rows = ((self.n_clients + axis - 1) // axis) * axis
        self._row_sharding = NamedSharding(mesh, P(CLIENTS_AXIS, None))
        self._rep = NamedSharding(mesh, P())
        n_rows, n_params = self.n_rows, store.n_params
        self._zeros = jax.jit(
            lambda: jnp.zeros((n_rows, n_params), jnp.float32),
            out_shardings=self._row_sharding)
        self.table = self._zeros()
        self._scatter = jax.jit(
            lambda t, i, v: t.at[i].set(v), donate_argnums=(0,),
            out_shardings=self._row_sharding)
        warm = [cid for cid in store.persisted_client_ids()
                if 0 <= cid < self.n_clients]
        for lo in range(0, len(warm), 512):
            chunk = warm[lo:lo + 512]
            rows = store.rows(np.asarray(chunk, np.int64))
            self.table = self._scatter(
                self.table, jnp.asarray(chunk, jnp.int32),
                # flint: disable=put-loop one-time table warm-up at construction
                jax.device_put(rows, self._rep))
        self._dirty = set()

        def gather_fn(table, ids):
            rows = table[jnp.clip(ids, 0, n_rows - 1)]
            valid = (ids >= 0).astype(jnp.float32)[:, None]
            return rows * valid

        self._gather = jax.jit(gather_fn, out_shardings=self._row_sharding)

        def update_fn(table, ids, new_res, ws):
            valid = (ids >= 0) & (ws > 0.0)
            return table.at[jnp.where(valid, ids, n_rows)].set(
                new_res, mode="drop")

        self._update = jax.jit(
            update_fn, donate_argnums=(0,),
            out_shardings=self._row_sharding)

    def rows(self, client_ids):
        """Client-sharded ``[K, n_params]`` residual rows (zeros for
        padding ids) — a device array, no host staging."""
        import jax.numpy as jnp
        return self._gather(self.table,
                            jnp.asarray(np.asarray(client_ids), jnp.int32))

    def update(self, client_ids, new_res, ws, ws_np) -> None:
        """Scatter the EF step's new residuals in-program.  ``new_res``
        and ``ws`` stay on device; ``ws_np`` (fetched for logging anyway)
        only marks dirty rows for ``flush()``."""
        import jax.numpy as jnp
        ids_np = np.asarray(client_ids)
        self.table = self._update(
            self.table, jnp.asarray(ids_np, jnp.int32), new_res, ws)
        for row, cid in enumerate(ids_np):
            if int(cid) >= 0 and float(ws_np[row]) > 0.0:
                self._dirty.add(int(cid))

    def flush(self) -> None:
        """Write dirty rows through to the durable ResidualStore."""
        if self._dirty:
            ids = np.asarray(sorted(self._dirty), np.int32)
            rows = np.asarray(jax.device_get(self.table[ids]))
            self.store.update(ids, rows, np.ones(len(ids), bool))
            self._dirty.clear()

    def reset(self) -> None:
        """Zero table + durable store (fallback semantics)."""
        self.table = self._zeros()
        self._dirty.clear()
        self.store.reset()


class EFQuant(FedAvg):
    """FedAvg weighting + error-feedback quantization on the
    host-orchestrated round path (``engine/server.py::_run_ef_round``).
    The strategy itself applies NO in-jit quantization — the EF step
    needs the per-client residual, which lives outside the fused round
    program."""

    supports_staleness = False
    supports_rl = False
    #: selects the host-orchestrated EF round path
    ef_rounds = True
    #: fleet paging: the residual table is the pageable state
    carry_tables = ("res",)

    def __init__(self, config, dp_config=None):
        super().__init__(config, dp_config)
        # fused carry mode (server_config.fused_carry): the [N, n_params]
        # residual table rides strategy_state as a donated device buffer;
        # the EF correct/quantize/remember cycle happens inside the vmap'd
        # client body and the round pipelines like FedAvg (PR 6).
        sc = getattr(config, "server_config", None)
        self.fused = bool(sc is not None and sc.get("fused_carry", False))
        if self.fused:
            self.ef_rounds = False
            self.device_carry = True
            if dp_config is not None and dp_config.get("adaptive_clipping"):
                raise ValueError(
                    "strategy: ef_quant with fused_carry does not compose "
                    "with dp_config.adaptive_clipping — the carry state "
                    "holds only the EF residual table, so the quantile-"
                    "tracking clip state would silently freeze at "
                    "max_grad; drop fused_carry (host EF round) or "
                    "adaptive_clipping")
        cc = config.client_config
        self.quant_bits = int(cc.get("quant_bits", 4))
        self.quant_thresh = float(cc.get("quant_thresh", 0.0))
        self.quant_anneal = float(cc.get("quant_anneal", 1.0) or 1.0)
        if not 1 <= self.quant_bits <= 16:
            raise ValueError(
                f"ef_quant quant_bits must be in [1, 16], "
                f"got {self.quant_bits}")
        if not 0.0 <= self.quant_thresh < 1.0:
            raise ValueError(
                f"ef_quant quant_thresh is an |.|-quantile in [0, 1), "
                f"got {self.quant_thresh}")

    # ---- fused carry mode (server_config.fused_carry) ----------------
    def init_state(self, params_like):
        if not self.fused:
            return super().init_state(params_like)
        if not self.carry_clients:
            raise ValueError(
                "fused_carry ef_quant needs carry_clients (the total "
                "client-pool size) set before init_state — the server "
                "does this from len(train_dataset)")
        n_params = sum(int(np.prod(leaf.shape))
                       for leaf in jax.tree.leaves(params_like))
        # leading dim: page-pool slots under fleet paging, else the pool
        return {"res": jnp.zeros((self._carry_table_rows(), n_params),
                                 jnp.float32)}

    def client_step_carry(self, client_update, global_params, arrays,
                          sample_mask, client_lr, rng, *, client_id,
                          live_mask, round_idx=None, leakage_threshold=None,
                          quant_threshold=None, strategy_state=None):
        from jax.flatten_util import ravel_pytree
        from ..ops.quantization import quantize_array
        # the payload post local-DP transform — exactly what the host EF
        # round compresses (DP before EF, so the residual never absorbs
        # the noise-free signal)
        parts, tl, ns, stats = super().client_step(
            client_update, global_params, arrays, sample_mask, client_lr,
            rng, round_idx=round_idx, leakage_threshold=leakage_threshold,
            quant_threshold=None, strategy_state=None)
        pg, w = parts["default"]
        pg_flat, unravel = ravel_pytree(pg)
        n_rows = strategy_state["res"].shape[0]
        valid = (client_id >= 0).astype(jnp.float32)
        res = strategy_state["res"][jnp.clip(client_id, 0, n_rows - 1)] \
            * valid
        corrected = pg_flat + res
        # per-round annealed threshold rides the quant_threshold operand
        # (the server's quant_anneal schedule, same metric log); -1 means
        # "not configured" -> the strategy's static default
        thresh = jnp.where(quant_threshold >= 0, quant_threshold,
                           self.quant_thresh) if quant_threshold is not None \
            else self.quant_thresh
        q = quantize_array(corrected, n_bins=2 ** self.quant_bits,
                           quant_threshold=thresh)
        new_res = corrected - q
        parts = dict(parts)
        parts["default"] = (unravel(q), w)
        keep = valid * live_mask * (w > 0).astype(jnp.float32)
        carry = {"row": jnp.where(keep > 0, new_res, res), "keep": keep}
        return parts, tl, ns, stats, carry

    def apply_carry(self, state, client_ids, carry, rng=None):
        rows, keep = carry["row"], carry["keep"]
        n_rows = state["res"].shape[0]
        idx = jnp.where(keep > 0, client_ids, n_rows)
        return {"res": state["res"].at[idx].set(rows, mode="drop")}

    def next_threshold(self) -> float:
        """Anneal the sparsification threshold per round — the same
        ``quant_anneal`` semantics the fused DGA path applies
        (``engine/server.py`` per-round multiply + metric log)."""
        self.quant_thresh *= self.quant_anneal
        return self.quant_thresh

    # ------------------------------------------------------------------
    def ef_step(self, pgs_flat: jnp.ndarray, residuals: jnp.ndarray,
                thresh=None):
        """One jitted EF compression over the payload stack.

        ``corrected = pgs + residuals``; per-row quantization; the new
        residual is ``corrected - q`` — the EF identity
        ``q + e' == corrected`` then holds to one f32 rounding of the
        subtraction (exact when q is near corrected, Sterbenz)."""
        from ..ops.quantization import quantize_array
        thresh = self.quant_thresh if thresh is None else thresh
        corrected = pgs_flat + residuals
        q = jax.vmap(lambda row: quantize_array(
            row, n_bins=2 ** self.quant_bits,
            quant_threshold=thresh))(corrected)
        return q, corrected - q
