"""Fleet paged carry tables (``server_config.fleet``) — O(cache) HBM,
mesh-sharded transfer plane.

The PR 6 carry design keeps each device-carry strategy's per-client
state (SCAFFOLD controls, EF residuals, personalization heads/alphas)
as ``[N, n_params]`` device residents inside ``strategy_state``.  That
is exactly the thing that cannot scale to 10^6 clients: at fleet size
the tables, not the model, own HBM.

This module replaces the resident tables with a **fixed-capacity page
pool** plus a **host backing store**, behind the SAME
``client_step_carry`` / ``apply_carry`` gather/scatter hooks:

- the tables shrink to ``[P, ...]`` where ``P = fleet.page_pool_slots``
  (``strategy.carry_rows``); the in-program math is unchanged because
  the engine feeds the carry hooks host-remapped SLOT ids instead of
  client ids (the per-client rng streams keep folding on the TRUE
  client id, so per-client math is bit-identical to resident mode);
- **the pool's slot axis is sharded over the clients mesh axis**
  (``parallel.sharding.slot_pool_sharding``), exactly like the resident
  tables it replaced: slots partition into ``mesh_size`` contiguous
  per-shard blocks, and the allocator is SHARD-AWARE — a lane's client
  gets a slot on the shard that computes the lane
  (``data.fleet.lane_shard_map``), so the in-program gather/scatter by
  ``carry_slots`` is shard-local with no cross-shard collective, and
  pool HBM / page-in bytes / writeback bytes all cost total/mesh_size
  per device instead of xmesh_size;
- before each chunk dispatches, :meth:`CarryPager.prepare_chunk` maps
  the cohort onto slots: hits reuse their resident row, misses page in
  from the host store as ONE fixed-shape SHARDED scatter — per-shard
  segments of a single ``[M*W]`` buffer (width pow2-quantized,
  sentinel-padded with out-of-bounds drop — zero post-warmup
  recompiles by construction) that donates the tables in sequence with
  the round programs; each device receives only its own segment;
- a client resampled onto a DIFFERENT shard migrates: its old slot is
  freed and the row pages in from the host store on the new shard.  If
  the old slot is still pinned by an in-flight chunk, the pager
  force-completes that chunk's already-dispatched writeback gather
  first (one explicit early fetch — the gather's value is the
  post-chunk row, so the host store is current before the migration
  pages it back in);
- right after dispatch, :meth:`queue_writeback` dispatches a small
  per-shard gather of the chunk's slot rows from the post-chunk tables
  (reading BEFORE the next dispatch donates them — the ``dp_clip``
  stash discipline); the pipeline drain completes it with one explicit
  ``device_get`` that fetches the per-shard slices, and writes the
  rows through to the host store, so a slot is evictable exactly when
  no in-flight chunk pins it;
- **prefetch** (``fleet.prefetch``, default on): while round k
  executes, a named ``fleet-prefetch`` worker thread stages round
  k+1's missing rows from the host store into a staging buffer —
  read-only against the store (RAM peek under the store lock, direct
  ``.npz`` read otherwise), so the allocator stays single-threaded and
  the staged values are exactly what the synchronous path would load
  (a prefetch-missing client cannot be resident, hence cannot have a
  pending writeback that would make the staged row stale).  The
  page-in's host IO leaves the critical path; the hit rate is a
  devbus gauge;
- eviction is LRU over unpinned slots PER SHARD; pinned (in-flight)
  rows are never evicted, so depth-N pipelining stays safe — per-shard
  contention drains the oldest outstanding writeback before giving up,
  and a pool too small overall refuses loudly instead of corrupting
  rows;
- durability rides the :class:`FleetRowStore`: RAM-LRU rows with
  crash-safe ``.npz`` spill under the model dir and the same
  round-marker pairing as the SCAFFOLD ``ControlStore`` — a resumed
  run reloads rows from disk into an EMPTY pool (slot numbering is
  invisible to the math), so preempt-and-resume stays bit-identical.
  Rows key by GLOBAL client id, so under multihost each host's shard
  of the page-in never needs another host's rows.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

import numpy as np

from ..data.fleet import lane_shard_map
from ..parallel.mesh import CLIENTS_AXIS, clients_axis_size
from ..telemetry import emit_event


def _pow2_width(n: int, floor: int = 8) -> int:
    """Pow2-quantized program width for the page-in/writeback programs:
    the compiled-variant set stays logarithmic and closes after
    warmup."""
    n = max(int(n), int(floor))
    return 1 << max(n - 1, 0).bit_length()


def read_marker(store_dir: Optional[str]) -> Optional[int]:
    """The durable fleet round marker under ``store_dir`` (None when the
    store has never committed one).  A module function so the server's
    resume-anchor pairing can probe the marker BEFORE deciding which
    checkpoint slot to restore — the :class:`FleetRowStore` itself is
    only built after that decision."""
    if store_dir is None:
        return None
    path = os.path.join(store_dir, "fleet_round.npy")
    if not os.path.exists(path):
        return None
    return int(np.load(path)[0])


def _parse_row_name(name: str) -> Optional[tuple]:
    """``row_{cid}.g{gen}.npz`` -> (cid, gen); legacy ``row_{cid}.npz``
    -> (cid, 0); anything else (tmp files, the marker) -> None."""
    if not name.startswith("row_") or not name.endswith(".npz") \
            or ".tmp" in name:
        return None
    stem = name[len("row_"):-len(".npz")]
    if ".g" in stem:
        cid_s, _, gen_s = stem.partition(".g")
    else:
        cid_s, gen_s = stem, "0"
    try:
        return int(cid_s), int(gen_s)
    except ValueError:
        return None


class FleetRowStore:
    """Host backing store for paged carry rows.

    One logical row per client, keyed by GLOBAL client id: a dict
    ``{table_key: np.ndarray}``.  RAM is LRU-bounded at ``cache_rows``;
    evicting a dirty row writes it through to disk first (crash-safe
    tmp+rename ``.npz``), so the union of RAM and disk is always the
    current row set.  ``flush()`` writes the remaining dirty rows
    through — the server calls it at ``fleet.spill_freq`` cadence and
    commits the round marker only after the paired model checkpoint is
    durable (the ControlStore discipline; a marker behind the resumed
    checkpoint resets the rows — carry state belongs to exactly one
    parameter trajectory).

    Spill files are GENERATION-versioned (flutearmor crash-point
    contract): each row lands at ``row_{cid}.g{round}.npz`` where
    ``round`` is the round whose writeback produced the content
    (``put_round``, set by the pager per writeback), and overwriting a
    row keeps its previous generation on disk until :meth:`mark_durable`
    says a checkpoint at or past that generation is durable.  A hard
    kill at ANY byte of the spill/marker/checkpoint sequence then leaves
    a bit-identical resume reachable: the server resumes from the slot
    matching the marker and :meth:`adopt_round` prunes the dead
    trajectory's newer generations, so every row read yields exactly the
    content it had at the resumed round.

    Mutations happen only on the server's round-loop thread; the
    ``fleet-prefetch`` worker reads through :meth:`peek` (RAM/spilling
    maps under ``_ram_lock``, no LRU mutation) and :meth:`_read_file`
    (atomic-replace ``.npz``, torn-read safe) — dirty evictees sit in
    the ``_spilling`` map until their file write lands, so a
    concurrent peek never sees a row in neither place.
    """

    def __init__(self, store_dir: Optional[str], cache_rows: int = 8192,
                 resume: bool = False, ladder=None):
        self.store_dir = store_dir
        self.cache_rows = max(int(cache_rows), 1)
        #: optional resilience.DurableIOLadder: spill writes and the
        #: round marker retry-then-escalate; reads retry-then-raise
        #: (losing a carry row corrupts training) — None keeps the
        #: historical raw-IO behaviour for direct constructions
        self.ladder = ladder
        self._rows: "OrderedDict[int, Dict[str, np.ndarray]]" = \
            OrderedDict()
        self._dirty: set = set()
        #: dirty evictees between pop-from-RAM and the (outside-lock)
        #: file write — readable by peek() so the row never vanishes
        self._spilling: Dict[int, Dict[str, np.ndarray]] = {}
        self._ram_lock = threading.Lock()
        self.spilled_rows = 0
        #: content round per RAM row (the generation a spill writes to)
        self._tags: Dict[int, int] = {}
        #: known on-disk generations per row, sorted ascending
        self._gens: Dict[int, List[int]] = {}
        #: newest round whose checkpoint is known durable — generations
        #: superseded by a newer one at/below this are garbage
        self._safe_round = -1
        #: round tag for incoming put()s — the pager sets this per
        #: writeback batch; direct constructions default to one
        #: generation (tag 0), the historical single-file behaviour
        self.put_round = 0
        if store_dir is not None:
            os.makedirs(store_dir, exist_ok=True)
            if resume:
                self._scan_gens()
            else:
                self._wipe_files()

    # -- paths ----------------------------------------------------------
    def _path(self, cid: int, gen: int = 0) -> str:
        return os.path.join(self.store_dir,
                            f"row_{int(cid)}.g{int(gen)}.npz")

    def _marker_path(self) -> str:
        return os.path.join(self.store_dir, "fleet_round.npy")

    def _wipe_files(self) -> None:
        for name in os.listdir(self.store_dir):
            if name.startswith("row_") or name == "fleet_round.npy":
                os.remove(os.path.join(self.store_dir, name))

    def _scan_gens(self) -> None:
        """Resume inventory: one directory listing builds the
        per-row generation map the reads select from."""
        gens: Dict[int, List[int]] = {}
        for name in os.listdir(self.store_dir):
            parsed = _parse_row_name(name)
            if parsed is not None:
                gens.setdefault(parsed[0], []).append(parsed[1])
        for lst in gens.values():
            lst.sort()
        with self._ram_lock:
            self._gens = gens

    def _newest_gen(self, cid: int) -> Optional[int]:
        with self._ram_lock:
            gens = self._gens.get(cid)
            return gens[-1] if gens else None

    def adopt_round(self, round_no: int) -> None:
        """Resume adoption: delete every generation NEWER than the
        resumed round — the dead trajectory's future — so every
        subsequent read yields the row exactly as of the anchor."""
        round_no = int(round_no)
        doomed: List[tuple] = []
        with self._ram_lock:
            for cid, gens in list(self._gens.items()):
                for g in [g for g in gens if g > round_no]:
                    gens.remove(g)
                    doomed.append((cid, g))
                if not gens:
                    del self._gens[cid]
        for cid, g in doomed:
            try:
                os.remove(self._path(cid, g))
            except OSError:
                pass

    def mark_durable(self, round_no: int) -> None:
        """A checkpoint at/past ``round_no`` is durable: generations
        superseded at/below it become prunable (GC happens lazily at
        each row's next spill — no directory scans on the hot path)."""
        self._safe_round = max(self._safe_round, int(round_no))

    def _register_gen(self, cid: int, gen: int) -> None:
        """Record a landed spill and GC this row's superseded
        generations: a generation is garbage once a NEWER one exists
        at or below the durable horizon (any future resume anchors at
        or past the horizon, so the newest covered generation is the
        one every reachable anchor selects)."""
        doomed: List[int] = []
        with self._ram_lock:
            gens = self._gens.setdefault(cid, [])
            if gen not in gens:
                gens.append(gen)
                gens.sort()
            covered = [g for g in gens if g <= self._safe_round]
            if covered:
                doomed = [g for g in gens if g < covered[-1]]
                for g in doomed:
                    gens.remove(g)
        for g in doomed:
            try:
                os.remove(self._path(cid, g))
            except OSError:
                pass

    # -- rows -----------------------------------------------------------
    def _read_file(self, cid: int) -> Optional[Dict[str, np.ndarray]]:
        """Stateless disk read (no RAM insert, no LRU motion) — the
        prefetch thread's half of :meth:`get`."""
        if self.store_dir is None:
            return None
        gen = self._newest_gen(cid)
        if gen is None:
            return None
        path = self._path(cid, gen)
        if not os.path.exists(path):
            return None
        with np.load(path) as zf:
            return {k: zf[k] for k in zf.files}

    def peek(self, cid: int) -> Optional[Dict[str, np.ndarray]]:
        """RAM (or in-spill) row WITHOUT LRU mutation — safe from the
        prefetch thread; row dicts are replaced, never mutated in
        place, so the returned mapping is stable."""
        cid = int(cid)
        with self._ram_lock:
            row = self._rows.get(cid)
            if row is None:
                row = self._spilling.get(cid)
        return row

    def _read_durable(self, cid: int) -> Optional[Dict[str, np.ndarray]]:
        """The main-thread disk read: under the ladder, a transient
        error retries with backoff and EXHAUSTION RAISES (DurableIOError
        -> flight-recorded abort) — a silently-lost carry row would
        corrupt training.  The prefetch thread never comes through here;
        its failures degrade to cold paging instead."""
        if self.ladder is None:
            return self._read_file(cid)
        box: Dict[str, Any] = {}

        def _do() -> None:
            box["row"] = self._read_file(cid)
        self.ladder.run(_do, surface="store_read",
                        what=f"fleet row {int(cid)} read")
        return box.get("row")

    def get(self, cid: int) -> Optional[Dict[str, np.ndarray]]:
        cid = int(cid)
        with self._ram_lock:
            row = self._rows.get(cid)
            if row is not None:
                self._rows.move_to_end(cid)
                return row
            row = self._spilling.get(cid)
            if row is not None:
                return row
        row = self._read_durable(cid)
        if row is not None:
            # the RAM copy inherits the on-disk generation's tag, so a
            # later clean re-spill is an idempotent same-file rewrite
            gen = self._newest_gen(cid)
            with self._ram_lock:
                self._tags[cid] = int(gen or 0)
            self._insert(cid, row, dirty=False)
        return row

    def put(self, cid: int, row: Dict[str, np.ndarray]) -> None:
        cid = int(cid)
        with self._ram_lock:
            self._tags[cid] = int(self.put_round)
        self._insert(cid, row, dirty=True)

    def _insert(self, cid: int, row: Dict[str, np.ndarray],
                dirty: bool) -> None:
        to_spill: List[tuple] = []
        with self._ram_lock:
            self._rows.pop(cid, None)
            self._rows[cid] = row
            if dirty:
                self._dirty.add(cid)
            while len(self._rows) > self.cache_rows:
                old_cid, old_row = self._rows.popitem(last=False)
                if old_cid in self._dirty:
                    # nowhere else holds the latest value: spill-through
                    # (file IO deferred past the lock; the row stays
                    # visible via _spilling until the write lands)
                    self._dirty.discard(old_cid)
                    self._spilling[old_cid] = old_row
                    to_spill.append((old_cid, old_row))
        for old_cid, old_row in to_spill:
            if self._write(old_cid, old_row):
                with self._ram_lock:
                    self._spilling.pop(old_cid, None)
                self.spilled_rows += 1
            # on exhausted retries the row STAYS in _spilling: still
            # served to peek/get, re-attempted at the next flush() —
            # a lost write degrades capacity, never correctness (the
            # ladder's escalator aborts a persistent outage)

    def _write(self, cid: int, row: Dict[str, np.ndarray]) -> bool:
        if self.store_dir is None:
            return True
        with self._ram_lock:
            gen = int(self._tags.get(cid, 0))
        path = self._path(cid, gen)
        tmp = path + ".tmp.npz"  # .npz suffix stops np.savez appending one

        def _do() -> None:
            np.savez(tmp, **row)
            os.replace(tmp, path)
        if self.ladder is None:
            _do()
            ok = True
        else:
            ok = self.ladder.run(_do, surface="store_write",
                                 what=f"fleet row {int(cid)} spill")
        if ok:
            self._register_gen(cid, gen)
        return ok

    def has_rows(self) -> bool:
        """Whether ANY client has a stored row (RAM or disk) — the
        cheap personalized-eval seen gate.  scandir short-circuits at
        the first row file: O(1), never an O(N)-filename listing."""
        if self._rows:
            return True
        if self.store_dir is None:
            return False
        with os.scandir(self.store_dir) as it:
            return any(entry.name.startswith("row_")
                       and ".tmp" not in entry.name for entry in it)

    # -- durability -----------------------------------------------------
    def flush(self) -> int:
        """Write every dirty RAM row through to disk; returns the row
        count (the spill transfer meter).  A row whose write exhausts
        its retries goes BACK on the dirty set (and stuck spill-through
        evictees re-attempt here too) — flush degrades to partial, never
        to silent loss."""
        if self.store_dir is None:
            self._dirty.clear()
            return 0
        n = 0
        with self._ram_lock:
            pending = [(cid, self._rows.get(cid))
                       for cid in sorted(self._dirty)]
            self._dirty.clear()
            stuck = sorted(self._spilling.items())
        for cid, row in pending:
            if row is None:
                continue
            if self._write(cid, row):
                n += 1
            else:
                with self._ram_lock:
                    if cid in self._rows:
                        self._dirty.add(cid)
        for cid, row in stuck:
            if self._write(cid, row):
                with self._ram_lock:
                    self._spilling.pop(cid, None)
                self.spilled_rows += 1
                n += 1
        return n

    def set_round(self, round_no: int) -> None:
        if self.store_dir is None:
            return
        path = self._marker_path()
        tmp = path + ".tmp.npy"

        def _do() -> None:
            np.save(tmp, np.asarray([int(round_no)], np.int64))
            os.replace(tmp, path)
        if self.ladder is None:
            _do()
        else:
            self.ladder.run(_do, surface="marker",
                            what=f"fleet round marker {int(round_no)}")

    def round(self) -> Optional[int]:
        return read_marker(self.store_dir)

    def reset(self) -> None:
        """Drop every row + marker (trajectory-mismatch semantics)."""
        with self._ram_lock:
            self._rows.clear()
            self._dirty.clear()
            self._spilling.clear()
            self._tags.clear()
            self._gens.clear()
        if self.store_dir is not None:
            self._wipe_files()


class CarryPager:
    """Shard-aware slot allocator + sharded page-in/writeback programs
    for ONE run's carry tables.  Allocator state is single-threaded by
    design: every mutating method is called from the server's round
    loop (prefetch -> prepare -> dispatch -> queue -> drain); the
    prefetch worker only stages row VALUES."""

    def __init__(self, strategy, state_tables: Dict[str, Any],
                 slots: int, mesh,
                 store_dir: Optional[str] = None,
                 host_cache_rows: int = 8192,
                 resume: bool = False,
                 partition_mode: str = "shard_map",
                 prefetch: bool = True,
                 ladder=None, faults=None):
        import jax
        from ..parallel.sharding import slot_pool_sharding

        self.strategy = strategy
        self.keys = tuple(strategy.carry_tables)
        if not self.keys:
            raise ValueError(
                f"{type(strategy).__name__} declares no carry_tables — "
                "fleet paging has nothing to page; drop the fleet block "
                "or use a device-carry strategy")
        self.n_slots = int(slots)
        self.mesh_shards = clients_axis_size(mesh)
        if self.n_slots % self.mesh_shards:
            raise ValueError(
                f"fleet.page_pool_slots={self.n_slots} does not split "
                f"over the {self.mesh_shards}-shard clients mesh axis — "
                "the server quantizes the pool to a mesh multiple; "
                "constructing CarryPager directly, do the same")
        #: per-shard block width: slot s lives on shard s // shard_slots
        self.shard_slots = self.n_slots // self.mesh_shards
        self.partition_mode = str(partition_mode)
        # per-key row geometry straight off the live tables (shape[0]
        # is the slot count; everything after is the row)
        self._row_shape = {}
        self._row_dtype = {}
        for k in self.keys:
            leaf = state_tables[k]
            if int(leaf.shape[0]) != self.n_slots:
                raise ValueError(
                    f"fleet paging: strategy_state[{k!r}] has "
                    f"{int(leaf.shape[0])} rows but the page pool is "
                    f"{self.n_slots} slots — carry_rows was not applied "
                    "before init_state")
            self._row_shape[k] = tuple(int(d) for d in leaf.shape[1:])
            self._row_dtype[k] = np.dtype(str(leaf.dtype))
        self._defaults = dict(strategy.carry_row_defaults())
        #: slot-axis tables and page-in/writeback buffers are SHARDED
        #: over the clients axis — per-device bytes = total/mesh_size
        self._pool_spec = slot_pool_sharding(mesh)
        #: one DurableIOLadder governs the store's spill/read/marker IO
        #: AND this pager's writeback fetch; the chaos InfraFaults (if
        #: any) supplies the prefetch-surface hooks below
        self.ladder = ladder
        self._infra = faults
        self._prefetch_fault = (faults.hook("prefetch")
                                if faults is not None else None)
        self.store = FleetRowStore(store_dir, cache_rows=host_cache_rows,
                                   resume=resume, ladder=ladder)

        # ---- slot state (per shard) ----------------------------------
        self._free: List[List[int]] = [
            list(range((s + 1) * self.shard_slots - 1,
                       s * self.shard_slots - 1, -1))
            for s in range(self.mesh_shards)]
        self._slot_client = np.full((self.n_slots,), -1, np.int64)
        self._client_slot: Dict[int, int] = {}
        self._pins = np.zeros((self.n_slots,), np.int64)
        #: per-shard unpinned slots in LRU order (front = evict first)
        self._lru: List["OrderedDict[int, None]"] = [
            OrderedDict() for _ in range(self.mesh_shards)]
        self._ticket: Optional[Dict[str, Any]] = None
        #: queued-but-uncompleted writeback handles, dispatch order —
        #: what a shard-migration force-completes to unpin old slots
        self._outstanding: deque = deque()

        # ---- prefetch staging ----------------------------------------
        self.prefetch_enabled = bool(prefetch)
        #: set on the first prefetch_chunk call — hit/miss accounting
        #: starts only once the server actually ENGAGES prefetch (a
        #: serial or sample-hooked run never does; its cold page-ins
        #: must not read as a 0.0 hit rate to the scope diff gate)
        self._prefetch_engaged = False
        self._staging: Dict[int, Optional[Dict[str, np.ndarray]]] = {}
        self._staging_lock = threading.Lock()
        self._prefetch_thread: Optional[threading.Thread] = None
        #: optional flutescope Telemetry (the server wires it): the
        #: worker opens a `fleet_prefetch` span on its OWN thread
        #: track, so the trace shows the paging host IO overlapping
        #: the device window instead of sitting on the critical path
        self.scope = None

        # ---- compiled program caches (one per pow2 width) ------------
        self._scatter_cache: Dict[int, Any] = {}
        self._gather_cache: Dict[int, Any] = {}
        self._jax = jax

        # ---- counters (bench marker + devbus gauges) -----------------
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.migrations = 0
        self.forced_drains = 0
        self.page_in_rows = 0
        self.writeback_rows = 0
        self.page_in_bytes = 0
        self.writeback_bytes = 0
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self.prefetch_degradations = 0

    # ------------------------------------------------------------------
    def describe(self) -> Dict[str, Any]:
        total_pf = self.prefetch_hits + self.prefetch_misses
        return {
            "pool_slots": self.n_slots,
            "mesh_shards": int(self.mesh_shards),
            "shard_slots": int(self.shard_slots),
            "resident": int(len(self._client_slot)),
            "hits": int(self.hits),
            "misses": int(self.misses),
            "evictions": int(self.evictions),
            "migrations": int(self.migrations),
            "forced_drains": int(self.forced_drains),
            "page_in_rows": int(self.page_in_rows),
            "writeback_rows": int(self.writeback_rows),
            "page_in_bytes": int(self.page_in_bytes),
            "page_in_bytes_per_device":
                int(self.page_in_bytes // self.mesh_shards),
            "writeback_bytes": int(self.writeback_bytes),
            "writeback_bytes_per_device":
                int(self.writeback_bytes // self.mesh_shards),
            "prefetch_hits": int(self.prefetch_hits),
            "prefetch_misses": int(self.prefetch_misses),
            "prefetch_degradations": int(self.prefetch_degradations),
            # None (not 0.0) when prefetch never engaged: a serial /
            # sample-hooked / prefetch-off run has no coverage to
            # report, and a 0.0 would trip the scope-diff hit-rate gate
            # against any prefetching baseline
            "prefetch_hit_rate": (float(self.prefetch_hits) / total_pf
                                  if total_pf else
                                  (0.0 if self._prefetch_engaged
                                   else None)),
            "spilled_rows": int(self.store.spilled_rows),
            "hbm_bytes_per_device":
                int(self.shard_slots * self.hbm_row_bytes()),
            "tables": list(self.keys),
        }

    def hbm_row_bytes(self) -> int:
        """Bytes one pool row costs across all table keys — PER-DEVICE
        pool HBM is ``shard_slots * hbm_row_bytes()`` (the slot axis is
        sharded), independent of N."""
        return int(sum(
            int(np.prod(self._row_shape[k], dtype=np.int64) or 1)
            * self._row_dtype[k].itemsize for k in self.keys))

    def pool_sharding(self):
        """The slot-axis NamedSharding the engine puts the carry tables
        with (``P(CLIENTS_AXIS)`` on axis 0)."""
        return self._pool_spec

    # ------------------------------------------------------------------
    # slot allocation (shard-aware)
    # ------------------------------------------------------------------
    def _shard_of(self, slot: int) -> int:
        return slot // self.shard_slots

    def _pin(self, slot: int) -> None:
        if self._pins[slot] == 0:
            self._lru[self._shard_of(slot)].pop(slot, None)
        self._pins[slot] += 1

    def _unpin(self, slot: int) -> None:
        self._pins[slot] -= 1
        if self._pins[slot] <= 0:
            self._pins[slot] = 0
            if self._slot_client[slot] >= 0:
                # tail = most recently used
                self._lru[self._shard_of(slot)][slot] = None

    def _force_drain_oldest(self) -> bool:
        """Complete the oldest outstanding writeback early (an explicit
        fetch of an already-dispatched gather — the value is the
        post-chunk rows, so the host store is current afterwards).
        Unblocks shard migrations and per-shard slot contention."""
        if not self._outstanding:
            return False
        self.forced_drains += 1
        self.complete_writeback(self._outstanding[0])
        return True

    def _alloc(self, cid: int, shard: int) -> int:
        while True:
            if self._free[shard]:
                slot = self._free[shard].pop()
                break
            if self._lru[shard]:
                slot, _ = self._lru[shard].popitem(last=False)  # LRU head
                old = int(self._slot_client[slot])
                # the host store already holds the evictee's current
                # row: unpinned means every chunk that touched it
                # drained, and the drain wrote the row back — eviction
                # costs zero device traffic
                self._client_slot.pop(old, None)
                self.evictions += 1
                break
            # every slot of this shard is pinned by an in-flight chunk:
            # drain the oldest outstanding writeback (early explicit
            # fetch) and retry — only a pool too small overall gives up
            if not self._force_drain_oldest():
                raise ValueError(
                    f"fleet.page_pool_slots={self.n_slots} cannot hold "
                    f"the in-flight cohorts: every slot of shard {shard} "
                    f"({self.shard_slots} of {self.n_slots}) is pinned "
                    "by a dispatched chunk — raise page_pool_slots (it "
                    "must cover (pipeline_depth + 1) x cohort x "
                    "rounds_per_step rows per shard)")
        self._slot_client[slot] = cid
        self._client_slot[cid] = slot
        return slot

    def _migrate_out(self, cid: int, slot: int) -> None:
        """Free a client's slot on the wrong shard so it can re-alloc
        on the shard that computes its lane.  An in-flight pin means an
        undrained chunk still owns the row — force-complete writebacks
        (oldest first) until the pin drops, so the host store holds the
        post-chunk value before the migration pages it back in."""
        while self._pins[slot] > 0:
            if not self._force_drain_oldest():
                raise RuntimeError(
                    "fleet pager: slot pinned with no outstanding "
                    "writeback — prepare/queue discipline broken")
        shard = self._shard_of(slot)
        self._lru[shard].pop(slot, None)
        self._client_slot.pop(cid, None)
        self._slot_client[slot] = -1
        self._free[shard].append(slot)
        self.migrations += 1

    # ------------------------------------------------------------------
    # prefetch (host-side async stage of next chunk's missing rows)
    # ------------------------------------------------------------------
    def prefetch_chunk(self, batches: list) -> int:
        """Stage the NEXT chunk's missing rows on a background thread
        while the device executes the current one.  Read-only against
        the store (peek + direct file read) — the allocator and LRU
        stay single-threaded, and a staged value cannot go stale: a
        client missing from the pool is in no in-flight chunk, so no
        writeback can update its row before the next prepare_chunk
        consumes the staging.  Returns the number of rows queued."""
        if not self.prefetch_enabled:
            return 0
        self._prefetch_engaged = True
        self._join_prefetch()
        flat = [b for entry in batches
                for b in (entry if isinstance(entry, list) else [entry])]
        want: List[int] = []
        seen: set = set()
        for b in flat:
            for cid in np.asarray(b.client_ids).ravel():
                cid = int(cid)
                if cid < 0 or cid in seen or cid in self._client_slot:
                    continue
                seen.add(cid)
                want.append(cid)
        with self._staging_lock:
            self._staging = {}
            staging = self._staging
        if not want:
            return 0
        t = threading.Thread(
            target=self._prefetch_worker, args=(want, staging),
            name="fleet-prefetch", daemon=True)
        self._prefetch_thread = t
        t.start()
        return len(want)

    def _prefetch_worker(self, cids: List[int], staging: dict) -> None:
        try:
            scope = self.scope
            if scope is not None:
                with scope.span("fleet_prefetch", rows=len(cids)):
                    self._prefetch_rows(cids, staging)
            else:
                self._prefetch_rows(cids, staging)
        except Exception as exc:  # noqa: BLE001 - any death must degrade
            self._degrade_prefetch(exc)

    def _degrade_prefetch(self, exc: BaseException) -> None:
        """The fleet-prefetch daemon died (injected chaos fault or a
        real one): permanently fall back to COLD paging — every later
        miss takes the synchronous ``store.get`` path, which loads the
        exact same values (bit-identical by the staging contract), just
        on the critical path.  One structured ``prefetch_degraded``
        instant event surfaces it; the thread never dies silently into
        a dead staging generation."""
        self.prefetch_enabled = False
        self.prefetch_degradations += 1
        with self._staging_lock:
            self._staging = {}
        emit_event(self.scope, "prefetch_degraded",
                   error=repr(exc),
                   degradations=int(self.prefetch_degradations))

    def _prefetch_rows(self, cids: List[int], staging: dict) -> None:
        store = self.store
        infra = self._infra
        if infra is not None:
            # seeded staging stall: exercises the superseded-generation
            # path (prepare_chunk clears a half-filled staging dict and
            # the loop below notices and stops) without killing the
            # worker
            delay = infra.prefetch_delay()
            if delay > 0.0:
                time.sleep(delay)
        for cid in cids:
            if self._prefetch_fault is not None:
                self._prefetch_fault()
            row = store.peek(cid)
            if row is None:
                row = store._read_file(cid)
            with self._staging_lock:
                if staging is not self._staging:
                    return  # superseded generation: stop loading
                staging[cid] = row

    def _join_prefetch(self) -> None:
        t = self._prefetch_thread
        if t is not None and t.is_alive():
            t.join()
        self._prefetch_thread = None

    def _load_row(self, cid: int) -> Optional[Dict[str, np.ndarray]]:
        """A miss's row: the prefetch staging if the worker got there
        (hit — host IO already off the critical path), else the
        synchronous store read (cold path; bit-identical values)."""
        if self._prefetch_engaged:
            with self._staging_lock:
                if cid in self._staging:
                    self.prefetch_hits += 1
                    return self._staging.pop(cid)
            self.prefetch_misses += 1
        return self.store.get(cid)

    # ------------------------------------------------------------------
    # per-chunk flow
    # ------------------------------------------------------------------
    def prepare_chunk(self, batches: list, strategy_state: Any) -> Any:
        """Map the chunk's cohorts onto pool slots (writes
        ``batch.carry_slots`` on every grid — GLOBAL slot ids; the
        engine converts to shard-local indices inside ``shard_map`` —
        -1 for padding lanes), page missing rows in as one fixed-shape
        donated SHARDED scatter, and pin the touched slots until this
        chunk drains.  Slot placement follows ``lane_shard_map``: each
        lane's row lands on the shard that computes it.  Returns the
        (possibly updated) ``strategy_state``."""
        if self._ticket is not None:
            raise RuntimeError(
                "fleet pager: prepare_chunk called with an unconsumed "
                "ticket — queue_writeback must run after each dispatch")
        flat = [b for entry in batches
                for b in (entry if isinstance(entry, list) else [entry])]
        chunk_slots: "OrderedDict[int, int]" = OrderedDict()  # slot->cid
        chunk_shard: Dict[int, int] = {}  # cid -> required shard
        miss: List[tuple] = []
        for b in flat:
            ids = np.asarray(b.client_ids)
            shards = lane_shard_map(ids.shape[0], self.mesh_shards)
            slots = np.full(ids.shape, -1, np.int32)
            for j, cid in enumerate(ids):
                cid = int(cid)
                if cid < 0:
                    continue
                shard = int(shards[j])
                prev = chunk_shard.get(cid)
                if prev is not None and prev != shard:
                    # the server refuses rounds_per_step > 1 on a >1-
                    # shard mesh exactly because this row dependency
                    # cannot be satisfied without a cross-shard
                    # collective; reaching here is a logic error
                    raise RuntimeError(
                        f"fleet pager: client {cid} appears on shards "
                        f"{prev} and {shard} within one chunk — "
                        "mid-chunk cross-shard carry reuse is "
                        "unsupported (rounds_per_step must be 1 on a "
                        "multi-device mesh)")
                chunk_shard[cid] = shard
                slot = self._client_slot.get(cid)
                if slot is not None and self._shard_of(slot) != shard:
                    # resampled onto a different shard: free the old
                    # slot (force-draining its in-flight writeback if
                    # needed) and treat as a miss on the new shard —
                    # the host store holds the current row
                    self._migrate_out(cid, slot)
                    slot = None
                if slot is None:
                    slot = self._alloc(cid, shard)
                    miss.append((cid, slot))
                    self.misses += 1
                else:
                    self.hits += 1
                    shard_lru = self._lru[shard]
                    if self._pins[slot] == 0 and slot in shard_lru:
                        shard_lru.move_to_end(slot)
                slots[j] = slot
                if slot not in chunk_slots:
                    chunk_slots[slot] = cid
                    self._pin(slot)
            b.carry_slots = slots
        page_in_bytes = 0
        if miss:
            strategy_state, page_in_bytes = \
                self._page_in(strategy_state, miss)
        self._ticket = {
            "slots": np.asarray(list(chunk_slots), np.int32),
            "ids": np.asarray(list(chunk_slots.values()), np.int64),
            "page_in_bytes": int(page_in_bytes),
        }
        if self.prefetch_enabled:
            # generation boundary: anything the worker staged for this
            # chunk and nobody consumed is dead weight now
            with self._staging_lock:
                self._staging = {}
        return strategy_state

    def _page_in(self, strategy_state: Any, miss: List[tuple]) -> tuple:
        jax = self._jax
        M, SS = self.mesh_shards, self.shard_slots
        per_shard: List[List[tuple]] = [[] for _ in range(M)]
        for cid, slot in miss:
            per_shard[self._shard_of(slot)].append((cid, slot))
        W = _pow2_width(max(len(g) for g in per_shard))
        local_ids = self.partition_mode == "shard_map"
        # sentinel index: one past the (local or global) slot range —
        # out of bounds, mode="drop", so padded lanes scatter nothing
        sentinel = SS if local_ids else self.n_slots
        slot_arr = np.full((M * W,), sentinel, np.int32)
        rows = {k: np.full((M * W,) + self._row_shape[k],
                           self._defaults.get(k, 0.0),
                           self._row_dtype[k]) for k in self.keys}
        for s, group in enumerate(per_shard):
            for i, (cid, slot) in enumerate(group):
                slot_arr[s * W + i] = (slot - s * SS) if local_ids \
                    else slot
                stored = self._load_row(cid)
                if stored is not None:
                    for k in self.keys:
                        rows[k][s * W + i] = stored[k]
        self.page_in_rows += len(miss)
        nbytes = int(sum(r.nbytes for r in rows.values())
                     + slot_arr.nbytes)
        self.page_in_bytes += nbytes
        fn = self._scatter_cache.get(W)
        if fn is None:
            fn = self._build_scatter(W)
            self._scatter_cache[W] = fn
        tables = {k: strategy_state[k] for k in self.keys}
        # ONE sharded put for the whole padded row dict: the leading
        # axis is P(CLIENTS_AXIS), so each device receives only its own
        # [W] segment — per-device page-in bytes = total / mesh_size
        rows_dev = jax.device_put(rows, self._pool_spec)
        slots_dev = jax.device_put(slot_arr, self._pool_spec)
        new_tables = fn(tables, slots_dev, rows_dev)
        new_state = dict(strategy_state)
        new_state.update(new_tables)
        return new_state, nbytes

    def _build_scatter(self, W: int):
        jax = self._jax
        keys = self.keys

        def scatter(tables, slots, new_rows):
            # sentinel-padded lanes target one past the slot range:
            # out of bounds, mode="drop" — the fixed [M*W] shape never
            # retraces on the miss count
            return {k: tables[k].at[slots].set(new_rows[k], mode="drop")
                    for k in keys}

        if self.partition_mode == "shard_map":
            from jax.sharding import PartitionSpec as P
            from jax import shard_map
            cspec = P(CLIENTS_AXIS)
            scatter = shard_map(
                scatter, mesh=self._pool_spec.mesh,
                in_specs=(cspec, cspec, cspec), out_specs=cspec,
                check_vma=False)
        return jax.jit(scatter, donate_argnums=(0,))

    def _build_gather(self, W: int):
        jax = self._jax
        import jax.numpy as jnp
        keys = self.keys
        hi = (self.shard_slots if self.partition_mode == "shard_map"
              else self.n_slots) - 1

        def gather(tables, slots):
            idx = jnp.clip(slots, 0, hi)
            return {k: tables[k][idx] for k in keys}

        if self.partition_mode == "shard_map":
            from jax.sharding import PartitionSpec as P
            from jax import shard_map
            cspec = P(CLIENTS_AXIS)
            gather = shard_map(
                gather, mesh=self._pool_spec.mesh,
                in_specs=(cspec, cspec), out_specs=cspec,
                check_vma=False)
        return jax.jit(gather)

    def queue_writeback(self, strategy_state: Any,
                        round_no: int = 0) -> Dict[str, Any]:
        """Dispatch the async per-shard gather of this chunk's slot
        rows from the POST-chunk tables.  Must run before the next
        dispatch donates ``strategy_state`` (program order then
        guarantees the gather reads the chunk's output).  ``round_no``
        is the chunk's LAST round — the generation tag the drained rows
        spill under (the crash-point rollback anchor).  Returns the
        handle the drain completes (idempotently — a shard migration
        may have force-completed it early)."""
        ticket = self._ticket
        self._ticket = None
        if ticket is None or ticket["slots"].size == 0:
            return {"ids": np.empty((0,), np.int64), "rows": None,
                    "slots": np.empty((0,), np.int32),
                    "pos": np.empty((0,), np.int64), "done": True,
                    "round": int(round_no),
                    "page_in_bytes": int((ticket or {}).get(
                        "page_in_bytes", 0)),
                    "writeback_bytes": 0}
        jax = self._jax
        M, SS = self.mesh_shards, self.shard_slots
        per_shard: List[List[int]] = [[] for _ in range(M)]
        order: List[int] = []  # ticket index in segment-layout order
        for i, slot in enumerate(ticket["slots"]):
            per_shard[self._shard_of(int(slot))].append(i)
        W = _pow2_width(max(len(g) for g in per_shard))
        local_ids = self.partition_mode == "shard_map"
        slot_arr = np.zeros((M * W,), np.int32)
        pos = np.empty((ticket["slots"].size,), np.int64)
        n = 0
        for s, group in enumerate(per_shard):
            for i, tick_i in enumerate(group):
                slot = int(ticket["slots"][tick_i])
                slot_arr[s * W + i] = (slot - s * SS) if local_ids \
                    else slot
                pos[n] = s * W + i
                order.append(tick_i)
                n += 1
        fn = self._gather_cache.get(W)
        if fn is None:
            fn = self._build_gather(W)
            self._gather_cache[W] = fn
        tables = {k: strategy_state[k] for k in self.keys}
        slots_dev = jax.device_put(slot_arr, self._pool_spec)
        rows = fn(tables, slots_dev)
        wb_bytes = int(sum(
            int(np.prod((M * W,) + self._row_shape[k], dtype=np.int64))
            * self._row_dtype[k].itemsize for k in self.keys))
        self.writeback_bytes += wb_bytes
        handle = {"ids": ticket["ids"][order],
                  "slots": ticket["slots"][order],
                  "pos": pos, "rows": rows, "done": False,
                  "round": int(round_no),
                  "page_in_bytes": int(ticket["page_in_bytes"]),
                  "writeback_bytes": wb_bytes}
        self._outstanding.append(handle)
        return handle

    def complete_writeback(self, handle: Dict[str, Any]) -> None:
        """Drain half: ONE explicit fetch of the gathered rows — the
        per-shard slices of the sharded gather output come back in the
        one ``device_get`` — written through to the host store; the
        chunk's slots unpin.  Idempotent: a shard migration may have
        force-completed this handle before the pipeline drain reaches
        it."""
        if handle.get("done"):
            return
        handle["done"] = True
        # identity scan, not deque.remove: == on handle dicts would
        # element-wise compare their numpy members
        for i, h in enumerate(self._outstanding):
            if h is handle:
                del self._outstanding[i]
                break
        ids = handle["ids"]
        if handle["rows"] is None or ids.size == 0:
            return
        jax = self._jax
        if self.ladder is None:
            fetched = jax.device_get(handle["rows"])
        else:
            # transient fetch failures retry under the ladder; an
            # exhausted fetch raises DurableIOError (these are the
            # post-chunk carry rows — losing them corrupts training)
            box: Dict[str, Any] = {}

            def _fetch() -> None:
                box["v"] = jax.device_get(handle["rows"])
            self.ladder.run(_fetch, surface="writeback",
                            what=f"fleet writeback of {int(ids.size)} rows")
            fetched = box["v"]
        pos = handle["pos"]
        # the rows about to land carry this chunk's final round as
        # their generation tag (crash-point rollback selects on it)
        self.store.put_round = int(handle.get("round", 0))
        for i, cid in enumerate(ids):
            # np.array (copy), not np.asarray (view): a view would pin
            # the whole padded [M*W] fetch buffer in the host row cache
            self.store.put(int(cid),
                           {k: np.array(fetched[k][pos[i]])
                            for k in self.keys})
        self.writeback_rows += int(ids.size)
        for slot in handle["slots"]:
            self._unpin(int(slot))

    # ------------------------------------------------------------------
    # host-side reads (personalized eval) + durability
    # ------------------------------------------------------------------
    def user_row(self, uid: int) -> Optional[Dict[str, np.ndarray]]:
        """The client's CURRENT carry row from the host store (valid at
        any drained boundary — eval boundaries fully drain the ring),
        or None for a never-participated client."""
        return self.store.get(int(uid))

    def has_rows(self) -> bool:
        return self.store.has_rows()

    def flush(self) -> int:
        return self.store.flush()

    def set_round(self, round_no: int) -> None:
        self.store.set_round(round_no)

    def round(self) -> Optional[int]:
        return self.store.round()

    def adopt_round(self, round_no: int) -> None:
        self.store.adopt_round(round_no)

    def mark_durable(self, round_no: int) -> None:
        self.store.mark_durable(round_no)

    def reset(self) -> None:
        """Trajectory mismatch on resume: drop the host rows AND the
        slot map — every next touch cold-starts from the defaults,
        exactly like a fresh table."""
        self._join_prefetch()
        self.store.reset()
        self._free = [
            list(range((s + 1) * self.shard_slots - 1,
                       s * self.shard_slots - 1, -1))
            for s in range(self.mesh_shards)]
        self._slot_client[:] = -1
        self._client_slot.clear()
        self._pins[:] = 0
        for lru in self._lru:
            lru.clear()
        self._ticket = None
        self._outstanding.clear()
        with self._staging_lock:
            self._staging = {}
