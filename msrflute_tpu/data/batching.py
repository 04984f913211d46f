"""Static-shape round batching — the TPU replacement for torch DataLoaders.

Parity target: reference per-task ``dataloaders/dataloader.py`` + the
samplers in ``utils/data_utils.py`` (``BatchSampler`` contiguous batches,
``DynamicBatchSampler`` padding-efficiency batching) + the
``desired_max_samples`` early stop (``core/trainer.py:363-364``).

TPU-first design: a round's sampled clients become ONE array program input of
static shape ``[K, S, B, ...]`` (K clients x S local steps x B batch) with a
``[K, S, B]`` sample mask.  Ragged client sizes are absorbed by masking, not
by Python-side dynamic batching, so the whole round jits once per (K, S, B)
and never retraces.  Sample weights count only *real* samples — the mask sums
reproduce FLUTE's ``num_samples`` aggregation weights exactly
(``core/strategies/fedavg.py:61-91``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Sequence

import numpy as np

from .dataset import BaseDataset


@dataclass
class RoundBatch:
    """One round's client data as static-shape arrays.

    arrays:       each ``[K, S, B, *feat]``
    sample_mask:  ``[K, S, B]`` — 1.0 for real samples
    num_samples:  ``[K]`` — real (capped) per-client sample counts
    client_mask:  ``[K]`` — 1.0 for real clients, 0.0 for mesh padding
    client_ids:   ``[K]`` — dataset user indices (-1 for padding)
    """

    arrays: Dict[str, np.ndarray]
    sample_mask: np.ndarray
    num_samples: np.ndarray
    client_mask: np.ndarray
    client_ids: np.ndarray
    #: fleet paging (server_config.fleet): per-lane PAGE-POOL SLOT ids
    #: for the carry gather/scatter, parallel to ``client_ids`` (-1 for
    #: padding).  None outside paged-carry mode — the engine then uses
    #: ``client_ids`` for both, which is the resident-table program.
    carry_slots: Optional[np.ndarray] = None
    #: cross-client megabatching (server_config.megabatch): the
    #: super-batch pointer tape covering this grid, attached by the
    #: server's bucket packer when the bucket's analytic gate holds.
    #: None = per-client vmap arm only.
    mega: Optional["MegaTape"] = None

    @property
    def shape(self):
        return self.sample_mask.shape


def ceil_div(n: int, d: int) -> int:
    """Integer ceiling division — the ONE spelling of the idiom that
    :func:`steps_for` and :func:`_sample_cap` both used to hand-roll
    (``math.ceil(a / b)`` truncates for large ints via the float detour;
    ``-(-a // b)`` is exact but write-only).  Property-tested at the
    ``desired_max_samples`` mid-batch boundary in
    ``tests/test_cohort_bucketing.py``."""
    return -(-int(n) // int(d))


def steps_for(max_samples: int, batch_size: int,
              desired_max_samples: Optional[int] = None) -> int:
    """Static local-step count S for a round program.

    FLUTE stops a client's epoch once ``desired_max_samples`` is reached
    (``core/trainer.py:363-364``); the static equivalent caps every client at
    ``S*B`` samples where ``S = ceil(min(max, desired)/B)``.
    """
    cap = max_samples if desired_max_samples is None else min(
        max_samples, desired_max_samples)
    return max(1, ceil_div(cap, batch_size))


def _sample_cap(S: int, B: int, desired_max_samples: Optional[int]) -> int:
    """Per-client sample cap in the reference's BATCH-granular semantics:
    its epoch loop checks the accumulated count at the TOP of each batch
    (``core/trainer.py:363-364``), so the batch that crosses
    ``desired_max_samples`` still trains in full — the effective cap is
    ``ceil(desired/B)*B``, not ``desired`` (an exact-sample cap would
    train on fewer samples than the reference whenever the cap is not a
    batch multiple; with one batch per client a cap below the batch size
    would wrongly engage at all)."""
    if desired_max_samples is None:
        return S * B
    return min(S * B, ceil_div(desired_max_samples, B) * B)


def _pad_feat(sample_count: int, shape: tuple, dtype) -> np.ndarray:
    return np.zeros((sample_count,) + shape, dtype=dtype)


def pack_round_batches(
    dataset: BaseDataset,
    client_indices: Sequence[int],
    batch_size: int,
    max_steps: int,
    rng: Optional[np.random.Generator] = None,
    shuffle: bool = True,
    pad_clients_to: Optional[int] = None,
    desired_max_samples: Optional[int] = None,
    orders: Optional[Dict[int, np.ndarray]] = None,
) -> RoundBatch:
    """Assemble ``[K, S, B, ...]`` arrays for the sampled clients.

    Per client: optionally shuffle its samples (the reference's train
    DataLoaders shuffle), truncate to ``min(S*B, desired_max_samples)``, and
    zero-pad to the static grid.  K is padded to ``pad_clients_to`` (mesh
    divisibility) with zero-weight clients — the masked equivalent of
    FLUTE's idle-node dummy syncs (``core/federated.py:251-262``).

    ``orders`` (client id -> sample permutation) overrides the in-place
    shuffle draw: cohort bucketing pre-draws every sampled client's
    permutation in COHORT order before packing per-bucket grids, so the
    rng trail — and hence every client's sample order — is identical to
    what the monolithic pack would have drawn (the cross-mode
    bit-identity anchor, ``tests/test_cohort_bucketing.py``).

    A ``-1`` entry in ``client_indices`` is an explicit PADDING HOLE:
    the row packs as all-padding (mask 0, id -1) exactly like the tail
    padding.  Megabatch grouping uses holes to shard-align rows with
    the super-batch tape's lane blocks (``plan_megabatch``).
    """
    rng = rng or np.random.default_rng(0)
    K = len(client_indices)
    K_pad = max(pad_clients_to or K, K)
    S, B = max_steps, batch_size
    spec = dataset.element_spec

    # an EMPTY client list still packs a valid all-padding grid (a
    # bucketed round dispatches every bucket at its static capacity,
    # occupied or not) — dtypes come from the first real user (or 0)
    first_real = next((int(ci) for ci in client_indices if int(ci) >= 0), 0)
    ref = dataset.user_arrays(first_real)
    arrays = {k: np.zeros((K_pad, S, B) + shape, dtype=ref[k].dtype)
              for k, shape in spec.items()}
    sample_mask = np.zeros((K_pad, S, B), dtype=np.float32)
    num_samples = np.zeros((K_pad,), dtype=np.float32)
    client_mask = np.zeros((K_pad,), dtype=np.float32)
    client_ids = np.full((K_pad,), -1, dtype=np.int32)

    cap = _sample_cap(S, B, desired_max_samples)
    users, takes = [], []
    for j, ci in enumerate(client_indices):
        if int(ci) < 0:
            # hole row: keep users/takes aligned with grid row j so the
            # parallel gather below writes nothing into it
            users.append({k: np.zeros((0,) + shape, dtype=ref[k].dtype)
                          for k, shape in spec.items()})
            takes.append(np.zeros((0,), dtype=np.int64))
            continue
        user = dataset.user_arrays(ci)
        n = len(next(iter(user.values())))
        if orders is not None:
            order = orders[ci]
        else:
            order = rng.permutation(n) if shuffle else np.arange(n)
        take = order[:cap]
        users.append(user)
        takes.append(take)
        t = len(take)
        sample_mask[j].reshape(-1)[:t] = 1.0
        num_samples[j] = t
        client_mask[j] = 1.0
        client_ids[j] = ci

    # row gather: the native packer memcpy's all clients in parallel (the
    # runtime analogue of the reference's DataLoader worker collation);
    # numpy fallback is identical, just single-threaded
    from ..native import gather_rows
    for k, shape in spec.items():
        if not users:
            break
        dst = arrays[k].reshape((K_pad, S * B) + shape)
        srcs = [np.asarray(u[k]) for u in users]
        if not gather_rows(dst, srcs, takes):
            for j, (src, take) in enumerate(zip(srcs, takes)):
                dst[j, :len(take)] = src[take]
    return RoundBatch(arrays, sample_mask, num_samples, client_mask, client_ids)


@dataclass
class IndexRoundBatch:
    """One round's client data as POOL INDICES instead of gathered rows
    (the device-resident dataset mode).

    ``indices``: ``[K, S, B]`` int32 rows into the flat sample pool built
    by :func:`build_sample_pool` (0 for padding slots — masked anyway).
    The mask/count fields match :class:`RoundBatch`; there is deliberately
    NO ``arrays`` field — feature rows exist only on-device, and the one
    consumer is ``RoundEngine._host_arrays`` (pool mode).
    """

    indices: np.ndarray
    sample_mask: np.ndarray
    num_samples: np.ndarray
    client_mask: np.ndarray
    client_ids: np.ndarray
    #: see :class:`RoundBatch.carry_slots`
    carry_slots: Optional[np.ndarray] = None
    #: see :class:`RoundBatch.mega`
    mega: Optional["MegaTape"] = None

    @property
    def shape(self):
        return self.sample_mask.shape


def build_sample_pool(dataset: BaseDataset):
    """Concatenate every user's samples into flat per-key arrays.

    Returns ``(pool, offsets)``: ``pool[k]`` is ``[total_samples, *feat]``
    in user order (dtype preserved — uint8 pixels stay uint8 so the
    one-time upload is as small as the dataset), ``offsets`` is ``[N+1]``
    int64 with user ``i``'s rows at ``offsets[i]:offsets[i+1]``.

    This is the TPU-native dataloader endgame: upload the pool to HBM
    ONCE, then each round ships only ``[K, S, B]`` int32 indices and the
    round program gathers on-device — no per-round host packing of
    feature bytes, no per-round host->device feature transfer.  Requires the
    dataset to fit in host memory to build and in HBM to use; the
    federated benchmarks (SURVEY §2.8) all fit with room to spare.
    """
    spec = dataset.element_spec
    n_users = len(dataset)
    counts = [int(dataset.num_samples[i]) for i in range(n_users)]
    offsets = np.zeros((n_users + 1,), np.int64)
    np.cumsum(counts, out=offsets[1:])
    total = int(offsets[-1])
    first = dataset.user_arrays(0)
    pool = {k: np.empty((total,) + shape, dtype=np.asarray(first[k]).dtype)
            for k, shape in spec.items()}
    for i in range(n_users):
        user = dataset.user_arrays(i)
        lo, hi = int(offsets[i]), int(offsets[i + 1])
        for k in pool:
            pool[k][lo:hi] = np.asarray(user[k])
    return pool, offsets


def pack_round_indices(
    dataset: BaseDataset,
    offsets: np.ndarray,
    client_indices: Sequence[int],
    batch_size: int,
    max_steps: int,
    rng: Optional[np.random.Generator] = None,
    shuffle: bool = True,
    pad_clients_to: Optional[int] = None,
    desired_max_samples: Optional[int] = None,
    orders: Optional[Dict[int, np.ndarray]] = None,
) -> IndexRoundBatch:
    """:func:`pack_round_batches` with the row gather deferred to the
    device: identical sampling/shuffle/cap/mask semantics (same rng
    consumption, so a pool-mode round is bit-comparable to a host-packed
    one), but the output is ``[K, S, B]`` int32 indices into the
    :func:`build_sample_pool` flat pool instead of gathered feature rows.
    ``orders`` and ``-1`` padding holes as in :func:`pack_round_batches`.
    """
    rng = rng or np.random.default_rng(0)
    K = len(client_indices)
    K_pad = max(pad_clients_to or K, K)
    S, B = max_steps, batch_size

    indices = np.zeros((K_pad, S, B), dtype=np.int32)
    sample_mask = np.zeros((K_pad, S, B), dtype=np.float32)
    num_samples = np.zeros((K_pad,), dtype=np.float32)
    client_mask = np.zeros((K_pad,), dtype=np.float32)
    client_ids = np.full((K_pad,), -1, dtype=np.int32)

    cap = _sample_cap(S, B, desired_max_samples)
    for j, ci in enumerate(client_indices):
        if int(ci) < 0:
            continue
        n = int(dataset.num_samples[ci])
        if orders is not None:
            order = orders[ci]
        else:
            order = rng.permutation(n) if shuffle else np.arange(n)
        take = order[:cap]
        t = len(take)
        indices[j].reshape(-1)[:t] = offsets[ci] + take
        sample_mask[j].reshape(-1)[:t] = 1.0
        num_samples[j] = t
        client_mask[j] = 1.0
        client_ids[j] = ci
    return IndexRoundBatch(indices, sample_mask, num_samples, client_mask,
                           client_ids)


def pack_eval_batches(
    dataset: BaseDataset,
    batch_size: int,
    pad_steps_to_multiple_of: int = 1,
    user_indices: Optional[Sequence[int]] = None,
) -> Dict[str, np.ndarray]:
    """Flatten eval users into ``[T, B, ...]`` batches with a mask.

    The reference chunks eval users ~evenly across workers
    (``core/evaluation.py:185-216``) and weights metrics by batch size
    (``core/evaluation.py:160-183``); here all samples go into one padded
    grid sharded over devices, and per-sample masking makes the weighted
    average exact.  Also returns ``user_idx`` ``[T, B]`` so personalization
    / per-user metrics can segment by user.
    """
    idxs = list(user_indices) if user_indices is not None else list(range(len(dataset)))
    spec = dataset.element_spec
    total = sum(int(dataset.num_samples[i]) for i in idxs)
    T = max(1, math.ceil(total / batch_size))
    if T % pad_steps_to_multiple_of:
        T += pad_steps_to_multiple_of - (T % pad_steps_to_multiple_of)
    B = batch_size

    first = dataset.user_arrays(idxs[0]) if idxs else {}
    out = {k: np.zeros((T * B,) + shape, dtype=first[k].dtype)
           for k, shape in spec.items()}
    mask = np.zeros((T * B,), dtype=np.float32)
    user_idx = np.full((T * B,), -1, dtype=np.int32)

    pos = 0
    for i in idxs:
        user = dataset.user_arrays(i)
        n = len(next(iter(user.values())))
        for k, arr in user.items():
            out[k][pos:pos + n] = arr
        mask[pos:pos + n] = 1.0
        user_idx[pos:pos + n] = i
        pos += n

    batched = {k: v.reshape((T, B) + v.shape[1:]) for k, v in out.items()}
    batched["sample_mask"] = mask.reshape(T, B)
    batched["user_idx"] = user_idx.reshape(T, B)
    return batched


# ----------------------------------------------------------------------
# cohort shape-bucketing (server_config.cohort_bucketing): the step-count
# analogue of seq_length_bucket.  One monolithic [K, S, B, ...] grid pads
# every client to the slowest one's step count; partitioning the cohort
# into a small set of power-of-two step buckets builds one COMPACT grid
# per bucket instead, so small clients stop burning masked FLOPs on a
# big client's steps.  Everything here is host-side numpy over counts —
# the device half (per-bucket collect + on-device combine) lives in
# engine/round.py.
# ----------------------------------------------------------------------
def pow2_ceil(n: int) -> int:
    """Smallest power of two >= n (min 1) — the shape quantizer that
    keeps the compiled-variant set logarithmic, same discipline as
    :func:`seq_length_bucket`'s length buckets."""
    return 1 << max(int(n) - 1, 0).bit_length()


def bucket_boundaries(needs: Sequence[int], max_buckets: int,
                      max_steps: int) -> list:
    """Derive the step-bucket boundary set from the POPULATION's
    per-client step needs: the distinct power-of-two ceilings (capped at
    ``max_steps``), greedily merged down to ``max_buckets`` by the
    smallest added padded-step cost.

    The result is strictly increasing and always ends at
    ``pow2_ceil(max need)`` (clamped to ``max_steps``), so every client
    fits some bucket — a client's grid S must be >= its need or its
    data would silently truncate.  Deterministic in the needs multiset.
    """
    if max_buckets < 1:
        raise ValueError("cohort_bucketing.max_buckets must be >= 1")
    # vectorized pow2-ceil histogram (fleet scale: a 10^6-entry needs
    # array is one numpy pass, not 10^6 interpreter iterations) —
    # searchsorted against the exact power table, no float log2 detour
    arr = np.maximum(np.asarray(needs, dtype=np.int64), 1)
    pow_table = np.int64(1) << np.arange(63, dtype=np.int64)
    ceils = np.minimum(pow_table[np.searchsorted(pow_table, arr)],
                       np.int64(max_steps))
    uniq, counts = np.unique(ceils, return_counts=True)
    pops: dict = {int(s): int(c) for s, c in zip(uniq, counts)}
    bounds = sorted(pops)
    # greedy merge: absorbing bucket b into the next-larger one costs its
    # population x the extra padded steps; drop the cheapest until bounded
    while len(bounds) > max_buckets:
        costs = [(pops[bounds[i]] * (bounds[i + 1] - bounds[i]), i)
                 for i in range(len(bounds) - 1)]
        _, i = min(costs)
        pops[bounds[i + 1]] += pops.pop(bounds[i])
        del bounds[i]
    return bounds


def assign_step_buckets(needs: Sequence[int],
                        boundaries: Sequence[int],
                        capacities: Optional[Sequence[int]] = None
                        ) -> "Dict[int, list]":
    """Deterministic bucket assignment for one round's cohort.

    ``needs[j]``: sampled client j's step need (``steps_for``);
    ``boundaries``: strictly increasing bucket S values whose last entry
    covers every need.  Each client goes to the SMALLEST bucket whose S
    covers it — a pure function of (needs, boundaries, capacities),
    independent of rng or host loop arrangement, so serial/pipelined/
    resumed runs bucket identically.

    Without ``capacities``: returns only occupied buckets.  With
    ``capacities`` (one per boundary): every bucket appears (possibly
    empty — the STATIC-shape contract: every bucket grid dispatches
    every round at its fixed capacity, so the compiled shape set is
    closed by construction), and a bucket at capacity spills its
    overflow UP to the next larger bucket — a larger S is always
    mathematically correct (masked padding steps are no-ops), it only
    wastes steps.  The TOP bucket ignores its capacity; the caller
    enlarges its grid for the (rare, sentinel-visible) overflow round.

    Returns ``{S: [cohort positions]}``, positions in cohort order,
    keys ascending.
    """
    bounds = list(boundaries)
    if any(b <= a for a, b in zip(bounds, bounds[1:])):
        raise ValueError(
            f"bucket boundaries must be strictly increasing, got {bounds}")
    # vectorized first-fit-with-spill (fleet scale: 10^6-entry cohorts
    # must assign in one numpy pass per bucket, not a python scan per
    # client).  Semantics are EXACTLY the sequential first-fit's:
    # bucket i holds the first cap_i cohort-order clients whose need
    # fits and who weren't placed lower — proved by induction on i and
    # pinned against the brute loop in tests/test_fleet.py.
    arr = np.maximum(np.asarray(needs, dtype=np.int64), 1)
    b_arr = np.asarray(bounds, dtype=np.int64)
    if arr.size and int(arr.max()) > int(b_arr[-1]):
        bad = int(arr.max())
        raise ValueError(
            f"client step need {bad} exceeds the largest bucket "
            f"boundary {bounds[-1]} — boundaries must cover max_steps")
    first_fit = np.searchsorted(b_arr, arr)  # smallest covering bucket
    out: Dict[int, list] = ({s: [] for s in bounds}
                            if capacities is not None else {})
    placed = np.zeros(arr.shape, dtype=bool)
    for i, s in enumerate(bounds):
        elig = np.flatnonzero((first_fit <= i) & ~placed)
        if capacities is not None and i < len(bounds) - 1:
            elig = elig[:int(capacities[i])]  # overflow spills UP
        if elig.size:
            out.setdefault(s, []).extend(int(j) for j in elig)
            placed[elig] = True
    return {s: out[s] for s in sorted(out)}


def bucket_capacities(needs: Sequence[int], boundaries: Sequence[int],
                      cohort_size: int, quantum: int = 1,
                      slack: float = 1.5) -> list:
    """Static per-bucket client capacities from the POPULATION mix.

    For each boundary: the expected bucket occupancy of a
    ``cohort_size`` sample (population fraction x cohort) with
    ``slack`` headroom for sampling variance, clamped to the cohort
    size and the bucket's population (without-replacement sampling can
    never exceed either), rounded up to ``quantum`` (mesh
    divisibility).  Computed ONCE at server init — capacities are what
    make every bucket grid's ``[K_b, S_b, B]`` shape static across
    rounds, so the run compiles exactly one collect program per bucket
    and zero post-warmup recompiles (overflow spills up; top-bucket
    overflow is the one sentinel-visible exception — ITS enlarged grid
    is pow2-quantized so even pathological overflow stays logarithmic
    in compiled variants)."""
    bounds = list(boundaries)
    # vectorized smallest-covering-bucket histogram (fleet scale): one
    # searchsorted over the population instead of a per-client scan
    arr = np.maximum(np.asarray(needs, dtype=np.int64), 1)
    b_arr = np.asarray(bounds, dtype=np.int64)
    fit = np.searchsorted(b_arr, arr)
    fit = fit[fit < len(bounds)]  # needs beyond the top bucket: uncounted
    hist = np.bincount(fit, minlength=len(bounds))
    counts = {s: int(hist[i]) for i, s in enumerate(bounds)}
    total = max(sum(counts.values()), 1)
    caps = []
    for s in bounds:
        pop_b = counts[s]
        want = ceil_div(int(math.ceil(slack * cohort_size * pop_b)), total) \
            if pop_b else 1
        cap = max(min(want, int(cohort_size), max(pop_b, 1)), 1)
        caps.append(ceil_div(cap, quantum) * quantum)
    return caps


# ----------------------------------------------------------------------
# cross-client megabatching (server_config.megabatch): within one step
# bucket, most clients need far fewer than S_b steps and a capacity-
# padded grid burns whole client rows — the super-batch tape re-reads
# the SAME [K_b, S_b, B, ...] grid through a [lanes, depth] pointer
# tape instead: each lane concatenates many small clients' step
# sequences back to back (segment ids mark the boundaries), so one
# scan step trains `lanes` different clients' batches at once and idle
# tape slots — not empty client rows — are the only padding.  Host
# side: pure numpy first-fit planning over step needs; the device half
# (the segment-carrying lane scan) lives in engine/client_update.py.
# ----------------------------------------------------------------------
@dataclass
class MegaTape:
    """Super-batch pointer tape for ONE bucket grid.

    ptr: ``[lanes, depth]`` int32 — flat SHARD-LOCAL grid step index
         ``row * S + step`` each tape slot trains on (0 for idle slots);
    seg: ``[lanes, depth]`` int32 — shard-local grid row (segment id /
         output slot) owning the slot, -1 for idle padding.

    A client occupies ``num_epochs * need`` CONSECUTIVE slots of one
    lane (pointers repeat per epoch — no feature duplication), entirely
    inside its mesh shard's lane block, so the engine's lane scan can
    reset params/optimizer/rng at segment starts and harvest at ends
    with shard-local gathers only.
    """

    ptr: np.ndarray
    seg: np.ndarray
    lanes: int
    depth: int
    shards: int
    #: real (non-idle) tape slots — numerator feed for the
    #: megabatch_utilization meter
    entries: int


def megabatch_lanes(needs: Sequence[int], boundaries: Sequence[int],
                    cohort_size: int, num_epochs: int,
                    quantum: int = 1, slack: float = 1.25,
                    lanes: Optional[int] = None,
                    caps: Optional[Sequence[int]] = None) -> list:
    """Static per-bucket lane counts from the POPULATION mix (the
    megabatch analogue of :func:`bucket_capacities`): expected tape
    entries of a ``cohort_size`` draw landing in each bucket, with
    ``slack`` headroom, divided by the bucket's tape depth
    (``num_epochs * S_b``), rounded up to ``quantum`` (mesh
    divisibility).  An explicit ``lanes`` overrides every bucket.
    ``caps`` (the bucket client capacities) clamps from above —
    ``lanes == K_b`` is the break-even where the tape holds as many
    padded slots as the per-client grid it replaces."""
    bounds = list(boundaries)
    E = max(int(num_epochs), 1)
    quantum = max(int(quantum), 1)
    if lanes is not None:
        out = [ceil_div(int(lanes), quantum) * quantum for _ in bounds]
    else:
        arr = np.maximum(np.asarray(needs, dtype=np.int64), 1)
        b_arr = np.asarray(bounds, dtype=np.int64)
        fit = np.searchsorted(b_arr, arr)
        keep = fit < len(bounds)
        fit_k, arr_k = fit[keep], arr[keep]
        total = max(int(keep.sum()), 1)
        out = []
        for i, s in enumerate(bounds):
            need_sum = float(arr_k[fit_k == i].sum())
            # expected entries = pop fraction x cohort x mean need x E
            exp_entries = slack * cohort_size * need_sum * E / total
            want = max(int(math.ceil(exp_entries / float(E * int(s)))), 1)
            out.append(ceil_div(want, quantum) * quantum)
    if caps is not None:
        out = [min(l, ceil_div(int(c), quantum) * quantum)
               for l, c in zip(out, caps)]
    return [max(l, quantum) for l in out]


def plan_megabatch(needs: Sequence[int], num_epochs: int, lanes: int,
                   step_grid: int, shards: int, capacity: int) -> list:
    """First-fit super-batch planning for one bucket's cohort.

    ``needs[j]``: step need of the bucket's j-th client (cohort order);
    the tape depth is ``num_epochs * step_grid``.  Returns a list of
    ``(rows, tape)`` groups: ``rows`` is a length-``capacity`` list of
    cohort positions with ``-1`` padding holes (feed it through the
    hole-aware packers), ``tape`` the matching :class:`MegaTape`.

    Shard locality: grid row block ``[m*K/M, (m+1)*K/M)`` and lane
    block ``[m*L/M, (m+1)*L/M)`` belong to mesh shard ``m``; a client's
    slots land in the same shard as its grid row, so the engine's
    shard_map lane scan never gathers across shards.  A cohort that
    exceeds one group's rows or lane capacity spills into EXTRA GROUPS
    OF THE SAME SHAPE — the compiled-variant set stays one program per
    bucket, same discipline as top-bucket overflow.  Deterministic in
    (needs, geometry)."""
    M = max(int(shards), 1)
    L, S, E = int(lanes), int(step_grid), max(int(num_epochs), 1)
    cap = int(capacity)
    if L % M or cap % M:
        raise ValueError(
            f"megabatch geometry must be mesh-divisible: lanes={L}, "
            f"capacity={cap}, shards={M}")
    depth = E * S
    L_loc, K_loc = L // M, cap // M
    groups: list = []

    def _new_group():
        groups.append({
            "rows": [[] for _ in range(M)],          # per-shard positions
            "fill": np.zeros((L,), dtype=np.int64),  # per-lane used depth
            "ptr": np.zeros((L, depth), dtype=np.int32),
            "seg": np.full((L, depth), -1, dtype=np.int32),
            "entries": 0,
        })

    for pos, need in enumerate(needs):
        e = E * max(int(need), 1)
        if e > depth:
            raise ValueError(
                f"megabatch: client step need {need} exceeds the bucket "
                f"grid S={S} — bucket assignment must cover every need")
        placed = False
        for g in groups:
            for m in range(M):
                if len(g["rows"][m]) >= K_loc:
                    continue
                lanes_m = range(m * L_loc, (m + 1) * L_loc)
                lane = next((l for l in lanes_m
                             if int(g["fill"][l]) + e <= depth), None)
                if lane is None:
                    continue
                r = len(g["rows"][m])      # shard-local grid row
                o = int(g["fill"][lane])
                j = np.arange(e)
                g["ptr"][lane, o:o + e] = r * S + (j % max(int(need), 1))
                g["seg"][lane, o:o + e] = r
                g["fill"][lane] += e
                g["rows"][m].append(pos)
                g["entries"] += e
                placed = True
                break
            if placed:
                break
        if not placed:
            _new_group()
            g = groups[-1]
            m = 0
            lane = 0
            g["ptr"][lane, :e] = 0 * S + (np.arange(e) % max(int(need), 1))
            g["seg"][lane, :e] = 0
            g["fill"][lane] = e
            g["rows"][m].append(pos)
            g["entries"] = e

    if not groups:
        _new_group()
    out = []
    for g in groups:
        rows: list = []
        for m in range(M):
            block = list(g["rows"][m])
            rows.extend(block + [-1] * (K_loc - len(block)))
        out.append((rows, MegaTape(g["ptr"], g["seg"], L, depth, M,
                                   int(g["entries"]))))
    return out


def megabatch_slots(tapes: Sequence[MegaTape], batch_size: int) -> int:
    """Total super-batch sample slots (``lanes * depth * B`` summed) —
    the denominator of the megabatch_utilization meter."""
    return sum(int(t.lanes) * int(t.depth) * int(batch_size)
               for t in tapes)


def grid_slots(batches: Sequence) -> int:
    """Total padded sample slots of a chunk's grids (``K*S*B`` summed) —
    the denominator of the padding-efficiency meter."""
    total = 0
    for b in batches:
        k, s, bs = b.sample_mask.shape
        total += int(k) * int(s) * int(bs)
    return total


def padding_efficiency(batches: Sequence) -> float:
    """Real samples / padded grid slots of a chunk (1.0 = zero waste).
    The scorecard/bench meter the cohort-bucketing win is gated on —
    counts REAL (capped) samples from ``num_samples``, same convention
    as the aggregation weights."""
    slots = grid_slots(batches)
    real = sum(float(np.sum(b.num_samples)) for b in batches)
    return real / slots if slots else 0.0


def seq_length_bucket(batches: Sequence[RoundBatch],
                      seq_keys: Sequence[str],
                      min_len: int = 8) -> Optional[dict]:
    """Crop token-sequence grids to the power-of-two bucket of the chunk's
    real max length (the static-shape answer to the reference's
    ``DynamicBatchSampler`` padding-efficiency packing,
    ``utils/data_utils.py:42-119``).

    ``seq_keys`` name 0-padded ``[K, S, B, L]`` int arrays (the task's
    ``seq_pad_keys``).  All batches of a fused chunk are cropped to one
    common bucket so the chunk still compiles as a single program; cropping
    only removes all-zero tail columns, and the in-model position mask is
    derived from the ids themselves, so the math is identical — XLA just
    stops running matmuls over padding.  Buckets are powers of two (floored
    at ``min_len``), so the number of distinct compiled programs stays
    logarithmic in max L.

    Returns a stats dict (tokens_real / tokens_grid_before/after, bucket,
    ``cropped``) when the grids hold sequence keys, else None.
    """
    keys = [k for k in seq_keys if batches and k in batches[0].arrays]
    if not keys:
        return None
    L = max(b.arrays[k].shape[-1] for b in batches for k in keys)
    # the padding-efficiency meter counts each real token ONCE, from a
    # single canonical key — tok_mask when present (it marks real
    # positions even where x holds id 0), else the first seq key; summing
    # over all keys would triple-count and the keys legitimately disagree
    canon = "tok_mask" if "tok_mask" in keys else keys[0]
    # max real length across the chunk: position of the last nonzero
    # column over ALL keys (the crop must cover every key's extent)
    need = 1
    tokens_real = 0
    for b in batches:
        for k in keys:
            arr = b.arrays[k]
            nz = arr.reshape(-1, arr.shape[-1]) != 0
            if k == canon:
                tokens_real += int(nz.sum())
            cols = nz.any(axis=0)
            if cols.any():
                need = max(need, int(np.max(np.nonzero(cols)[0])) + 1)
    bucket = max(min_len, 1 << max(need - 1, 0).bit_length())
    stats = {
        "bucket": int(min(bucket, L)),
        "full_len": int(L),
        "tokens_real": int(tokens_real),
        "tokens_grid_before": int(sum(
            b.arrays[canon].reshape(-1, b.arrays[canon].shape[-1]).shape[0]
            * L for b in batches)),
    }
    stats["cropped"] = bucket < L
    if bucket < L:
        for b in batches:
            for k in keys:
                b.arrays[k] = np.ascontiguousarray(b.arrays[k][..., :bucket])
    stats["tokens_grid_after"] = int(sum(
        b.arrays[canon].reshape(-1, b.arrays[canon].shape[-1]).shape[0]
        * b.arrays[canon].shape[-1] for b in batches))
    return stats
