"""Cohort shape-bucketing (ISSUE 8): stop padding every client to the
slowest one.

The tentpole contract: a round's sampled clients partition into a small
config-bounded set of power-of-two step buckets; each bucket dispatches
one COMPACT ``[K_b, S_b, B, ...]`` collect program and a finalize
program combines the per-bucket partials into the weighted aggregate on
device, in deterministic bucket order.  Pinned here:

1. unit — boundary derivation (pow2, greedy merge to ``max_buckets``),
   deterministic assignment with spill-up, static capacities, the
   padding-efficiency meter, and the consolidated ceil-division idiom;
2. bit-identity — per-client pseudo-gradients on a compact bucket grid
   are BIT-IDENTICAL to the monolithic grid (masked padding steps are
   no-op-pinned; client rng folds on client id);
3. equivalence — a bucketed run's final params match the monolithic
   run's (reassociation-only difference) and are bit-reproducible;
4. composition — chaos (dropout/straggler/corruption), fluteshield
   quarantine (screened mean AND trimmed-mean stack aggregation),
   fused_carry SCAFFOLD at pipeline depth 3, rounds_per_step > 1, all
   clean under ``MSRFLUTE_STRICT_TRANSFERS=1``;
5. shape closure — exactly one collect program per bucket
   (``<= max_buckets``) + one finalize, ZERO post-warmup recompiles
   (sentinel-verified), and padding efficiency >= 2x monolithic on a
   heterogeneous cohort;
6. guards — host-orchestrated paths, clients_per_chunk,
   dump_norm_stats, schema misconfigurations all refused loudly.
"""

import tempfile

import jax
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from conftest import make_synthetic_classification
from msrflute_tpu import schema
from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.data import ArraysDataset
from msrflute_tpu.data.batching import (assign_step_buckets,
                                        bucket_boundaries,
                                        bucket_capacities, ceil_div,
                                        grid_slots, pack_round_batches,
                                        padding_efficiency, pow2_ceil,
                                        steps_for)
from msrflute_tpu.engine import OptimizationServer
from msrflute_tpu.engine.round import BucketedStats
from msrflute_tpu.models import make_task


def _hetero_dataset(seed=0, num_users=16, sizes=None):
    """Skewed federated pool: mostly tiny clients, a heavy tail."""
    rng = np.random.default_rng(seed)
    if sizes is None:
        sizes = [3, 4, 5, 5, 6, 6, 7, 8, 9, 10, 12, 14, 30, 34, 70, 80]
    users, per_user = [], []
    w = rng.normal(size=(8, 4))
    for u, n in enumerate(sizes[:num_users]):
        x = rng.normal(size=(n, 8)).astype(np.float32)
        y = np.argmax(x @ w, axis=-1).astype(np.int32)
        users.append(f"u{u:03d}")
        per_user.append({"x": x, "y": y})
    return ArraysDataset(users, per_user)


def _cfg(bucketing=None, *, rounds=6, depth=0, strategy="fedavg",
         ncpi=6, fuse=1, server_over=None):
    sc = {
        "max_iteration": rounds, "num_clients_per_iteration": ncpi,
        "initial_lr_client": 0.2, "pipeline_depth": depth,
        "rounds_per_step": fuse, "val_freq": 100, "initial_val": False,
        "optimizer_config": {"type": "sgd", "lr": 1.0},
        "data_config": {"val": {"batch_size": 8}},
    }
    if bucketing is not None:
        sc["cohort_bucketing"] = bucketing
    if server_over:
        sc.update(server_over)
    return FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": strategy,
        "server_config": sc,
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.2},
            "data_config": {"train": {"batch_size": 4}}},
    })


def _run(cfg, dataset, seed=7, mesh=None):
    task = make_task(cfg.model_config)
    with tempfile.TemporaryDirectory() as tmp:
        server = OptimizationServer(task, cfg, dataset, model_dir=tmp,
                                    seed=seed, mesh=mesh)
        state = server.train()
        flat = np.asarray(ravel_pytree(jax.device_get(state.params))[0])
    return flat, server


# ======================================================================
# 1. unit: ceil division, boundaries, assignment, capacities, meter
# ======================================================================
def test_ceil_div_and_sample_cap_mid_batch_boundary():
    """The consolidated ceil-division idiom, property-tested where the
    ``desired_max_samples`` cap lands MID-batch: the crossing batch
    still trains in full (reference checks the count at batch top), so
    the effective cap is ``ceil(desired/B)*B``, never ``desired``."""
    from msrflute_tpu.data.batching import _sample_cap
    rng = np.random.default_rng(0)
    for _ in range(300):
        n = int(rng.integers(1, 500))
        b = int(rng.integers(1, 33))
        d = int(rng.integers(1, 500))
        assert ceil_div(n, b) == -(-n // b) == int(np.ceil(n / b))
        s = steps_for(n, b, desired_max_samples=d)
        cap = _sample_cap(s, b, d)
        # batch-granular semantics: cap is a batch multiple covering
        # desired (unless the client grid is smaller)
        assert cap == min(s * b, ceil_div(d, b) * b)
        assert cap % b == 0 or cap == s * b
        if d % b:  # mid-batch crossing: cap strictly exceeds desired
            assert cap >= min(s * b, d)
    # regression anchors
    assert steps_for(10, 4) == 3 and steps_for(100, 4, 10) == 3
    assert _sample_cap(5, 4, 10) == 12  # 10 crosses mid-batch -> 3 full


def test_pow2_ceil_and_boundaries():
    assert [pow2_ceil(n) for n in (0, 1, 2, 3, 4, 5, 9, 16, 17)] == \
        [1, 1, 2, 4, 4, 8, 16, 16, 32]
    needs = [1, 1, 2, 3, 5, 9, 9, 17, 33]
    bounds = bucket_boundaries(needs, max_buckets=8, max_steps=40)
    # pow2 ceilings of the distinct needs, capped at max_steps
    assert bounds == [1, 2, 4, 8, 16, 32, 40]
    merged = bucket_boundaries(needs, max_buckets=3, max_steps=40)
    assert len(merged) == 3
    assert merged[-1] == 40  # top bucket always covers the max need
    assert all(y > x for x, y in zip(merged, merged[1:]))
    with pytest.raises(ValueError):
        bucket_boundaries(needs, max_buckets=0, max_steps=40)


def test_assign_step_buckets_deterministic_and_covering():
    needs = [1, 3, 9, 2, 8, 16]
    out = assign_step_buckets(needs, [2, 8, 16])
    assert out == {2: [0, 3], 8: [1, 4], 16: [2, 5]}
    # pure function: identical on repeat, keys ascending
    assert assign_step_buckets(needs, [2, 8, 16]) == out
    with pytest.raises(ValueError, match="exceeds the largest"):
        assign_step_buckets([99], [2, 8, 16])
    with pytest.raises(ValueError, match="strictly increasing"):
        assign_step_buckets(needs, [8, 2])


def test_assign_step_buckets_capacity_spill_up():
    needs = [1, 1, 1, 1, 9]
    out = assign_step_buckets(needs, [2, 8, 16], capacities=[2, 1, 2])
    # every bucket present (static-shape contract), overflow spills UP
    assert list(out) == [2, 8, 16]
    assert out[2] == [0, 1]          # at capacity
    assert out[8] == [2]             # spill from bucket 2
    assert out[16] == [3, 4]         # cascade + the natural resident
    # the TOP bucket ignores its capacity (caller splits grids)
    out = assign_step_buckets([16] * 5, [2, 8, 16], capacities=[1, 1, 2])
    assert out[16] == [0, 1, 2, 3, 4]


def test_bucket_capacities_clamped_and_quantized():
    needs = [1] * 12 + [8] * 4
    caps = bucket_capacities(needs, [2, 8], cohort_size=8, quantum=2,
                             slack=1.5)
    assert all(c % 2 == 0 for c in caps)
    # small bucket: 1.5 * 8 * 12/16 = 9 -> clamp cohort 8; big bucket:
    # 1.5 * 8 * 4/16 = 3 -> quantum 4; never exceeds pop or cohort
    assert caps[0] <= 8 and caps[1] <= 4 + 2
    caps1 = bucket_capacities(needs, [2, 8], cohort_size=8, quantum=1,
                              slack=1.5)
    assert caps1[0] <= 8 and caps1[1] >= 1


def test_padding_efficiency_meter():
    ds = _hetero_dataset()
    full = pack_round_batches(ds, [0, 1, 14], 4, 20)
    assert grid_slots([full]) == 3 * 20 * 4
    pe_full = padding_efficiency([full])
    tight = pack_round_batches(ds, [0, 1], 4, 2)
    pe_tight = padding_efficiency([tight])
    assert 0 < pe_full < pe_tight <= 1.0
    # empty grid packs as all padding (static-capacity contract)
    empty = pack_round_batches(ds, [], 4, 2, pad_clients_to=2)
    assert float(empty.sample_mask.sum()) == 0.0
    assert float(empty.client_mask.sum()) == 0.0
    assert padding_efficiency([empty]) == 0.0


# ======================================================================
# 2. per-client bit-identity across grid shapes
# ======================================================================
def test_per_client_payloads_bit_identical_across_bucket_shapes():
    """A client's pseudo-gradient on a compact [K_b, S_b, B] bucket grid
    is BIT-identical to its row in the monolithic [K, S_max, B] grid:
    masked padding steps are no-op-pinned and the client rng folds on
    the client ID, not the slot."""
    ds = _hetero_dataset()
    cfg = _cfg()
    task = make_task(cfg.model_config)
    with tempfile.TemporaryDirectory() as tmp:
        server = OptimizationServer(task, cfg, ds, model_dir=tmp, seed=0)
        rng = jax.random.PRNGKey(3)
        ids = [0, 2, 12, 15]  # needs 1, 2, 8, 20 at B=4
        pad = server.mesh.shape["clients"]
        mono = pack_round_batches(ds, ids, 4, 20, shuffle=False,
                                  pad_clients_to=pad)
        pgs_m, ws_m, _, _ = server.engine.client_payloads(
            server.state, mono, 0.2, rng)
        pgs_m = jax.device_get(pgs_m)
        for bucket_ids, s_b in (([0, 2], 2), ([12], 8), ([15], 20)):
            small = pack_round_batches(ds, bucket_ids, 4, s_b,
                                       shuffle=False, pad_clients_to=pad)
            pgs_b, ws_b, _, _ = server.engine.client_payloads(
                server.state, small, 0.2, rng)
            pgs_b = jax.device_get(pgs_b)
            for row, cid in enumerate(bucket_ids):
                mrow = ids.index(cid)
                for la, lb in zip(jax.tree.leaves(pgs_b),
                                  jax.tree.leaves(pgs_m)):
                    assert np.array_equal(np.asarray(la)[row],
                                          np.asarray(lb)[mrow]), \
                        f"client {cid} differs on S={s_b} grid"


_BUCKETING = {"cohort_bucketing": {"enable": True, "max_buckets": 3}}
_ROBUST = {"screen_nonfinite": True, "norm_multiplier": 0,
           "aggregator": "mean"}
#: flag the shared per-client body takes -> (strategy, server overlay,
#: one device, a stat that reads > 0 when the flag took).  Secure aggregation and the megabatch replay are not
#: doubled here: tests/test_secagg_compose.py::
#: test_bucketed_x_secagg_bit_identical_to_monolithic and
#: tests/test_megabatch.py::test_megabatch_matches_vmap_bitwise_e1 hold
#: their pairs bit for bit.
_ONE_BUCKET_CASES = {
    "plain": ("fedavg", {}, False, "client_count"),
    "chaos_faults_and_corruption": ("fedavg", {"chaos": {
        "seed": 5, "dropout_rate": 0.2, "straggler_rate": 0.2,
        "corrupt_scale_rate": 0.2, "corrupt_sign_flip_rate": 0.2}}, False,
        "chaos_sign_flipped"),
    "traced_staleness": ("fedbuff", {"traffic": {"seed": 1}}, False,
                         "traffic_stale_sum"),
    "stale_prob": ("dga", {"stale_prob": 0.5}, False, "client_count"),
    "fused_carry": ("scaffold", {"fused_carry": True}, False,
                    "client_count"),
    "paged_carry": ("scaffold", {
        "fused_carry": True, "fleet": {"page_pool_slots": 16}}, True,
        "client_count"),
    # the finalize screens the gathered cohort, the monolithic round each
    # shard before its psum: the same association on one device only
    "shield_quarantine": ("fedavg", {
        "chaos": {"seed": 11, "corrupt_nan_rate": 0.3},
        "robust": _ROBUST}, True, "shield_nonfinite"),
    "device_pool": ("fedavg", {}, False, "client_count"),
}


@pytest.mark.parametrize("flag", sorted(_ONE_BUCKET_CASES))
def test_one_bucket_round_is_the_monolithic_round_bit_for_bit(flag):
    """One bucket that holds the whole cohort on the monolithic grid:
    collect + finalize give the monolithic round's new state and stats
    to the bit, under each flag the builders' shared per-client body,
    fault fold and sums take (engine/round.py)."""
    from msrflute_tpu.data.batching import pack_round_indices
    from msrflute_tpu.parallel import make_mesh
    from msrflute_tpu.resilience.chaos import (CORRUPT_NAN, CORRUPT_SCALE,
                                               CORRUPT_SIGN_FLIP)
    strategy, over, one_device, witness = _ONE_BUCKET_CASES[flag]
    ds = _hetero_dataset()
    ids, steps = [0, 2, 5, 12, 3], 8  # needs 1, 2, 2, 8, 2 at B=4

    def dispatch(bucketed):
        cfg = _cfg(strategy=strategy, ncpi=8, server_over={
            **over, **(_BUCKETING if bucketed else {})})
        if flag == "device_pool":
            cfg.client_config.data_config.train["device_resident"] = True
        server = OptimizationServer(
            make_task(cfg.model_config), cfg, ds,
            model_dir=tempfile.mkdtemp(), seed=0,
            mesh=make_mesh(num_devices=1) if one_device else None)
        engine = server.engine
        pad = server.mesh.shape["clients"]
        if flag == "device_pool":
            batch = pack_round_indices(ds, server._pool_offsets, ids, 4,
                                       steps, shuffle=False,
                                       pad_clients_to=pad)
        else:
            batch = pack_round_batches(ds, ids, 4, steps, shuffle=False,
                                       pad_clients_to=pad)
        k = batch.client_ids.shape[0]
        lanes = np.arange(k)
        if engine.carry_paged:
            batch.carry_slots = np.where(batch.client_ids >= 0, lanes,
                                         -1).astype(np.int32)
        entry = []
        if engine.chaos_client_faults:
            entry += [(lanes == 1).astype(np.float32),
                      np.where(lanes == 3, 2, steps).astype(np.float32)]
        if engine.chaos_corruption:
            modes = ([CORRUPT_NAN, 0, CORRUPT_NAN] if flag.startswith(
                "shield") else [0, 0, CORRUPT_SCALE, 0, CORRUPT_SIGN_FLIP])
            entry.append(np.resize(np.asarray(modes + [0] * k, np.int32),
                                   k))
        if engine.traffic_staleness:
            entry.append((lanes % 3).astype(np.int32))
        entry = tuple(entry) or None
        rng = jax.random.PRNGKey(3)
        if bucketed:
            state, packed = engine.dispatch_bucketed_rounds(
                server.state, [[batch]], [0.2], [1.0], rng,
                chaos_vecs=[[entry]] if entry else None)
        else:
            state, packed = engine.dispatch_rounds(
                server.state, [batch], [0.2], [1.0], rng,
                chaos_vecs=[entry] if entry else None)
        return jax.device_get((state.params, state.strategy_state)), \
            packed.fetch()

    (state_m, stats_m), (state_b, stats_b) = dispatch(False), dispatch(True)
    leaves_m, leaves_b = jax.tree.leaves(state_m), jax.tree.leaves(state_b)
    assert len(leaves_m) == len(leaves_b)
    for a, b in zip(leaves_m, leaves_b):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(np.asarray(leaves_m[0])).all()
    assert set(stats_m) == set(stats_b)
    for key in stats_m:
        if flag == "shield_quarantine":
            # the aggregate is summed in another program there (the
            # finalize, from the gathered stack): its norm to rounding
            np.testing.assert_allclose(stats_m[key], stats_b[key],
                                       rtol=4 * np.finfo(np.float32).eps,
                                       err_msg=key)
        else:
            assert np.array_equal(stats_m[key], stats_b[key]), key
    assert float(stats_m[witness][0]) > 0


# ======================================================================
# 3. end-to-end equivalence + determinism
# ======================================================================
def test_bucketed_matches_monolithic_and_is_deterministic():
    ds = _hetero_dataset()
    mono, server_m = _run(_cfg(), ds)
    buck, server_b = _run(_cfg({"enable": True, "max_buckets": 3}), ds)
    buck2, _ = _run(_cfg({"enable": True, "max_buckets": 3}), ds)
    # deterministic on-device aggregation order: bit-reproducible
    assert np.array_equal(buck, buck2)
    # vs monolithic: same math, different summation association only
    assert np.allclose(mono, buck, rtol=2e-4, atol=1e-6)
    assert not np.array_equal(mono, np.zeros_like(mono))
    # the compiled-shape ledger: one collect per bucket + one finalize
    names = set(server_b.engine.compile_log)
    assert "bucket_finalize" in names
    collects = [n for n in server_b.engine.compile_log
                if n.startswith("bucket_collect_s")]
    assert 1 <= len(set(collects)) <= 3
    assert server_m.engine.bucket_shapes_seen == set()


def test_bucketed_explicit_boundaries_and_fused_chunks():
    """User boundaries + rounds_per_step > 1: every round is its own
    bucketed dispatch set; the chunk drain still sees per-round stats."""
    ds = _hetero_dataset()
    cfg = _cfg({"enable": True, "max_buckets": 4,
                "boundaries": [2, 8, 32]}, rounds=6, fuse=3)
    flat, server = _run(cfg, ds)
    assert np.isfinite(flat).all()
    assert server.cohort_bucketing["boundaries"][-1] == 20  # clamped to
    # max_steps (80 samples / B=4), user's oversized 32 dropped
    flat2, _ = _run(cfg, ds)
    assert np.array_equal(flat, flat2)


def test_bucketed_stats_fetch_layout():
    """BucketedStats stacks scalars to [R] and zero-pads per-client
    vectors to the chunk max — the layout _drain_host_tail and the
    privacy processing consume."""
    ds = _hetero_dataset()
    cfg = _cfg({"enable": True, "max_buckets": 3}, rounds=2)
    task = make_task(cfg.model_config)
    with tempfile.TemporaryDirectory() as tmp:
        server = OptimizationServer(task, cfg, ds, model_dir=tmp, seed=0)
        batches = [server._pack_bucketed_round(server._sample())
                   for _ in range(2)]
        state, packed = server.engine.dispatch_bucketed_rounds(
            server.state, batches, [0.2, 0.2], [1.0, 1.0],
            jax.random.PRNGKey(0))
        assert isinstance(packed, BucketedStats)
        stats = packed.fetch()
        assert stats["train_loss_sum"].shape == (2,)
        assert stats["client_count"].shape == (2,)
        assert float(stats["client_count"][0]) > 0
        masks = server._chunk_client_masks(batches)
        assert masks.shape[0] == 2


# ======================================================================
# 4. composition: chaos, shield, fused_carry pipeline, strict transfers
# ======================================================================
def test_bucketed_with_chaos_faults_and_corruption(monkeypatch):
    monkeypatch.setenv("MSRFLUTE_STRICT_TRANSFERS", "1")
    ds = _hetero_dataset()
    chaos = {"seed": 5, "dropout_rate": 0.2, "straggler_rate": 0.2,
             "corrupt_scale_rate": 0.2, "corrupt_scale_factor": 3.0}
    cfg = _cfg({"enable": True, "max_buckets": 3}, rounds=6, depth=2,
               server_over={"chaos": chaos})
    flat, server = _run(cfg, ds)
    assert np.isfinite(flat).all()
    # seeded determinism survives bucketing (salted per-bucket streams)
    flat2, server2 = _run(cfg, ds)
    assert np.array_equal(flat, flat2)
    assert server.chaos.counters == server2.chaos.counters
    counters = server.chaos.counters
    assert counters["dropped"] + counters["straggled"] + \
        counters["scaled"] > 0
    assert server.pipelined_chunks > 0


def test_bucketed_shield_quarantines_nan_clients(monkeypatch):
    monkeypatch.setenv("MSRFLUTE_STRICT_TRANSFERS", "1")
    ds = _hetero_dataset()
    chaos = {"seed": 11, "corrupt_nan_rate": 0.3}
    cfg = _cfg({"enable": True, "max_buckets": 3}, rounds=6,
               server_over={"chaos": chaos,
                            "robust": {"screen_nonfinite": True,
                                       "norm_multiplier": 0,
                                       "aggregator": "mean"}})
    flat, server = _run(cfg, ds)
    # screening spans the WHOLE multi-grid cohort: NaN payloads are
    # quarantined at finalize and the params stay finite
    assert np.isfinite(flat).all()
    assert server.shield.counters["quarantined_nonfinite"] > 0
    # undefended control diverges under the same attack
    cfg_open = _cfg({"enable": True, "max_buckets": 3}, rounds=6,
                    server_over={"chaos": chaos})
    flat_open, _ = _run(cfg_open, ds)
    assert not np.isfinite(flat_open).all()


def test_bucketed_shield_trimmed_mean_stack_combine():
    ds = _hetero_dataset()
    cfg = _cfg({"enable": True, "max_buckets": 3}, rounds=4,
               server_over={"robust": {"aggregator": "trimmed_mean",
                                       "trim_fraction": 0.1,
                                       "norm_multiplier": 5.0}})
    flat, server = _run(cfg, ds)
    assert np.isfinite(flat).all()
    from msrflute_tpu.strategies.robust import RobustFedAvg
    assert isinstance(server.strategy, RobustFedAvg)
    flat2, _ = _run(cfg, ds)
    assert np.array_equal(flat, flat2)


def test_bucketed_fused_carry_scaffold_depth3_pipeline(monkeypatch):
    """The hard composition: device-carry SCAFFOLD (per-client control
    table gather/scatter by client id) + depth-3 pipeline ring +
    bucketed grids, strict transfers — bit-identical to the serial
    bucketed run."""
    monkeypatch.setenv("MSRFLUTE_STRICT_TRANSFERS", "1")
    ds = _hetero_dataset()

    def cfg(depth):
        return _cfg({"enable": True, "max_buckets": 3},
                    rounds=6, depth=depth, strategy="scaffold",
                    server_over={"fused_carry": True})

    serial, server_s = _run(cfg(0), ds)
    piped, server_p = _run(cfg(3), ds)
    assert np.array_equal(serial, piped)
    assert server_p.pipelined_chunks > 0
    assert server_s.engine.device_carry and server_p.engine.device_carry


# ======================================================================
# 5. shape closure + the recompile sentinel + padding efficiency
# ======================================================================
def test_sentinel_bucket_programs_closed_and_no_post_warmup_recompiles():
    """Device-truth acceptance: <= max_buckets compiled bucket-grid
    programs, and after the warmup rounds ZERO new compiles — the
    static-capacity grids make the shape set closed by construction."""
    ds = _hetero_dataset()
    cfg = _cfg({"enable": True, "max_buckets": 3}, rounds=12,
               server_over={"telemetry": {"enable": True}})
    task = make_task(cfg.model_config)
    with tempfile.TemporaryDirectory() as tmp:
        server = OptimizationServer(task, cfg, ds, model_dir=tmp, seed=7)
        cfg.server_config.max_iteration = 3
        server.train()  # warmup: every bucket shape compiles here
        warm_compiles = len(server.engine.compile_log)
        warm_events = server.engine.xla.compiles
        cfg.server_config.max_iteration = 12
        server.train()
        # closure: no compile after warmup, zero sentinel recompiles
        assert len(server.engine.compile_log) == warm_compiles
        assert server.engine.xla.compiles == warm_events
        assert server.engine.xla.recompiles == 0
        collect_shapes = server.engine.bucket_shapes_seen
        assert 1 <= len(collect_shapes) <= 3
        card = server.build_scorecard()
        assert card["cohort_bucketing"]["bucket_grid_variants"] == \
            len(collect_shapes)
        assert card["cohort_bucketing"]["max_buckets"] == 3
        assert card["padding_efficiency"] is not None
        assert card["recompiles"] == 0


def test_padding_efficiency_at_least_2x_on_heterogeneous_cohort():
    """The headline win, server-level: run-total real samples / padded
    grid slots on a skewed cohort is >= 2x the monolithic grid's."""
    from msrflute_tpu.parallel import make_mesh
    sizes = ([3, 4, 4, 5, 5, 6, 6, 7, 8, 8, 9, 10, 11, 12, 13, 14,
              15, 16, 18, 20] + [120, 160, 200, 200])
    ds = _hetero_dataset(seed=1, num_users=24, sizes=sizes)
    # a 1-device mesh: capacity quantization to the 8-wide test mesh
    # would dominate the tiny cohort and measure the mesh, not the
    # bucketing (on real hardware cohorts are many times the mesh)
    mono, server_m = _run(_cfg(rounds=8, ncpi=8), ds,
                          mesh=make_mesh(num_devices=1))
    buck, server_b = _run(
        _cfg({"enable": True, "max_buckets": 4, "slack": 1.25},
             rounds=8, ncpi=8), ds, mesh=make_mesh(num_devices=1))
    pe_m = server_m.padding_efficiency
    pe_b = server_b.padding_efficiency
    assert pe_m is not None and pe_b is not None
    assert pe_b >= 2.0 * pe_m, (pe_b, pe_m)
    assert len(server_b.engine.bucket_shapes_seen) <= 4


# ======================================================================
# 6. guards: refusals + schema
# ======================================================================
def test_guard_host_orchestrated_paths_refused():
    ds = _hetero_dataset()
    task_cfg = _cfg({"enable": True}, strategy="scaffold")  # NO fused_carry
    with pytest.raises(ValueError, match="fused round path"):
        OptimizationServer(make_task(task_cfg.model_config), task_cfg, ds,
                           model_dir=tempfile.mkdtemp(), seed=0)


@pytest.mark.parametrize("over,msg", [
    ({"clients_per_chunk": 2}, "clients_per_chunk"),
    ({"dump_norm_stats": True}, "dump_norm_stats"),
])
def test_guard_incompatible_engine_modes(over, msg):
    ds = _hetero_dataset()
    cfg = _cfg({"enable": True}, ncpi=4, server_over=over)
    with pytest.raises(ValueError, match=msg):
        OptimizationServer(make_task(cfg.model_config), cfg, ds,
                           model_dir=tempfile.mkdtemp(), seed=0)


def test_schema_validates_cohort_bucketing_block():
    base = {
        "model_config": {"model_type": "LR"},
        "server_config": {"cohort_bucketing": {"enable": True}},
    }
    schema.validate(dict(base))  # minimal block passes

    bad = {"model_config": {"model_type": "LR"},
           "server_config": {"cohort_bucketing": {"max_buckets": 0}}}
    with pytest.raises(schema.SchemaError, match="max_buckets"):
        schema.validate(bad)

    bad = {"model_config": {"model_type": "LR"},
           "server_config": {"cohort_bucketing": {
               "boundaries": [8, 2]}}}
    with pytest.raises(schema.SchemaError, match="strictly increasing"):
        schema.validate(bad)

    bad = {"model_config": {"model_type": "LR"},
           "server_config": {"cohort_bucketing": {
               "boundaries": [2, 4, 8], "max_buckets": 2}}}
    with pytest.raises(schema.SchemaError, match="exceed"):
        schema.validate(bad)

    bad = {"model_config": {"model_type": "LR"},
           "server_config": {"cohort_bucketing": {"slack": 0.5}}}
    with pytest.raises(schema.SchemaError, match="slack"):
        schema.validate(bad)

    bad = {"model_config": {"model_type": "LR"},
           "server_config": {"cohort_bucketing": {"bucket_count": 3}}}
    with pytest.raises(schema.SchemaError, match="unknown key"):
        schema.validate(bad)

    bad = {"model_config": {"model_type": "LR"},
           "server_config": {"cohort_bucketing": "on"}}
    with pytest.raises(schema.SchemaError, match="mapping"):
        schema.validate(bad)
