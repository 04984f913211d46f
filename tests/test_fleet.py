"""Fleet mode (ISSUE 14): million-client populations via O(cohort)
sampling + paged device carry tables.

The tentpole contract: with ``server_config.fleet`` on, host and device
state are O(cohort)/O(cache) — never O(N) — and, for a population that
fits resident, paged carry is BITWISE identical to the PR 6 resident
tables (serial and pipelined, scaffold + ef_quant + personalization),
including preempt-at-round + resume.
"""

import json
import os
import tempfile
import time

import jax
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from conftest import make_synthetic_classification
from msrflute_tpu import schema
from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.data.batching import (assign_step_buckets,
                                        bucket_boundaries,
                                        bucket_capacities)
from msrflute_tpu.data.fleet import (LazyNameList, SyntheticFleetDataset,
                                     floyd_sample, sample_cohort,
                                     steps_for_array,
                                     weighted_reservoir_sample)
from msrflute_tpu.engine.server import select_server
from msrflute_tpu.models import make_task


# ======================================================================
# O(cohort) samplers
# ======================================================================
def test_floyd_sample_distinct_in_range_deterministic():
    a = floyd_sample(np.random.default_rng(5), 10_000, 64)
    b = floyd_sample(np.random.default_rng(5), 10_000, 64)
    assert a == b
    assert len(set(a)) == 64
    assert all(0 <= i < 10_000 for i in a)
    # k >= population degrades to a permutation of everyone
    small = floyd_sample(np.random.default_rng(0), 7, 20)
    assert sorted(small) == list(range(7))


def test_floyd_sample_is_o_cohort_at_billion_population():
    rng = np.random.default_rng(3)
    tic = time.time()
    for _ in range(50):
        out = floyd_sample(rng, 10**9, 256)
        assert len(set(out)) == 256
    assert time.time() - tic < 2.0  # O(k), not O(population)


def test_default_cohort_draw_is_o_cohort():
    """Satellite: the DEFAULT server draw — numpy Generator.choice with
    replace=False — is already O(cohort) (Floyd's algorithm), so the
    rng trail survives fleet scale unchanged.  200 draws from a 10^7
    population must be near-instant; a permutation-based draw would
    take minutes and gigabytes."""
    rng = np.random.default_rng(0)
    tic = time.time()
    for _ in range(200):
        out = rng.choice(10**7, size=1000, replace=False)
    assert time.time() - tic < 2.0
    assert len(np.unique(out)) == 1000


def test_sample_cohort_uniform_preserves_numpy_trail():
    """fleet.sampling: uniform must consume the EXACT numpy draw the
    non-fleet server path consumes — the bit-identity anchor between
    fleet and resident runs."""
    a = sample_cohort(np.random.default_rng(11), 500, 20, "uniform")
    b = list(np.random.default_rng(11).choice(500, size=20,
                                              replace=False))
    assert a == b


def test_weighted_reservoir_sample_weighting_and_memory():
    rng = np.random.default_rng(2)
    weights = np.zeros(1000)
    weights[::2] = 1.0
    weights[100] = 0.0
    picks = weighted_reservoir_sample(rng, weights, 50)
    assert len(set(picks)) == 50
    assert all(weights[i] > 0 for i in picks)  # zero-weight never drawn
    # heavy items dominate: one item with 1000x weight lands in a
    # modest draw essentially always
    heavy = np.ones(5000)
    heavy[42] = 5000.0
    hits = sum(42 in weighted_reservoir_sample(
        np.random.default_rng(s), heavy, 100) for s in range(20))
    assert hits >= 18
    # chunking changes nothing but memory
    r1 = weighted_reservoir_sample(np.random.default_rng(9),
                                   np.arange(1, 301, dtype=float), 10,
                                   chunk=300)
    assert len(set(r1)) == 10


def test_sample_cohort_rejects_unknown_mode():
    with pytest.raises(ValueError, match="sampling mode"):
        sample_cohort(np.random.default_rng(0), 10, 2, "banana")


# ======================================================================
# bucket machinery at 10^6 entries (satellite)
# ======================================================================
def _brute_assign(needs, bounds, capacities):
    """The pre-vectorization sequential first-fit — the semantics
    anchor the numpy implementation must reproduce exactly."""
    out = {s: [] for s in bounds} if capacities is not None else {}
    for j, need in enumerate(needs):
        need = max(int(need), 1)
        for i, s in enumerate(bounds):
            if need > s:
                continue
            if capacities is not None and i < len(bounds) - 1 and \
                    len(out[s]) >= int(capacities[i]):
                continue
            out.setdefault(s, []).append(j)
            break
    return {s: out[s] for s in sorted(out)}


def test_assign_step_buckets_matches_brute_force_reference():
    rng = np.random.default_rng(7)
    for trial in range(25):
        needs = rng.integers(1, 65, size=rng.integers(1, 200)).tolist()
        bounds = [4, 16, 64]
        caps = [int(rng.integers(1, 8)), int(rng.integers(1, 8)), 4]
        assert assign_step_buckets(needs, bounds, caps) == \
            _brute_assign(needs, bounds, caps)
        assert assign_step_buckets(needs, bounds) == \
            _brute_assign(needs, bounds, None)


def test_bucket_fns_at_million_entries_fast_and_sane():
    def bucket_pass(n):
        rng = np.random.default_rng(0)
        needs = rng.integers(1, 2**20, size=n)
        others = rng.integers(1, 2**20, size=n)
        tic = time.process_time()
        bounds = bucket_boundaries(needs, max_buckets=4, max_steps=2**20)
        caps = bucket_capacities(needs, bounds, cohort_size=1024, quantum=8)
        assignment = assign_step_buckets(others, bounds, capacities=caps)
        return time.process_time() - tic, needs, bounds, caps, assignment

    # the subject is "not quadratic in the population", so the bound is a
    # ratio against a tenth of the entries and not seconds: beside five
    # other test workers this process's CPU seconds for the same pass read
    # 0.4 to 3.8, which no limit in seconds holds.  A sort-bound pass reads
    # 10-15 here, a quadratic one 100; the best of a few runs on each
    # side, because a first call also pays for its fresh pages
    small = min(bucket_pass(100_000)[0] for _ in range(3))
    elapsed, needs, bounds, caps, assignment = min(
        (bucket_pass(1_000_000) for _ in range(2)), key=lambda r: r[0])
    assert elapsed < 40 * small, (
        f"bucket pass took {elapsed:.2f} CPU-s at 10^6, "
        f"{elapsed / small:.0f} x its {small:.3f} at 10^5")
    assert len(bounds) <= 4 and bounds == sorted(bounds)
    assert bounds[-1] >= int(needs.max())  # no silent truncation
    assert all(c % 8 == 0 for c in caps)  # mesh-quantized capacities
    placed = sum(len(v) for v in assignment.values())
    assert placed == 1_000_000  # every client lands somewhere
    # int sanity at scale: capacities derive from slack * cohort * pop
    # products in the 10^9 range — they must stay positive ints
    assert all(isinstance(c, int) and 0 < c <= 1024 for c in caps)


def test_steps_for_array_matches_scalar_steps_for():
    from msrflute_tpu.data.batching import steps_for
    ns = np.random.default_rng(1).integers(0, 500, size=2000)
    vec = steps_for_array(ns, batch_size=8, desired_max_samples=100)
    ref = [steps_for(int(n), 8, 100) for n in ns]
    assert vec.tolist() == ref
    vec2 = steps_for_array(ns, batch_size=8)
    assert vec2.tolist() == [steps_for(int(n), 8) for n in ns]


# ======================================================================
# fleet population dataset + lazy-cache counters (satellite)
# ======================================================================
def test_synthetic_fleet_dataset_metadata_is_cheap_and_deterministic():
    tic = time.time()
    ds = SyntheticFleetDataset(1_000_000, cache_users=8)
    assert time.time() - tic < 2.0
    assert len(ds) == 1_000_000
    assert ds.num_samples.dtype == np.int32  # 4 bytes/user, not a list
    assert isinstance(ds.user_list, LazyNameList)
    assert ds.user_list[123456] == "u123456"
    ds2 = SyntheticFleetDataset(1_000_000, cache_users=8)
    u = ds.user_arrays(999_999)
    u2 = ds2.user_arrays(999_999)
    np.testing.assert_array_equal(u["x"], u2["x"])
    np.testing.assert_array_equal(u["y"], u2["y"])
    assert len(u["x"]) == int(ds.num_samples[999_999])


def test_synthetic_fleet_dataset_cache_counters():
    ds = SyntheticFleetDataset(100, cache_users=2)
    ds.user_arrays(0)
    ds.user_arrays(0)
    ds.user_arrays(1)
    ds.user_arrays(2)  # evicts 0
    ds.user_arrays(0)  # miss again
    st = ds.cache_stats()
    assert st["hits"] == 1 and st["misses"] == 4
    assert st["evictions"] == 2 and st["resident"] == 2


def test_lazy_user_dataset_cache_counters(tmp_path):
    from msrflute_tpu.data.dataset import LazyUserDataset

    class FakeUsers:
        user_list = ["a", "b", "c"]
        num_samples = [2, 2, 2]

        def read(self, name):
            return np.ones((2, 3)), np.zeros((2,))

    ds = LazyUserDataset(FakeUsers(), cache_users=2)
    ds.user_arrays(0)
    ds.user_arrays(0)
    ds.user_arrays(1)
    ds.user_arrays(2)
    st = ds.cache_stats()
    assert st == {"hits": 1, "misses": 3, "evictions": 1, "resident": 2}


# ======================================================================
# schema: the fleet block
# ======================================================================
def _raw(server_over):
    sc = {"max_iteration": 1,
          "optimizer_config": {"type": "sgd", "lr": 1.0},
          "data_config": {}}
    sc.update(server_over)
    return {
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": "fedavg",
        "server_config": sc,
        "client_config": {"optimizer_config": {"type": "sgd", "lr": 0.1},
                          "data_config": {"train": {}}},
    }


def test_schema_accepts_fleet_block():
    FLUTEConfig.from_dict(_raw({"fleet": {
        "enable": True, "page_pool_slots": 256, "host_cache_rows": 512,
        "spill_freq": 2, "sampling": "by_samples"}}))


def test_schema_rejects_bad_fleet_keys_and_values():
    with pytest.raises(ValueError, match="fleet"):
        FLUTEConfig.from_dict(_raw({"fleet": {"page_pool_slots": 0}}))
    with pytest.raises(ValueError, match="sampling"):
        FLUTEConfig.from_dict(_raw({"fleet": {"sampling": "banana"}}))
    with pytest.raises(ValueError, match="fleet"):
        FLUTEConfig.from_dict(_raw({"fleet": "yes"}))
    assert "fleet" in schema.SERVER_KEYS
    assert set(schema.FLEET_FIELD_SPECS) <= schema.FLEET_KEYS


# ======================================================================
# paged carry: bit-identity vs resident tables
# ======================================================================
def _cfg(strategy, depth, *, fleet=None, rounds=5, chaos=None,
         server_over=None):
    sc = {
        "max_iteration": rounds, "num_clients_per_iteration": 4,
        "initial_lr_client": 0.2, "pipeline_depth": depth,
        "fused_carry": True, "rounds_per_step": 1,
        "val_freq": 100, "initial_val": False,
        "optimizer_config": {"type": "sgd", "lr": 1.0},
        "data_config": {"val": {"batch_size": 8}},
    }
    if strategy == "personalization":
        strategy = "fedavg"
        sc["type"] = "personalization"
    if fleet is not None:
        sc["fleet"] = fleet
    if chaos is not None:
        sc["chaos"] = chaos
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


def _run(cfg, model_dir=None, val=False, seed=7):
    ds = make_synthetic_classification()
    task = make_task(cfg.model_config)
    cls = select_server(cfg.server_config.get("type"))
    if model_dir is None:
        with tempfile.TemporaryDirectory() as tmp:
            server = cls(task, cfg, ds, model_dir=tmp, seed=seed,
                         val_dataset=ds if val else None)
            state = server.train()
            flat = np.asarray(
                ravel_pytree(jax.device_get(state.params))[0])
        return flat, server, state
    server = cls(task, cfg, ds, model_dir=model_dir, seed=seed,
                 val_dataset=ds if val else None)
    state = server.train()
    flat = np.asarray(ravel_pytree(jax.device_get(state.params))[0])
    return flat, server, state


STRATEGIES = ["scaffold", "ef_quant", "personalization"]
_resident_cache = {}


def _resident_flat(strategy):
    if strategy not in _resident_cache:
        _resident_cache[strategy] = _run(_cfg(strategy, 0))[0]
    return _resident_cache[strategy]


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_paged_carry_serial_matches_resident_bit_exact(strategy):
    # a deliberately tight pool (8 slots < 16 users) so LRU eviction
    # and host-store page-back actually run on the identity path
    flat, server, state = _run(_cfg(strategy, 0,
                                    fleet={"page_pool_slots": 8}))
    assert server.fleet_pager is not None
    assert server.fleet_pager.evictions > 0  # paging really exercised
    for key in server.strategy.carry_tables:
        assert int(state.strategy_state[key].shape[0]) == 8
    np.testing.assert_array_equal(_resident_flat(strategy), flat)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_paged_carry_pipelined_matches_resident_bit_exact(strategy):
    flat, server, _ = _run(_cfg(strategy, 3, fleet={"enable": True}))
    assert server._pipeline_ok()
    assert server.pipelined_chunks > 0
    np.testing.assert_array_equal(_resident_flat(strategy), flat)


_CHAOS = {"enable": True, "seed": 3, "dropout_rate": 0.25,
          "straggler_rate": 0.25}


def test_paged_carry_chaos_strict_transfers(monkeypatch):
    monkeypatch.setenv("MSRFLUTE_STRICT_TRANSFERS", "1")
    serial = _run(_cfg("scaffold", 0, chaos=_CHAOS))[0]
    flat, server, _ = _run(_cfg("scaffold", 2, fleet={"enable": True},
                                chaos=_CHAOS))
    assert server.pipelined_chunks > 0
    np.testing.assert_array_equal(serial, flat)


def test_paged_carry_bucketed_matches_resident(monkeypatch):
    buck = {"cohort_bucketing": {"max_buckets": 2}}
    base = _run(_cfg("scaffold", 0, server_over=buck))[0]
    flat, server, _ = _run(_cfg("scaffold", 2, fleet={"enable": True},
                                server_over=buck))
    assert server.pipelined_chunks > 0
    np.testing.assert_array_equal(base, flat)


def test_paged_preempt_resume_bit_identical(tmp_path):
    chaos = dict(_CHAOS, preempt_at_round=3)
    fleet = {"enable": True}
    ref = _run(_cfg("scaffold", 3, rounds=7, fleet=fleet, chaos=_CHAOS),
               model_dir=str(tmp_path / "ref"))[0]
    run_dir = str(tmp_path / "run")
    _, pre, pre_state = _run(
        _cfg("scaffold", 3, rounds=7, fleet=fleet, chaos=chaos),
        model_dir=run_dir)
    assert pre.preempted
    assert 3 <= pre_state.round < 7
    res_cfg = _cfg("scaffold", 3, rounds=7, fleet=fleet, chaos=chaos,
                   server_over={"resume_from_checkpoint": True})
    flat, res, res_state = _run(res_cfg, model_dir=run_dir)
    assert res_state.round == 7 and not res.preempted
    np.testing.assert_array_equal(ref, flat)


def test_paged_personalized_eval_reads_host_rows(tmp_path):
    ds = make_synthetic_classification()
    flat, server, state = _run(
        _cfg("personalization", 2, fleet={"enable": True}),
        model_dir=str(tmp_path), val=True)
    assert server.store is None
    assert server.fleet_pager.has_rows()
    paged_res = server.personalized_eval(ds)
    assert paged_res is not None
    assert paged_res == server.personalized_eval(ds)  # deterministic
    # the paged eval computes the SAME numbers the resident tables give
    _, resident_srv, _ = _run(_cfg("personalization", 2), val=True)
    assert paged_res == resident_srv.personalized_eval(ds)


# ======================================================================
# refusals + pool geometry
# ======================================================================
def test_fleet_pool_below_in_flight_floor_is_refused():
    with pytest.raises(ValueError, match="in-flight floor"):
        _run(_cfg("scaffold", 3, fleet={"page_pool_slots": 4}))


def test_fleet_refuses_full_device_tables():
    with pytest.raises(ValueError, match="scaffold_device_controls"):
        _run(_cfg("fedavg", 0, fleet={"enable": True},
                  server_over={"scaffold_device_controls": True}))


def test_pager_refuses_strategy_without_carry_tables():
    from msrflute_tpu.engine.paging import CarryPager
    from msrflute_tpu.parallel.mesh import make_mesh
    from msrflute_tpu.strategies.fedavg import FedAvg

    cfg = _cfg("fedavg", 0)
    strat = FedAvg(cfg)
    with pytest.raises(ValueError, match="carry_tables"):
        CarryPager(strat, {}, slots=8, mesh=make_mesh())


# ======================================================================
# the fleet smoke, in-process (small geometry of the acceptance drill)
# ======================================================================
def test_fleet_smoke_million_users_pool_bounded(tmp_path, monkeypatch):
    """10^6-user synthetic population, chaos + bucketing + depth-3
    pipeline + strict transfers: device carry HBM bounded by the page
    pool (not N), fleet/cache telemetry live, zero steady-state
    recompile growth."""
    monkeypatch.setenv("MSRFLUTE_STRICT_TRANSFERS", "1")
    from msrflute_tpu.engine import OptimizationServer

    ds = SyntheticFleetDataset(1_000_000, cache_users=64)
    cfg = FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": "scaffold",
        "server_config": {
            "max_iteration": 3, "num_clients_per_iteration": 16,
            "initial_lr_client": 0.2, "pipeline_depth": 3,
            "fused_carry": True,
            "val_freq": 1000, "initial_val": False,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "data_config": {},
            "cohort_bucketing": {"max_buckets": 2},
            "chaos": {"enable": True, "seed": 5, "dropout_rate": 0.1,
                      "straggler_rate": 0.1},
            "fleet": {"enable": True},
        },
        "client_config": {"optimizer_config": {"type": "sgd", "lr": 0.2},
                          "data_config": {"train": {"batch_size": 4}}},
    })
    server = OptimizationServer(make_task(cfg.model_config), cfg, ds,
                                model_dir=str(tmp_path), seed=0)
    slots = server.fleet_pager.n_slots
    assert slots < 100_000  # O(cohort), five orders under N
    state = server.train()
    assert state.round == 3
    for key in server.strategy.carry_tables:
        assert int(state.strategy_state[key].shape[0]) == slots
    desc = server.fleet_pager.describe()
    assert desc["misses"] > 0 and desc["writeback_rows"] > 0
    assert ds.cache_stats()["misses"] > 0
    card = server.build_scorecard()
    assert card["fleet"]["pool_slots"] == slots
    assert card["lazy_cache"]["misses"] > 0
