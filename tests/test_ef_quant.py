"""Error-feedback quantization (strategies/ef_quant.py): the EF identity
holds exactly, residuals persist per client across rounds and resumes,
and aggressive quantization WITH memory out-converges the same
quantizer without it."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.data import ArraysDataset
from msrflute_tpu.engine import OptimizationServer
from msrflute_tpu.models import make_task
from msrflute_tpu.parallel import make_mesh
from msrflute_tpu.strategies.ef_quant import EFQuant, ResidualStore


def _cfg(strategy="ef_quant", rounds=2, bits=2, client_extra=None,
         server_extra=None):
    client = {
        "optimizer_config": {"type": "sgd", "lr": 0.3},
        "data_config": {"train": {"batch_size": 5}},
        "quant_bits": bits, "quant_thresh": 0.0,
    }
    client.update(client_extra or {})
    server = {
        "max_iteration": rounds, "num_clients_per_iteration": 6,
        "initial_lr_client": 0.3,
        "optimizer_config": {"type": "sgd", "lr": 1.0},
        "val_freq": max(rounds, 2), "initial_val": False,
        "data_config": {"val": {"batch_size": 16}},
        # the no-EF comparison uses dga's in-jit quantizer
        "aggregate_median": "mean",
    }
    server.update(server_extra or {})
    return FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 3,
                         "input_dim": 6},
        "strategy": strategy,
        "server_config": server,
        "client_config": client,
    })


def _data(users=8, n=10, seed=0):
    rng = np.random.default_rng(seed)
    names, per_user = [], []
    for u in range(users):
        y = rng.integers(0, 3, size=n)
        x = rng.normal(size=(n, 6)).astype(np.float32) * 0.3
        x[np.arange(n), y % 6] += 1.5
        names.append(f"u{u}")
        per_user.append({"x": x, "y": y.astype(np.int64)})
    return ArraysDataset(names, per_user)


def test_ef_identity():
    """q + new_residual == pgs + residual to one f32 rounding (a+(b-a)
    is not exactly b in floats; EF only needs the error to be carried,
    not bit-preserved)."""
    strat = EFQuant(_cfg(bits=2))
    rng = np.random.default_rng(0)
    pgs = jnp.asarray(rng.normal(size=(5, 33)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(5, 33)) * 0.1, jnp.float32)
    q, new_res = jax.jit(strat.ef_step)(pgs, res)
    np.testing.assert_allclose(np.asarray(q + new_res),
                               np.asarray(pgs + res), rtol=0, atol=1e-6)
    # 2-bit quantization actually quantized: <= 4 bin levels plus the
    # zero the |.|-threshold floor introduces (min-|g| elements zero out
    # even at quantile 0.0 because the comparison is strict)
    for row in np.asarray(q):
        assert len(np.unique(row)) <= 5


def test_residual_store_roundtrip(tmp_path):
    store = ResidualStore(7, store_dir=str(tmp_path))
    ids = np.asarray([3, -1, 11])
    rows = np.arange(21, dtype=np.float32).reshape(3, 7)
    store.update(ids, rows, keep_mask=[True, True, True])
    got = store.rows(ids)
    np.testing.assert_array_equal(got[0], rows[0])
    np.testing.assert_array_equal(got[1], 0)     # padding never stored
    np.testing.assert_array_equal(got[2], rows[2])
    # durable: a fresh store with resume=True reads the files back
    store2 = ResidualStore(7, store_dir=str(tmp_path), resume=True)
    np.testing.assert_array_equal(store2.rows([11])[0], rows[2])
    # a fresh NON-resume store wipes them (new trajectory)
    store3 = ResidualStore(7, store_dir=str(tmp_path))
    np.testing.assert_array_equal(store3.rows([11])[0], 0)


def test_ef_round_populates_residuals(tmp_path):
    data = _data()
    cfg = _cfg(rounds=2)
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, data, val_dataset=data,
                                model_dir=str(tmp_path), mesh=make_mesh(),
                                seed=0)
    state = server.train()
    assert state.round == 2
    # sampled clients now carry nonzero residuals in the durable store
    stored = [f for f in (tmp_path / "ef_residuals").iterdir()
              if f.name.startswith("residual_")]
    assert len(stored) >= 4
    row = np.load(stored[0])
    assert np.abs(row).max() > 0


def test_ef_beats_memoryless_at_2bit():
    """The EF pitch, measured: at 2-bit quantization the memoryless
    quantizer (dga's in-jit path) stalls well below the error-feedback
    run on the same data/seed/rounds."""
    data = _data()
    accs = {}
    for strat, client_extra in (("ef_quant", None),
                                ("dga", {"quant_thresh": 0.0})):
        cfg = _cfg(strategy=strat, rounds=12, bits=2,
                   client_extra=client_extra)
        cfg.server_config["val_freq"] = 12
        task = make_task(cfg.model_config)
        with tempfile.TemporaryDirectory() as tmp:
            server = OptimizationServer(task, cfg, data, val_dataset=data,
                                        model_dir=tmp, mesh=make_mesh(),
                                        seed=0)
            server.train()
        accs[strat] = float(server.best_val["acc"].value)
    assert accs["ef_quant"] >= accs["dga"], accs
    assert accs["ef_quant"] > 0.6, accs


def test_ef_quant_config_validation():
    # the schema rejects bad values first (first line of defense)...
    from msrflute_tpu.schema import SchemaError
    with pytest.raises(SchemaError):
        _cfg(bits=0)
    # ...and the strategy re-validates for programmatic configs that
    # bypassed the schema
    cfg = _cfg(bits=2)
    cfg.client_config["quant_bits"] = 0
    with pytest.raises(ValueError, match="quant_bits"):
        EFQuant(cfg)
    cfg2 = _cfg(bits=2)
    cfg2.client_config["quant_thresh"] = 1.5
    with pytest.raises(ValueError, match="quant_thresh"):
        EFQuant(cfg2)


def test_ef_device_table_bit_matches_host_path(tmp_path):
    """ef_device_residuals keeps the [K, n_params] residual traffic in
    HBM; the trajectory must be BIT-identical to the host path (same
    gathers, same jitted EF step, same participation gating)."""
    data = _data()
    params, residuals = {}, {}
    for mode in ("host", "device"):
        extra = ({"ef_device_residuals": True, "ef_flush_freq": 1}
                 if mode == "device" else None)
        cfg = _cfg(rounds=3, server_extra=extra)
        task = make_task(cfg.model_config)
        mdir = tmp_path / mode
        server = OptimizationServer(task, cfg, data, val_dataset=data,
                                    model_dir=str(mdir), mesh=make_mesh(),
                                    seed=0)
        state = server.train()
        params[mode] = np.concatenate(
            [np.ravel(x) for x in jax.tree.leaves(
                jax.device_get(state.params))])
        residuals[mode] = server.ef_store.rows(list(range(8)))
    np.testing.assert_array_equal(params["host"], params["device"])
    # the flushed durable rows match the host path's rows exactly
    np.testing.assert_array_equal(residuals["host"], residuals["device"])
    assert np.abs(residuals["host"]).max() > 0


def test_ef_device_table_unit_semantics(tmp_path):
    from msrflute_tpu.strategies.ef_quant import DeviceResidualTable
    store = ResidualStore(5, store_dir=str(tmp_path))
    store.update(np.asarray([2]), np.full((1, 5), 7.0, np.float32), [True])
    mesh = make_mesh()
    table = DeviceResidualTable(store, n_clients=10, mesh=mesh)
    # shards evenly over the clients axis (8 virtual devices in the CPU
    # suite; 1 on the single real chip — the assert must not bake in 8)
    from msrflute_tpu.parallel.mesh import CLIENTS_AXIS
    axis = int(mesh.shape[CLIENTS_AXIS])
    assert table.n_rows % axis == 0 and table.n_rows >= 10
    # gathers/scatters take the engine's cohort shape: K is always padded
    # to a multiple of the clients axis
    ids = np.asarray([2, -1, 3, -1, -1, -1, -1, -1])
    # warm-up picked the persisted row; padding gathers zeros
    got = np.asarray(jax.device_get(table.rows(ids)))
    np.testing.assert_array_equal(got[0], 7.0)
    np.testing.assert_array_equal(got[1:], 0.0)
    # scatter gates on participation: id -1 and w=0 rows are dropped
    new = jnp.asarray(np.stack(
        [np.full((5,), float(i + 1), np.float32) for i in range(8)]))
    ws = jnp.asarray([1.0, 1.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0])
    table.update(ids, new, ws, np.asarray(jax.device_get(ws)))
    got = np.asarray(jax.device_get(
        table.rows(np.asarray([2, 3, -1, -1, -1, -1, -1, -1]))))
    np.testing.assert_array_equal(got[0], 1.0)   # updated
    np.testing.assert_array_equal(got[1], 0.0)   # w=0: kept out
    # flush writes the dirty row through to the durable store
    table.flush()
    np.testing.assert_array_equal(store.rows([2])[0], 1.0)
    # reset zeroes table AND store (fallback semantics)
    table.reset()
    pad8 = np.asarray([2, -1, -1, -1, -1, -1, -1, -1])
    assert np.abs(np.asarray(jax.device_get(table.rows(pad8)))).max() == 0
    np.testing.assert_array_equal(store.rows([2])[0], 0.0)


def test_ef_device_table_k512_round(tmp_path):
    """review round 4 #7: the device-resident EF path at K=512 on the
    virtual 8-device mesh — one full engine round, residuals land for
    every participating client, RAM never holds a [K, n_params] host
    matrix on the round path."""
    data = _data(users=520, n=6)
    cfg = _cfg(rounds=1, server_extra={
        "num_clients_per_iteration": 512, "ef_device_residuals": True})
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, data, val_dataset=data,
                                model_dir=str(tmp_path), mesh=make_mesh(),
                                seed=0)
    state = server.train()
    assert state.round == 1
    stored = [f for f in (tmp_path / "ef_residuals").iterdir()
              if f.name.startswith("residual_") and
              f.name[len("residual_"):-len(".npy")].isdigit()]
    assert len(stored) >= 500  # ~all sampled clients flushed through


def test_ef_flush_freq_defers_durability(tmp_path):
    """ef_flush_freq > 1: between flushes the durable marker stays at
    the -1 sentinel (a crash inside the window resets residuals on
    resume — never a silent mismatch), and the final round always
    flushes."""
    data = _data()
    cfg = _cfg(rounds=3, server_extra={
        "ef_device_residuals": True, "ef_flush_freq": 10})
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, data, val_dataset=data,
                                model_dir=str(tmp_path), mesh=make_mesh(),
                                seed=0)
    server.train()
    # final=True at round 3 forces the flush + marker commit
    assert server.ef_store.round() == 3
    stored = [f for f in (tmp_path / "ef_residuals").iterdir()
              if f.name.startswith("residual_") and
              f.name[len("residual_"):-len(".npy")].lstrip("-").isdigit()]
    assert stored  # dirty rows written through at the final flush
    # resume with a crashed-window sentinel: reset semantics (as host path)
    server.ef_store.set_round(-1)
    cfg2 = _cfg(rounds=3, server_extra={
        "ef_device_residuals": True, "ef_flush_freq": 10})
    cfg2.server_config["resume_from_checkpoint"] = True
    server2 = OptimizationServer(task, cfg2, data, val_dataset=data,
                                 model_dir=str(tmp_path), mesh=make_mesh(),
                                 seed=0)
    assert np.abs(server2.ef_store.rows(list(range(8)))).max() == 0


def test_storeless_eviction_bounds_ram():
    """Without a disk store there is nowhere to spill: eviction DROPS
    LRU residuals (graceful EF degradation) instead of growing RAM
    without bound, and counts the drops."""
    store = ResidualStore(4, store_dir=None)
    store._MAX_RESIDENT = 8  # instance override keeps the test small
    ids = np.arange(12)
    store.update(ids, np.ones((12, 4), np.float32), np.ones(12, bool))
    assert len(store._rows) == 8
    assert store.dropped_rows == 4
    # the dropped clients read back as zero (memoryless next round)
    np.testing.assert_array_equal(store.rows([0])[0], 0.0)
    np.testing.assert_array_equal(store.rows([11])[0], 1.0)


def test_ef_duplicate_client_ids_rejected(tmp_path):
    """Per-client residuals assume without-replacement sampling; a
    duplicated id in a round batch must fail loudly, not silently lose
    one occurrence's compression error."""
    data = _data()
    cfg = _cfg(rounds=1)
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, data, val_dataset=data,
                                model_dir=str(tmp_path), mesh=make_mesh(),
                                seed=0)
    server._sample = lambda: [0, 1, 2, 2, 3, 4]
    with pytest.raises(ValueError, match="duplicate client ids"):
        server.train()


def test_quant_thresh_anneal_fast_forwards_on_resume(tmp_path):
    """ADVICE r4: the annealed threshold is a geometric schedule; a
    resumed run must continue at thresh0 * anneal^R, not restart."""
    data = _data()
    cfg = _cfg(rounds=2, client_extra={"quant_thresh": 0.5,
                                       "quant_anneal": 0.5})
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, data, val_dataset=data,
                                model_dir=str(tmp_path), mesh=make_mesh(),
                                seed=0)
    server.train()
    # after 2 rounds of next_threshold() the live value is 0.5 * 0.5^2
    assert server.strategy.quant_thresh == pytest.approx(0.125)
    cfg2 = _cfg(rounds=2, client_extra={"quant_thresh": 0.5,
                                        "quant_anneal": 0.5})
    cfg2.server_config["resume_from_checkpoint"] = True
    server2 = OptimizationServer(task, cfg2, data, val_dataset=data,
                                 model_dir=str(tmp_path), mesh=make_mesh(),
                                 seed=0)
    assert server2.state.round == 2
    # fast-forwarded at construction: 0.5 * 0.5^2, NOT the config's 0.5
    assert server2.strategy.quant_thresh == pytest.approx(0.125)


def test_ef_residuals_survive_resume_and_reset_on_mismatch(tmp_path):
    data = _data()
    cfg = _cfg(rounds=2)
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, data, val_dataset=data,
                                model_dir=str(tmp_path), mesh=make_mesh(),
                                seed=0)
    server.train()
    assert server.ef_store.round() == 2
    # clean resume: residuals and marker carry forward
    cfg2 = _cfg(rounds=4)
    cfg2.server_config["resume_from_checkpoint"] = True
    server2 = OptimizationServer(task, cfg2, data, val_dataset=data,
                                 model_dir=str(tmp_path), mesh=make_mesh(),
                                 seed=0)
    assert server2.state.round == 2
    assert any(np.abs(server2.ef_store.rows(list(range(8)))).max(axis=1) > 0)
    # crashed-window resume: a -1 sentinel mismatches -> residuals reset
    server2.ef_store.set_round(-1)
    server3 = OptimizationServer(task, cfg2, data, val_dataset=data,
                                 model_dir=str(tmp_path), mesh=make_mesh(),
                                 seed=0)
    assert np.abs(server3.ef_store.rows(list(range(8)))).max() == 0
