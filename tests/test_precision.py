"""`server_config.precision` (ISSUE 12): the bf16 training path and its
two contracts — absent (or explicit f32) is BIT-identical to the
historical trace, and bf16 compute converges within a documented
tolerance of f32 while keeping f32 master params and f32 stats
accumulators.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from msrflute_tpu.config import FLUTEConfig, ModelConfig, OptimizerConfig
from msrflute_tpu.engine.client_update import (ClientHParams,
                                               build_client_update)
from msrflute_tpu.models import make_task
from msrflute_tpu.schema import SchemaError, validate

#: documented bf16-vs-f32 FINAL-LOSS tolerance per protocol (relative):
#: bf16 has ~8 mantissa bits, so per-step rounding wanders the
#: trajectory — what must hold is the destination, not the path.  These
#: values are deliberately loose enough to be stable across hosts and
#: tight enough that a broken cast path (e.g. bf16 stats accumulators
#: silently saturating) blows through them.
BF16_FINAL_LOSS_RTOL = {"lr": 0.10, "cnn": 0.15}


def _raw_cfg(precision=None, model=None, rounds=6):
    raw = {
        "model_config": model or {"model_type": "LR", "num_classes": 4,
                                  "input_dim": 8},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": rounds, "num_clients_per_iteration": 8,
            "initial_lr_client": 0.3,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 10_000, "initial_val": False,
            "data_config": {"val": {"batch_size": 64}},
        },
        "client_config": {
            "num_epochs": 2,
            "optimizer_config": {"type": "sgd", "lr": 0.3},
            "data_config": {"train": {"batch_size": 4}},
        },
    }
    if precision is not None:
        raw["server_config"]["precision"] = precision
    return raw


def _population_loss(task, params, dataset, users=8):
    xs = np.concatenate([dataset.user_arrays(i)["x"] for i in range(users)])
    ys = np.concatenate([dataset.user_arrays(i)["y"] for i in range(users)])
    batch = {"x": jnp.asarray(xs, jnp.float32),
             "y": jnp.asarray(ys, jnp.int32),
             "sample_mask": jnp.ones((len(xs),), jnp.float32)}
    return float(task.loss(params, batch, jax.random.PRNGKey(0), False)[0])


def _train(raw, dataset, mesh, tmp_path, tag):
    from msrflute_tpu.engine import OptimizationServer
    cfg = FLUTEConfig.from_dict(raw)
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, dataset,
                                model_dir=str(tmp_path / tag), mesh=mesh,
                                seed=0)
    init_loss = _population_loss(task, server.state.params, dataset)
    server.train()
    return server.state.params, (
        init_loss, _population_loss(task, server.state.params, dataset))


# ----------------------------------------------------------------------
# schema
# ----------------------------------------------------------------------
def test_schema_accepts_precision_block():
    validate(_raw_cfg({"compute": "bfloat16", "params": "float32",
                       "stats": "float32"}))


def test_schema_rejects_bad_precision_dtype():
    with pytest.raises(SchemaError, match="precision"):
        validate(_raw_cfg({"compute": "float64"}))


def test_schema_rejects_unknown_precision_key():
    with pytest.raises(SchemaError, match="precision"):
        validate(_raw_cfg({"computee": "bfloat16"}))


def test_schema_rejects_non_mapping_precision():
    with pytest.raises(SchemaError, match="must be a mapping"):
        validate(_raw_cfg("bfloat16"))


def test_schema_rejects_unknown_megakernel_key():
    raw = _raw_cfg()
    raw["server_config"]["megakernel"] = {"pallas_aply": True}
    with pytest.raises(SchemaError, match="megakernel"):
        validate(raw)


def test_schema_accepts_megakernel_block():
    raw = _raw_cfg()
    raw["server_config"]["megakernel"] = {"pallas_apply": False}
    validate(raw)


# ----------------------------------------------------------------------
# f32 bit-identity guard
# ----------------------------------------------------------------------
def test_absent_precision_bitwise_equals_explicit_f32(synth_dataset, mesh8,
                                                      tmp_path):
    """An explicit all-f32 precision block must compile the IDENTICAL
    program as no block at all — "float32" and "absent" are the same
    spelling of the bit-identity default."""
    p_none, _ = _train(_raw_cfg(), synth_dataset, mesh8, tmp_path, "none")
    p_f32, _ = _train(_raw_cfg({"params": "float32", "compute": "float32",
                                "stats": "float32"}),
                      synth_dataset, mesh8, tmp_path, "f32")
    for a, b in zip(jax.tree.leaves(p_none), jax.tree.leaves(p_f32)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# bf16-vs-f32 tolerance suite
# ----------------------------------------------------------------------
def test_bf16_compute_final_loss_within_tolerance(synth_dataset, mesh8,
                                                  tmp_path):
    _, (init_f32, final_f32) = _train(_raw_cfg(), synth_dataset, mesh8,
                                      tmp_path, "f32ref")
    _, (init_bf16, final_bf16) = _train(_raw_cfg({"compute": "bfloat16"}),
                                        synth_dataset, mesh8, tmp_path,
                                        "bf16")
    np.testing.assert_allclose(final_bf16, final_f32,
                               rtol=BF16_FINAL_LOSS_RTOL["lr"])
    # both must actually LEARN — a tolerance pass on two flat curves
    # would prove nothing
    assert final_f32 < init_f32
    assert final_bf16 < init_bf16


def test_bf16_params_policy_trains(synth_dataset, mesh8, tmp_path):
    """params: bfloat16 (local working copy + optimizer state in bf16)
    still converges on the toy problem; server master params stay f32."""
    params, (init_loss, final_loss) = _train(
        _raw_cfg({"params": "bfloat16", "compute": "bfloat16"}),
        synth_dataset, mesh8, tmp_path, "pbf16")
    assert all(p.dtype == jnp.float32 for p in jax.tree.leaves(params))
    assert final_loss < init_loss


# ----------------------------------------------------------------------
# client_update-level dtype contracts
# ----------------------------------------------------------------------
def _client_run(hp):
    task = make_task(ModelConfig(model_type="LR",
                                 extra={"num_classes": 4, "input_dim": 8}))
    rng = np.random.default_rng(0)
    arrays = {"x": jnp.asarray(rng.normal(size=(3, 4, 8)), jnp.float32),
              "y": jnp.asarray(rng.integers(0, 4, size=(3, 4)), jnp.int32)}
    mask = jnp.ones((3, 4), jnp.float32)
    cu = jax.jit(build_client_update(
        task, OptimizerConfig(type="sgd", lr=0.1), hp))
    return cu(task.init_params(jax.random.PRNGKey(0)), arrays, mask,
              jnp.float32(0.1), jax.random.PRNGKey(1))


def test_bf16_compute_keeps_f32_master_params_and_stats():
    pg, tl, ns, stats = _client_run(ClientHParams(
        num_epochs=2, compute_dtype="bfloat16"))
    # pseudo-gradients (w0 - w_trained over the f32 master copy) and the
    # packed-stats scalars stay f32 — only the fwd/bwd ran in bf16
    assert all(g.dtype == jnp.float32 for g in jax.tree.leaves(pg))
    assert tl.dtype == jnp.float32
    for key in ("mean", "mag", "norm"):
        assert stats[key].dtype == jnp.float32, key
    assert bool(jnp.isfinite(tl))


def test_rejects_non_float_precision_dtype():
    with pytest.raises(ValueError, match="floating"):
        build_client_update(
            make_task(ModelConfig(model_type="LR",
                                  extra={"num_classes": 4,
                                         "input_dim": 8})),
            OptimizerConfig(type="sgd", lr=0.1),
            ClientHParams(compute_dtype="int32"))


def test_engine_exposes_precision_policy(synth_dataset, mesh8):
    """RoundEngine normalizes the block (enable honored, dtype strings
    kept) — the surface bench.py's contract marker reads."""
    from msrflute_tpu.engine.round import RoundEngine
    from msrflute_tpu.strategies import select_strategy
    cfg = FLUTEConfig.from_dict(_raw_cfg({"compute": "bfloat16"}))
    task = make_task(cfg.model_config)
    engine = RoundEngine(task, cfg,
                         select_strategy(cfg.strategy)(cfg, None),
                         mesh=mesh8)
    assert engine.precision == {"compute": "bfloat16"}
    assert engine.hparams.pallas_apply is False


def test_engine_refuses_pallas_apply_off_tpu(synth_dataset, mesh8):
    """The shard_map'd round would deadlock an interpret-mode pallas
    kernel on virtual CPU devices — the engine refuses at build."""
    from msrflute_tpu.engine.round import RoundEngine
    from msrflute_tpu.strategies import select_strategy
    raw = _raw_cfg()
    raw["server_config"]["megakernel"] = {"pallas_apply": True}
    cfg = FLUTEConfig.from_dict(raw)
    task = make_task(cfg.model_config)
    with pytest.raises(ValueError, match="TPU backend"):
        RoundEngine(task, cfg, select_strategy(cfg.strategy)(cfg, None),
                    mesh=mesh8)
