import pytest

from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.schema import SchemaError


MINI = {
    "model_config": {"model_type": "LR", "num_classes": 4, "input_dim": 8},
    "strategy": "fedavg",
    "server_config": {
        "max_iteration": 5,
        "num_clients_per_iteration": 4,
        "initial_lr_client": 0.1,
        "optimizer_config": {"type": "sgd", "lr": 1.0},
        "annealing_config": {"type": "step_lr", "step_interval": "epoch",
                             "step_size": 1, "gamma": 1.0},
        "val_freq": 2,
        "data_config": {"val": {"batch_size": 8}, "test": {"batch_size": 8}},
    },
    "client_config": {
        "optimizer_config": {"type": "sgd", "lr": 0.1},
        "data_config": {"train": {"batch_size": 4}},
    },
}


def test_from_dict_and_lookup():
    cfg = FLUTEConfig.from_dict(MINI)
    assert cfg.server_config.max_iteration == 5
    assert cfg.lookup("server_config.optimizer_config.lr") == 1.0
    assert cfg.lookup("client_config.data_config.train.batch_size") == 4
    assert cfg.lookup("does.not.exist", default=7) == 7
    # unknown model params preserved in extra + mapping access
    assert cfg.model_config["num_classes"] == 4
    assert cfg.model_config.get("input_dim") == 8


def test_schema_rejects_bad_optimizer():
    bad = {**MINI, "server_config": {**MINI["server_config"],
                                     "optimizer_config": {"type": "rmsprop"}}}
    with pytest.raises(SchemaError, match="rmsprop"):
        FLUTEConfig.from_dict(bad)


def test_schema_requires_model_type():
    with pytest.raises(SchemaError, match="model_type"):
        FLUTEConfig.from_dict({"model_config": {}, "server_config": {}})


def test_clients_per_round_range():
    import numpy as np
    from msrflute_tpu.config import parse_clients_per_round
    rng = np.random.default_rng(0)
    vals = {parse_clients_per_round("3:6", rng) for _ in range(50)}
    assert vals <= {3, 4, 5, 6} and len(vals) > 1
    assert parse_clients_per_round(10, rng) == 10


def test_to_dict_roundtrip():
    cfg = FLUTEConfig.from_dict(MINI)
    d = cfg.to_dict()
    cfg2 = FLUTEConfig.from_dict(d)
    assert cfg2.server_config.max_iteration == cfg.server_config.max_iteration
    assert cfg2.model_config["num_classes"] == 4


def test_schema_rejects_unknown_key_with_suggestion():
    # review round 2: a typo'd ``initial_lr_clients`` must fail loudly
    # instead of silently falling back to the 0.01 default
    bad = {**MINI, "server_config": {**MINI["server_config"],
                                     "initial_lr_clients": 0.5}}
    with pytest.raises(SchemaError, match=r"initial_lr_clients.*did you mean"):
        FLUTEConfig.from_dict(bad)


def test_schema_unknown_key_nested_dataset_block():
    bad = {**MINI, "client_config": {
        "optimizer_config": {"type": "sgd", "lr": 0.1},
        "data_config": {"train": {"batch_sizes": 4}},
    }}
    with pytest.raises(SchemaError, match="batch_sizes"):
        FLUTEConfig.from_dict(bad)


@pytest.mark.parametrize("section,block,name", [
    ("server_config", {"input_staging": False},
     "server_config.input_staging"),
    ("server_config", {"megakernel": {"enable": False}},
     "server_config.megakernel.enable"),
    ("server_config", {"megakernel": {"fused_epochs": False}},
     "server_config.megakernel.fused_epochs"),
    ("client_config", {"quant_approx": True}, "client_config.quant_approx"),
])
def test_schema_refuses_keys_deleted_with_their_arms(section, block, name):
    """The arms of three settled A/Bs went with their keys (PR 30): a
    configuration that still names one is refused by name, like any
    misspelt key, whatever value it asks for."""
    import re
    bad = {**MINI, section: {**MINI[section], **block}}
    with pytest.raises(SchemaError, match=re.escape(name) + ": unknown key"):
        FLUTEConfig.from_dict(bad)


def test_schema_allow_unknown_downgrades_to_warning(monkeypatch):
    monkeypatch.setenv("MSRFLUTE_ALLOW_UNKNOWN", "1")
    bad = {**MINI, "server_config": {**MINI["server_config"],
                                     "initial_lr_clients": 0.5}}
    with pytest.warns(UserWarning, match="initial_lr_clients"):
        FLUTEConfig.from_dict(bad)


def test_schema_freeform_sections_stay_open():
    ok = {**MINI, "model_config": {"model_type": "LR", "num_classes": 4,
                                   "input_dim": 8, "whatever_plugin_param": 1},
          "mesh_config": {"axis_names": ["clients"], "custom": True}}
    FLUTEConfig.from_dict(ok)  # must not raise


def test_applied_defaults_report():
    from msrflute_tpu.schema import applied_defaults
    cfg = FLUTEConfig.from_dict(MINI)
    rep = applied_defaults(MINI, cfg)
    # user never set rec_freq / lr_decay_factor -> reported with defaults
    assert "server_config.rec_freq" in rep
    # user DID set max_iteration -> not reported
    assert "server_config.max_iteration" not in rep


def test_schema_field_type_and_range_rules():
    """Per-field cerberus-style type/min/max rules (schema.py
    *_FIELD_SPECS): every violation is collected into one SchemaError."""
    bad = {**MINI, "server_config": {
        **MINI["server_config"],
        "stale_prob": 1.5,              # > 1
        "rounds_per_step": 0,           # < 1
        "initial_val": "yes",           # not a boolean
    }, "client_config": {
        **MINI["client_config"],
        "num_epochs": 0,                # < 1
        "data_config": {"train": {"batch_size": 0}},  # < 1
    }, "dp_config": {"eps": -1.0, "delta": 2.0}}  # eps<0 = clip-only, OK
    with pytest.raises(SchemaError) as ei:
        FLUTEConfig.from_dict(bad)
    msg = str(ei.value)
    for frag in ("stale_prob", "rounds_per_step", "initial_val",
                 "num_epochs", "batch_size", "dp_config.delta"):
        assert frag in msg, (frag, msg)
    assert "dp_config.eps" not in msg  # the clip-only sentinel must pass


def test_schema_bool_does_not_pass_as_int():
    bad = {**MINI, "server_config": {**MINI["server_config"],
                                     "rounds_per_step": True}}
    with pytest.raises(SchemaError, match="rounds_per_step"):
        FLUTEConfig.from_dict(bad)


def test_schema_optimizer_field_rules():
    bad = {**MINI, "client_config": {
        **MINI["client_config"],
        "optimizer_config": {"type": "sgd", "lr": -0.1, "momentum": 2.0}}}
    with pytest.raises(SchemaError) as ei:
        FLUTEConfig.from_dict(bad)
    assert "lr" in str(ei.value) and "momentum" in str(ei.value)


def test_schema_rejects_nan_in_bounded_fields():
    bad = {**MINI, "server_config": {**MINI["server_config"],
                                     "stale_prob": float("nan")}}
    with pytest.raises(SchemaError, match="NaN"):
        FLUTEConfig.from_dict(bad)


def test_schema_quant_thresh_is_a_quantile():
    bad = {**MINI, "client_config": {**MINI["client_config"],
                                     "quant_thresh": 1.5}}
    with pytest.raises(SchemaError, match="quant_thresh"):
        FLUTEConfig.from_dict(bad)


def test_schema_chaos_block_is_validated():
    """The resilience fault-injection block: typed keys, ranged rates,
    unknown keys rejected with a did-you-mean (PR 3)."""
    ok = {**MINI, "server_config": {
        **MINI["server_config"],
        "chaos": {"seed": 3, "dropout_rate": 0.2, "straggler_rate": 0.1,
                  "straggler_inflation": 2.0, "ckpt_io_error_rate": 0.05,
                  "preempt_at_round": 10}}}
    cfg = FLUTEConfig.from_dict(ok)
    assert cfg.server_config.get("chaos")["dropout_rate"] == 0.2

    bad_rate = {**MINI, "server_config": {**MINI["server_config"],
                                          "chaos": {"dropout_rate": 1.5}}}
    with pytest.raises(SchemaError, match="dropout_rate"):
        FLUTEConfig.from_dict(bad_rate)

    typo = {**MINI, "server_config": {**MINI["server_config"],
                                      "chaos": {"dropout_rte": 0.1}}}
    with pytest.raises(SchemaError, match="dropout_rte"):
        FLUTEConfig.from_dict(typo)

    # inflation < 1 would mean stragglers do MORE work than the barrier
    bad_inf = {**MINI, "server_config": {
        **MINI["server_config"], "chaos": {"straggler_inflation": 0.5}}}
    with pytest.raises(SchemaError, match="straggler_inflation"):
        FLUTEConfig.from_dict(bad_inf)


def test_schema_checkpoint_retry_block_is_validated():
    ok = {**MINI, "server_config": {
        **MINI["server_config"],
        "checkpoint_retry": {"retries": 5, "backoff_base_s": 0.1,
                             "backoff_max_s": 10, "jitter": 0.5,
                             "escalation_threshold": 4}}}
    FLUTEConfig.from_dict(ok)

    bad = {**MINI, "server_config": {**MINI["server_config"],
                                     "checkpoint_retry": {"retries": 0}}}
    with pytest.raises(SchemaError, match="retries"):
        FLUTEConfig.from_dict(bad)

    typo = {**MINI, "server_config": {**MINI["server_config"],
                                      "checkpoint_retry": {"retrys": 2}}}
    with pytest.raises(SchemaError, match="retrys"):
        FLUTEConfig.from_dict(typo)
