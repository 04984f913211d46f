"""Experiment config zoo: every shipped config parses and (except the
full-size BERT) its task instantiates; nlg_gru and shakespeare run e2e from
generated synthetic data through the CLI — the closest analogue of reference
``testing/test_e2e_trainer.py`` over ``testing/create_data.py`` fixtures."""

import glob
import json
import os
import subprocess
import sys

import pytest
import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(glob.glob(os.path.join(REPO, "experiments", "*", "config.yaml")))


def test_configs_exist():
    tasks = {os.path.basename(os.path.dirname(p)) for p in CONFIGS}
    assert {"cv_lr_mnist", "cv_cnn_femnist", "cv_resnet_fedcifar100",
            "nlp_rnn_fedshakespeare", "nlg_gru", "mlm_bert", "classif_cnn",
            "ecg_cnn", "cv", "semisupervision", "fednewsrec"} <= tasks


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.split(os.sep)[-2])
def test_config_parses_and_task_builds(path):
    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.models import make_task
    with open(path) as fh:
        raw = yaml.safe_load(fh)
    cfg = FLUTEConfig.from_dict(raw)
    assert cfg.server_config.max_iteration > 0
    if cfg.model_config.model_type == "BERT":
        pytest.skip("full-size BERT init is exercised in test_bert with a "
                    "tiny config")
    make_task(cfg.model_config)


def _run_cli(task, cfg_override, tmp_path, extra_env=None):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    data = tmp_path / "data"
    out = tmp_path / "out"
    subprocess.run([sys.executable, os.path.join(REPO, "tools/create_data.py"),
                    "--task", task, "--out", str(data), "--users", "12"],
                   check=True, env=env, timeout=120)
    cfg_path = os.path.join(REPO, "experiments", task, "config.yaml")
    with open(cfg_path) as fh:
        raw = yaml.safe_load(fh)
    for dotted, value in cfg_override.items():
        node = raw
        keys = dotted.split(".")
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = value
    new_cfg = tmp_path / "cfg.yaml"
    new_cfg.write_text(yaml.safe_dump(raw))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "e2e_trainer.py"),
         "-config", str(new_cfg), "-dataPath", str(data),
         "-outputPath", str(out), "-task", task],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2500:]
    return out


def test_nlg_gru_e2e_from_config(tmp_path):
    out = _run_cli("nlg_gru", {
        "server_config.max_iteration": 2,
        "server_config.val_freq": 2,
        "server_config.rec_freq": 100,
        "server_config.initial_val": False,
        "server_config.rounds_per_step": 2,
        "client_config.data_config.train.batch_size": 4,
        "client_config.desired_max_samples": 16,
        "model_config.vocab_size": 64,
        "model_config.embed_dim": 16,
        "model_config.hidden_dim": 32,
    }, tmp_path)
    status = json.loads((out / "models" / "status_log.json").read_text())
    assert status["i"] == 2


@pytest.mark.slow
def test_cv_personalization_e2e_from_config(tmp_path):
    """Dirichlet + rotation-wedge partitioned blob through the
    PersonalizationServer (reference experiments/cv; the partitioner is
    experiments/cv/data.py:118-149).  Small CNN stands in for ResNet-18 to
    keep the CPU smoke fast — the data pipeline is what's under test."""
    out = _run_cli("cv", {
        "model_config.model_type": "CIFAR_CNN",
        "server_config.max_iteration": 2,
        "server_config.val_freq": 2,
        "server_config.rec_freq": 100,
        "server_config.initial_val": False,
        "server_config.data_config.val.batch_size": 32,
        "client_config.data_config.train.batch_size": 8,
        "client_config.desired_max_samples": 8,
    }, tmp_path)
    status = json.loads((out / "models" / "status_log.json").read_text())
    assert status["i"] == 2
    # personalization artifacts: per-user local models persisted
    assert any(n.endswith("_model.msgpack")
               for n in os.listdir(out / "models" / "personalization"))


@pytest.mark.slow
def test_semisupervision_e2e_from_config(tmp_path):
    """FedLabels uda:1 path end-to-end: the blob's unlabeled ``ux`` gets a
    RandAugment view (``ux_rand``) at featurize time via the config's
    ``data_config.train.augment`` (reference RandAugment.py)."""
    out = _run_cli("semisupervision", {
        "server_config.max_iteration": 2,
        "server_config.val_freq": 2,
        "server_config.rec_freq": 100,
        "server_config.initial_val": False,
        "server_config.data_config.val.batch_size": 32,
        "client_config.data_config.train.batch_size": 8,
        "client_config.desired_max_samples": 8,
        "client_config.semisupervision.burnout_round": 0,
    }, tmp_path)
    status = json.loads((out / "models" / "status_log.json").read_text())
    assert status["i"] == 2


@pytest.mark.slow
def test_fednewsrec_e2e_from_config(tmp_path):
    """MIND-style featurizer end-to-end: clicked/impressions blob ->
    npratio train slates + padded eval slates -> NRMS federated rounds with
    AUC/MRR/nDCG eval (reference experiments/fednewsrec/dataloaders/)."""
    out = _run_cli("fednewsrec", {
        "model_config.vocab_size": 500,
        "model_config.embed_dim": 24,
        "model_config.num_heads": 2,
        "model_config.head_dim": 8,
        "model_config.max_title_length": 12,
        "model_config.max_history": 6,
        "model_config.npratio": 2,
        "model_config.max_candidates": 10,
        "server_config.max_iteration": 2,
        "server_config.val_freq": 2,
        "server_config.rec_freq": 100,
        "server_config.initial_val": False,
        "server_config.data_config.val.batch_size": 16,
        "client_config.data_config.train.batch_size": 4,
        "client_config.desired_max_samples": 8,
    }, tmp_path)
    status = json.loads((out / "models" / "status_log.json").read_text())
    assert status["i"] == 2
    metrics = [json.loads(l) for l in
               (out / "log" / "metrics.jsonl").read_text().splitlines()]
    assert any(m["name"] == "Val auc" for m in metrics)


def test_ringlm_e2e_from_config(tmp_path):
    """Long-context RingLM family from raw-text blobs through the CLI
    (char featurizer; net-new family, docs/architecture.md)."""
    out = _run_cli("ringlm", {
        "model_config.embed_dim": 16,
        "model_config.num_heads": 2,
        "model_config.head_dim": 8,
        "model_config.mlp_dim": 32,
        "model_config.num_layers": 1,
        "model_config.seq_len": 64,
        "server_config.max_iteration": 2,
        "server_config.val_freq": 2,
        "server_config.rec_freq": 100,
        "server_config.initial_val": False,
        "server_config.rounds_per_step": 2,
        "server_config.data_config.val.batch_size": 8,
        "client_config.data_config.train.batch_size": 2,
    }, tmp_path)
    status = json.loads((out / "models" / "status_log.json").read_text())
    assert status["i"] == 2


@pytest.mark.slow
def test_shakespeare_e2e_from_config(tmp_path):
    out = _run_cli("nlp_rnn_fedshakespeare", {
        "server_config.max_iteration": 2,
        "server_config.val_freq": 2,
        "server_config.rec_freq": 100,
        "server_config.initial_val": False,
        "model_config.hidden_dim": 32,
        "model_config.seq_len": 48,
        "client_config.data_config.train.batch_size": 4,
    }, tmp_path)
    status = json.loads((out / "models" / "status_log.json").read_text())
    assert status["i"] == 2
