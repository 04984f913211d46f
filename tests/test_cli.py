"""CLI e2e smoke test — the direct analogue of reference
``testing/test_e2e_trainer.py`` (subprocess run of the trainer on dummy
data, assert exit 0), but also asserts on produced artifacts.
"""

import json
import os
import subprocess
import sys

import numpy as np
import yaml


def _write_blob(path, num_users, dim=6, classes=3, lo=4, hi=10, seed=0):
    rng = np.random.default_rng(seed)
    users = [f"u{i}" for i in range(num_users)]
    data, labels, counts = {}, {}, []
    w = rng.normal(size=(dim, classes))
    for u in users:
        n = int(rng.integers(lo, hi))
        x = rng.normal(size=(n, dim))
        y = np.argmax(x @ w, axis=1)
        data[u] = {"x": x.tolist()}
        labels[u] = y.tolist()
        counts.append(n)
    with open(path, "w") as fh:
        json.dump({"users": users, "num_samples": counts,
                   "user_data": data, "user_data_label": labels}, fh)


def test_cli_end_to_end(tmp_path):
    data_dir = tmp_path / "data"
    out_dir = tmp_path / "out"
    data_dir.mkdir()
    _write_blob(data_dir / "train.json", 12)
    _write_blob(data_dir / "val.json", 4, seed=1)
    _write_blob(data_dir / "test.json", 4, seed=2)

    cfg = {
        "model_config": {"model_type": "LR", "num_classes": 3, "input_dim": 6},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": 3,
            "num_clients_per_iteration": 4,
            "initial_lr_client": 0.3,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 2, "rec_freq": 2, "initial_val": True,
            "best_model_criterion": "acc",
            "data_config": {"val": {"batch_size": 8, "val_data": "val.json"},
                            "test": {"batch_size": 8, "test_data": "test.json"}},
        },
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.3},
            "data_config": {"train": {"batch_size": 4,
                                      "list_of_train_data": "train.json"}},
        },
    }
    cfg_path = tmp_path / "cfg.yaml"
    cfg_path.write_text(yaml.safe_dump(cfg))

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "e2e_trainer.py"),
         "-config", str(cfg_path), "-dataPath", str(data_dir),
         "-outputPath", str(out_dir), "-task", "cv_lr_mnist"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    # artifacts: checkpoint + status log + metrics stream + copied config
    assert (out_dir / "models" / "latest_model.msgpack").exists()
    status = json.loads((out_dir / "models" / "status_log.json").read_text())
    assert status["i"] == 3
    metrics = [json.loads(l) for l in
               (out_dir / "log" / "metrics.jsonl").read_text().splitlines()]
    assert any(m["name"] == "Val acc" for m in metrics)
    assert (out_dir / "cfg.yaml").exists()

    # ---- warm-start: a second run from the first run's best checkpoint
    # (reference model_config.pretrained_model_path, core/config.py:93) ----
    best = out_dir / "models" / "best_val_acc_model.msgpack"
    assert best.exists()
    cfg["model_config"]["pretrained_model_path"] = str(best)
    cfg["server_config"]["max_iteration"] = 1
    cfg["server_config"]["initial_val"] = False
    cfg2_path = tmp_path / "cfg2.yaml"
    cfg2_path.write_text(yaml.safe_dump(cfg))
    out2 = tmp_path / "out2"
    proc2 = subprocess.run(
        [sys.executable, os.path.join(repo, "e2e_trainer.py"),
         "-config", str(cfg2_path), "-dataPath", str(data_dir),
         "-outputPath", str(out2), "-task", "cv_lr_mnist"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc2.returncode == 0, proc2.stderr[-3000:]
    assert "warm-started from pretrained model" in (proc2.stdout + proc2.stderr)
    assert (out2 / "models" / "latest_model.msgpack").exists()


def test_summarize_run_tool(tmp_path):
    """tools/summarize_run.py renders a per-metric table from a run's
    metrics.jsonl (the offline stand-in for the reference's AzureML
    dashboard)."""
    log_dir = tmp_path / "log"
    log_dir.mkdir()
    lines = [{"name": "Val acc", "value": 0.5, "step": 2},
             {"name": "Val acc", "value": 0.8, "step": 4},
             {"name": "Training loss", "value": 1.2, "step": 4}]
    (log_dir / "metrics.jsonl").write_text(
        "\n".join(json.dumps(l) for l in lines))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools/summarize_run.py"),
         str(tmp_path)], capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "Val acc" in proc.stdout and "0.8" in proc.stdout
    assert "Training loss" in proc.stdout


def test_cli_secure_agg_and_ef_quant(tmp_path):
    """The round-4 net-new strategies through the FULL user path:
    YAML -> schema -> select_strategy -> engine, one CLI run each."""
    data_dir = tmp_path / "data"
    data_dir.mkdir()
    _write_blob(data_dir / "train.json", 12)
    _write_blob(data_dir / "val.json", 4, seed=1)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    for strategy, server_extra, client_extra in (
            ("secure_agg", {"secure_agg": {"frac_bits": 12, "clip": 4.0}},
             {}),
            ("ef_quant", {}, {"quant_bits": 4})):
        cfg = {
            "model_config": {"model_type": "LR", "num_classes": 3,
                             "input_dim": 6},
            "strategy": strategy,
            "server_config": {
                "max_iteration": 2, "num_clients_per_iteration": 4,
                "initial_lr_client": 0.3,
                "optimizer_config": {"type": "sgd", "lr": 1.0},
                "val_freq": 2, "initial_val": False,
                "data_config": {"val": {"batch_size": 8,
                                        "val_data": "val.json"}},
                **server_extra,
            },
            "client_config": {
                "optimizer_config": {"type": "sgd", "lr": 0.3},
                "data_config": {"train": {"batch_size": 4,
                                          "list_of_train_data":
                                          "train.json"}},
                **client_extra,
            },
        }
        cfg_path = tmp_path / f"cfg_{strategy}.yaml"
        cfg_path.write_text(yaml.safe_dump(cfg))
        out_dir = tmp_path / f"out_{strategy}"
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "e2e_trainer.py"),
             "-config", str(cfg_path), "-dataPath", str(data_dir),
             "-outputPath", str(out_dir), "-task", "cv_lr_mnist"],
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, (strategy, proc.stderr[-3000:])
        status = json.loads(
            (out_dir / "models" / "status_log.json").read_text())
        assert status["i"] == 2, strategy
        if strategy == "ef_quant":
            stored = list((out_dir / "models" / "ef_residuals").iterdir())
            assert any(f.name.startswith("residual_") for f in stored)
