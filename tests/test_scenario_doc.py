"""The scenario-authoring guide's worked example, end-to-end.

docs/scenarios.md promises that experiments/hello_mlp/ (plugin task.py +
config.py defaults + config.yaml) runs through the CLI from an empty
output dir and learns; this test keeps that promise verifiable (review round 2
item 8 / reference doc/sphinx/scenarios.rst).
"""
import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_blob(path, means, users=16, samples=20, seed=0):
    rng = np.random.default_rng(seed)
    classes, dim = means.shape
    blob = {"users": [], "num_samples": [], "user_data": {},
            "user_data_label": {}}
    for u in range(users):
        y = rng.integers(0, classes, size=samples)
        x = means[y] + rng.normal(size=(samples, dim))
        name = f"u{u}"
        blob["users"].append(name)
        blob["num_samples"].append(samples)
        blob["user_data"][name] = {"x": x.tolist()}
        blob["user_data_label"][name] = y.tolist()
    with open(path, "w") as fh:
        json.dump(blob, fh)


def test_hello_mlp_scenario(tmp_path):
    data = tmp_path / "data"
    out = tmp_path / "out"
    data.mkdir()
    # one class-mean set for BOTH splits (val must come from the train
    # distribution, just with fresh noise)
    means = 2.5 * np.random.default_rng(7).normal(size=(3, 16))
    _write_blob(data / "train.json", means, seed=0)
    _write_blob(data / "val.json", means, users=4, samples=40, seed=1)

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "e2e_trainer.py"),
         "-config", os.path.join(REPO, "experiments", "hello_mlp",
                                 "config.yaml"),
         "-dataPath", str(data), "-outputPath", str(out),
         "-task", "hello_mlp"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]

    # metrics.jsonl carries Val acc AND the guide's custom top2_acc metric
    vals, top2 = {}, {}
    with open(out / "log" / "metrics.jsonl") as fh:
        for line in fh:
            rec = json.loads(line)
            if rec.get("name") == "Val acc":
                vals[rec["step"]] = rec["value"]
            elif rec.get("name") == "Val top2_acc":
                top2[rec["step"]] = rec["value"]
    assert vals, "no Val acc logged"
    assert top2, "custom metric top2_acc not logged"
    first, last = vals[min(vals)], vals[max(vals)]
    assert last > 0.8, f"hello_mlp failed to learn: {vals}"
    assert last > first
    assert top2[max(top2)] >= last  # top-2 can only beat top-1

    # checkpoints + status log as promised by the guide
    assert (out / "models" / "latest_model.msgpack").exists()
    assert (out / "models" / "status_log.json").exists()
