"""Contract tests for the whole-run evidence tools.

``tools/fullrun_protocols.py`` and ``tools/parity/longrun.py`` drive whole
runs through the CLI; these smoke their CPU contracts so a broken tool is
caught in CI, not in a chip call.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_fullrun_smoke_contract(tmp_path):
    """Smoke geometry, LR only: the tool must drive the real CLI to
    completion and report the device the child says it ran on (the
    platform is what the child reports, checked against the explicit
    ``--platform``), a parsed val-acc curve and per-round checkpointing
    timing."""
    env = dict(os.environ, FULLRUN_SMOKE="1", FULLRUN_PROTOCOLS="lr_mnist",
               FULLRUN_DATA_DIR=str(tmp_path / "data"), JAX_PLATFORMS="cpu")
    tool = os.path.join(REPO, "tools", "fullrun_protocols.py")
    proc = subprocess.run(
        [sys.executable, tool, "--platform", "cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["kind"] == "fullrun_protocols"
    assert line["backend"] == "cpu" and line["smoke"] is True
    lr = line["protocols"]["lr_mnist"]
    assert lr["returncode"] == 0
    assert lr["device"]["platform"] == "cpu" and lr["device"]["count"] >= 1
    assert lr["rounds_per_step"] == 1  # faithful mode: per-round ckpt
    assert lr["total_secs"] > 0
    assert lr["val_acc_curve"], lr
    assert "secsPerRound (mean)" in lr["timing"]


@pytest.mark.skipif(not os.path.isdir("/root/reference"),
                    reason="reference FLUTE checkout not mounted in this "
                           "container (longrun drives BOTH frameworks)")
def test_longrun_smoke_contract(tmp_path):
    """Tiny geometry through BOTH frameworks: curves parse, align at the
    shared cadence, and the artifact carries the comparison fields."""
    out = tmp_path / "PARITY_LONGRUN_SMOKE.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parity",
                                      "longrun.py"),
         "--smoke", "--scratch", str(tmp_path / "scratch"),
         "--out", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    payload = json.load(open(out))
    assert payload["ok"] is True
    assert payload["ref"]["curve"] and payload["tpu"]["curve"]
    # aligned cadence: both curves share round keys
    ref_rounds = {r for r, _ in payload["ref"]["curve"]}
    tpu_rounds = {r for r, _ in payload["tpu"]["curve"]}
    assert ref_rounds & tpu_rounds
    assert payload["second_half_mean_gap"] is not None
