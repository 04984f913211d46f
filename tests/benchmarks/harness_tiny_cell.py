"""What the harness's end-to-end files share (``test_benchmark_harness*``,
one ``run_cell`` to a file, so that no file is most of tier-1's
limit in one worker): a benchmark root of new files only, and the timed
path broken underneath the harness.  A plain module whose fixtures the
files import by name: a second ``conftest.py`` under ``tests/`` would
take the name that other files import ``tests/conftest.py`` by."""

import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402

BENCH = harness.BENCH_DIR
SEED = 2 ** 31 + 12345

FAULTS = {
    # fault: the number that has to catch it
    "state_unchanged": "timed_update_projection_gap",
    "half_the_cohort": "timed_client_gap",
    "server_lr_halved": "timed_update_projection_gap",
    "sign_flipped": "timed_update_projection_gap",
    "shard_left_out": "timed_update_projection_gap",
    "noise_skipped": "timed_noise_std_gap",
}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A benchmark root of NEW files only: a configuration, a traffic mix,
    a cell and a layer metric nobody has named anywhere else."""
    root = tmp_path_factory.mktemp("bench_root")
    for sub in ("workloads", "configs", "traffic", "layer_metrics",
                "controls"):
        os.makedirs(root / sub)
    with open(os.path.join(BENCH, "configs",
                           "cnn_femnist_dga_dp_q8.json")) as fh:
        config = json.load(fh)
    config["data"].update(train_users=6, val_users=2, test_users=2,
                          samples_per_user=40)
    config["overlay"]["server_config"].update(val_freq=2, rec_freq=2)
    # with 3 clients the DP noise (sigma x max_grad / K) is 57 times the
    # cell's and projects 1-2% onto the update
    config["check_limits"]["timed_update_projection_gap"]["limit"] = 0.1
    (root / "configs" / "tiny_cnn.json").write_text(json.dumps(config))
    (root / "traffic" / "tiny_mix.json").write_text(json.dumps({
        "overlay": {"server_config": {"num_clients_per_iteration": 3,
                                      "rounds_per_step": 1}},
        "period_rounds": 2}))
    (root / "workloads" / "tiny_cell.json").write_text(json.dumps(
        {"config": "tiny_cnn", "traffic": "tiny_mix", "chips": 1}))
    (root / "layer_metrics" / "toy_ms.py").write_text(
        "UNIT = 'ms'\n\n\ndef read(ctx):\n    return ctx.get('toy')\n")
    shutil.copy(os.path.join(BENCH, "controls", "bf16.json"),
                root / "controls" / "bf16.json")
    return str(root)


def verdicts(result):
    return {v["name"]: v for v in result["compared"]}


@pytest.fixture
def broken_timed_path_is_not_correct(tiny_root, monkeypatch):
    """``check(fault)``: one run of the tiny cell with the timed path
    broken underneath the harness: a dispatch that hands its weights back
    unchanged, leaves out part of the batch, steps with half the server's
    learning rate, steps the wrong way, loses one of four equal shards'
    share of the sum, or adds no DP noise.  The first call (the check
    program) is left sound, so what fails is the tie between the timed
    program and the reference."""
    import jax

    from msrflute_tpu.engine import round as round_mod
    real = round_mod.RoundEngine.dispatch_rounds

    def moved(state, new_state, fn):
        """The new state with ``fn(old, new)`` for weights."""
        return round_mod.ServerState(
            jax.tree.map(fn, state, new_state.params), new_state.opt_state,
            new_state.strategy_state, new_state.round)

    def noiseless(weights, batches, client_lrs, server_lrs, kwargs):
        fedround = harness.load_module(os.path.join(
            BENCH, "reference", "fedround.py"))
        model = harness.load_module(os.path.join(
            BENCH, "reference", "cnn_femnist.py"))
        cell = harness.load_cell(tiny_root, "tiny_cell")
        cfg = harness.build_config(cell, False, None)
        rounds = harness.round_inputs(batches, client_lrs, server_lrs,
                                      kwargs.get("quant_thresholds"))
        return fedround.run_rounds(
            model.forward, cfg["model_config"], jax.device_get(weights),
            rounds, cell["config_doc"]["reference"]["strategy"],
            block=2, precision=None)[-1]["new_params"]

    def check(fault):
        calls = {"n": 0}

        def broken(engine, state, batches, client_lrs, server_lrs, rng,
                   **kwargs):
            calls["n"] += 1
            if calls["n"] == 1:
                return real(engine, state, batches, client_lrs, server_lrs,
                            rng, **kwargs)
            if fault == "half_the_cohort":
                for batch in batches:
                    live = np.flatnonzero(batch.client_mask > 0)
                    batch.client_mask[live[: len(live) // 2 + 1]] = 0.0
            if fault == "server_lr_halved":
                server_lrs = [0.5 * lr for lr in server_lrs]
            keep = harness._tree_copy(state.params)
            new_state, stats = real(engine, state, batches, client_lrs,
                                    server_lrs, rng, **kwargs)
            if fault == "state_unchanged":
                new_state = moved(keep, new_state, lambda old, new: old)
            elif fault == "sign_flipped":
                new_state = moved(keep, new_state,
                                  lambda old, new: old - (new - old))
            elif fault == "shard_left_out":
                new_state = moved(keep, new_state,
                                  lambda old, new: old + 0.75 * (new - old))
            elif fault == "noise_skipped":
                # what a program that adds no noise hands back: the
                # noiseless update, here the plain reference's
                new_state = moved(noiseless(keep, batches, client_lrs,
                                            server_lrs, kwargs), new_state,
                                  lambda quiet, new: jax.numpy.asarray(quiet))
            return new_state, stats

        monkeypatch.setattr(round_mod.RoundEngine, "dispatch_rounds", broken)
        result = harness.run_cell("tiny_cell", SEED + 2, 0.2, False,
                                  root=tiny_root)
        assert not result["correct"]
        got = verdicts(result)
        assert not got[FAULTS[fault]]["ok"], got[FAULTS[fault]]
        assert got["loss_gap"]["ok"]  # the check program itself was sound

    return check
