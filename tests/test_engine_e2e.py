"""End-to-end round-loop smoke tests on the 8-device virtual mesh —
the analogue of reference ``testing/test_e2e_trainer.py`` (which shells out
to a 2-process torch.distributed run), plus correctness assertions the
reference never had: learning actually reduces loss, checkpoints resume.
"""

import numpy as np
import pytest

from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.engine import OptimizationServer
from msrflute_tpu.models import make_task


def _config(max_iteration=6, **server_over):
    raw = {
        "model_config": {"model_type": "LR", "num_classes": 4, "input_dim": 8},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": max_iteration,
            "num_clients_per_iteration": 4,
            "initial_lr_client": 0.5,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 2,
            "rec_freq": 100,
            "initial_val": True,
            "best_model_criterion": "acc",
            "data_config": {"val": {"batch_size": 8}, "test": {"batch_size": 8}},
            **server_over,
        },
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.5},
            "data_config": {"train": {"batch_size": 4}},
        },
    }
    return FLUTEConfig.from_dict(raw)


@pytest.fixture(scope="module")
def trained(tmp_path_factory, synth_dataset, mesh8):
    cfg = _config()
    task = make_task(cfg.model_config)
    server = OptimizationServer(
        task, cfg, synth_dataset, val_dataset=synth_dataset,
        model_dir=str(tmp_path_factory.mktemp("models")), mesh=mesh8, seed=1)
    initial = server._maybe_eval  # run explicit initial eval through train()
    state = server.train()
    return server, state


def test_training_improves_metrics(trained, synth_dataset):
    server, state = trained
    assert state.round == 6
    # linear separable toy data: accuracy should beat the 1/4 chance level
    assert server.best_val["acc"].value > 0.5
    assert "loss" in server.best_val


def test_checkpoint_resume(trained, synth_dataset, mesh8, tmp_path):
    server, state = trained
    # latest checkpoint exists and loads back with identical params
    restored = server.ckpt.load(server.engine.init_state(
        __import__("jax").random.PRNGKey(0)))
    assert restored is not None
    assert restored.round == 6
    import jax
    old = jax.device_get(state.params)
    new = jax.device_get(restored.params)
    for a, b in zip(jax.tree.leaves(old), jax.tree.leaves(new)):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_resume_continues_rounds(synth_dataset, mesh8, tmp_path):
    cfg = _config(max_iteration=2)
    task = make_task(cfg.model_config)
    d = str(tmp_path / "m")
    s1 = OptimizationServer(task, cfg, synth_dataset, val_dataset=synth_dataset,
                            model_dir=d, mesh=mesh8, seed=2)
    s1.train()
    cfg2 = _config(max_iteration=4, resume_from_checkpoint=True)
    s2 = OptimizationServer(task, cfg2, synth_dataset, val_dataset=synth_dataset,
                            model_dir=d, mesh=mesh8, seed=3)
    assert s2.state.round == 2
    final = s2.train()
    assert final.round == 4


def test_dga_strategy_runs(synth_dataset, mesh8, tmp_path):
    raw_over = {"aggregate_median": "softmax", "softmax_beta": 0.5,
                "weight_train_loss": "train_loss", "stale_prob": 0.3}
    cfg = _config(max_iteration=3, **raw_over)
    cfg.strategy = "dga"
    from msrflute_tpu.strategies import select_strategy, DGA
    assert select_strategy("dga") is DGA
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, synth_dataset,
                                val_dataset=synth_dataset,
                                model_dir=str(tmp_path / "dga"), mesh=mesh8)
    state = server.train()
    assert state.round == 3
    # staleness buffer is threaded state
    assert "stale_grad_sum" in state.strategy_state



def test_async_latest_msgpack_checkpoint(synth_dataset, mesh8, tmp_path):
    """server_config.checkpoint_async: true — per-round latest saves run
    on the writer thread (overlapping the next round on a real chip) yet
    land bit-identical durable state; resume restores it exactly."""
    import jax
    cfg = _config(max_iteration=3, checkpoint_async=True)
    task = make_task(cfg.model_config)
    d = str(tmp_path / "async")
    s1 = OptimizationServer(task, cfg, synth_dataset,
                            val_dataset=synth_dataset,
                            model_dir=d, mesh=mesh8, seed=5)
    state = s1.train()  # train() waits on the writer before returning
    assert s1.ckpt.async_latest
    restored = s1.ckpt.load(s1.engine.init_state(jax.random.PRNGKey(0)))
    assert restored is not None and restored.round == 3
    for a, b in zip(jax.tree.leaves(jax.device_get(state.params)),
                    jax.tree.leaves(jax.device_get(restored.params))):
        np.testing.assert_array_equal(a, b)
    # resume through the ordinary ctor path sees the async-written file
    cfg2 = _config(max_iteration=5, checkpoint_async=True,
                   resume_from_checkpoint=True)
    s2 = OptimizationServer(task, cfg2, synth_dataset,
                            val_dataset=synth_dataset,
                            model_dir=d, mesh=mesh8, seed=6)
    assert s2.state.round == 3
    assert s2.train().round == 5


@pytest.mark.parametrize("pipeline_depth", [0, 1], ids=["serial", "ring"])
def test_status_log_never_names_a_best_model_that_is_not_on_disk(
        synth_dataset, mesh8, tmp_path, pipeline_depth):
    """With the async writer too, a best-model save is durable before
    ``status_log.json`` names the new ``best_val`` (a crash in between
    would otherwise resume with a best value that no file holds); and an
    evaluation round's ``latest``, told to be of the very state the best
    model was just written from, is a link to that file."""
    import json
    import os

    from msrflute_tpu.engine.checkpoint import LATEST
    from msrflute_tpu.resilience.integrity import blob_checksum

    cfg = _config(max_iteration=6, checkpoint_async=True,
                  pipeline_depth=pipeline_depth)
    task = make_task(cfg.model_config)
    d = tmp_path / "models"
    server = OptimizationServer(task, cfg, synth_dataset,
                                val_dataset=synth_dataset,
                                model_dir=str(d), mesh=mesh8, seed=7)
    assert server.ckpt.async_latest
    named, linked = {}, []
    update_status = server.ckpt.update_status
    save_latest = server.ckpt.save_latest

    def checked_status(update):
        for key, value in update.items():
            if key.startswith("best_val_") and key != "best_val_hib" and \
                    named.get(key) != value:
                named[key] = value
                path = d / f"{key}_model.msgpack"
                meta = json.loads((d / f"{key}_model.msgpack.sum")
                                  .read_text())
                assert meta["crc32"] == blob_checksum(path.read_bytes())
        return update_status(update)

    def watched_latest(state, same_as=None, **how):
        out = save_latest(state, same_as=same_as, **how)
        if same_as is not None:
            linked.append(os.path.samefile(d / LATEST, same_as))
        return out

    server.ckpt.update_status = checked_status
    server.ckpt.save_latest = watched_latest
    server.train()
    assert {"best_val_acc", "best_val_loss"} <= set(named)
    if pipeline_depth == 0:
        # every round's latest goes out in housekeeping: the rounds whose
        # evaluation improved a metric linked it
        assert linked and all(linked)


def test_orbax_async_checkpoint_backend(synth_dataset, mesh8, tmp_path):
    """server_config.checkpoint_backend: orbax — async saves land durable
    checkpoints and resume restores the exact state, like msgpack."""
    import os
    import jax

    cfg = _config(max_iteration=3)
    cfg.server_config["checkpoint_backend"] = "orbax"
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, synth_dataset,
                                val_dataset=synth_dataset,
                                model_dir=str(tmp_path), mesh=mesh8, seed=0)
    state = server.train()
    # two-slot latest: pointer file names the committed slot directory
    # and (since the resilience PR) records its tree checksum
    import json as _json
    ptr = _json.loads((tmp_path / "latest_model.orbax.ptr").read_text())
    assert os.path.isdir(tmp_path / ptr["slot"])
    assert ptr["crc32"]
    assert any(n.startswith("best_val_") and n.endswith(".orbax")
               for n in os.listdir(tmp_path))

    # resume: fresh server restores round + params, and — crucially —
    # TRAINS on, which requires the optax namedtuple structure (not a
    # plain state-dict) to have been reconstructed
    cfg2 = _config(max_iteration=5)
    cfg2.server_config["checkpoint_backend"] = "orbax"
    cfg2.server_config["resume_from_checkpoint"] = True
    server2 = OptimizationServer(task, cfg2, synth_dataset,
                                 val_dataset=synth_dataset,
                                 model_dir=str(tmp_path), mesh=mesh8, seed=0)
    assert server2.state.round == 3
    for a, b in zip(jax.tree.leaves(jax.device_get(state.params)),
                    jax.tree.leaves(jax.device_get(server2.state.params))):
        np.testing.assert_array_equal(a, b)
    assert server2.train().round == 5

    # warm-start from an orbax checkpoint directory (pretrained_model_path
    # accepts either backend's output)
    from msrflute_tpu.engine.checkpoint import load_pretrained_params
    best_dir = next(str(tmp_path / n) for n in os.listdir(tmp_path)
                    if n.startswith("best_val_") and n.endswith(".orbax"))
    warm = load_pretrained_params(best_dir, server2.state.params)
    assert jax.tree.structure(warm) == jax.tree.structure(
        jax.device_get(server2.state.params))
