"""Overlapped host/device round pipeline — equivalence + fallback.

The pipelined loop (``server_config.pipeline_depth: 1``, the default)
drains round k's host tail (packed-stats decode, metric logging, privacy
processing, checkpoint submit) AFTER dispatching round k+1.  Its whole
contract is that this is a pure scheduling change: trained params,
metrics.jsonl contents (per-round values and step ordering), and
checkpoint state must be BIT-identical to the serial loop — across eval
boundaries, a mid-run plateau/client-LR decay, and privacy-stats rounds.
Host-orchestrated paths (RL, SCAFFOLD, EF, server replay) must fall back
to serial automatically.
"""

import json
import os

import flax.linen as nn
import jax
import numpy as np
import pytest
from flax import serialization
from jax.flatten_util import ravel_pytree

from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.engine import OptimizationServer
from msrflute_tpu.models import make_task
from msrflute_tpu.models.cv import ClassificationTask
from msrflute_tpu.utils.logging import init_logging


def _cfg(depth, step_bucketing=True, **server_over):
    sc = {
        "max_iteration": 9, "num_clients_per_iteration": 4,
        "initial_lr_client": 0.2, "pipeline_depth": depth,
        # exercise the host-tail state machinery the pipeline must not
        # reorder: plateau server-LR decay + client-LR decay at val
        # boundaries, periodic epoch backups
        "lr_decay_factor": 0.5, "model_backup_freq": 3,
        "val_freq": 3, "initial_val": False,
        "optimizer_config": {"type": "sgd", "lr": 1.0},
        "annealing_config": {"type": "val_loss", "patience": 0,
                             "factor": 0.5},
        "data_config": {"val": {"batch_size": 8}},
    }
    sc.update(server_over)
    return FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": "fedavg",
        # privacy stats flow through the packed buffer and the host tail
        # ("Dropped clients" logs per chunk); no adaptive threshold, so
        # the pipeline stays eligible
        "privacy_metrics_config": {"apply_metrics": True},
        "server_config": sc,
        "client_config": {
            "step_bucketing": step_bucketing,
            "optimizer_config": {"type": "sgd", "lr": 0.2},
            "data_config": {"train": {"batch_size": 4}}},
    })


def _val_ds():
    """Random-label val split (seeded): as the model fits the train
    structure, val loss on these labels worsens — a DETERMINISTIC plateau
    + client-LR decay trigger for the equivalence run."""
    from msrflute_tpu.data import ArraysDataset
    rng = np.random.default_rng(5)
    users, per = [], []
    for u in range(4):
        users.append(f"v{u}")
        per.append({"x": rng.normal(size=(12, 8)).astype(np.float32),
                    "y": rng.integers(0, 4, 12).astype(np.int32)})
    return ArraysDataset(users, per)


class _DeepMLP(nn.Module):
    """33 small Dense layers = 66 parameter leaves: more device leaves
    than a runtime lets programs be in flight (32), the shape of state
    on which a copy per leaf made the pre-dispatch snapshot wait for the
    running round program (ISSUE 25)."""

    num_classes: int = 4
    width: int = 8
    depth: int = 32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.reshape((x.shape[0], -1))
        for _ in range(self.depth):
            x = x + 0.1 * nn.tanh(nn.Dense(self.width)(x))
        return nn.Dense(self.num_classes)(x)


def _task(model, cfg):
    if model == "lr":
        return make_task(cfg.model_config)
    return ClassificationTask(_DeepMLP(), example_shape=(8,),
                              name="deep_mlp_66_leaves", num_classes=4)


def _run(depth, synth_dataset, root, model="lr", tag="", **server_over):
    model_dir = os.path.join(root, f"models_d{depth}{tag}")
    log_dir = os.path.join(root, f"log_d{depth}{tag}")
    init_logging(log_dir)
    cfg = _cfg(depth, **server_over)
    task = _task(model, cfg)
    server = OptimizationServer(task, cfg, synth_dataset,
                                val_dataset=_val_ds(),
                                model_dir=model_dir, seed=7)
    state = server.train()
    with open(os.path.join(log_dir, "metrics.jsonl")) as fh:
        records = [json.loads(line) for line in fh]
    with open(os.path.join(model_dir, "latest_model.msgpack"), "rb") as fh:
        latest = serialization.msgpack_restore(fh.read())
    with open(os.path.join(model_dir, "status_log.json")) as fh:
        status = json.load(fh)
    return server, state, records, latest, status


def _stepped_series(records):
    """{metric name: [(step, value), ...]} for step-carrying records —
    the per-round values and step ordering the issue pins (timing
    summaries carry no step and legitimately differ)."""
    series = {}
    for rec in records:
        if "step" in rec:
            series.setdefault(rec["name"], []).append(
                (rec["step"], rec["value"]))
    return series


@pytest.mark.parametrize("model", ["lr", "deep_66_leaves"])
def test_pipeline_bit_identical_to_serial(synth_dataset, tmp_path, model):
    srv0, st0, rec0, latest0, status0 = _run(0, synth_dataset,
                                             str(tmp_path), model)
    srv1, st1, rec1, latest1, status1 = _run(1, synth_dataset,
                                             str(tmp_path), model)
    assert len(jax.tree.leaves(st1.params)) == (2 if model == "lr" else 66)

    # the depth-1 run must actually have overlapped (6 of 9 chunks sit
    # strictly inside val boundaries), the depth-0 run never
    assert srv0.pipelined_chunks == 0
    assert srv1.pipelined_chunks == 6

    # final params: bit-identical
    flat0 = np.asarray(ravel_pytree(jax.device_get(st0.params))[0])
    flat1 = np.asarray(ravel_pytree(jax.device_get(st1.params))[0])
    np.testing.assert_array_equal(flat0, flat1)
    assert st0.round == st1.round == 9

    # metrics.jsonl: identical per-round values and step ordering
    s0, s1 = _stepped_series(rec0), _stepped_series(rec1)
    assert set(s0) == set(s1)
    # the state machinery under test really fired
    assert "Dropped clients" in s0          # privacy-stats rounds
    assert any(v != s0["LR for agg. opt."][0][1]
               for _, v in s0["LR for agg. opt."]), \
        "plateau decay never fired; the equivalence test lost its teeth"
    assert any(v != s0["Client learning rate"][0][1]
               for _, v in s0["Client learning rate"]), \
        "client-LR decay never fired"
    for name in s0:
        assert s0[name] == s1[name], name

    # checkpoint state (async writer in the pipelined run, sync in the
    # serial run) and status log: identical
    for leaf0, leaf1 in zip(jax.tree.leaves(latest0),
                            jax.tree.leaves(latest1)):
        np.testing.assert_array_equal(np.asarray(leaf0), np.asarray(leaf1))
    assert status0 == status1

    # host-tail observability feeds bench.py's new output fields
    assert len(srv1.run_stats["secsPerRoundHostTail"]) == 9

    # a run stopped at round 6 and resumed from the `latest` its
    # pre-dispatch snapshots wrote continues to the same bits
    _run(1, synth_dataset, str(tmp_path), model, tag="_resumed",
         max_iteration=6)
    srv2, st2, _, latest2, _ = _run(
        1, synth_dataset, str(tmp_path), model, tag="_resumed",
        resume_from_checkpoint=True)
    assert st2.round == 9 and srv2.pipelined_chunks == 2
    np.testing.assert_array_equal(
        flat1, np.asarray(ravel_pytree(jax.device_get(st2.params))[0]))
    for leaf1, leaf2 in zip(jax.tree.leaves(latest1),
                            jax.tree.leaves(latest2)):
        np.testing.assert_array_equal(np.asarray(leaf1), np.asarray(leaf2))


@pytest.mark.parametrize("depth", [1, 3])
def test_fused_ring_reuses_staging_buffers_bit_identical(
        synth_dataset, tmp_path, monkeypatch, depth):
    """Fused chunks through a ring of ``depth``: the host buffers the
    inputs are staged in are kept and written again, and none before the
    fence of the chunk whose program read it.  On the CPU backend the
    device array may BE the numpy buffer, so a buffer written too early
    changes what a program still in flight reads: the run then leaves
    the serial run's bits (ISSUE 34)."""
    from msrflute_tpu.engine.round import StagingPool
    # one step grid for every chunk, as where the clients hold equal
    # shares: the staged shapes stay, and so do the buffers
    fused = dict(rounds_per_step=2, max_iteration=24, val_freq=12,
                 model_backup_freq=12, step_bucketing=False)
    _, st0, rec0, latest0, status0 = _run(0, synth_dataset, str(tmp_path),
                                          tag="_fused", **fused)
    uses = []  # (buffer, reused) of every float32 group staged
    take = StagingPool.take

    def watched(pool, shapes):
        bufs, reused = take(pool, shapes)
        uses.append((bufs["float32"], reused))
        return bufs, reused

    monkeypatch.setattr(StagingPool, "take", watched)
    srv1, st1, rec1, latest1, status1 = _run(
        depth, synth_dataset, str(tmp_path), tag="_fused", **fused)
    monkeypatch.setattr(StagingPool, "take", take)
    # 12 chunks of two rounds, 5 of each period inside the boundaries
    assert srv1.pipelined_chunks == 10 and len(uses) == 12
    distinct = {id(buf): buf for buf, _ in uses}
    # what the ring has in flight plus the one being filled, each of
    # them written at least twice, none allocated after the ring filled
    assert len(distinct) == depth + 1
    assert min(sum(buf is b for b, _ in uses)
               for buf in distinct.values()) >= 2
    assert [reused for _, reused in uses] == \
        [False] * (depth + 1) + [True] * (11 - depth)

    flat0 = np.asarray(ravel_pytree(jax.device_get(st0.params))[0])
    flat1 = np.asarray(ravel_pytree(jax.device_get(st1.params))[0])
    np.testing.assert_array_equal(flat0, flat1)
    assert st0.round == st1.round == 24
    s0, s1 = _stepped_series(rec0), _stepped_series(rec1)
    assert set(s0) == set(s1)
    for name in s0:
        assert s0[name] == s1[name], name
    for leaf0, leaf1 in zip(jax.tree.leaves(latest0),
                            jax.tree.leaves(latest1)):
        np.testing.assert_array_equal(np.asarray(leaf0), np.asarray(leaf1))
    assert status0 == status1

    # stopped after the first period and resumed: a new engine, an
    # empty pool, the same bits
    resumed = dict(fused, max_iteration=12)
    _run(depth, synth_dataset, str(tmp_path), tag="_fused_resumed",
         **resumed)
    _, st2, _, latest2, _ = _run(
        depth, synth_dataset, str(tmp_path), tag="_fused_resumed",
        resume_from_checkpoint=True, **fused)
    assert st2.round == 24
    np.testing.assert_array_equal(
        flat1, np.asarray(ravel_pytree(jax.device_get(st2.params))[0]))
    for leaf1, leaf2 in zip(jax.tree.leaves(latest1),
                            jax.tree.leaves(latest2)):
        np.testing.assert_array_equal(np.asarray(leaf1), np.asarray(leaf2))


def test_host_orchestrated_paths_fall_back_to_serial(synth_dataset,
                                                     tmp_path):
    task_cfg = {"model_type": "LR", "num_classes": 4, "input_dim": 8}

    # SCAFFOLD: per-round host control exchange
    cfg = FLUTEConfig.from_dict({
        "model_config": task_cfg, "strategy": "scaffold",
        "server_config": {
            "max_iteration": 2, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.2,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 100, "initial_val": False, "data_config": {}},
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.2},
            "data_config": {"train": {"batch_size": 4}}},
    })
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, synth_dataset,
                                model_dir=str(tmp_path / "scaffold"),
                                seed=0)
    assert not server._pipeline_ok()
    state = server.train()  # default pipeline_depth=1 must degrade cleanly
    assert state.round == 2 and server.pipelined_chunks == 0

    # server replay: host training between rounds
    from msrflute_tpu.config import OptimizerConfig, ServerReplayConfig
    cfg = _cfg(1, max_iteration=2)
    cfg.server_config.server_replay_config = ServerReplayConfig(
        server_iterations=1,
        optimizer_config=OptimizerConfig(type="sgd", lr=0.05))
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, synth_dataset,
                                server_train_dataset=synth_dataset,
                                model_dir=str(tmp_path / "replay"), seed=0)
    assert not server._pipeline_ok()
    state = server.train()
    assert state.round == 2 and server.pipelined_chunks == 0

    # RL meta-aggregation: per-round val feedback
    cfg = FLUTEConfig.from_dict({
        "model_config": task_cfg, "strategy": "dga",
        "server_config": {
            "max_iteration": 1, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.2, "wantRL": True,
            "aggregate_median": "softmax", "softmax_beta": 1.0,
            "weight_train_loss": "train_loss",
            "RL": {"initial_epsilon": 0.5, "minibatch_size": 4,
                   "optimizer_config": {"type": "adam", "lr": 0.01}},
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 100, "initial_val": False,
            "data_config": {"val": {"batch_size": 16}}},
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.2},
            "data_config": {"train": {"batch_size": 4}}},
    })
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, synth_dataset,
                                val_dataset=synth_dataset,
                                model_dir=str(tmp_path / "rl"), seed=0)
    assert not server._pipeline_ok()

    # adaptive leakage threshold: this chunk's stats set the NEXT chunk's
    # drop threshold, so overlapping them would change the trajectory
    cfg = _cfg(1, max_iteration=2)
    cfg.privacy_metrics_config["adaptive_leakage_threshold"] = 0.9
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, synth_dataset,
                                model_dir=str(tmp_path / "adaptive"),
                                seed=0)
    assert not server._pipeline_ok()

    # pipeline-eligible baseline sanity: same construction, depth 1
    cfg = _cfg(1, max_iteration=2)
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, synth_dataset,
                                model_dir=str(tmp_path / "ok"), seed=0)
    assert server._pipeline_ok()


def test_explicit_sync_checkpoint_respected_in_pipelined_mode(
        synth_dataset, tmp_path):
    """pipeline_depth=1 defaults checkpoint_async on, but an explicit
    ``checkpoint_async: false`` must win (the knob for deployments that
    refuse the one-round status/params skew window)."""
    cfg = _cfg(1, max_iteration=3, checkpoint_async=False)
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, synth_dataset,
                                val_dataset=synth_dataset,
                                model_dir=str(tmp_path), seed=0)
    assert not server.ckpt.async_latest
    state = server.train()  # sync saves inside the pipelined loop
    assert state.round == 3
    assert os.path.exists(tmp_path / "latest_model.msgpack")
