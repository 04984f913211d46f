"""Length bucketing (review round 2 item 5): variable-length token tasks stop
paying max-L padding FLOPs — cropping all-pad tail columns is math-identical
because SeqLMTask's position masks derive from the ids, not from L.

Reference analogue: ``utils/data_utils.py:42-119`` (DynamicBatchSampler's
frames-budget packing + padding-efficiency meter).
"""
import numpy as np
import pytest

from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.data import ArraysDataset
from msrflute_tpu.data.batching import pack_round_batches, seq_length_bucket
from msrflute_tpu.models import make_task


def _varlen_dataset(users=6, rows=8, L=64, real_max=11, vocab=50, seed=0):
    rng = np.random.default_rng(seed)
    per_user = []
    for _ in range(users):
        x = np.zeros((rows, L), np.int32)
        for r in range(rows):
            n = rng.integers(3, real_max + 1)
            x[r, :n] = rng.integers(1, vocab, size=n)
        per_user.append({"x": x})
    return ArraysDataset([f"u{i}" for i in range(users)], per_user)


def test_crop_is_pow2_and_keeps_tokens():
    ds = _varlen_dataset()
    batch = pack_round_batches(ds, [0, 1, 2], 4, 2,
                               rng=np.random.default_rng(0))
    before = int((batch.arrays["x"] != 0).sum())
    stats = seq_length_bucket([batch], ("x", "y"))
    assert stats is not None
    assert batch.arrays["x"].shape[-1] == 16  # max real len 11 -> bucket 16
    assert stats["bucket"] == 16 and stats["full_len"] == 64
    assert int((batch.arrays["x"] != 0).sum()) == before
    assert stats["tokens_grid_after"] < stats["tokens_grid_before"]


def test_no_crop_when_grid_is_full():
    ds = _varlen_dataset(L=16, real_max=16)
    batch = pack_round_batches(ds, [0, 1], 4, 2,
                               rng=np.random.default_rng(0))
    stats = seq_length_bucket([batch], ("x",))
    assert batch.arrays["x"].shape[-1] == 16


def test_chunk_shares_one_bucket():
    ds = _varlen_dataset()
    batches = [pack_round_batches(ds, [0, 1], 4, 2,
                                  rng=np.random.default_rng(s))
               for s in range(3)]
    seq_length_bucket(batches, ("x",))
    Ls = {b.arrays["x"].shape[-1] for b in batches}
    assert len(Ls) == 1


def test_client_update_identical_after_crop():
    """Pseudo-gradient and train loss are bit-identical between the full-L
    grid and the cropped grid (the whole point: only no-op FLOPs removed)."""
    import jax

    from msrflute_tpu.engine.client_update import (ClientHParams,
                                                   build_client_update)

    ds = _varlen_dataset(users=2, rows=6, L=32, real_max=9, vocab=30)
    task = make_task(_mc())
    params = task.init_params(jax.random.PRNGKey(0))

    from msrflute_tpu.config import OptimizerConfig
    upd = build_client_update(task,
                              OptimizerConfig.from_dict({"type": "sgd",
                                                         "lr": 0.5}),
                              ClientHParams())
    out = {}
    for tag, crop in (("full", False), ("crop", True)):
        batch = pack_round_batches(ds, [0, 1], 3, 2,
                                   rng=np.random.default_rng(0))
        if crop:
            stats = seq_length_bucket([batch], task.seq_pad_keys)
            assert stats["bucket"] == 16
        pg, tl, ns, _ = upd(params,
                            {"x": batch.arrays["x"][0]},
                            batch.sample_mask[0],
                            np.float32(0.5), jax.random.PRNGKey(1))
        out[tag] = (jax.device_get(pg), float(tl), float(ns))

    assert out["full"][1] == pytest.approx(out["crop"][1], abs=1e-6)
    assert out["full"][2] == out["crop"][2]
    for a, b in zip(jax.tree.leaves(out["full"][0]),
                    jax.tree.leaves(out["crop"][0])):
        np.testing.assert_allclose(a, b, atol=1e-6)


def _mc():
    from msrflute_tpu.config import ModelConfig
    return ModelConfig(model_type="LSTM",
                       extra={"vocab_size": 30, "seq_len": 32})


@pytest.mark.slow
def test_e2e_server_buckets(tmp_path):
    """Through OptimizationServer: a varlen LSTM round trains with
    length_bucketing on and off to the same val loss."""
    import jax

    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.parallel import make_mesh

    ds = _varlen_dataset(users=8, rows=6, L=32, real_max=9, vocab=30)
    finals = {}
    for onoff in (True, False):
        cfg = FLUTEConfig.from_dict({
            "model_config": {"model_type": "LSTM", "vocab_size": 30,
                             "seq_len": 32},
            "server_config": {
                "max_iteration": 2, "num_clients_per_iteration": 4,
                "initial_lr_client": 0.5, "val_freq": 100,
                "initial_val": False,
                "optimizer_config": {"type": "sgd", "lr": 1.0},
                "data_config": {"val": {"batch_size": 8}},
            },
            "client_config": {
                "optimizer_config": {"type": "sgd", "lr": 0.5},
                "data_config": {"train": {"batch_size": 3,
                                          "length_bucketing": onoff}},
            },
        })
        task = make_task(cfg.model_config)
        server = OptimizationServer(task, cfg, ds, val_dataset=ds,
                                    model_dir=str(tmp_path / str(onoff)),
                                    mesh=make_mesh(), seed=0)
        server.train()
        finals[onoff] = jax.device_get(server.state.params)
        if onoff:
            assert server._length_bucket_stats is not None
            assert server._length_bucket_stats["bucket"] == 16
    for a, b in zip(jax.tree.leaves(finals[True]),
                    jax.tree.leaves(finals[False])):
        np.testing.assert_allclose(a, b, atol=1e-5)
