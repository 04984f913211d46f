import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_local_dp_clip_only():
    from msrflute_tpu.privacy import apply_local_dp
    tree = {"a": jnp.ones((4,)) * 3.0, "b": jnp.ones((2, 2)) * 4.0}
    dp = {"eps": -1.0, "max_grad": 1.0}
    out, w = apply_local_dp(tree, jnp.asarray(5.0), dp, False,
                            jax.random.PRNGKey(0))
    from jax.flatten_util import ravel_pytree
    flat, _ = ravel_pytree(out)
    np.testing.assert_allclose(float(jnp.linalg.norm(flat)), 1.0, rtol=1e-5)
    assert float(w) == 5.0


def test_local_dp_noise_normalizes_and_noises_weight():
    from msrflute_tpu.privacy import apply_local_dp
    tree = {"a": jnp.arange(1, 9, dtype=jnp.float32)}
    dp = {"eps": 10000.0, "delta": 1e-7, "max_grad": 1.0, "max_weight": 10.0,
          "min_weight": 0.0, "weight_scaler": 1.0}
    out, w = apply_local_dp(tree, jnp.asarray(2.0), dp, True,
                            jax.random.PRNGKey(1))
    # high eps => tiny noise: norm ~ max_grad, weight ~ 2
    flat = out["a"]
    assert abs(float(jnp.linalg.norm(flat)) - 1.0) < 0.1
    assert abs(float(w) - 2.0) < 0.5


def test_global_dp_noise_scale():
    from msrflute_tpu.privacy import apply_global_dp
    tree = {"a": jnp.zeros((10000,))}
    dp = {"global_sigma": 1.0, "max_grad": 2.0}
    out = apply_global_dp(tree, dp, jax.random.PRNGKey(0),
                          num_clients=jnp.asarray(10.0))
    std = float(jnp.std(out["a"]))
    np.testing.assert_allclose(std, 2.0 / 10.0, rtol=0.1)


def test_rdp_accountant_sane():
    from msrflute_tpu.privacy.accountant import compute_rdp, get_privacy_spent
    orders = list(range(2, 64))
    # classic DP-SGD setting: q=0.01, sigma=1.1, T=1000
    rdp = compute_rdp(0.01, 1.1, 1000, orders)
    eps, order = get_privacy_spent(orders, rdp, 1e-5)
    # known ballpark from TF-privacy for these parameters: eps ~ 1-1.2
    assert 0.5 < eps < 2.5, eps
    # monotone in T
    rdp2 = compute_rdp(0.01, 1.1, 2000, orders)
    eps2, _ = get_privacy_spent(orders, rdp2, 1e-5)
    assert eps2 > eps
    # q=1 reduces to plain Gaussian mechanism
    rdp_full = compute_rdp(1.0, 2.0, 1, [2])
    np.testing.assert_allclose(rdp_full[0], 2 / (2 * 4.0))


def test_quantization_levels_and_sparsity():
    from msrflute_tpu.ops import quantize_array, quantize_pytree
    g = jnp.asarray(np.random.default_rng(0).normal(size=(1000,)), jnp.float32)
    q = quantize_array(g, n_bins=16, quant_threshold=0.5)
    # at most 16 distinct non-zero levels
    uniq = np.unique(np.asarray(q))
    assert len(uniq) <= 17
    # ~half the components zeroed
    frac_zero = float((q == 0).mean())
    assert 0.4 < frac_zero < 0.6
    # pytree version preserves structure
    tree = {"w": g.reshape(10, 100), "b": g[:10]}
    qt = quantize_pytree(tree, quant_threshold=0.5, quant_bits=4)
    assert qt["w"].shape == (10, 100)
    # None threshold = no-op (reference quant.py:30-31)
    same = quantize_pytree(tree, quant_threshold=None)
    assert same is tree


def test_dp_end_to_end_round(synth_dataset, mesh8, tmp_path):
    """Local DP + global DP flow through a full DGA round."""
    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task
    cfg = FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 4, "input_dim": 8},
        "strategy": "dga",
        "dp_config": {"enable_local_dp": True, "enable_global_dp": True,
                      "eps": 1000.0, "delta": 1e-7, "max_grad": 1.0,
                      "max_weight": 10.0, "min_weight": 0.0,
                      "weight_scaler": 1.0, "global_sigma": 0.1},
        "server_config": {
            "max_iteration": 2, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.1, "aggregate_median": "softmax",
            "softmax_beta": 1.0, "weight_train_loss": "train_loss",
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 100, "initial_val": False,
            "data_config": {"val": {"batch_size": 8}},
        },
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.1},
            "data_config": {"train": {"batch_size": 4}},
        },
    })
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, synth_dataset,
                                model_dir=str(tmp_path), mesh=mesh8)
    state = server.train()
    assert state.round == 2
    # accountant runs host-side
    from msrflute_tpu.privacy import update_privacy_accountant
    eps = update_privacy_accountant(cfg, num_clients=len(synth_dataset),
                                    curr_iter=1, num_clients_curr_iter=4)
    assert eps is not None and eps > 0


def test_dp_kmeans_clusters_separated_data():
    from msrflute_tpu.privacy.dp_kmeans import (
        dp_kmeans, sphere_packing_initialization)
    rng = np.random.default_rng(0)
    # three well-separated blobs on the unit sphere scale
    blobs = [rng.normal(loc=c, scale=0.03, size=(40, 2))
             for c in ([0.6, 0.0], [-0.5, 0.4], [0.0, -0.7])]
    x = np.concatenate(blobs)
    centers, labels, n_iter = dp_kmeans(
        x, n_clusters=3, eps=50.0, max_cluster_l2=1.0, max_iter=20, seed=1)
    assert centers.shape == (3, 2)
    assert n_iter <= 20
    # high-eps DP: blob members mostly agree on a label
    for i in range(3):
        blk = labels[i * 40:(i + 1) * 40]
        counts = np.bincount(blk, minlength=3)
        assert counts.max() >= 30
    # packing invariant: pairwise center distance >= 2a at returned radius
    packed, a = sphere_packing_initialization(4, 3, 0.2, 1.0,
                                              rng=np.random.default_rng(2))
    d = np.linalg.norm(packed[:, None] - packed[None], axis=-1)
    d[np.arange(4), np.arange(4)] = np.inf
    assert d.min() >= 2 * a - 1e-9


def test_privacy_extras():
    """The reference's 'unused extras' mechanisms (extensions/privacy
    __init__.py:51-102) exist and behave sanely."""
    from msrflute_tpu.privacy import (
        add_private_unit2_noise, laplace_noise, privacy_parameters,
        scalar_dp)
    rng = np.random.default_rng(0)
    g = rng.normal(size=32)
    g /= np.linalg.norm(g)
    out = add_private_unit2_noise(8.0, g, rng=rng)
    assert out.shape == g.shape and np.isfinite(out).all()
    # scalar mechanism is approximately unbiased for high eps
    vals = [scalar_dp(0.7, 50.0, 16, 1.0, rng=np.random.default_rng(i))
            for i in range(300)]
    assert abs(np.mean(vals) - 0.7) < 0.05
    lap = laplace_noise(1.0, 2.0, 1000, rng=rng)
    assert abs(np.mean(np.abs(lap)) - 0.5) < 0.1  # E|Lap(b)| = b
    p0, gamma = privacy_parameters(0.1, 4.0, 64)
    assert 0.5 <= p0 <= 1.0 and 0.0 <= gamma <= 1.0


def test_adaptive_clipping_tracks_quantile(synth_dataset, mesh8, tmp_path):
    """dp_config.adaptive_clipping (Andrew et al., arXiv:1905.03871):
    the in-jit clip state must move toward the target quantile of client
    update norms — starting far above, it must shrink, stay positive, and
    training must still learn."""
    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task

    cfg = FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": "fedavg",
        "dp_config": {"enable_local_dp": True, "eps": -1.0,  # clip-only
                      "max_grad": 10.0,
                      "adaptive_clipping": {"target_quantile": 0.5,
                                            "clip_lr": 0.5,
                                            "initial_clip": 10.0}},
        "server_config": {
            "max_iteration": 12, "num_clients_per_iteration": 8,
            "initial_lr_client": 0.3, "rounds_per_step": 4,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": 12, "initial_val": False,
            "best_model_criterion": "acc",
            "data_config": {"val": {"batch_size": 64}},
        },
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.3},
            "data_config": {"train": {"batch_size": 4}},
        },
    })
    task = make_task(cfg.model_config)
    server = OptimizationServer(task, cfg, synth_dataset,
                                val_dataset=synth_dataset,
                                model_dir=str(tmp_path), mesh=mesh8, seed=0)
    assert float(server.state.strategy_state["dp_clip"]) == 10.0
    server.train()
    final_clip = float(server.state.strategy_state["dp_clip"])
    # update norms on this problem are ~0.1-1; the clip must have come
    # DOWN from 10 toward the data's scale and stayed sane
    assert 0.0 < final_clip < 10.0
    assert server.best_val["acc"].value > 0.6
