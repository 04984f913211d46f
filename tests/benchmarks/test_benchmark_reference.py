"""The plain references against the system's models on the same weights
(float32, CPU), the required-operations count, and the generator."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import datagen, harness  # noqa: E402
from benchmarks.flops import matmul_flops  # noqa: E402

MODELS = {
    "resnet18gn": {"model_type": "RESNET", "depth": 18, "num_classes": 100,
                   "image_size": 32, "channels_per_group": 16},
    "cnn_femnist": {"model_type": "CNN", "num_classes": 62,
                    "image_size": 28, "dropout1": 0.0, "dropout2": 0.0},
}
SHAPES = {"resnet18gn": (32, 32, 3), "cnn_femnist": (28, 28, 1)}


def _reference(name):
    return harness.load_module(os.path.join(
        harness.BENCH_DIR, "reference", f"{name}.py"))


@pytest.mark.parametrize("name", sorted(MODELS))
def test_reference_forward_is_the_systems_forward(name):
    import jax

    from msrflute_tpu.models import make_task
    ref = _reference(name)
    rng = np.random.default_rng(7)
    weights = ref.init(rng, MODELS[name])
    # the zero-initialised norm scales would hide the second half of a block
    weights = jax.tree.map(
        lambda w: w + 0.1 * rng.standard_normal(w.shape).astype(w.dtype),
        weights)
    task = make_task(MODELS[name])
    theirs = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
    assert jax.tree.structure(theirs) == jax.tree.structure(weights)
    assert [l.shape for l in jax.tree.leaves(theirs)] == \
        [l.shape for l in jax.tree.leaves(weights)]
    x = rng.standard_normal((2,) + SHAPES[name]).astype(np.float32)
    want = np.asarray(task.apply(weights, x))
    got = np.asarray(ref.forward(weights, x, MODELS[name]))
    assert np.max(np.abs(got - want)) < 1e-4 * max(np.max(np.abs(want)), 1)


def test_required_operations_of_a_dense_layer_and_a_conv():
    import jax.numpy as jnp
    a, b = jnp.zeros((4, 8)), jnp.zeros((8, 16))
    assert matmul_flops(lambda x, y: x @ y, a, b) == 2 * 4 * 8 * 16
    ref = _reference("cnn_femnist")
    cfg = MODELS["cnn_femnist"]
    weights = ref.init(np.random.default_rng(0), cfg)
    x = jnp.zeros((1, 28, 28, 1))
    forward = matmul_flops(lambda w, v: ref.forward(w, v, cfg), weights, x)
    want = 2 * (26 * 26 * 32 * 9 + 24 * 24 * 64 * 9 * 32 +
                9216 * 128 + 128 * 62)
    assert forward == want


def test_generator_is_a_function_of_the_seed(tmp_path):
    import h5py
    spec = {"shape": [4, 4, 1], "classes": 3, "samples_per_user": 5,
            "scale": 1.0, "noise": 0.5, "train_users": 3, "val_users": 1,
            "test_users": 1}
    big = 2 ** 31 + 7
    for sub in ("a", "b"):
        datagen.write_splits(str(tmp_path / sub), big, spec)
    datagen.write_splits(str(tmp_path / "c"), big + 1, spec)

    def first(sub):
        with h5py.File(tmp_path / sub / "train.hdf5", "r") as fh:
            assert len(fh["users"]) == 3
            assert list(fh["num_samples"][()]) == [5, 5, 5]
            return fh["user_data"]["u00000"]["x"][()]

    assert first("a").shape == (5, 4, 4, 1)
    assert np.array_equal(first("a"), first("b"))
    assert not np.array_equal(first("a"), first("c"))


@pytest.mark.parametrize("name", sorted(MODELS))
def test_reference_gradient_is_the_systems_gradient(name):
    import jax
    import jax.numpy as jnp

    from msrflute_tpu.models import make_task
    ref = _reference(name)
    fedround = _reference("fedround")
    rng = np.random.default_rng(11)
    weights = jax.tree.map(
        lambda w: w + 0.1 * rng.standard_normal(w.shape).astype(w.dtype),
        ref.init(rng, MODELS[name]))
    x = rng.standard_normal((3,) + SHAPES[name]).astype(np.float32)
    y = rng.integers(0, MODELS[name]["num_classes"], size=(3,)).astype(
        np.int32)
    mask = np.asarray([1.0, 1.0, 0.0], np.float32)
    task = make_task(MODELS[name])
    want = jax.grad(lambda p: task.loss(
        p, {"x": x, "y": y, "sample_mask": mask},
        jax.random.PRNGKey(0), True)[0])(weights)
    got = jax.grad(lambda p: fedround.xent(
        ref.forward(p, jnp.asarray(x), MODELS[name]), jnp.asarray(y),
        jnp.asarray(mask)))(weights)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = max(float(np.max(np.abs(b))), 1e-6)
        assert float(np.max(np.abs(np.asarray(a) - np.asarray(b)))) < \
            2e-4 * scale
