"""The plain references against the system's models on the same weights
(float32, CPU), the required-operations count, the generators, and the
seam through which a model reference brings its own loss."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import datagen, harness  # noqa: E402
from benchmarks.flops import matmul_flops  # noqa: E402

# a benchmark root of its own (new files only) with a token-sequence
# configuration: its model reference is found there, not under benchmarks/
SEQ_ROOT = os.path.join(HERE, "data", "seq_root")
MODELS = {
    "resnet18gn": {"model_type": "RESNET", "depth": 18, "num_classes": 100,
                   "image_size": 32, "channels_per_group": 16},
    "cnn_femnist": {"model_type": "CNN", "num_classes": 62,
                    "image_size": 28, "dropout1": 0.0, "dropout2": 0.0},
    "causal_lm": {"model_type": "RINGLM", "vocab_size": 64, "embed_dim": 128,
                  "num_heads": 4, "head_dim": 32, "mlp_dim": 512,
                  "num_layers": 2, "seq_len": 32},
}
SHAPES = {"resnet18gn": (32, 32, 3), "cnn_femnist": (28, 28, 1)}


def _reference(name):
    if name == "fedround":
        return harness.load_module(os.path.join(
            harness.BENCH_DIR, "reference", "fedround.py"))
    return harness.find_module(SEQ_ROOT, "reference", name)


def _is_sequence(name):
    return name not in SHAPES


def _token_batch(rng, name, rows):
    """``[rows, seq_len]`` ids with padded tails, as the packer hands them."""
    vocab, length = MODELS[name]["vocab_size"], MODELS[name]["seq_len"]
    real = np.arange(length) < rng.integers(length // 2, length + 1,
                                            size=(rows, 1))
    x = np.where(real, rng.integers(1, vocab, size=(rows, length)), 0)
    return x.astype(np.int32), real.astype(np.float32)


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
    if _is_sequence(name):
        x = _token_batch(rng, name, 2)[0][:, :-1]
        want = np.asarray(task.module.apply({"params": weights}, x))
    else:
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
    mask = np.asarray([1.0, 1.0, 0.0], np.float32)
    if _is_sequence(name):
        # no ``y``: the task and the plain next-token loss both shift ``x``
        x, tok_mask = _token_batch(rng, name, 3)
        batch = {"x": x, "tok_mask": tok_mask, "sample_mask": mask}
    else:
        x = rng.standard_normal((3,) + SHAPES[name]).astype(np.float32)
        y = rng.integers(0, MODELS[name]["num_classes"], size=(3,)).astype(
            np.int32)
        batch = {"x": x, "y": y, "sample_mask": mask}
    task = make_task(MODELS[name])
    want = jax.grad(lambda p: task.loss(
        p, batch, jax.random.PRNGKey(0), True)[0])(weights)
    # the loss the reference round takes: the model reference's own where
    # it has one, else classification
    loss, _ = fedround.seam(ref.forward, getattr(ref, "loss", None))
    assert (getattr(ref, "loss", None) is not None) == _is_sequence(name)
    got = jax.grad(lambda p: loss(
        p, {k: jnp.asarray(v) for k, v in batch.items()},
        MODELS[name]))(weights)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = max(float(np.max(np.abs(b))), 1e-6)
        assert float(np.max(np.abs(np.asarray(a) - np.asarray(b)))) < \
            2e-4 * scale


def test_token_generator_is_a_function_of_the_seed(tmp_path):
    """``generators/tokens.py``: the same seed gives the same rows; every
    seed gives the same users, rows, lengths and real tokens (the same
    work) in ids of ``[1, vocab)`` that follow the seeded chain."""
    import h5py
    tokens = harness.load_generator(SEQ_ROOT, {"generator": "tokens"})
    assert harness.load_generator(SEQ_ROOT, {}) is datagen
    spec = {"vocab": 50, "len_min": 5, "len_max": 16, "noise": 0.25,
            "samples_per_user": 7, "train_users": 4, "val_users": 1,
            "test_users": 1}
    big = 2 ** 31 + 7
    for sub, seed in (("a", big), ("b", big), ("c", big + 1)):
        tokens.write_splits(str(tmp_path / sub), seed, spec)

    def rows(sub):
        with h5py.File(tmp_path / sub / "train.hdf5", "r") as fh:
            assert len(fh["users"]) == 4
            assert list(fh["num_samples"][()]) == [7] * 4
            assert set(fh["user_data"]["u00000"]) == {"x"}
            return np.stack([fh["user_data"][f"u{u:05d}"]["x"][()]
                             for u in range(4)])

    a, b, c = rows("a"), rows("b"), rows("c")
    assert a.shape == (4, 7, 16) and a.dtype == np.int32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    for got in (a, c):
        real = got >= 0
        assert np.all(got[real] >= 1) and np.all(got[real] < 50)
        assert np.all(got[~real] == -1)
        lengths = real.sum(axis=-1)
        # padding is a tail; every user holds the same lengths
        assert np.array_equal(real, np.arange(16) < lengths[..., None])
        assert all(sorted(user) == sorted(lengths[0]) for user in lengths)
        assert lengths.min() == 5 and lengths.max() == 16
    assert (a >= 0).sum() == (c >= 0).sum()
    # a first-order chain: most real successors are the one the seed's
    # permutation names (1 - noise, and 1/49 of the noise by chance)
    prev, nxt = a[..., :-1][a[..., 1:] >= 0], a[..., 1:][a[..., 1:] >= 0]
    pairs = {}
    for p, n in zip(prev, nxt):
        pairs.setdefault(int(p), []).append(int(n))
    top = sum(max(np.bincount(v)) for v in pairs.values())
    assert 0.6 < top / len(nxt) < 0.95


def test_image_round_is_bit_identical_through_an_explicit_loss():
    """The reference round on the image path: the default (no loss given)
    and the classification loss handed in through the seam give the same
    bits, and so does a sample count of the real rows."""
    import jax.numpy as jnp
    ref, fedround = _reference("cnn_femnist"), _reference("fedround")
    cfg = MODELS["cnn_femnist"]
    rng = np.random.default_rng(3)
    weights = ref.init(rng, cfg)
    clients, steps, rows = 3, 2, 4
    mask = np.ones((clients, steps, rows), np.float32)
    mask[1, 1, 2:] = 0.0
    mask[2, 1] = 0.0  # an all-padding step is skipped
    batch = {
        "x": rng.standard_normal((clients, steps, rows, 28, 28, 1)).astype(
            np.float32),
        "y": rng.integers(0, 62, size=(clients, steps, rows)),
        "sample_mask": mask, "client_mask": np.ones(clients, np.float32)}

    def classification(params, step, model_config):
        return fedround.xent(ref.forward(params, step["x"], model_config),
                             step["y"], step["sample_mask"])

    def real_rows(step):
        return jnp.sum(step["sample_mask"])

    rounds = [{**batch, "client_lr": 0.1, "server_lr": 1.0,
               "quant_quantile": 0.5}]
    for strategy in ({"name": "fedavg"},
                     {"name": "dga", "beta": 1.0, "quant_bits": 8}):
        args = (ref.forward, cfg, weights, rounds, strategy, 2, None)
        default, = fedround.run_rounds(*args)
        explicit, = fedround.run_rounds(*args, loss=classification,
                                        sample_count=real_rows)
        assert list(default["num_samples"]) == [8.0, 6.0, 4.0]
        import jax
        # a one-round dispatch: its aggregate and its new weights both
        assert set(default) == {"aggregate", "new_params", "train_loss",
                                "num_samples", "weight", "pseudo_norm",
                                "seconds"}
        default.pop("seconds"), explicit.pop("seconds")
        for a, b in zip(jax.tree.leaves(default), jax.tree.leaves(explicit)):
            assert np.array_equal(a, b)


def test_rounds_chained_on_the_device_give_the_bits_of_rounds_through_the_host():
    """A dispatch of three rounds with the weights left on the device
    between rounds: every number and the two trees that are fetched
    (round 0's aggregate, the last round's weights) are, bit for bit,
    those of three one-round calls chained through the host, and the
    rounds between carry no tree."""
    import jax
    ref, fedround = _reference("cnn_femnist"), _reference("fedround")
    cfg = MODELS["cnn_femnist"]
    rng = np.random.default_rng(4)
    weights = ref.init(rng, cfg)
    clients, steps, rows = 3, 2, 4
    rounds = [{
        "x": rng.standard_normal((clients, steps, rows, 28, 28, 1)).astype(
            np.float32),
        "y": rng.integers(0, 62, size=(clients, steps, rows)),
        "sample_mask": np.ones((clients, steps, rows), np.float32),
        "client_mask": np.ones(clients, np.float32),
        "client_lr": 0.1, "server_lr": lr, "quant_quantile": 0.5}
        for lr in (1.0, 0.9, 0.8)]
    strategy = {"name": "dga", "beta": 1.0, "quant_bits": 8}
    args = (ref.forward, cfg)
    together = fedround.run_rounds(*args, weights, rounds, strategy, 2, None)
    apart, params = [], weights
    for one in rounds:
        apart += fedround.run_rounds(*args, params, [one], strategy, 2, None)
        params = apart[-1]["new_params"]
    trees = {"aggregate", "new_params"}
    assert [trees & set(r) for r in together] == [
        {"aggregate"}, set(), {"new_params"}]
    for a, b in zip(together, apart):
        for key in set(a) - {"seconds"}:
            for x, y in zip(jax.tree.leaves(a[key]), jax.tree.leaves(b[key])):
                assert np.array_equal(x, y), key
        assert set(a["seconds"]) == {"upload", "clients", "server", "fetch"}


def test_train_mfu_reads_a_model_references_own_operation_count():
    """``required_flops`` of a model reference, where present, is what
    ``train_mfu`` divides by (per live step); absent, the dots of the
    step's loss as written."""
    import types
    fedround = _reference("fedround")
    plain = _reference("causal_lm")
    cfg = MODELS["causal_lm"]
    weights = plain.init(np.random.default_rng(0), cfg)
    x, tok_mask = _token_batch(np.random.default_rng(1), "causal_lm", 8)
    mask = np.ones((2, 2, 2), np.float32)
    mask[1, 1] = 0.0  # three live steps of four
    first = {"x": x.reshape(2, 2, 2, -1),
             "tok_mask": tok_mask.reshape(2, 2, 2, -1), "sample_mask": mask,
             "client_mask": np.ones(2, np.float32), "client_lr": 0.1,
             "server_lr": 1.0, "quant_quantile": None}
    seen = {}

    def required_flops(params, batch, model_config):
        seen.update(batch=batch, params=params, config=model_config)
        return 7.0e9

    sparse = types.SimpleNamespace(forward=plain.forward, loss=plain.loss,
                                   required_flops=required_flops)
    ctx = {
        "first_inputs": first, "fedround": fedround, "weights": weights,
        "config": {"model_config": cfg,
                   "server_config": {"rounds_per_step": 1}},
        "trace": {"module_seconds": {"jit_staged": 1.0},
                  "module_counts": {"jit_staged": 4}, "chips": 1,
                  "window_s": 2.0},
        "device": {"kind": "toy"}, "peaks": {"toy": {"flops_per_s": 1e12}}}
    train_mfu = harness.load_layer_metrics(harness.BENCH_DIR)["train_mfu"]
    from benchmarks import readers
    assert readers.required_flops_per_round(
        {**ctx, "model": sparse}) == 3 * 7.0e9
    assert set(seen["batch"]) == {"x", "tok_mask", "sample_mask"}
    assert seen["batch"]["x"].shape == (2, 32) and seen["config"] is cfg
    # 4 rounds x 21 GFLOP / (2 s x 1 chip x 1 TFLOP/s)
    assert train_mfu.read({**ctx, "model": sparse}) == pytest.approx(4.2)
    # without it: the loss's own dots (forward + backward of the matmuls
    # on [2, 31] tokens), the same through the seam's count
    written = readers.required_flops_per_round({**ctx, "model": plain})
    per_step = fedround.flops_per_step(
        plain.forward, cfg, weights,
        {k: v[0, 0] for k, v in fedround.step_arrays(first).items()},
        matmul_flops, plain.loss)
    assert written == 3 * per_step
    tokens, embed, mlp, heads = 2 * 31, 128, 512, 4 * 32
    forward = 2 * tokens * 2 * (embed * 3 * heads + heads * embed +
                                2 * embed * mlp) + 2 * tokens * embed * 64
    assert 2.5 * forward < per_step < 3.5 * forward
