"""The quantiser's threshold as an exact rank selection
(``ops.quantization.abs_order_stats`` / ``quantile_abs``): the same two
order statistics a sort would give, bit for bit, the same payload as with
``jnp.quantile``, and no sort in the lowered program."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from msrflute_tpu.ops.quantization import (abs_order_stats, bin_sparsify,
                                           quantile_abs, quantile_ranks,
                                           quantize_array, quantize_pytree)

SIZES = [1, 2, 3, 1000, 4097, 1_179_648]
QS = [0.0, 0.25, 0.5, 0.9, 1.0]
KINDS = ["normal", "all_equal", "all_zero", "half_zeros", "neg_zero",
         "subnormal", "one_inf"]
#: the selection's named scope in a lowered program's debug text, bare or
#: as ``vmap(quant_select)`` (this file's own name is in there too)
SCOPE = re.compile(r"[/(]quant_select[)/]")
#: the CNN_FEMNIST leaves the benchmark's cell quantises
CNN_LEAVES = {"conv1": (3, 3, 1, 32), "conv1_b": (32,),
              "conv2": (3, 3, 32, 64), "conv2_b": (64,),
              "dense1": (9216, 128), "dense1_b": (128,),
              "dense2": (128, 62), "dense2_b": (62,)}


@functools.lru_cache(maxsize=None)
def _case(n: int, kind: str):
    """Input and its sorted magnitudes (the reference), once per case.
    The reference is ``jnp.sort(jnp.abs(x))`` except among subnormals,
    which XLA's CPU sort compares as zeros and so leaves in input order:
    there numpy's sort, which orders them, is what "sorted" means."""
    rng = np.random.default_rng(n * 31 + KINDS.index(kind))
    x = rng.normal(size=n).astype(np.float32) * 0.01
    if kind == "all_equal":
        x[:] = -0.37
    elif kind == "all_zero":
        x[:] = 0.0
    elif kind == "half_zeros":        # ties across the rank at q = 0.25, 0.5
        x[rng.permutation(n)[:(n + 1) // 2]] = 0.0
    elif kind == "neg_zero":
        x[rng.permutation(n)[:(n + 1) // 2]] = -0.0
    elif kind == "subnormal":         # patterns below the smallest normal
        x = (rng.integers(0, 2 ** 23, size=n, dtype=np.int32)
             | (rng.integers(0, 2, size=n, dtype=np.int32) << 31)
             ).view(np.float32)
    elif kind == "one_inf":
        x[rng.integers(n)] = -np.inf
    ordered = np.sort(np.abs(x))
    if kind != "subnormal":
        np.testing.assert_array_equal(
            np.asarray(jnp.sort(jnp.abs(jnp.asarray(x)))), ordered)
    return jnp.asarray(x), ordered


@jax.jit
def _select(x, q):
    """``q`` arrives traced, as the annealed threshold does in the round."""
    low, high, _, _ = quantile_ranks(x.size, q)
    return (low, high) + abs_order_stats(x, low, high)


_threshold = jax.jit(quantile_abs)


def _bits(a):
    return np.asarray(a, np.float32).view(np.int32)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("q", QS)
@pytest.mark.parametrize("n", SIZES)
def test_order_stats_equal_the_sort(n, q, kind):
    x, ordered = _case(n, kind)
    low, high, low_value, high_value, has_nan = _select(x, jnp.float32(q))
    # the ranks, as jax's _quantile has them (float32 arithmetic)
    pos = np.float32(q) * (np.float32(n) - np.float32(1))
    assert int(low) == int(np.clip(np.floor(pos), 0, n - 1))
    assert int(high) == int(np.clip(np.ceil(pos), 0, n - 1))
    assert _bits(low_value) == _bits(ordered[int(low)])
    assert _bits(high_value) == _bits(ordered[int(high)])
    assert not bool(has_nan)


@pytest.mark.parametrize("q", [0.0, 0.5, 1.0])
@pytest.mark.parametrize("n", [2 ** 24 + 2, 2 ** 24 + 3, 2 ** 25 + 5,
                               2 ** 30 + 65])
def test_ranks_stay_inside_a_leaf_above_2_to_the_24(n, q):
    """float32 ``n - 1`` rounds up to ``n`` there (2**24 + 3 at q = 1);
    the ranks alone, no array of that size."""
    low, high, low_weight, high_weight = jax.jit(
        lambda qq: quantile_ranks(n, qq))(jnp.float32(q))
    assert 0 <= int(low) <= int(high) <= n - 1
    assert int(high) - int(low) <= 1
    assert float(low_weight) + float(high_weight) == 1.0
    if q == 1.0:
        assert int(high) == min(
            int(np.float32(n) - np.float32(1)), n - 1)


@pytest.mark.parametrize("kind", ["normal", "half_zeros", "one_inf"])
@pytest.mark.parametrize("q", QS)
@pytest.mark.parametrize("n", SIZES)
def test_threshold_equals_jnp_quantile(n, q, kind):
    """Exact where the blend has one term or two halves; elsewhere the
    two products may contract differently: one ulp."""
    x, _ = _case(n, kind)
    got = np.asarray(_threshold(x, jnp.float32(q)))
    want = np.asarray(jnp.quantile(jnp.abs(x), q))
    if q in (0.0, 0.5, 1.0) or not np.isfinite(want):
        np.testing.assert_array_equal(got, want)
    else:
        assert abs(float(got) - float(want)) <= np.spacing(np.abs(want))


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("n", [1, 3, 1000, 4097])
def test_nan_gives_nan(n, where):
    x, _ = _case(n, "normal")
    at = {"first": 0, "middle": n // 2, "last": n - 1}[where]
    x = x.at[at].set(jnp.nan)
    for q in QS:
        assert np.isnan(_threshold(x, jnp.float32(q)))
        assert np.isnan(jnp.quantile(jnp.abs(x), q))
    assert bool(_select(x, jnp.float32(0.5))[4])


@pytest.mark.parametrize("q", QS)
def test_vmap_rows_each_their_own_rank_holder(q):
    """Five rows whose rank-holders sit at different places and scales:
    the batched loop carries one prefix a row."""
    rng = np.random.default_rng(5)
    base = rng.normal(size=1000).astype(np.float32)
    rows = np.stack([np.roll(base, 137 * r) * 10.0 ** (r - 2)
                     for r in range(5)])
    rows[3, :600] = 0.0
    x = jnp.asarray(rows)
    low, high, low_value, high_value, has_nan = jax.vmap(
        _select, (0, None))(x, jnp.float32(q))
    ordered = np.sort(np.abs(rows), axis=1)
    for r in range(5):
        assert _bits(low_value[r]) == _bits(ordered[r, int(low[r])])
        assert _bits(high_value[r]) == _bits(ordered[r, int(high[r])])
    assert len({int(v) for v in _bits(low_value)}) == 5
    assert not np.any(np.asarray(has_nan))


def _old_threshold_payload(g, q, n_bins):
    """What ``quantize_array`` gave while it sorted."""
    return bin_sparsify(g, jnp.min(g), jnp.max(g),
                        jnp.quantile(jnp.abs(g), q), n_bins)


def _cnn_tree(clients=4):
    rng = np.random.default_rng(11)
    return {k: jnp.asarray(rng.normal(size=(clients,) + s) * 0.01,
                           jnp.float32) for k, s in CNN_LEAVES.items()}


@pytest.mark.parametrize("q", [0.0, 0.5, 1.0])
@pytest.mark.parametrize("leaf", list(CNN_LEAVES))
def test_quantize_array_same_payload(leaf, q):
    g = _cnn_tree()[leaf]
    got = jax.jit(jax.vmap(lambda r, qq: quantize_array(r, 256, qq),
                           (0, None)))(g, jnp.float32(q))
    want = jax.jit(jax.vmap(lambda r, qq: _old_threshold_payload(
        r, qq, 256), (0, None)))(g, jnp.float32(q))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("global_stats,dtype,traced_q", [
    (False, jnp.float32, True), (True, jnp.float32, True),
    (True, jnp.bfloat16, True), (True, jnp.bfloat16, False)],
    ids=["per_leaf", "global_stats", "global_stats_bf16",
         "global_stats_bf16_static_q"])
def test_quantize_pytree_same_payload(global_stats, dtype, traced_q):
    """Values and types: with one threshold for the tree, a static
    quantile of bfloat16 leaves is a bfloat16, a traced one a float32,
    as ``jnp.quantile`` promotes them."""
    from jax.flatten_util import ravel_pytree
    tree = jax.tree.map(lambda g: g.astype(dtype), _cnn_tree())

    def old(t, q):
        if not global_stats:
            return jax.tree.map(
                lambda g: _old_threshold_payload(g, q, 256), t)
        flat, unravel = ravel_pytree(t)
        return unravel(_old_threshold_payload(flat, q, 256))

    def new(t, q):
        return quantize_pytree(t, q, quant_bits=8,
                               global_stats=global_stats)
    if traced_q:
        got = jax.jit(jax.vmap(new, (0, None)))(tree, jnp.float32(0.5))
        want = jax.jit(jax.vmap(old, (0, None)))(tree, jnp.float32(0.5))
    else:
        got = jax.jit(jax.vmap(lambda t: new(t, 0.5)))(tree)
        want = jax.jit(jax.vmap(lambda t: old(t, 0.5)))(tree)
    for k in tree:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(np.asarray(got[k], np.float32),
                                      np.asarray(want[k], np.float32),
                                      err_msg=k)


def test_no_sort_in_the_quantiser_and_a_fixed_trip_count():
    """The exact path lowers to one counted loop and no sort, under the
    round's client ``vmap`` and a traced threshold."""
    fn = jax.jit(jax.vmap(lambda g, q: quantize_array(g, 256, q),
                          (0, None)))
    lowered = fn.lower(jax.ShapeDtypeStruct((4, 96, 128), jnp.float32),
                       jax.ShapeDtypeStruct((), jnp.float32))
    text = lowered.as_text(debug_info=True)
    assert "stablehlo.sort" not in text
    assert SCOPE.search(text)              # the name a trace finds it by
    hlo = lowered.compile().as_text()
    assert not re.search(r"\bsort\(", hlo)
    trips = re.findall(r'"known_trip_count":\{"n":"(\d+)"\}', hlo)
    assert trips == ["31"], trips


def test_round_without_quantisation_holds_no_quantiser_operation(
        synth_dataset, mesh8):
    """FedAvg (the ResNet cell's strategy) never reaches the quantiser:
    its round program holds no sort and names nothing of the selection."""
    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.data import pack_round_batches
    from msrflute_tpu.engine.round import RoundEngine
    from msrflute_tpu.models import make_task
    from msrflute_tpu.strategies import select_strategy

    cfg = FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": 1, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.2,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "data_config": {}},
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.2},
            "data_config": {"train": {"batch_size": 4}}}})
    engine = RoundEngine(make_task(cfg.model_config), cfg,
                         select_strategy("fedavg")(cfg, None), mesh8)
    texts = []
    instrument = engine._instrument

    def spy(name, jitted, **kwargs):
        fn = instrument(name, jitted, **kwargs)

        def call(*args):
            texts.append(jitted.lower(*args).as_text(debug_info=True))
            return fn(*args)
        return call
    engine._instrument = spy
    batch = pack_round_batches(synth_dataset, [0, 1, 2, 3], 4, 3,
                               rng=np.random.default_rng(0),
                               pad_clients_to=8)
    engine.run_round(engine.init_state(jax.random.PRNGKey(0)), batch,
                     0.2, 1.0, jax.random.PRNGKey(1))
    assert len(texts) == 1
    assert "stablehlo.sort" not in texts[0]
    assert not SCOPE.search(texts[0])
