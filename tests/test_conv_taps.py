"""``ops/conv.py::live_tap_conv``: a convolution over the kernel taps that
can meet an input — the plain call's outputs and gradients, the plain
call itself where no tap is dead, and ResNet-18+GN's tree untouched;
``ops/conv.py::Conv``, which also takes a kernel already cut to its live
window, and the local-steps loop that then carries the windows alone
(``engine/client_update.py``)."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from msrflute_tpu.config import OptimizerConfig
from msrflute_tpu.engine import client_update as cu
from msrflute_tpu.models import make_task, resnet
from msrflute_tpu.ops import conv as conv_ops
from msrflute_tpu.ops.conv import live_tap_conv, live_taps

NHWC = ("NHWC", "HWIO", "NHWC")
NWC = ("NWC", "WIO", "NWC")

#: id -> (input extent, kernel extent, stride, padding, kernel dilation,
#: dimension numbers, live window or None where nothing is cut)
CASES = {
    "1x1_k3_p1_s1": ((1, 1), (3, 3), (1, 1), [(1, 1), (1, 1)], None, NHWC,
                     [(1, 1), (1, 1)]),
    "2x2_k3_p1_s2": ((2, 2), (3, 3), (2, 2), [(1, 1), (1, 1)], None, NHWC,
                     [(1, 2), (1, 2)]),
    "2x2_k3_p1_s1": ((2, 2), (3, 3), (1, 1), [(1, 1), (1, 1)], None, NHWC,
                     None),
    "32x32_k7_p3_s2": ((32, 32), (7, 7), (2, 2), [(3, 3), (3, 3)], None,
                       NHWC, None),
    "same_1x2_k3": ((1, 2), (3, 3), (1, 1), "SAME", None, NHWC,
                    [(1, 1), (0, 2)]),
    "valid_3x3_k3": ((3, 3), (3, 3), (1, 1), "VALID", None, NHWC, None),
    "dilation2_2x2_k3_p2": ((2, 2), (3, 3), (1, 1), [(2, 2), (2, 2)],
                            (2, 2), NHWC, [(1, 1), (1, 1)]),
    "1d_1_k5_p2": ((1,), (5,), (1,), [(2, 2)], None, NWC, [(2, 2)]),
    "unequal_1x3_k3_p2_0": ((1, 3), (3, 3), (1, 1), [(2, 0), (1, 0)], None,
                            NHWC, [(2, 2), (0, 2)]),
    # a stride that steps over the one pixel: taps 0, 2 and 4 are live,
    # and the window runs from the first live tap to the last
    "1d_1_k5_p4_s2": ((1,), (5,), (2,), [(4, 4)], None, NWC, None),
}


def _operands(case, clients=None, cin=4, cout=6, batch=3):
    size, taps, *_ = CASES[case]
    rng = np.random.default_rng(7)
    lead = () if clients is None else (clients,)
    x = rng.normal(size=lead + (batch,) + size + (cin,))
    w = rng.normal(size=lead + taps + (cin, cout))
    return x.astype(np.float32), w.astype(np.float32)


def _call(fn, case):
    _, _, strides, padding, dilation, dnums, _ = CASES[case]

    def conv(x, w):
        return fn(x, w, strides, padding, rhs_dilation=dilation,
                  dimension_numbers=dnums, precision="highest")
    return conv


def _out_and_grads(conv, x, w):
    def loss(x, w):
        y = conv(x, w)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y
    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
        x, w)
    return (y,) + grads


@pytest.mark.parametrize("per_client", [False, True],
                         ids=["one_kernel", "vmap_kernel_per_client"])
@pytest.mark.parametrize("case", list(CASES))
def test_live_tap_conv_is_the_plain_convolution(case, per_client):
    x, w = _operands(case, clients=2 if per_client else None)
    wrap = jax.vmap if per_client else (lambda f: f)
    got, want = jax.jit(lambda x, w: [
        wrap(lambda x, w: _out_and_grads(_call(fn, case), x, w))(x, w)
        for fn in (live_tap_conv, lax.conv_general_dilated)])(x, w)
    for name, a, b in zip(("out", "d_input", "d_kernel"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6,
                                   err_msg=name)
    window = CASES[case][-1]
    if window is None:
        return
    # a dead tap's gradient is an exact zero, in the plain call too
    dead = np.ones(w.shape[-len(window) - 2:-2], bool)
    dead[tuple(slice(a, b + 1) for a, b in window)] = False
    for d_kernel in (got[2], want[2]):
        assert not np.any(np.asarray(d_kernel)[..., dead, :, :])


@pytest.mark.parametrize("case", list(CASES))
def test_live_window_and_the_untouched_call(case):
    size, taps, strides, padding, dilation, dnums, window = CASES[case]
    x, w = _operands(case)
    jaxpr = jax.make_jaxpr(_call(live_tap_conv, case))(x, w)
    plain = jax.make_jaxpr(_call(lax.conv_general_dilated, case))(x, w)
    if window is None:
        # no dead tap: the plain call, argument for argument
        assert str(jaxpr) == str(plain)
        return
    eqns = jaxpr.jaxpr.eqns
    assert [e.primitive.name for e in eqns] == [
        "slice", "conv_general_dilated"]
    cut = eqns[0].outvars[0].aval.shape[:len(taps)]
    assert cut == tuple(b - a + 1 for a, b in window)
    # the padding shrinks by what was cut, so the output keeps its shape
    assert jaxpr.out_avals == plain.out_avals


def test_an_axis_that_meets_only_padding_is_left_alone():
    x = jnp.ones((1, 1, 2))
    w = jnp.ones((1, 2, 3))
    assert live_taps(1, 1, 1, 1, -1, 1) is None
    assert live_taps(1, 5, 2, 1, 4, 4) == (0, 4)
    assert live_taps(1, 3, 1, 1, 1, 1) == (1, 1)

    def conv(fn):
        return jax.make_jaxpr(lambda x, w: fn(
            x, w, (1,), [(-1, 1)], dimension_numbers=NWC))(x, w)
    assert str(conv(live_tap_conv)) == str(conv(lax.conv_general_dilated))


def test_an_input_dilation_falls_through():
    x = jnp.ones((1, 1, 1, 2))
    w = jnp.ones((3, 3, 2, 2))

    def conv(fn):
        return jax.make_jaxpr(lambda x, w: fn(
            x, w, (1, 1), [(1, 1), (1, 1)], lhs_dilation=(2, 2),
            dimension_numbers=NHWC))(x, w)
    assert str(conv(live_tap_conv)) == str(conv(lax.conv_general_dilated))


# ----------------------------------------------------------------------
# ResNet-18+GN at 32x32: the tree is the parent's, the numbers the plain
# model's, and the dead positions' gradient an exact zero
# ----------------------------------------------------------------------
#: kernel path -> live window of the four kernels cut at 32x32
CUT_AT_32 = {
    ("_BasicBlock_6", "Conv_0"): [(1, 2), (1, 2)],
    ("_BasicBlock_6", "Conv_1"): [(1, 1), (1, 1)],
    ("_BasicBlock_7", "Conv_0"): [(1, 1), (1, 1)],
    ("_BasicBlock_7", "Conv_1"): [(1, 1), (1, 1)],
}


def _loss_and_grads(model, params, x, y):
    def loss(p):
        logits = model.apply(p, x)
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits),
                                     y[:, None], axis=1)
        return -jnp.mean(picked), logits
    (_, logits), grads = jax.jit(
        jax.value_and_grad(loss, has_aux=True))(params)
    return logits, grads


def test_resnet_at_32x32_is_the_plain_model_with_zero_dead_gradients(
        monkeypatch):
    model = resnet._ResNetGN(num_classes=100, channels_per_group=16)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 100, size=(2,))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)
    # weights from numpy: noise in the kernels, ones in the norms' scales
    # and biases (the block-final scales, which start at zero, included:
    # every kernel gets a gradient)
    params = jax.tree.map(
        lambda a: (0.05 * rng.normal(size=a.shape)).astype(np.float32)
        if len(a.shape) > 1 else np.ones(a.shape, np.float32), shapes)
    logits, grads = _loss_and_grads(model, params, x, y)
    monkeypatch.setattr(resnet, "_conv",
                        functools.partial(nn.Conv, use_bias=False))
    plain_params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)
    plain_logits, plain_grads = _loss_and_grads(model, params, x, y)

    assert (jax.tree.map(jnp.shape, params)
            == jax.tree.map(lambda a: a.shape, plain_params))
    assert jax.tree.structure(params) == jax.tree.structure(plain_params)
    assert sum(a.size for a in jax.tree.leaves(params)) == 11_227_812
    np.testing.assert_array_equal(
        params["params"]["_BasicBlock_7"]["Conv_1"]["kernel"].shape,
        (3, 3, 512, 512))

    # equal up to the order of a float32 sum
    np.testing.assert_allclose(
        logits, plain_logits, rtol=0,
        atol=1e-5 * float(jnp.max(jnp.abs(plain_logits))))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(plain_grads)):
        scale = float(jnp.max(jnp.abs(want))) or 1.0
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-5 * scale,
            err_msg=jax.tree_util.keystr(path))

    dead_positions = 0
    for (block, name), window in CUT_AT_32.items():
        dead = np.ones((3, 3), bool)
        dead[tuple(slice(a, b + 1) for a, b in window)] = False
        for tree in (grads, plain_grads):
            g = np.asarray(tree["params"][block][name]["kernel"])
            assert not np.any(g[dead]), (block, name)
            assert np.any(g[~dead]), (block, name)
        dead_positions += int(dead.sum()) * int(np.prod(g.shape[2:]))
    assert dead_positions == 6_946_816


def test_vmapped_gradient_at_layer4_moves_no_whole_kernel():
    """The batching rule's ``rev`` and transposing reshapes run over the
    live window ``[K, 1, 1, 512, 512]``, never over ``[K, 3, 3, 512,
    512]``; what is left over the whole leaf is the zero ``pad`` of the
    live gradient and the client axis moved home behind it (on the chip
    a bitcast and the weight-gradient product's own padding:
    ``tests/test_tpu_compile.py``)."""
    clients, whole = 3, 3 * 3 * 3 * 512 * 512

    def grad(fn):
        def loss(w, x):
            return jnp.sum(fn(x, w, (1, 1), [(1, 1), (1, 1)],
                              dimension_numbers=NHWC) ** 2)
        w = jax.ShapeDtypeStruct((clients, 3, 3, 512, 512), jnp.float32)
        x = jax.ShapeDtypeStruct((clients, 20, 1, 1, 512), jnp.float32)
        eqns = jax.make_jaxpr(jax.vmap(jax.grad(loss, argnums=(0, 1))))(
                w, x).jaxpr.eqns
        return [(e.primitive.name, e.invars[0].aval.shape) for e in eqns
                if int(np.prod(e.invars[0].aval.shape)) >= whole]

    plain = grad(lax.conv_general_dilated)
    assert ("rev", (clients, 3, 3, 512, 512)) in plain, plain
    cut = grad(live_tap_conv)
    assert cut == [("slice", (clients, 3, 3, 512, 512)),
                   ("transpose", (3, 3, 512, clients, 512))], cut


# ----------------------------------------------------------------------
# the ``conv_taps`` event
# ----------------------------------------------------------------------
@pytest.mark.parametrize("side, cut_convs", [(32, 4), (64, 0)])
def test_conv_taps_events_of_a_resnet_trace(side, cut_convs):
    conv_ops.drain_conv_events()
    model = resnet._ResNetGN()
    jax.eval_shape(model.init, jax.random.PRNGKey(0),
                   jnp.ones((2, side, side, 3)))
    said = conv_ops.drain_conv_events()
    assert conv_ops.drain_conv_events() == []
    assert sum(e["convs"] for e in said) == cut_convs
    if not cut_convs:
        assert said == []
        return
    assert all(e["kind"] == "conv_taps" and e["convs_traced"] == 20
               and e["carried_live"] == 0
               and e["weights_carried"] == e["weights_total"] for e in said)
    by_lhs = {tuple(e["lhs_shape"]): e for e in said}
    assert set(by_lhs) == {(2, 2, 2, 256), (2, 1, 1, 512)}
    first, rest = by_lhs[(2, 2, 2, 256)], by_lhs[(2, 1, 1, 512)]
    assert (first["kernel_shape"], first["live_window"], first["convs"],
            first["weights_total"], first["weights_live"]) == (
        [3, 3, 256, 512], [[1, 2], [1, 2]], 1, 1_179_648, 524_288)
    assert (rest["kernel_shape"], rest["live_window"], rest["convs"],
            rest["weights_total"], rest["weights_live"]) == (
        [3, 3, 512, 512], [[1, 1], [1, 1]], 3, 2_359_296, 262_144)
    total = sum(e["convs"] * e["weights_total"] for e in said)
    live = sum(e["convs"] * e["weights_live"] for e in said)
    assert (total, live) == (8_257_536, 1_310_720)


# ----------------------------------------------------------------------
# ``Conv``: the declared kernel or its live window, the same products
# ----------------------------------------------------------------------
#: id -> (input extent, stride, the window of a 3x3 kernel with padding 1)
MODULE_CASES = {
    "1x1": ((1, 1), 1, ((1, 1, 0, 0), (2, 2, 4, 6))),
    "2x2_s2": ((2, 2), 2, ((1, 1, 0, 0), (3, 3, 4, 6))),
    "2x2_no_dead_tap": ((2, 2), 1, None),
}


@pytest.mark.parametrize("case", list(MODULE_CASES))
def test_conv_module_takes_the_whole_kernel_or_its_window(case):
    size, stride, window = MODULE_CASES[case]
    rng = np.random.default_rng(11)
    x = rng.normal(size=(3,) + size + (4,)).astype(np.float32)
    w = rng.normal(size=(3, 3, 4, 6)).astype(np.float32)
    module = conv_ops.Conv(6, (3, 3), strides=(stride, stride), padding=1)
    flax_conv = nn.Conv(6, (3, 3), strides=(stride, stride), padding=1,
                        use_bias=False, precision="highest")

    def apply(kernel):
        with jax.default_matmul_precision("highest"):
            return module.apply({"params": {"kernel": kernel}}, x)

    key = jax.random.PRNGKey(0)
    assert (jax.tree.map(jnp.shape, module.init(key, x))
            == jax.tree.map(jnp.shape, flax_conv.init(key, x)))
    whole = apply(w)
    np.testing.assert_allclose(
        whole, flax_conv.apply({"params": {"kernel": w}}, x),
        rtol=1e-6, atol=1e-6)
    with conv_ops.collecting_windows() as found:
        jax.eval_shape(apply, w)
    if window is None:
        assert found == {}
    else:
        assert found == {("kernel",): window}
        cut = w[tuple(slice(a, b) for a, b in zip(*window))]
        np.testing.assert_array_equal(apply(cut), whole)
    # neither the declared shape nor the window: refused, by name
    with pytest.raises(ValueError, match="neither the declared"):
        apply(w[:2])


# ----------------------------------------------------------------------
# the local-steps loop carries the windows: the whole-leaf carry's
# numbers to the bit, and the whole-leaf carry itself wherever a zero
# gradient is not a coordinate left alone
# ----------------------------------------------------------------------
CLIENTS, STEPS, ROWS = 2, 3, 4
WHOLE_TREE = 11_227_812
DEAD = 6_946_816


def _resnet_task():
    return resnet.make_resnet_task({"num_classes": 100, "image_size": 32})


def _resnet_round(seed=5, side=32):
    rng = np.random.default_rng(seed)
    shapes = jax.eval_shape(_resnet_task().init_params, jax.random.PRNGKey(0))
    # noise in the kernels, ones in the norms (the block-final scales
    # start at zero: no kernel behind them would get a gradient)
    params = jax.tree.map(
        lambda a: (0.05 * rng.normal(size=a.shape)).astype(np.float32)
        if len(a.shape) > 1 else np.ones(a.shape, np.float32), shapes)
    arrays = {
        "x": rng.normal(size=(CLIENTS, STEPS, ROWS, side, side, 3)).astype(
            np.float32),
        "y": rng.integers(0, 100, size=(CLIENTS, STEPS, ROWS)).astype(
            np.int32)}
    mask = np.ones((CLIENTS, STEPS, ROWS), np.float32)
    mask[1, 2] = 0.0  # an all-padding step: pinned to a no-op
    return params, arrays, mask


def _cohort(update, offset=None):
    def run(params, arrays, mask):
        return jax.vmap(
            lambda a, m, r: update(params, a, m, jnp.float32(0.05), r,
                                   offset))(
            arrays, mask, jax.random.split(jax.random.PRNGKey(1), CLIENTS))
    return run


def _whole_leaf(monkeypatch):
    """The carry every leaf whole, as before the windows: no optimizer
    is taken to leave a zero gradient's coordinate alone."""
    monkeypatch.setattr(cu, "zero_grad_is_noop", lambda cfg: False)


@pytest.mark.parametrize("opt, smooth", [
    (dict(type="sgd", lr=0.05), True),
    (dict(type="sgd", lr=0.05), False),
    (dict(type="sgd", lr=0.05, momentum=0.9), True),
    (dict(type="adam", lr=0.001), True)],
    ids=["sgd", "sgd_stats_of_steps", "momentum", "adam"])
def test_windowed_carry_is_the_whole_leaf_carry_to_the_bit(
        opt, smooth, monkeypatch):
    hparams = cu.ClientHParams(stats_on_smooth_grad=smooth, fedprox_mu=0.01)
    params, arrays, mask = _resnet_round()
    conv_ops.drain_conv_events()
    got = jax.jit(_cohort(cu.build_client_update(
        _resnet_task(), OptimizerConfig(**opt), hparams)))(
        params, arrays, mask)
    said = conv_ops.drain_conv_events()
    # the four cut kernels of the forward trace arrived as their windows
    assert sum(e["carried_live"] for e in said) == 4 == sum(
        e["convs"] for e in said)
    assert sum(e["carried_live"] * e["weights_carried"] for e in said) \
        == 8_257_536 - DEAD
    assert all(e["convs_traced"] == 20 for e in said)

    _whole_leaf(monkeypatch)
    want = jax.jit(_cohort(cu.build_client_update(
        _resnet_task(), OptimizerConfig(**opt), hparams)))(
        params, arrays, mask)
    assert sum(e["carried_live"] for e in conv_ops.drain_conv_events()) == 0

    (pg, loss, samples, stats), (pg0, loss0, samples0, stats0) = got, want
    flat, _ = jax.tree_util.tree_flatten_with_path(pg)
    for (path, a), b in zip(flat, jax.tree.leaves(pg0)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))
    np.testing.assert_array_equal(loss, loss0)
    np.testing.assert_array_equal(samples, samples0)
    dead_seen = 0
    for (block, name), window in CUT_AT_32.items():
        dead = np.ones((3, 3), bool)
        dead[tuple(slice(a, b + 1) for a, b in window)] = False
        leaf = np.asarray(pg[block][name]["kernel"])
        assert not np.any(leaf[:, dead]) and np.any(leaf[:, ~dead])
        assert not np.any(np.signbit(leaf[:, dead]))
        dead_seen += int(dead.sum()) * int(np.prod(leaf.shape[3:]))
    assert dead_seen == DEAD
    # a sum's order changes with the shape; the count is the whole tree's
    steps = STEPS if not smooth else 1
    np.testing.assert_array_equal(
        stats["n"], np.float32([steps * WHOLE_TREE,
                                (steps - (not smooth)) * WHOLE_TREE]))
    for name in stats:
        np.testing.assert_allclose(stats[name], stats0[name], rtol=2e-5,
                                   atol=1e-9, err_msg=name)


def _traces_windows(update, *args):
    conv_ops.drain_conv_events()
    jaxpr = jax.make_jaxpr(_cohort(update, *args[3:]))(*args[:3])
    carried = sum(e["carried_live"] for e in conv_ops.drain_conv_events())
    # what the local-steps loop carries of a 512->512 kernel: its centre
    # tap or the whole of it, never both
    (loop,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    shapes = {v.aval.shape for v in loop.outvars}
    assert ((CLIENTS, 1, 1, 512, 512) in shapes) == bool(carried)
    assert ((CLIENTS, 3, 3, 512, 512) in shapes) != bool(carried)
    return carried


FALLBACKS = {
    "weight_decay": (dict(type="sgd", lr=0.05, weight_decay=1e-4), {}),
    "lars": (dict(type="lars", lr=0.05), {}),
    "freeze_layers": (dict(type="sgd", lr=0.05),
                      dict(freeze_layers=("Dense_0",))),
    "updatable_layers": (dict(type="sgd", lr=0.05),
                         dict(updatable_layers=("_BasicBlock_7",))),
    "param_dtype": (dict(type="sgd", lr=0.05),
                    dict(param_dtype="bfloat16")),
    "grad_offset": (dict(type="sgd", lr=0.05), {}),
}


@pytest.mark.parametrize("why", list(FALLBACKS))
def test_whole_leaf_carry_where_a_zero_gradient_is_not_a_no_op(why):
    opt, hparams = FALLBACKS[why]
    params, arrays, mask = _resnet_round()
    update = cu.build_client_update(
        _resnet_task(), OptimizerConfig(**opt), cu.ClientHParams(**hparams))
    offset = (jax.tree.map(np.ones_like, params)
              if why == "grad_offset" else None)
    assert _traces_windows(update, params, arrays, mask, offset) == 0
    if why == "grad_offset":
        # the same builder without the offset carries the windows
        assert _traces_windows(update, params, arrays, mask) == 4


def _token_round(model):
    if model == "LFM2_MOE":
        from test_lfm2_moe import TINY, _weights
    else:
        from test_mla_moe import TINY, _weights
    ids = np.random.default_rng(4).integers(
        1, TINY["vocab_size"], size=(CLIENTS, 2, 1, 17)).astype(np.int32)
    return (make_task(TINY), _weights(), {"x": ids},
            np.ones((CLIENTS, 2, 1), np.float32))


def _cnn_round():
    task = make_task({"model_type": "CNN", "num_classes": 62})
    rng = np.random.default_rng(2)
    return (task, task.init_params(jax.random.PRNGKey(0)),
            {"x": rng.normal(size=(CLIENTS, 2, 3, 28, 28, 1)).astype(
                np.float32),
             "y": rng.integers(0, 62, size=(CLIENTS, 2, 3)).astype(np.int32)},
            np.ones((CLIENTS, 2, 3), np.float32))


def _resnet64_round():
    params, arrays, mask = _resnet_round(side=64)
    arrays = jax.tree.map(lambda a: a[:, :1, :2], arrays)
    return _resnet_task(), params, arrays, mask[:, :1, :2]


@pytest.mark.parametrize("make_round", [
    _cnn_round, lambda: _token_round("LFM2_MOE"),
    lambda: _token_round("MLA_MOE"), _resnet64_round],
    ids=["cnn_femnist", "lfm2_moe", "mla_moe", "resnet_at_64x64"])
def test_a_task_without_windows_traces_to_the_whole_leaf_program(
        make_round, monkeypatch):
    task, params, arrays, mask = make_round()
    assert task.kernel_windows(params, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[2:], a.dtype), arrays)) == {}

    def jaxpr():
        update = cu.build_client_update(
            task, OptimizerConfig(type="sgd", lr=0.05, momentum=0.9),
            cu.ClientHParams(fedprox_mu=0.01))
        return str(jax.make_jaxpr(_cohort(update))(params, arrays, mask))

    asked = jaxpr()
    _whole_leaf(monkeypatch)
    assert asked == jaxpr()
