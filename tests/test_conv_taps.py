"""``ops/conv.py::live_tap_conv``: a convolution over the kernel taps that
can meet an input — the plain call's outputs and gradients, the plain
call itself where no tap is dead, and ResNet-18+GN's tree untouched."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from msrflute_tpu.models import resnet
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
    monkeypatch.setattr(resnet, "_conv", nn.Conv)
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
               for e in said)
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
