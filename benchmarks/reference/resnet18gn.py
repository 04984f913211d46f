"""Plain reference: ResNet-18 with GroupNorm (FedML's Fed-CIFAR-100 model,
the reference's ``experiments/cv_resnet_fedcifar100/model.py``), forward
pass written out in ``jax.numpy``.  Imports nothing of ``msrflute_tpu``.

Layout: NHWC, kernels HWIO.  Stem 7x7/2 conv (no bias) -> GN -> relu ->
3x3/2 max pool (pad 1); four stages of two basic blocks (64, 128, 256, 512
planes, stride 2 at the first block of stages 2-4, 1x1 conv + GN on the
skip where shape changes); global average pool; dense to ``num_classes``.
GroupNorm: ``channels // channels_per_group`` groups, eps 1e-5, affine per
channel, statistics over (H, W, channels of the group) per sample with
``var = E[x^2] - E[x]^2`` (flax's ``use_fast_variance``).  The second
norm of each block starts with scale 0 (zero-init residual).

The parameter tree uses the names the system's checkpoint uses
(``Conv_0``, ``GroupNorm_0``, ``_BasicBlock_<i>``, ``Dense_0``) so that
the harness can hand these weights to the program leaf by leaf.

Departure from the published init, immaterial to a speed or agreement
measurement: the dense layer draws from an untruncated normal of the
lecun variance (the system's flax default truncates at two sigma).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

STAGES = (2, 2, 2, 2)


def _block_specs():
    planes, inp, out = 64, 64, []
    for stage, blocks in enumerate(STAGES):
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out.append((inp, planes, stride))
            inp = planes
        planes *= 2
    return out


def _he(rng, shape):
    fan_out = shape[0] * shape[1] * shape[3]
    return (rng.standard_normal(shape, dtype=np.float32) *
            np.float32(np.sqrt(2.0 / fan_out)))


def _gn_params(ch, zero_scale=False):
    return {"scale": (np.zeros if zero_scale else np.ones)((ch,), np.float32),
            "bias": np.zeros((ch,), np.float32)}


def init(rng: np.random.Generator, model_config: dict) -> dict:
    classes = int(model_config.get("num_classes", 100))
    chans = int(model_config.get("in_channels", 3))
    params = {"Conv_0": {"kernel": _he(rng, (7, 7, chans, 64))},
              "GroupNorm_0": _gn_params(64)}
    for i, (inp, planes, stride) in enumerate(_block_specs()):
        block = {"Conv_0": {"kernel": _he(rng, (3, 3, inp, planes))},
                 "GroupNorm_0": _gn_params(planes),
                 "Conv_1": {"kernel": _he(rng, (3, 3, planes, planes))},
                 "GroupNorm_1": _gn_params(planes, zero_scale=True)}
        if inp != planes or stride != 1:
            block["Conv_2"] = {"kernel": _he(rng, (1, 1, inp, planes))}
            block["GroupNorm_2"] = _gn_params(planes)
        params[f"_BasicBlock_{i}"] = block
    params["Dense_0"] = {
        "kernel": rng.standard_normal((512, classes), dtype=np.float32) *
        np.float32(np.sqrt(1.0 / 512)),
        "bias": np.zeros((classes,), np.float32)}
    return params


def _conv(x, kernel, stride, pad):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(x, p, channels_per_group, eps=1e-5):
    n, h, w, c = x.shape
    groups = max(c // max(channels_per_group, 1), 1)
    g = x.reshape(n, h, w, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.maximum(
        jnp.mean(g * g, axis=(1, 2, 4), keepdims=True) - mean * mean, 0.0)
    y = ((g - mean) * jax.lax.rsqrt(var + eps)).reshape(n, h, w, c)
    return y * p["scale"] + p["bias"]


def forward(params: dict, x, model_config: dict):
    """Logits ``[N, classes]``, float32 throughout."""
    cpg = int(model_config.get("channels_per_group", 32))

    def gn(t, p):
        return _group_norm(t, p, cpg)

    x = x.astype(jnp.float32)
    x = _conv(x, params["Conv_0"]["kernel"], 2, 3)
    x = jax.nn.relu(gn(x, params["GroupNorm_0"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    for i, (inp, planes, stride) in enumerate(_block_specs()):
        b = params[f"_BasicBlock_{i}"]
        y = _conv(x, b["Conv_0"]["kernel"], stride, 1)
        y = jax.nn.relu(gn(y, b["GroupNorm_0"]))
        y = _conv(y, b["Conv_1"]["kernel"], 1, 1)
        y = gn(y, b["GroupNorm_1"])
        skip = x
        if "Conv_2" in b:
            skip = gn(_conv(x, b["Conv_2"]["kernel"], stride, 0),
                      b["GroupNorm_2"])
        x = jax.nn.relu(y + skip)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]
