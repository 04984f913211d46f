"""Plain reference: the FEMNIST benchmark CNN (FedML ``CNN_DropOut`` of
"Adaptive Federated Optimization", arXiv:2003.00295; the reference's
``experiments/cv_cnn_femnist/model.py``), forward pass in ``jax.numpy``.
Imports nothing of ``msrflute_tpu``.

conv3x3x32 VALID -> relu -> conv3x3x64 VALID -> relu -> maxpool 2x2 ->
flatten(9216) -> fc128 -> relu -> fc ``num_classes``.  NHWC, kernels HWIO,
every layer with a bias.  The two dropout layers (0.25, 0.5) are NOT
here: the configuration that uses this reference switches them off (see
its ``assumed``), because a reference cannot follow the system's dropout
masks without taking its random streams from it.

Init: untruncated normal of the lecun variance (fan-in), zero biases
(the system's flax default truncates at two sigma — immaterial here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _lecun(rng, shape):
    fan_in = int(np.prod(shape[:-1]))
    return (rng.standard_normal(shape, dtype=np.float32) *
            np.float32(np.sqrt(1.0 / fan_in)))


def init(rng: np.random.Generator, model_config: dict) -> dict:
    classes = int(model_config.get("num_classes", 62))
    side = int(model_config.get("image_size", 28))
    flat = ((side - 4) // 2) ** 2 * 64
    shapes = {"Conv_0": (3, 3, 1, 32), "Conv_1": (3, 3, 32, 64),
              "Dense_0": (flat, 128), "Dense_1": (128, classes)}
    return {name: {"kernel": _lecun(rng, shape),
                   "bias": np.zeros((shape[-1],), np.float32)}
            for name, shape in shapes.items()}


def _conv(x, p):
    return jax.lax.conv_general_dilated(
        x, p["kernel"], (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["bias"]


def forward(params: dict, x, model_config: dict):
    """Logits ``[N, classes]``, float32 throughout."""
    x = x.astype(jnp.float32)
    if x.ndim == 3:
        x = x[..., None]
    x = jax.nn.relu(_conv(x, params["Conv_0"]))
    x = jax.nn.relu(_conv(x, params["Conv_1"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
    x = x.reshape((x.shape[0], -1))
    x = jax.nn.relu(x @ params["Dense_0"]["kernel"] +
                    params["Dense_0"]["bias"])
    return x @ params["Dense_1"]["kernel"] + params["Dense_1"]["bias"]
