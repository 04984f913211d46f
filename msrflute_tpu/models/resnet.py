"""ResNet-18 with GroupNorm for Fed-CIFAR-100.

Parity target: reference ``experiments/cv_resnet_fedcifar100/model.py`` +
``group_normalization.py`` — a FedML-style ResNet with GroupNorm in place of
BatchNorm (no running stats: the right normalization for federated clients,
and for vmap-over-clients here — every client's stats stay self-contained).

Flax implementation, NHWC, GroupNorm native (``nn.GroupNorm``).  The stem is
the ImageNet-style 7x7/stride-2 + maxpool of the reference; CIFAR inputs
(32x32) pass through it exactly as they do in the reference.

Behind that stem the four stages run at 8x8, 4x4, 2x2 and, at 32x32
inputs, 1x1: the last stage's three 3x3 512->512 kernels meet their one
pixel at the centre tap only, and its first (256->512, stride 2, 2x2 ->
1x1) at four of nine.  Every convolution is an ``ops/conv.py::Conv``,
which reads only those taps through ``live_tap_conv`` (the kernels keep
their 3x3 shape; a dead tap's gradient is the zero it always was); at
64x64 inputs and above no tap is dead and the call is the plain one.
The task says which kernels have such a window
(:meth:`ResNetTask.kernel_windows`), and the module takes a kernel that
is already cut to it, so the local-steps loop carries the windows alone
(``engine/client_update.py``).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.conv import Conv as _conv
from ..ops.conv import collecting_windows
from .base import parse_dtype, to_float_image
from .cv import ClassificationTask

#: He fan-out init, the reference's ``normal_(0, sqrt(2/n))`` on convs
#: (``model.py:139-140``)
_he_init = nn.initializers.variance_scaling(2.0, "fan_out", "normal")


def _gn(channels: int, channels_per_group: int = 32,
        zero_scale: bool = False, dtype=jnp.float32) -> nn.GroupNorm:
    groups = max(channels // max(channels_per_group, 1), 1)
    # flax GroupNorm computes its statistics in float32 regardless of
    # ``dtype``; passing the compute dtype only keeps activations bf16.
    # epsilon matches the reference's F.batch_norm default 1e-5
    # (group_normalization.py:19 via _BatchNorm) — flax's own default is
    # 1e-6, a visible round-0 forward delta.  NOTE a deliberate
    # divergence kept per-channel: the reference's GroupNorm affine is
    # per-GROUP (weight shape c/32, group_normalization.py:104-112);
    # ours is flax-standard per-channel (strictly more expressive;
    # identical at init, transplant repeats each group scalar across its
    # channels — see tests/test_parity_harness.py resnet transplant).
    return nn.GroupNorm(num_groups=groups, dtype=dtype, epsilon=1e-5,
                        scale_init=(nn.initializers.zeros if zero_scale
                                    else nn.initializers.ones))


class _BasicBlock(nn.Module):
    planes: int
    stride: int = 1
    channels_per_group: int = 32
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        residual = x
        y = _conv(self.planes, (3, 3), strides=(self.stride, self.stride),
                  padding=1, kernel_init=_he_init, dtype=self.dtype)(x)
        y = _gn(self.planes, self.channels_per_group, dtype=self.dtype)(y)
        y = nn.relu(y)
        y = _conv(self.planes, (3, 3), padding=1, kernel_init=_he_init,
                  dtype=self.dtype)(y)
        # block-final norm scale starts at zero so every block begins as
        # identity (the reference's zero_init_residual,
        # ``model.py:148-152``) — without it the 8-block stack amplifies
        # activations and early SGD diverges
        y = _gn(self.planes, self.channels_per_group, zero_scale=True,
                dtype=self.dtype)(y)
        if residual.shape[-1] != self.planes or self.stride != 1:
            residual = _conv(self.planes, (1, 1),
                             strides=(self.stride, self.stride),
                             kernel_init=_he_init, dtype=self.dtype)(x)
            residual = _gn(self.planes, self.channels_per_group,
                           dtype=self.dtype)(residual)
        return nn.relu(y + residual)


class _ResNetGN(nn.Module):
    stage_sizes: Sequence[int] = (2, 2, 2, 2)  # ResNet-18
    num_classes: int = 100
    channels_per_group: int = 32
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = to_float_image(x, self.dtype)
        x = _conv(64, (7, 7), strides=(2, 2), padding=3,
                  kernel_init=_he_init, dtype=self.dtype)(x)
        x = _gn(64, self.channels_per_group, dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        planes = 64
        for stage, blocks in enumerate(self.stage_sizes):
            for block in range(blocks):
                stride = 2 if stage > 0 and block == 0 else 1
                x = _BasicBlock(planes, stride,
                                self.channels_per_group, self.dtype)(x)
            planes *= 2
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        return nn.Dense(self.num_classes, dtype=self.dtype)(x)


class ResNetTask(ClassificationTask):
    """The classification task of a model built from ``ops/conv.py::Conv``:
    it can say which kernels a batch's shape reads a window of."""

    def kernel_windows(self, params, batch
                       ) -> Dict[Tuple[str, ...], Tuple[tuple, tuple]]:
        with collecting_windows() as found:
            jax.eval_shape(self.apply, params, batch["x"])
        return found


def make_resnet_task(model_config) -> ResNetTask:
    num_classes = int(model_config.get("num_classes", 100))
    side = int(model_config.get("image_size", 32))
    depth = {18: (2, 2, 2, 2), 34: (3, 4, 6, 3)}[
        int(model_config.get("depth", 18))]
    module = _ResNetGN(
        stage_sizes=depth, num_classes=num_classes,
        channels_per_group=int(model_config.get("channels_per_group", 32)),
        dtype=parse_dtype(model_config))
    # in_channels: the reference model is RGB-only; grayscale corpora
    # (e.g. the bundled digits convergence probe) need 1 here
    chans = int(model_config.get("in_channels", 3))
    return ResNetTask(module, example_shape=(side, side, chans),
                      name="cv_resnet_fedcifar100", num_classes=num_classes)
