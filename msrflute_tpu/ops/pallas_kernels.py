"""Pallas TPU kernels for the DP/quantization/optimizer hot ops.

SURVEY.md §2.9: the reference has no native components — its NCCL/Gloo layer
maps to XLA collectives here, and the "custom kernel" obligation lands on
the fused elementwise passes over flattened updates.  Three kernels:

- :func:`fused_gaussian_noise` — ``out = x * scale + sigma * N(0,1)`` with
  the Gaussian generated **on-core** (pltpu PRNG + Box-Muller).  The jnp
  path materializes a full noise array in HBM
  (``jax.random.normal`` -> add), i.e. 3 HBM streams; the kernel reads x
  and writes out only — the noise never touches HBM.  Used by the
  server-side global-DP step (``privacy.apply_global_dp``).
- :func:`quant_bin_sparsify` — histogram binning to ``n_bins`` levels +
  magnitude sparsification in one pass (the elementwise core of
  ``ops.quantization``; min/max and the threshold's rank selection stay
  in XLA, as reductions).
- :func:`fused_sgd_apply` — the momentum-SGD parameter update over the
  FLATTENED param vector in one pass: ``m' = g + mu*m``, ``p' = p -
  lr*m'``, with the all-padding-step no-op gate folded in.  The opt-in
  megakernel tail for small-model protocols whose per-leaf optimizer
  ops are too tiny to feed the MXU (``server_config.megakernel.
  pallas_apply``); XLA spells the same math as a dozen sub-lane-sized
  ops per leaf, this kernel as three aligned HBM streams.

On non-TPU backends they run under the Pallas TPU interpreter (tests);
the callers in ``privacy`` and ``ops.quantization`` take their jnp path
there instead.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
_BLOCK_ROWS = 256  # rows of 128 lanes per grid step (128 KiB f32 blocks)


def _pad_to_grid(flat: jnp.ndarray):
    n = flat.shape[0]
    per_block = _BLOCK_ROWS * _LANES
    padded = int(np.ceil(max(n, 1) / per_block)) * per_block
    x = jnp.zeros((padded,), flat.dtype).at[:n].set(flat)
    return x.reshape(padded // _LANES, _LANES), n


def compiled_kernels_apply() -> bool:
    """Whether a caller with a jnp alternative should take the compiled
    kernel at this point of the trace: on a TPU backend, and only where
    one device holds the whole operand — a single-device process, or
    inside ``shard_map`` (manual mesh axes).  Mosaic kernels cannot be
    partitioned by GSPMD: under a multi-device ``jit`` outside
    ``shard_map`` (the ``(clients, model)`` tensor-sharded round, the
    server-side tail of a sharded round) lowering refuses them, so those
    traces keep the jnp path."""
    if jax.default_backend() != "tpu":
        return False
    return jax.device_count() == 1 or \
        bool(jax.sharding.get_abstract_mesh().manual_axes)


def _resolve_interpret(interpret):
    """``None`` -> compiled on TPU, the TPU interpreter elsewhere;
    ``True`` -> the TPU interpreter (it implements the pltpu PRNG
    primitives, unlike generic interpret mode)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if interpret is True:
        return pltpu.InterpretParams()
    return interpret


# ----------------------------------------------------------------------
def bits_to_normal(b1: jnp.ndarray, b2: jnp.ndarray) -> jnp.ndarray:
    """Box-Muller: two uint32 random-bit draws -> standard normal.

    This is the DP-critical math of the noise kernel (a wrong sigma here
    silently under-noises every global-DP update), factored out so its
    statistics are testable with ANY uint32 source: the tests feed
    ``jax.random.bits`` on CPU (``tests/test_pallas_kernels.py``), the
    kernel feeds the on-core pltpu PRNG — the transform is identical.
    Top 24 bits -> uniform with 2^-24 resolution (f32-exact); the +1e-12
    floor guards ``log(0)`` and caps |z| at ~7.43.

    The float conversion routes through int32: after ``>> 8`` the value
    fits in 24 bits so the reinterpretation is exact, and mosaic lowers
    uint32->int32->f32 while rejecting the direct uint32->f32 cast
    (observed on silicon).
    """
    u1 = (b1 >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24)) + 1e-12
    u2 = (b2 >> 8).astype(jnp.int32).astype(jnp.float32) * (1.0 / (1 << 24))
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * np.pi * u2)


def _noise_kernel(seed_ref, params_ref, x_ref, o_ref):
    # distinct stream per grid block
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
    scale = params_ref[0]
    sigma = params_ref[1]
    shape = x_ref.shape
    b1 = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    b2 = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    o_ref[:] = x_ref[:] * scale + sigma * bits_to_normal(b1, b2)


def fused_gaussian_noise(flat: jnp.ndarray, scale: jnp.ndarray,
                         sigma: jnp.ndarray, seed: jnp.ndarray,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """``flat * scale + sigma * N(0,1)`` with on-core noise generation."""
    interpret = _resolve_interpret(interpret)
    x2d, n = _pad_to_grid(flat.astype(jnp.float32))
    rows = x2d.shape[0]
    grid = rows // _BLOCK_ROWS
    out = pl.pallas_call(
        _noise_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(grid,),
            in_specs=[pl.BlockSpec((_BLOCK_ROWS, _LANES),
                                   lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANES),
                                   lambda i, *_: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.float32),
        interpret=interpret,
    )(jnp.asarray([seed], jnp.int32),
      jnp.asarray([scale, sigma], jnp.float32), x2d)
    return out.reshape(-1)[:n].astype(flat.dtype)


# ----------------------------------------------------------------------
def _quant_kernel(params_ref, x_ref, o_ref, *, n_bins):
    lo = params_ref[0]
    hi = params_ref[1]
    thresh = params_ref[2]
    x = x_ref[:]
    width = jnp.maximum((hi - lo) / max(n_bins - 1, 1), 1e-30)
    idx = jnp.clip(jnp.round((x - lo) / width), 0, n_bins - 1)
    binned = lo + idx * width
    o_ref[:] = jnp.where(jnp.abs(x) > thresh, binned, 0.0)


def quant_bin_sparsify(flat: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                       thresh: jnp.ndarray, n_bins: int,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused histogram binning + sub-threshold zeroing over a flat vector."""
    interpret = _resolve_interpret(interpret)
    x2d, n = _pad_to_grid(flat.astype(jnp.float32))
    rows = x2d.shape[0]
    grid = rows // _BLOCK_ROWS
    out = pl.pallas_call(
        functools.partial(_quant_kernel, n_bins=n_bins),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid,),
            in_specs=[pl.BlockSpec((_BLOCK_ROWS, _LANES),
                                   lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANES),
                                   lambda i, *_: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.float32),
        interpret=interpret,
    )(jnp.asarray([lo, hi, thresh], jnp.float32), x2d)
    return out.reshape(-1)[:n].astype(flat.dtype)


# ----------------------------------------------------------------------
def _sgd_kernel(hyper_ref, p_ref, g_ref, m_ref, op_ref, om_ref):
    lr = hyper_ref[0]
    mu = hyper_ref[1]
    gate = hyper_ref[2]
    m_new = g_ref[:] + mu * m_ref[:]
    p_new = p_ref[:] - lr * m_new
    live = gate > 0
    op_ref[:] = jnp.where(live, p_new, p_ref[:])
    om_ref[:] = jnp.where(live, m_new, m_ref[:])


def fused_sgd_apply(p_flat: jnp.ndarray, g_flat: jnp.ndarray,
                    m_flat: jnp.ndarray, lr: jnp.ndarray,
                    momentum: jnp.ndarray, gate: jnp.ndarray,
                    interpret: Optional[bool] = None):
    """One-pass momentum-SGD apply over flat f32 vectors.

    ``(p', m') = (p - lr * m', g + mu * m)`` with ``gate <= 0`` pinning
    both outputs to their inputs (the all-padding-step no-op of
    ``engine/client_update.py``).  Matches ``optax.sgd(momentum=mu)``
    exactly: the optax trace is ``t' = g + mu*t`` and the applied update
    ``p + (-lr)*t'``, which is bitwise ``p - lr*t'`` in IEEE arithmetic
    (tests/test_pallas_kernels.py pins the equivalence).
    """
    interpret = _resolve_interpret(interpret)
    x2d, n = _pad_to_grid(p_flat.astype(jnp.float32))
    g2d, _ = _pad_to_grid(g_flat.astype(jnp.float32))
    m2d, _ = _pad_to_grid(m_flat.astype(jnp.float32))
    rows = x2d.shape[0]
    grid = rows // _BLOCK_ROWS
    spec = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i, *_: (i, 0))
    new_p, new_m = pl.pallas_call(
        _sgd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid,),
            in_specs=[spec, spec, spec],
            out_specs=[spec, spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(x2d.shape, jnp.float32),
                   jax.ShapeDtypeStruct(x2d.shape, jnp.float32)],
        interpret=interpret,
    )(jnp.stack([jnp.asarray(lr, jnp.float32),
                 jnp.asarray(momentum, jnp.float32),
                 jnp.asarray(gate, jnp.float32)]), x2d, g2d, m2d)
    return (new_p.reshape(-1)[:n].astype(p_flat.dtype),
            new_m.reshape(-1)[:n].astype(m_flat.dtype))
