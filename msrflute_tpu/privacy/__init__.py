"""Differential privacy — on-device mechanisms + host-side accounting.

Parity target: reference ``extensions/privacy/__init__.py``:

- LDP noise std from (eps, sensitivity, delta)  (``:15-16``)
- ``apply_local_dp`` (``:154-201``): flatten the update; eps < 0 => clip-only
  to ``max_grad``; else normalize the flat update to norm ``max_grad``,
  append the (scaled, clamped) aggregation weight when weight noising is on,
  add Gaussian noise calibrated to the joint sensitivity
  ``sqrt(max_grad^2 + max_weight^2)``, then unclamp/unscale the weight.
- ``apply_global_dp`` (``:128-151``): server-side Gaussian noise with scale
  ``global_sigma * max_grad / num_clients`` on the aggregated update.
- ``update_privacy_accountant`` (``:204-260``): host-side RDP accounting —
  our own implementation of the sampled-Gaussian-mechanism RDP bound in
  :mod:`msrflute_tpu.privacy.accountant` (the reference vendors
  TF-Privacy's; we reimplement from the published formulas).

TPU-native: the mechanisms are pure jnp over ``ravel_pytree``-flattened
updates (the functional replacement of ``unroll_network``/``update_network``,
``:105-125``) and run *inside* the jitted round program under vmap — one
fused pass instead of host-side tensor surgery.
"""

from __future__ import annotations

import logging
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

# NOTE: the near-exact PRV accountant lives in .prv and is NOT re-exported
# here — it is offline-only (tools/compute_dp_epsilon.py) and importing it
# would put scipy.stats on every training-process startup path.
from .accountant import DEFAULT_ORDERS, compute_rdp, get_privacy_spent  # noqa: F401


def compute_ldp_noise_std(eps: float, max_sensitivity: float, delta: float) -> float:
    """Gaussian-mechanism sigma (reference ``:15-16``)."""
    return float(np.sqrt(2.0 * np.log(1.25 / delta)) * max_sensitivity / eps)


def add_gaussian_noise(flat: jnp.ndarray, eps: float, max_sensitivity: float,
                       delta: float, rng: jax.Array) -> Tuple[jnp.ndarray, float]:
    sigma = compute_ldp_noise_std(eps, max_sensitivity, delta)
    return flat + sigma * jax.random.normal(rng, flat.shape, flat.dtype), sigma


# ---------------------------------------------------------------------
# "unused extras" kept for parity (reference :51-102): alternative local
# mechanisms — the d-sphere PrivateUnit2 sampler, discrete scalar DP and
# Laplace noise.  Host-side numpy like the reference.

def privacy_parameters(eps0: float, eps: float, d: int):
    """Split epsilons into (sampling prob, gamma) for PrivateUnit2
    (reference ``:37-48``)."""
    exp_eps0 = np.exp(eps0)
    exp_eps = np.exp(eps)
    p0 = 1.0 if np.isinf(exp_eps0) else exp_eps0 / (1 + exp_eps0)
    base = np.sqrt(np.pi / (2 * (d - 1)))
    gamma = base if np.isinf(exp_eps) else \
        ((exp_eps - 1) / (exp_eps + 1)) * base
    return p0, gamma


def private_unit2(grad: np.ndarray, gamma: float, prob: float,
                  rng: Optional[np.random.Generator] = None) -> np.ndarray:
    """d-sphere mechanism for a unit vector (reference ``:51-66``):
    rejection-sample a unit direction correlated with ``grad`` w.p.
    ``prob``, anti-correlated otherwise, unbiased via the 1/m factor."""
    from scipy.special import betainc, betaln
    rng = rng if rng is not None else np.random.default_rng()
    grad = np.asarray(grad, np.float64)
    assert abs(np.linalg.norm(grad) - 1.0) < 1e-4
    assert prob >= 0.5 and 0.0 <= gamma <= 1.0
    p = rng.random()
    while True:
        v = rng.normal(size=grad.shape)
        v /= np.linalg.norm(v)
        dot = float(v @ grad)
        if (dot >= gamma and p < prob) or (dot < gamma and p >= prob):
            break
    d = grad.shape[0]
    alpha = (d - 1) / 2
    tau = (1 + gamma) / 2
    ratio = 1.0 / betainc(alpha, alpha, tau)
    log_m1 = alpha * np.log(1 - gamma ** 2) - (d - 2) * np.log(2) - \
        np.log(d - 1)
    log_m2 = (np.log(prob / (ratio - 1) - (1 - prob)) + np.log(ratio) -
              betaln(alpha, alpha))
    m = np.exp(log_m1 + log_m2)
    return v / m


def add_private_unit2_noise(eps: float, grad: np.ndarray,
                            rng: Optional[np.random.Generator] = None):
    """Reference ``:75-79``: split eps 1%/99% between sampling and gamma."""
    p0, gamma = privacy_parameters(0.01 * eps, 0.99 * eps, grad.shape[0])
    return private_unit2(grad, gamma, p0, rng)


def scalar_dp(r: float, eps: float, k: int, r_max: float,
              rng: Optional[np.random.Generator] = None) -> float:
    """Discrete scalar DP mechanism (reference ``scalar_DP``, ``:82-98``):
    stochastic rounding to k levels + randomized response, debiased."""
    rng = rng if rng is not None else np.random.default_rng()
    r = min(r, r_max)
    val = k * r / r_max
    f_val, c_val = math.floor(val), math.ceil(val)
    j = f_val if rng.random() < (c_val - val) else c_val
    exp_eps = np.exp(eps)
    if rng.random() >= exp_eps / (exp_eps + k):
        while True:
            j_new = int(rng.integers(0, k + 1))
            if j_new != j:
                j = j_new
                break
    a = ((exp_eps + k) / (exp_eps - 1)) * (r_max / k)
    b = (k * (k + 1)) / (2 * (exp_eps + k))
    return float(a * (j - b))


def laplace_noise(max_sens: float, eps: float, size: int,
                  rng: Optional[np.random.Generator] = None) -> np.ndarray:
    """Reference ``laplace_noise`` (``:101-102``)."""
    rng = rng if rng is not None else np.random.default_rng()
    return rng.laplace(0.0, max_sens / eps, size)


def apply_local_dp(pseudo_grad: Any, weight: jnp.ndarray, dp_config,
                   add_weight_noise: bool, rng: jax.Array,
                   clip_override=None) -> Tuple[Any, jnp.ndarray]:
    """Client-side DP on the flattened pseudo-gradient (traced; vmap-safe).

    Reproduces reference ``apply_local_dp`` (``:154-201``) including the
    weight scale/clamp/noise/unscale dance.  ``clip_override`` (a traced
    scalar) substitutes the static ``max_grad`` — the adaptive-clipping
    hook (strategies/fedavg.py).  NOTE: with eps >= 0 the noise sigma uses
    the STATIC max_grad sensitivity bound, which stays valid as long as
    the adaptive clip <= max_grad (enforced by the caller).
    """
    flat, unravel = ravel_pytree(pseudo_grad)
    eps = float(dp_config.get("eps", -1.0))
    static_max_grad = float(dp_config.get("max_grad", 1.0))
    max_grad = static_max_grad
    if clip_override is not None:
        max_grad = jnp.minimum(jnp.asarray(clip_override, jnp.float32),
                               static_max_grad)

    if eps < 0:
        # clip-only mode
        norm = jnp.linalg.norm(flat)
        scale = jnp.minimum(1.0, max_grad / jnp.maximum(norm, 1e-12))
        return unravel(flat * scale), weight

    delta = float(dp_config.get("delta", 1e-7))
    max_weight = float(dp_config.get("max_weight", 100.0))
    min_weight = float(dp_config.get("min_weight", 0.0))
    weight_scaler = float(dp_config.get("weight_scaler", 1.0))

    orig_weight = weight
    scaled_weight = jnp.minimum(weight * weight_scaler, max_weight)
    # normalize the update to exactly max_grad norm (reference :182)
    normed = max_grad * flat / jnp.maximum(jnp.linalg.norm(flat), 1e-12)
    # sensitivity stays the STATIC bound: sigma must not depend on the
    # (traced) adaptive clip, and static >= adaptive keeps it an upper bound
    max_sensitivity = math.sqrt(static_max_grad ** 2 +
                                (max_weight ** 2 if add_weight_noise else 0.0))
    joint = jnp.concatenate([normed, scaled_weight[None]])
    noisy, _sigma = add_gaussian_noise(joint, eps, max_sensitivity, delta, rng)
    noisy_weight = jnp.clip(noisy[-1], min_weight, max_weight) / weight_scaler
    new_weight = noisy_weight if add_weight_noise else orig_weight
    return unravel(noisy[:-1]), new_weight


def apply_global_dp(agg_grad: Any, dp_config, rng: jax.Array,
                    num_clients: jnp.ndarray) -> Any:
    """Server-side Gaussian noise on the aggregate (reference ``:128-151``):
    per-element std ``global_sigma * max_grad / num_clients``.

    On TPU this runs the fused Pallas kernel (noise generated on-core,
    never materialized in HBM) where a compiled kernel can apply
    (``ops.pallas_kernels.compiled_kernels_apply``); elsewhere the jnp
    path.
    """
    flat, unravel = ravel_pytree(agg_grad)
    sigma = float(dp_config.get("global_sigma", 0.0))
    max_grad = float(dp_config.get("max_grad", 1.0))
    noise_scale = sigma * max_grad / jnp.maximum(num_clients, 1.0)
    from ..ops.pallas_kernels import (compiled_kernels_apply,
                                      fused_gaussian_noise)
    if compiled_kernels_apply():
        seed = jax.random.randint(rng, (), 0, 2**31 - 1)
        noisy = fused_gaussian_noise(flat, jnp.asarray(1.0, flat.dtype),
                                     noise_scale, seed)
    else:
        noisy = flat + noise_scale * jax.random.normal(rng, flat.shape,
                                                       flat.dtype)
    return unravel(noisy)


def update_privacy_accountant(config, num_clients: int, curr_iter: int,
                              num_clients_curr_iter: int) -> Optional[float]:
    """Host-side RDP accounting (reference ``:204-260``): log K/B/n/T/sigma/mu
    and return the RDP epsilon for the run so far."""
    dp_config = config.dp_config
    if dp_config is None or not (dp_config.get("enable_global_dp", False) or
                                 dp_config.get("enable_local_dp", False)):
        return None

    from ..utils.logging import log_metric, print_rank

    K = 1
    B = num_clients_curr_iter
    n = max(num_clients, 2)
    T_iters = curr_iter + 1
    delta = float(dp_config.get("delta") or min(1e-7, 1.0 / (n * math.log(n))))
    if dp_config.get("global_sigma") in (None, 0.0):
        max_sensitivity = math.sqrt(float(dp_config.get("max_grad", 1.0)) ** 2 +
                                    float(dp_config.get("max_weight", 100.0)) ** 2)
        noise_scale = compute_ldp_noise_std(float(dp_config.get("eps", 1.0)),
                                            max_sensitivity, delta)
        global_sigma = noise_scale * math.sqrt(B) / max_sensitivity
    else:
        global_sigma = float(dp_config.get("global_sigma"))
        noise_scale = global_sigma * float(dp_config.get("max_grad", 1.0)) / B

    try:
        mu = K * B / n * math.sqrt(T_iters * math.exp((1.0 / global_sigma) ** 2 - 1))
    except OverflowError:
        mu = -1.0

    q = B / n
    rdp = compute_rdp(q, global_sigma, T_iters, DEFAULT_ORDERS)
    rdp_epsilon, opt_order = get_privacy_spent(DEFAULT_ORDERS, rdp, delta)

    props = {
        "dp_global_K": K, "dp_global_B": B, "dp_global_n": n,
        "dp_global_T": T_iters, "dp_sigma": global_sigma, "dp_global_mu": mu,
        "dp_epsilon_rdp": rdp_epsilon, "dp_opt_order": opt_order,
        "dp_delta": delta, "dp_noise_scale": noise_scale,
    }
    print_rank(f"DP accounting: {props}", loglevel=logging.DEBUG)
    for key, value in props.items():
        log_metric(key, value, step=curr_iter)
    return rdp_epsilon
