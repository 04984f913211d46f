"""Megakernel local SGD (ISSUE 12): fused epoch/step scan, fused
apply-updates, the opt-in pallas SGD apply, and the epoch program-bloat
regression guard.

The two invariants this file pins:

- **the same training** — the fused single-scan inner loop computes what
  a plain loop over epochs x steps computes (optax and the task's loss,
  written out in this file), and the fused apply-updates traversals the
  EXACT f32 bits of their three-pass spelling;
- **program-size class** — a ``num_epochs=4`` program sits in the same
  compiled-program size class as ``num_epochs=1``, pinned via
  ``telemetry.xla.program_size_bytes`` (program TEXT, not wall-clock).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from msrflute_tpu.config import ModelConfig, OptimizerConfig
from msrflute_tpu.engine.client_update import (ClientHParams,
                                               build_client_update)
from msrflute_tpu.models import make_task
from msrflute_tpu.telemetry.xla import program_size_bytes


def _lr_task():
    return make_task(ModelConfig(model_type="LR",
                                 extra={"num_classes": 4, "input_dim": 8}))


def _client_inputs(S=3, B=4, dim=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    arrays = {"x": jnp.asarray(rng.normal(size=(S, B, dim)), jnp.float32),
              "y": jnp.asarray(rng.integers(0, classes, size=(S, B)),
                               jnp.int32)}
    # a ragged tail exercises the all-padding no-op pin
    mask = jnp.ones((S, B), jnp.float32).at[S - 1, B // 2:].set(0.0)
    return arrays, mask


def _run(task, opt, hp, seed=42):
    arrays, mask = _client_inputs()
    cu = jax.jit(build_client_update(task, opt, hp))
    return cu(task.init_params(jax.random.PRNGKey(0)), arrays, mask,
              jnp.float32(0.1), jax.random.PRNGKey(seed))


# ----------------------------------------------------------------------
# the fused inner loop against a plain loop over epochs x steps
# ----------------------------------------------------------------------
def _plain_local_steps(task, tx, params0, arrays, mask, rng, *, epochs,
                       max_norm, mu):
    """The same local training as a plain Python loop: optax and the
    task's loss, nothing of the engine's.  Every step of
    ``_client_inputs`` holds data, so no step is a no-op."""
    params, opt_state = params0, tx.init(params0)
    loss_sum = jnp.zeros(())
    for _ in range(epochs):
        for t in range(mask.shape[0]):
            batch = {k: v[t] for k, v in arrays.items()}
            batch["sample_mask"] = mask[t]
            rng, sub = jax.random.split(rng)
            (loss, _), grads = jax.value_and_grad(task.loss, has_aux=True)(
                params, batch, sub, True)
            grads = jax.tree.map(lambda g, w, w0: g + mu * (w - w0),
                                 grads, params, params0)
            scale = jnp.minimum(1.0, max_norm / jnp.maximum(
                optax.global_norm(grads), 1e-12))
            grads = jax.tree.map(lambda g: g * scale, grads)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            loss_sum = loss_sum + loss
    return jax.tree.map(lambda w0, w: w0 - w, params0, params), loss_sum


@pytest.mark.parametrize("opt,tx", [
    (OptimizerConfig(type="sgd", lr=0.1, momentum=0.9),
     optax.sgd(0.1, momentum=0.9)),
    (OptimizerConfig(type="adam", lr=0.01), optax.adam(0.1)),
], ids=["sgd_momentum", "adam"])
def test_fused_scan_equals_a_plain_epoch_loop(opt, tx):
    """Four epochs as ONE scan over the flattened grid against the loop
    written out (a wrong step order, a skipped epoch or a stale
    optimizer state reads 1e-2 or more); ``_run`` hands the client lr
    0.1 whatever the optimizer's own."""
    task = _lr_task()
    hp = ClientHParams(num_epochs=4, max_grad_norm=1.0, fedprox_mu=0.01)
    pseudo_grad, loss_sum, _, _ = _run(task, opt, hp)
    arrays, mask = _client_inputs()
    want_pg, want_loss = _plain_local_steps(
        task, tx, task.init_params(jax.random.PRNGKey(0)), arrays, mask,
        jax.random.PRNGKey(42), epochs=4, max_norm=1.0, mu=0.01)
    # not bitwise: the loop runs operation by operation, the scan as one
    # fused program (they differ in the last bit after a step).  Twelve
    # float32 steps: 100 x the type's epsilon, relative
    tol = dict(rtol=100 * np.finfo(np.float32).eps, atol=1e-6)
    for a, b in zip(jax.tree.leaves(pseudo_grad), jax.tree.leaves(want_pg)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)
    np.testing.assert_allclose(np.asarray(loss_sum), np.asarray(want_loss),
                               **tol)


# ----------------------------------------------------------------------
# epoch program-bloat regression guard (ISSUE 12 satellite)
# ----------------------------------------------------------------------
def _program_size(num_epochs):
    task = _lr_task()
    opt = OptimizerConfig(type="sgd", lr=0.1, momentum=0.9)
    cu = build_client_update(task, opt, ClientHParams(
        num_epochs=num_epochs, max_grad_norm=1.0))
    arrays, mask = _client_inputs()
    size = program_size_bytes(
        jax.jit(cu), task.init_params(jax.random.PRNGKey(0)), arrays,
        mask, jnp.float32(0.1), jax.random.PRNGKey(1))
    assert size is not None and size > 0
    return size


def test_fused_epochs_hold_program_size_class():
    """num_epochs=4 compiles the same program SIZE class as num_epochs=1
    (pinned via telemetry.xla program bytes, not wall-clock): the scan
    body is traced once whatever the epoch count, where a body cloned
    per epoch would grow by one body an epoch."""
    size_1 = _program_size(1)
    size_4 = _program_size(4)
    size_8 = _program_size(8)
    # one-time delta for the indexed-gather body is allowed; past that
    # the program must be FLAT in the epoch count
    assert size_4 <= 1.25 * size_1, (size_1, size_4)
    assert size_8 == size_4, (size_4, size_8)


# ----------------------------------------------------------------------
# fused apply-updates building blocks (optim/fused.py)
# ----------------------------------------------------------------------
def test_combine_grad_terms_matches_three_pass_spelling():
    from msrflute_tpu.engine.client_update import _clip_by_global_norm
    from msrflute_tpu.optim.fused import combine_grad_terms
    rng = np.random.default_rng(3)
    mk = lambda: {"a": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}
    g, off, w, w0 = mk(), mk(), mk(), mk()
    mu, max_norm = 0.05, 0.7
    legacy = jax.tree.map(lambda x, o: x + o, g, off)
    legacy = jax.tree.map(lambda x, a, b: x + mu * (a - b), legacy, w, w0)
    legacy = _clip_by_global_norm(legacy, max_norm)
    fused = combine_grad_terms(g, offset=off, prox_mu=mu, params=w,
                               global_params=w0, max_norm=max_norm)
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(legacy)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_apply_pins_no_data_steps():
    import optax

    from msrflute_tpu.optim.fused import fused_apply
    tx = optax.sgd(0.1, momentum=0.9)
    params = {"w": jnp.ones((3,))}
    state = tx.init(params)
    grads = {"w": jnp.full((3,), 2.0)}
    moved, moved_state = fused_apply(tx, grads, state, params,
                                     has_data=jnp.float32(1.0))
    pinned, pinned_state = fused_apply(tx, grads, state, params,
                                       has_data=jnp.float32(0.0))
    assert not np.allclose(np.asarray(moved["w"]), np.asarray(params["w"]))
    np.testing.assert_array_equal(np.asarray(pinned["w"]),
                                  np.asarray(params["w"]))
    for a, b in zip(jax.tree.leaves(pinned_state), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------------
# pallas fused SGD apply (opt-in megakernel tail)
# ----------------------------------------------------------------------
def test_fused_sgd_apply_kernel_matches_optax():
    import optax

    from msrflute_tpu.ops.pallas_kernels import fused_sgd_apply
    rng = np.random.default_rng(7)
    n, mu, lr = 1000, 0.9, 0.05
    p = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    tx = optax.sgd(lr, momentum=mu)
    state = tx.init(p)
    state = (optax.TraceState(trace=m),) + tuple(state[1:])
    updates, new_state = tx.update(g, state, p)
    want_p = optax.apply_updates(p, updates)
    got_p, got_m = fused_sgd_apply(p, g, m, lr, mu, jnp.float32(1.0))
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(want_p),
                               rtol=1e-7, atol=1e-7)
    np.testing.assert_allclose(np.asarray(got_m),
                               np.asarray(new_state[0].trace),
                               rtol=1e-7, atol=1e-7)
    # gate <= 0 pins both outputs
    pin_p, pin_m = fused_sgd_apply(p, g, m, lr, mu, jnp.float32(0.0))
    np.testing.assert_array_equal(np.asarray(pin_p), np.asarray(p))
    np.testing.assert_array_equal(np.asarray(pin_m), np.asarray(m))


def test_pallas_apply_client_update_matches_optax_path():
    task = _lr_task()
    opt = OptimizerConfig(type="sgd", lr=0.1, momentum=0.9)
    out_p = _run(task, opt, ClientHParams(num_epochs=2, pallas_apply=True))
    out_o = _run(task, opt, ClientHParams(num_epochs=2, pallas_apply=False))
    for a, b in zip(jax.tree.leaves(out_p), jax.tree.leaves(out_o)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_pallas_apply_refuses_unfusable_optimizers():
    task = _lr_task()
    with pytest.raises(ValueError, match="plain SGD"):
        build_client_update(task, OptimizerConfig(type="adam", lr=0.01),
                            ClientHParams(pallas_apply=True))
    with pytest.raises(ValueError, match="updatable_layers"):
        build_client_update(task, OptimizerConfig(type="sgd", lr=0.01),
                            ClientHParams(pallas_apply=True,
                                          updatable_layers=("dense",)))
