"""Fused apply-updates — the per-step optimizer tail as few traversals.

The megakernel local-SGD work (ISSUE 12) found the inner-step tail of
``engine/client_update.py`` paying five separate pytree traversals per
local step: grad-offset add (SCAFFOLD), FedProx proximal add, global-norm
clip scale, ``optax.apply_updates``, and the all-padding-step no-op pin.
Each traversal is a Python loop over every leaf at trace time — for a
scan body that is pure program text, and for deep models it is the bulk
of the traced op count.  This module collapses them:

- :func:`combine_grad_terms` — offset + proximal + clip in ONE combining
  traversal plus the unavoidable global-norm pass (the clip scale depends
  on the combined gradient, so it cannot fold further);
- :func:`fused_apply` — optimizer transform + frozen-layer mask +
  parameter apply + no-op pinning, with the apply and the pin fused into
  a single traversal (``where(live, p + u, p)``), and the optimizer-state
  pin kept as its own traversal only because optax state trees differ in
  structure from the param tree.

Bit-identity contract: every fused expression evaluates the SAME ops in
the SAME association as the legacy spelling (``(g + o) + mu*(w - w0)``,
``g * scale``, ``(p + u)`` then select), so an f32 run is bit-identical
to the pre-fusion program — pinned by tests/test_megakernel.py.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import optax


def combine_grad_terms(grads: Any, *, offset: Any = None,
                       prox_mu: float = 0.0, params: Any = None,
                       global_params: Any = None,
                       max_norm: Optional[float] = None) -> Any:
    """``clip((g + offset) + mu * (w - w0))`` with one combining
    traversal.  ``offset`` is the SCAFFOLD drift correction, ``prox_mu``
    the FedProx proximal weight (needs ``params``/``global_params``),
    ``max_norm`` the global-norm clip bound; any of them absent compiles
    to nothing."""
    if offset is not None and prox_mu > 0.0:
        grads = jax.tree.map(
            lambda g, o, w, w0: (g + o) + prox_mu * (w - w0),
            grads, offset, params, global_params)
    elif offset is not None:
        grads = jax.tree.map(lambda g, o: g + o, grads, offset)
    elif prox_mu > 0.0:
        grads = jax.tree.map(
            lambda g, w, w0: g + prox_mu * (w - w0),
            grads, params, global_params)
    if max_norm is not None:
        norm = optax.global_norm(grads)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
    return grads


def fused_apply(tx: optax.GradientTransformation, grads: Any,
                opt_state: Any, params: Any, *, update_mask: Any = None,
                has_data: Any = None) -> Tuple[Any, Any]:
    """Optimizer update + masked apply + no-op pin.

    ``update_mask`` (per-leaf static Python bools, or None) freezes
    non-updatable layers; ``has_data`` (traced scalar, or None) pins
    all-padding steps to a no-op — params AND optimizer state — exactly
    like the legacy two-pass spelling, but the apply and the param pin
    share one traversal."""
    updates, new_opt = tx.update(grads, opt_state, params)
    if update_mask is not None:
        # static mask: frozen leaves are zero constants in XLA
        updates = jax.tree.map(
            lambda u, keep: u if keep else jnp.zeros_like(u),
            updates, update_mask)
    if has_data is None:
        return optax.apply_updates(params, updates), new_opt
    live = has_data > 0
    # apply + pin in one traversal; the (p + u) cast matches
    # optax.apply_updates so the f32 trace is bit-identical
    new_params = jax.tree.map(
        lambda p, u: jnp.where(live, jnp.asarray(p + u).astype(
            jnp.asarray(p).dtype), p),
        params, updates)
    new_opt = jax.tree.map(
        lambda new, old: jnp.where(live, new, old), new_opt, opt_state)
    return new_params, new_opt


def segment_select(pred: Any, fresh: Any, carried: Any) -> Any:
    """Tree-wise ``where(pred, fresh, carried)`` — the cross-client
    megabatch lane scan's SEGMENT-RESET primitive (engine/client_update.
    build_mega_update).  At a tape slot whose segment id differs from the
    previous slot's, the lane is starting a NEW client: params, optimizer
    state, rng, and loss/stat accumulators all select the fresh client
    values in one spelling.  ``pred`` is a scalar (per lane under vmap),
    so every leaf compiles to a broadcast select — the grouped analogue
    of :func:`fused_apply`'s no-op pin, and like it the select is the
    LAST op on each leaf, keeping the f32 segment math bit-identical to
    a per-client trace that never selects."""
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b), fresh, carried)


def sgd_pallas_fusable(opt_cfg: Any) -> bool:
    """True when the client optimizer is the plain-SGD shape the pallas
    fused apply kernel implements: ``type: sgd``, no nesterov, no weight
    decay (momentum is fine — the kernel carries the trace buffer)."""
    kind = str(opt_cfg.get("type", "sgd")).lower()
    return (kind == "sgd"
            and not bool(opt_cfg.get("nesterov", False))
            and not float(opt_cfg.get("weight_decay", 0.0) or 0.0))


def zero_grad_is_noop(opt_cfg: Any) -> bool:
    """True when a coordinate whose gradient is zero at every step keeps
    its value and its optimizer state under this optimizer, whatever the
    rest of its leaf does: no weight decay (it moves a weight without a
    gradient) and no per-leaf norm (lars / lamb scale a step by the whole
    leaf's).  Then a loop may leave such coordinates out of what it
    carries (``engine/client_update.py``: a kernel's dead taps)."""
    kind = str(opt_cfg.get("type", "sgd")).lower()
    return (kind in ("sgd", "adam", "adamax", "adamw", "yogi")
            and not float(opt_cfg.get("weight_decay", 0.0) or 0.0))
