"""Per-client local training as a pure jittable function.

Parity target: reference ``Client.process_round`` + ``Trainer``
(``core/client.py:226-511``, ``core/trainer.py:200-687``).  Semantics
preserved exactly (SURVEY.md §7):

- model reset per client: local params start from the server's globals
  (``core/client.py:294-302``) — here simply the function argument;
- fresh optimizer per client with the server-dictated LR
  (``core/client.py:309-312``) — optax init inside the function;
- per-batch loss -> grad -> clip -> stats -> step
  (``core/trainer.py:341-414``) — ONE ``lax.scan`` over the flattened
  ``[num_epochs * steps]`` grid (epoch fusion, PR 12: the body is traced
  once whatever the epoch count, so program size and compile time stay
  flat in it);
- ``desired_max_samples`` early stop (``core/trainer.py:363-364``) — encoded
  in the batch packing (zero-mask beyond the cap), with all-padding steps
  gated so they change nothing;
- FedProx proximal term ``mu * (w - w_global)`` added to gradients
  (``core/trainer.py:416-501``);
- pseudo-gradient = w_server - w_trained (``core/client.py:380-383``);
- gradient sufficient stats accumulated per batch
  (``core/trainer.py:263-312``): ``sum``, ``sq_sum``, ``n``, and derived
  ``mean = sum/n``, ``mag = sqrt(sq_sum/n)``, ``norm = sqrt(sq_sum)``.
  NOTE the reference computes ``var = sq_sum/n - mag**2`` which is
  identically zero (``core/trainer.py:301``); we keep that key for parity
  but also expose the statistically meaningful ``var_corrected =
  sq_sum/n - mean**2``.
- per-layer freezing (``core/client.py:306-307``): frozen layers get zero
  pseudo-gradient, equivalent to the reference's zeroed ``p.grad``.

This function is ``vmap``-ed over the round's clients and ``shard_map``-ed
over the mesh by :mod:`msrflute_tpu.engine.round` — the role FLUTE's Worker
processes play (``core/federated.py:482-632``), with no RPC.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ..models.base import BaseTask
from ..optim import make_optimizer
from ..optim.fused import (combine_grad_terms, fused_apply, segment_select,
                           sgd_pallas_fusable, zero_grad_is_noop)


@dataclass(frozen=True)
class ClientHParams:
    """Static client-update hyperparameters (compiled into the program)."""

    max_grad_norm: Optional[float] = None       # core/trainer clip
    fedprox_mu: float = 0.0                     # FedProx proximal weight
    num_epochs: int = 1                         # local epochs per round
    stats_on_smooth_grad: bool = True           # dga.py:104-108
    freeze_layers: Tuple[str, ...] = ()         # core/client.py:306-307
    #: regex allowlist — when set, ONLY matching layers move; the rest are
    #: frozen at every inner step, like the reference's per-param lr=0
    #: (set_component_wise_lr, core/trainer.py:725-751)
    updatable_layers: Optional[Tuple[str, ...]] = None
    #: opt-in pallas fused SGD apply (``server_config.megakernel.
    #: pallas_apply``): the inner step's optimizer tail runs as ONE
    #: kernel pass over the flattened param vector
    #: (``ops.pallas_kernels.fused_sgd_apply``) instead of per-leaf XLA
    #: ops — for small-model protocols whose leaves are too tiny to
    #: tile.  Plain-SGD optimizers only (momentum ok); TPU-targeted
    #: (interpret mode elsewhere).
    pallas_apply: bool = False
    #: precision policy (``server_config.precision``), each a dtype name
    #: or None.  ``compute`` casts params + float batch features for the
    #: forward/backward only (grads come back in the params dtype — the
    #: f32 master-params discipline); ``params`` holds the client's
    #: LOCAL working copy (and optimizer state) in that dtype;
    #: ``stats`` sets the loss/grad-stat accumulator dtype.  None (or
    #: "float32") compiles the exact f32 legacy trace — the bit-identity
    #: default.
    param_dtype: Optional[str] = None
    compute_dtype: Optional[str] = None
    stats_dtype: Optional[str] = None


def _global_norm(tree: Any) -> jnp.ndarray:
    return optax.global_norm(tree)


def _clip_by_global_norm(tree: Any, max_norm: float) -> Any:
    norm = _global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale, tree)


def _suff_stats_of(tree: Any, zeros_left_out: int = 0
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``zeros_left_out``: elements of the whole tree that ``tree`` does
    not hold because they are exact zeros (a narrowed gradient's dead
    taps): they add nothing to the sums and count in ``n``."""
    leaves = jax.tree.leaves(tree)
    s = sum(jnp.sum(g) for g in leaves)
    s2 = sum(jnp.sum(g * g) for g in leaves)
    n = float(sum(g.size for g in leaves) + zeros_left_out)
    return s, s2, jnp.asarray(n)


def _derive_stats(s, s2, n) -> Dict[str, jnp.ndarray]:
    n = jnp.maximum(n, 1.0)
    mean = s / n
    mag = jnp.sqrt(s2 / n)
    return {
        "sum": s,
        "sq_sum": s2,
        "n": n,
        "mean": mean,
        "mag": mag,
        "var": s2 / n - mag ** 2,            # reference formula (== 0)
        "var_corrected": s2 / n - mean ** 2,  # meaningful variance
        "norm": jnp.sqrt(s2),
    }


def _resolve_dtype(name: Optional[str]):
    """Dtype of a precision-policy entry; None for absent OR an explicit
    "float32" — the two spellings must compile the identical program."""
    if name is None or str(name) == "float32":
        return None
    dt = jnp.dtype(name)
    if not jnp.issubdtype(dt, jnp.floating):
        raise ValueError(f"precision dtype must be floating, got {name!r}")
    return dt


def _cast_floats(tree: Any, dt) -> Any:
    """Cast every floating leaf to ``dt`` (ints/bools pass through —
    token ids and masks keep their layouts)."""
    return jax.tree.map(
        lambda x: x.astype(dt)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x, tree)


def build_client_update(task: BaseTask, client_opt_cfg,
                        hparams: ClientHParams) -> Callable:
    """Returns ``client_update(global_params, arrays, sample_mask, lr, rng)``
    -> ``(pseudo_grad, train_loss, num_samples, stats)``.

    ``arrays``: dict of ``[S, B, ...]`` feature arrays; ``sample_mask``:
    ``[S, B]``.  Pure and side-effect free: safe under vmap/shard_map/jit.
    """
    tx = make_optimizer(client_opt_cfg)
    freeze = hparams.freeze_layers
    # NOTE on rematerialization: each local step's grad is taken inside the
    # step scan, so wrapping task.loss in jax.checkpoint here would buy no
    # peak-HBM reduction (the step's own residuals still materialize).
    # Remat belongs INSIDE the model, per block — see model_config.remat
    # (models/ringlm.py, nn.remat around the transformer block).
    loss_fn = task.loss

    # precision policy: "float32"/None compile the exact legacy trace —
    # the cast helpers are built ONLY for a non-f32 dtype, so an absent
    # (or explicit f32) policy cannot perturb bit-identity
    pdt = _resolve_dtype(hparams.param_dtype)
    cdt = _resolve_dtype(hparams.compute_dtype)
    sdt = _resolve_dtype(hparams.stats_dtype) or jnp.float32
    if cdt is not None:
        base_loss = loss_fn

        def loss_fn(p, batch, rng, train):  # noqa: F811 - deliberate wrap
            # bf16 forward/backward: params + float features cast at the
            # loss boundary; autodiff transposes the cast, so grads come
            # back in the (f32 master) params dtype
            return base_loss(_cast_floats(p, cdt),
                             {k: _cast_floats(v, cdt)
                              for k, v in batch.items()}, rng, train)

    pallas_sgd = bool(hparams.pallas_apply)
    if pallas_sgd and not sgd_pallas_fusable(client_opt_cfg):
        raise ValueError(
            "megakernel.pallas_apply requires a plain SGD client "
            "optimizer (momentum ok; no nesterov/weight_decay) — got "
            f"type={client_opt_cfg.get('type', 'sgd')!r}")
    if pallas_sgd and hparams.updatable_layers is not None:
        raise ValueError(
            "megakernel.pallas_apply does not compose with "
            "updatable_layers: the flat fused kernel has no per-leaf "
            "freeze mask — drop one of them")
    sgd_mu = float(client_opt_cfg.get("momentum", 0.0) or 0.0)
    # the loop may carry a leaf as the window the model reads of it only
    # where a coordinate whose gradient is always zero keeps its value
    # and its optimizer state, and its pseudo-gradient is the exact zero
    # (a working copy in another dtype leaves ``w0 - w0.astype(dt)``
    # there); SCAFFOLD's offset is asked at the call
    may_narrow = (not pallas_sgd and pdt is None and not freeze
                  and hparams.updatable_layers is None
                  and zero_grad_is_noop(client_opt_cfg))
    # what the model counts inside its forward pass (an expert layer's
    # load): summed over the local steps, out as ``stats["ctr_<name>"]``
    counter_names = tuple(getattr(task, "counter_names", ()))

    def client_update(global_params, arrays: Dict[str, jnp.ndarray],
                      sample_mask: jnp.ndarray, lr: jnp.ndarray,
                      rng: jax.Array, grad_offset=None):
        """``grad_offset`` (optional params-shaped pytree) is added to every
        inner step's gradient — the drift-correction hook used by SCAFFOLD's
        ``c - c_i`` control variate (``strategies/scaffold.py``); it
        participates in clipping like any other gradient term.  ``None``
        compiles to the plain path."""
        local_params = (jax.tree.map(lambda w: w.astype(pdt), global_params)
                        if pdt is not None else global_params)
        # which leaves the model reads a window of at these shapes (a
        # kernel's taps that can meet an input): the loop carries, steps
        # and differentiates the window alone; ``{}`` (every task but
        # the ResNet's, every shape without a dead tap) narrows nothing
        # and is the whole-leaf trace
        windows = {}
        if may_narrow and grad_offset is None:
            windows = task.kernel_windows(global_params, {
                **{k: jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                   for k, a in arrays.items()},
                "sample_mask": jax.ShapeDtypeStruct(
                    sample_mask.shape[1:], sample_mask.dtype)})
        anchor = _narrow(global_params, windows)
        local_params = _narrow(local_params, windows)
        dead = sum(a.size - b.size for a, b in zip(
            jax.tree.leaves(global_params), jax.tree.leaves(anchor)))
        if pallas_sgd:
            # flat momentum carry + the trace-time unravel closure; the
            # optax state machinery is bypassed entirely
            from jax.flatten_util import ravel_pytree
            flat0, unravel = ravel_pytree(local_params)
            opt_state = jnp.zeros_like(flat0)
        else:
            opt_state = tx.init(local_params)
            opt_state.hyperparams["learning_rate"] = lr
        update_mask = (_updatable_mask(global_params,
                                       hparams.updatable_layers)
                       if hparams.updatable_layers is not None else None)

        def one_step(carry, xs):
            (params, opt_state, rng, loss_sum, s, s2, n_acc, wloss_acc,
             ns_acc, ctr_acc) = carry
            batch_arrays, mask = xs
            batch = dict(batch_arrays)
            batch["sample_mask"] = mask
            rng, sub = jax.random.split(rng)
            (loss, _aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch, sub, True)
            # offset + proximal + clip in one combining traversal
            # (optim/fused.py; bit-identical association to the legacy
            # three-pass spelling)
            grads = combine_grad_terms(
                grads, offset=grad_offset, prox_mu=hparams.fedprox_mu,
                params=params, global_params=anchor,
                max_norm=hparams.max_grad_norm)
            has_data = (jnp.sum(mask) > 0).astype(jnp.float32)
            # sufficient stats per batch (core/trainer.py:271-292)
            ds, ds2, dn = _suff_stats_of(grads, dead)
            # the .astype(sdt) keeps the scan carry dtype stable under a
            # non-f32 stats policy; same-dtype casts compile to nothing,
            # so the f32 default trace is unchanged
            s = (s + has_data * ds).astype(sdt)
            s2 = (s2 + has_data * ds2).astype(sdt)
            n_acc = (n_acc + has_data * dn).astype(sdt)
            loss_sum = (loss_sum + has_data * loss).astype(sdt)
            # SAMPLE-weighted loss sum: loss is the batch's masked MEAN,
            # so loss * sum(mask) restores the per-sample sum — dividing
            # by (num_epochs * n_k) later gives a mean that is invariant
            # to how the samples were split into batches (q-FFL weights)
            wloss_acc = (wloss_acc + loss * jnp.sum(mask)).astype(sdt)
            # the task decides how the trainer COUNTS its samples
            # (reference core/trainer.py:397-405: rows by default, token
            # positions for mlm/frame-bearing batches) — this feeds
            # aggregation weights and DGA's train_loss/num_samples metric
            ns_acc = (ns_acc + has_data * _aux.get(
                "train_sample_count", jnp.sum(mask))).astype(sdt)
            ctr_acc = {name: ctr_acc[name] + has_data *
                       _aux["counters"][name] for name in counter_names}
            if pallas_sgd:
                # megakernel tail: the whole optimizer step is one
                # fused pass over the flattened param vector, with the
                # all-padding no-op gate folded into the kernel
                from jax.flatten_util import ravel_pytree
                from ..ops.pallas_kernels import fused_sgd_apply
                new_p, opt_state = fused_sgd_apply(
                    ravel_pytree(params)[0], ravel_pytree(grads)[0],
                    opt_state, lr, sgd_mu, has_data)
                params = unravel(new_p)
            else:
                # optimizer transform + frozen-layer mask + apply + the
                # all-padding no-op pin (momentum included), apply+pin
                # fused into one traversal (optim/fused.py)
                params, opt_state = fused_apply(
                    tx, grads, opt_state, params,
                    update_mask=update_mask, has_data=has_data)
            return (params, opt_state, rng, loss_sum, s, s2, n_acc,
                    wloss_acc, ns_acc, ctr_acc), None

        params = local_params
        loss_sum = jnp.zeros((), sdt)
        s = jnp.zeros((), sdt)
        s2 = jnp.zeros((), sdt)
        n_acc = jnp.zeros((), sdt)
        wloss_acc = jnp.zeros((), sdt)
        ns_acc = jnp.zeros((), sdt)
        carry = (params, opt_state, rng, loss_sum, s, s2, n_acc, wloss_acc,
                 ns_acc, {name: jnp.zeros((), jnp.float32)
                          for name in counter_names})
        # the catalogue scope of the local steps (docs/observability.md,
        # "Named scopes"): forward, backward, the client optimizer's
        # update; every model-level scope lies inside it
        with jax.named_scope("client_steps"):
            if hparams.num_epochs <= 1:
                # one epoch: the scan reads the step grids as its xs
                carry, _ = jax.lax.scan(one_step, carry,
                                        (arrays, sample_mask))
            else:
                # epoch fusion: ONE scan over the flattened
                # [num_epochs * steps] grid — the body is traced once,
                # and each step dynamic-slices its batch out of the
                # resident [S, B, ...] grids (an HBM-local gather, no
                # host bytes)
                n_steps = sample_mask.shape[0]
                step_ids = (jnp.arange(hparams.num_epochs * n_steps,
                                       dtype=jnp.int32) % n_steps)

                def fused_step(carry, t):
                    xs = jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(
                            a, t, 0, keepdims=False),
                        (arrays, sample_mask))
                    return one_step(carry, xs)

                carry, _ = jax.lax.scan(fused_step, carry, step_ids)
        (params, opt_state, rng, loss_sum, s, s2, n_acc, wloss_acc,
         ns_acc, ctr_acc) = carry

        pseudo_grad = jax.tree.map(lambda w0, w: w0 - w, anchor, params)
        if freeze:
            pseudo_grad = _freeze_layers(pseudo_grad, freeze)

        if hparams.stats_on_smooth_grad:
            # recompute stats on the pseudo-gradient (dga.py:104-108)
            s, s2, n = _suff_stats_of(pseudo_grad, dead)
            stats = _derive_stats(s, s2, n)
        else:
            stats = _derive_stats(s, s2, n_acc)
        # once a round: a window's pseudo-gradient into the shape of its
        # leaf; a dead tap never moved, so its ``w0 - w`` is the zero
        # the padding writes
        pseudo_grad = _widen(pseudo_grad, global_params, windows)

        rows = jnp.sum(sample_mask)
        # per-SAMPLE (per-ROW) mean training loss, invariant to batch
        # partitioning (consumed by q-FFL's fairness weights,
        # strategies/qffl.py) — rows on purpose: wloss_acc accumulates
        # row-weighted batch means, regardless of the task's trainer
        # counting unit below
        stats["mean_sample_loss"] = wloss_acc / jnp.maximum(
            rows * hparams.num_epochs, 1.0)
        for name in counter_names:
            stats[f"ctr_{name}"] = ctr_acc[name]
        # ns_acc is the task's counting unit for this client — the
        # epoch loop re-counts per epoch like the reference
        # (train_desired_samples accumulates per epoch), so divide back
        num_samples = ns_acc / jnp.maximum(hparams.num_epochs, 1)
        return pseudo_grad, loss_sum, num_samples, stats

    return client_update


def build_mega_update(task: BaseTask, client_opt_cfg,
                      hparams: ClientHParams) -> Callable:
    """Cross-client megabatch lane scan (``server_config.megabatch``).

    Returns ``mega_update(global_params, arrays, sample_mask, client_ids,
    ptr, seg, lr, rng, init_rows=None, offset_rows=None, rng_salt=None)``
    -> the SAME per-row outputs as ``vmap(client_update)`` over the grid:
    ``(pseudo_grad [K,...], train_loss [K], num_samples [K], stats {[K]})``.

    Geometry: ``arrays``/``sample_mask`` are the bucket's shard-local
    ``[K, S, B, ...]`` grids; ``ptr``/``seg`` the ``[L, T]`` pointer tape
    from :func:`..data.batching.plan_megabatch`.  Instead of one vmap
    lane per client (K lanes, most steps padding), the scan runs ``L``
    lanes for ``T`` steps and every lane trains a CONCATENATION of small
    clients: at a slot whose segment id changes, the lane resets params /
    optimizer / rng / accumulators to the fresh client state
    (:func:`..optim.fused.segment_select`); at a segment's last slot the
    finished client's outputs scatter into its grid row of the output
    stacks.  Per-step math is ``one_step`` verbatim — same fused grad
    combine, same accumulator order, same no-op pinning — so each
    client's update is computed from exactly its own samples.

    rng identity contract (tests/test_megabatch.py): the per-client rng
    still folds on TRUE client ids, but the lane stream is COMPACT — it
    splits only on the client's real steps, while the vmap arm also
    splits on the grid's padded tail steps.  For ``num_epochs == 1`` the
    real steps consume the identical split prefix, so f32 results are
    BITWISE equal; for ``num_epochs > 1`` the streams diverge from epoch
    2 onward and rng-consuming losses (dropout) are only equal to
    MEGABATCH_FINAL_LOSS_RTOL — rng-free losses stay bitwise.

    Strategy hooks (``BaseStrategy.megabatch_passes``): ``init_rows``
    (``[K, n_flat]``) replaces the global start/anchor per client —
    FedBuff's stale history rows, personalization's local models;
    ``offset_rows`` is SCAFFOLD's flattened ``c - c_i`` drift correction;
    ``rng_salt`` reproduces a strategy's ``fold_in(rng_c, salt)``
    sub-stream.  Padding rows (``seg`` never points at them) come back
    with the exact values the vmap arm produces for masked-out rows.
    """
    tx = make_optimizer(client_opt_cfg)
    freeze = hparams.freeze_layers
    loss_fn = task.loss
    pdt = _resolve_dtype(hparams.param_dtype)
    cdt = _resolve_dtype(hparams.compute_dtype)
    sdt = _resolve_dtype(hparams.stats_dtype) or jnp.float32
    if cdt is not None:
        base_loss = loss_fn

        def loss_fn(p, batch, rng, train):  # noqa: F811 - deliberate wrap
            return base_loss(_cast_floats(p, cdt),
                             {k: _cast_floats(v, cdt)
                              for k, v in batch.items()}, rng, train)

    if hparams.pallas_apply:
        # engine/round.py refuses this combination up front; the raise
        # here keeps the builder safe standalone
        raise ValueError(
            "server_config.megabatch is incompatible with "
            "megakernel.pallas_apply: the flat fused kernel has no "
            "segment-reset lane — drop one of them")
    E = max(int(hparams.num_epochs), 1)

    def mega_update(global_params, arrays: Dict[str, jnp.ndarray],
                    sample_mask: jnp.ndarray, client_ids: jnp.ndarray,
                    ptr: jnp.ndarray, seg: jnp.ndarray, lr: jnp.ndarray,
                    rng: jax.Array, init_rows=None, offset_rows=None,
                    rng_salt=None):
        from jax.flatten_util import ravel_pytree
        K, S = int(sample_mask.shape[0]), int(sample_mask.shape[1])
        L = int(ptr.shape[0])
        _, unravel = ravel_pytree(global_params)
        update_mask = (_updatable_mask(global_params,
                                       hparams.updatable_layers)
                       if hparams.updatable_layers is not None else None)

        # flatten [K, S, ...] -> [K*S, ...]: a tape pointer is the
        # shard-local flat step index row*S + step, so each lane's batch
        # is ONE dynamic row gather out of the resident grids
        arrays_flat = {k: a.reshape((K * S,) + a.shape[2:])
                       for k, a in arrays.items()}
        mask_flat = sample_mask.reshape((K * S,) + sample_mask.shape[2:])

        def _fresh(seg_t):
            """(anchor, local-params) of the segment's client — the
            anchor is what prox/pseudo-grad measure against (the global,
            or the strategy's per-client start row)."""
            if init_rows is None:
                anchor = global_params
            else:
                anchor = unravel(init_rows[jnp.clip(seg_t, 0, K - 1)])
            lp = (jax.tree.map(lambda w: w.astype(pdt), anchor)
                  if pdt is not None else anchor)
            return anchor, lp

        def _fresh_rng(seg_t):
            cid = client_ids[jnp.clip(seg_t, 0, K - 1)]
            r = jax.random.fold_in(rng, cid)
            if rng_salt is not None:
                r = jax.random.fold_in(r, int(rng_salt))
            return r

        def lane_step(carry, xs):
            """ONE tape slot of ONE lane (vmapped over lanes).  Body is
            ``one_step`` with the segment reset in front and the harvest
            candidate behind."""
            (params, opt_state, rng_l, loss_sum, s, s2, n_acc, wloss_acc,
             ns_acc, rows_acc) = carry
            ptr_t, seg_t, start_t, _end_t = xs
            live = seg_t >= 0

            # --- segment start: this slot begins a NEW client
            anchor, fresh_lp = _fresh(seg_t)
            fresh_opt = tx.init(fresh_lp)
            fresh_opt.hyperparams["learning_rate"] = lr
            params = segment_select(start_t, fresh_lp, params)
            opt_state = segment_select(start_t, fresh_opt, opt_state)
            rng_l = jnp.where(start_t, _fresh_rng(seg_t), rng_l)
            zero = jnp.zeros((), sdt)
            loss_sum, s, s2, n_acc, wloss_acc, ns_acc, rows_acc = (
                jnp.where(start_t, zero, v)
                for v in (loss_sum, s, s2, n_acc, wloss_acc, ns_acc,
                          rows_acc))

            # --- one_step verbatim on the gathered batch
            batch = {k: a[ptr_t] for k, a in arrays_flat.items()}
            mask = jnp.where(live, mask_flat[ptr_t],
                             jnp.zeros_like(mask_flat[ptr_t]))
            batch["sample_mask"] = mask
            off = (None if offset_rows is None else
                   unravel(offset_rows[jnp.clip(seg_t, 0, K - 1)]))
            rng_l, sub = jax.random.split(rng_l)
            (loss, _aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, sub, True)
            grads = combine_grad_terms(
                grads, offset=off, prox_mu=hparams.fedprox_mu,
                params=params, global_params=anchor,
                max_norm=hparams.max_grad_norm)
            has_data = (jnp.sum(mask) > 0).astype(jnp.float32)
            ds, ds2, dn = _suff_stats_of(grads)
            s = (s + has_data * ds).astype(sdt)
            s2 = (s2 + has_data * ds2).astype(sdt)
            n_acc = (n_acc + has_data * dn).astype(sdt)
            loss_sum = (loss_sum + has_data * loss).astype(sdt)
            wloss_acc = (wloss_acc + loss * jnp.sum(mask)).astype(sdt)
            ns_acc = (ns_acc + has_data * _aux.get(
                "train_sample_count", jnp.sum(mask))).astype(sdt)
            # mask rows are 0/1 so the stepwise sum is exact in f32 —
            # rows_acc lands on rows * num_epochs bitwise, the vmap
            # arm's mean_sample_loss denominator
            rows_acc = (rows_acc + jnp.sum(mask)).astype(sdt)
            params, opt_state = fused_apply(
                tx, grads, opt_state, params,
                update_mask=update_mask, has_data=has_data)

            # --- harvest candidate (scattered only at segment ends)
            pg = jax.tree.map(lambda w0, w: w0 - w, anchor, params)
            if freeze:
                pg = _freeze_layers(pg, freeze)
            if hparams.stats_on_smooth_grad:
                hs, hs2, hn = _suff_stats_of(pg)
                stats = _derive_stats(hs, hs2, hn)
            else:
                stats = _derive_stats(s, s2, n_acc)
            stats["mean_sample_loss"] = wloss_acc / jnp.maximum(
                rows_acc, 1.0)
            num_samples = ns_acc / jnp.maximum(E, 1)
            new_carry = (params, opt_state, rng_l, loss_sum, s, s2,
                         n_acc, wloss_acc, ns_acc, rows_acc)
            return new_carry, (pg, loss_sum, num_samples, stats)

        def scan_body(carry, xs):
            lane_carry, (pg_stack, tl_stack, ns_stack, stats_stack) = carry
            ptr_t, seg_t, start_t, end_t = xs
            new_lane_carry, cand = jax.vmap(lane_step)(
                lane_carry, (ptr_t, seg_t, start_t, end_t))
            # each finished segment owns exactly one grid row, so the
            # lane->row scatter has unique in-bounds targets; idle/non-
            # end lanes aim at row K and drop
            idx = jnp.where(end_t & (seg_t >= 0), seg_t, K)
            pg_stack = jax.tree.map(
                lambda o, v: o.at[idx].set(v, mode="drop"),
                pg_stack, cand[0])
            tl_stack = tl_stack.at[idx].set(cand[1], mode="drop")
            ns_stack = ns_stack.at[idx].set(cand[2], mode="drop")
            stats_stack = jax.tree.map(
                lambda o, v: o.at[idx].set(v, mode="drop"),
                stats_stack, cand[3])
            return (new_lane_carry,
                    (pg_stack, tl_stack, ns_stack, stats_stack)), None

        # --- segment boundaries, derived from the tape in-trace
        ptr_T, seg_T = ptr.T, seg.T                      # [T, L]
        fence = jnp.full((1, L), -2, seg.dtype)
        start_T = seg_T != jnp.concatenate([fence, seg_T[:-1]])
        end_T = seg_T != jnp.concatenate([seg_T[1:], fence])

        # --- output stacks start at the vmap arm's PADDING-ROW values,
        # so rows no segment ends on (client_mask == 0 rows) come back
        # identical to a grid row that ran all-masked steps
        def _pg0_of(tree):
            if pdt is None:
                out = jax.tree.map(jnp.zeros_like, tree)
            else:
                out = jax.tree.map(lambda w: w - w.astype(pdt), tree)
            return _freeze_layers(out, freeze) if freeze else out

        if init_rows is None:
            pg0_one = _pg0_of(global_params)
            pg0 = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (K,) + x.shape),
                pg0_one)
        else:
            pg0 = jax.vmap(lambda r: _pg0_of(unravel(r)))(init_rows)
        if hparams.stats_on_smooth_grad:
            stats0 = jax.vmap(
                lambda t: _derive_stats(*_suff_stats_of(t)))(pg0)
        else:
            z_k = jnp.zeros((K,), sdt)
            stats0 = _derive_stats(z_k, z_k, z_k)
        stats0 = dict(stats0)
        stats0["mean_sample_loss"] = jnp.zeros((K,), sdt)
        tl0 = jnp.zeros((K,), sdt)
        ns0 = jnp.zeros((K,), sdt)

        # --- initial lane carry (slot 0 always starts a segment, so
        # these are reset before any math touches them)
        lp0_one = (jax.tree.map(lambda w: w.astype(pdt), global_params)
                   if pdt is not None else global_params)
        opt0_one = tx.init(lp0_one)
        opt0_one.hyperparams["learning_rate"] = lr
        bcast = lambda x: jnp.broadcast_to(  # noqa: E731
            jnp.asarray(x)[None], (L,) + jnp.asarray(x).shape)
        lane_params0 = jax.tree.map(bcast, lp0_one)
        lane_opt0 = jax.tree.map(bcast, opt0_one)
        rng0 = bcast(rng)
        z_l = jnp.zeros((L,), sdt)
        lane_carry0 = (lane_params0, lane_opt0, rng0, z_l, z_l, z_l, z_l,
                       z_l, z_l, z_l)

        # the lanes' local steps: the same catalogue scope as
        # ``client_update``'s loop
        with jax.named_scope("client_steps"):
            (_, outs), _ = jax.lax.scan(
                scan_body, (lane_carry0, (pg0, tl0, ns0, stats0)),
                (ptr_T, seg_T, start_T, end_T))
        return outs

    return mega_update


def _key_path(path) -> Tuple[str, ...]:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _narrow(tree: Any, windows: Dict[Tuple[str, ...], Tuple]) -> Any:
    """``tree`` with each leaf named in ``windows`` (``BaseTask.
    kernel_windows``) cut to its ``(start, limit)``; the tree itself
    where there is none."""
    if not windows:
        return tree

    def cut(path, leaf):
        window = windows.get(_key_path(path))
        return leaf if window is None else jax.lax.slice(leaf, *window)
    return jax.tree_util.tree_map_with_path(cut, tree)


def _widen(narrow: Any, whole: Any,
           windows: Dict[Tuple[str, ...], Tuple]) -> Any:
    """Inverse of :func:`_narrow` around zeros: each cut leaf of
    ``narrow`` padded to the shape of ``whole``'s leaf, at its place."""
    if not windows:
        return narrow

    def pad(path, cut, leaf):
        window = windows.get(_key_path(path))
        if window is None:
            return cut
        return jax.lax.pad(cut, jnp.zeros((), cut.dtype), [
            (lo, size - hi, 0) for lo, hi, size
            in zip(*window, leaf.shape)])
    return jax.tree_util.tree_map_with_path(pad, narrow, whole)


def _updatable_mask(params, patterns) -> Any:
    """Per-leaf PYTHON bools from the updatable_layers regex allowlist
    (names are '.'-joined like torch's named_parameters; patterns are
    start-anchored via re.match, matching the reference).  Static at
    trace time, so frozen updates compile to nothing.  Shared by the
    per-client and megabatch update builders."""
    import logging
    import re

    from ..utils.logging import print_rank
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keeps = []
    for path, leaf in flat:
        name = ".".join(_key_path(path))
        keep = any(re.match(pat, name) for pat in patterns)
        print_rank(("updating " if keep else "freezing ") + name,
                   loglevel=logging.DEBUG)
        keeps.append(bool(keep))
    return jax.tree_util.tree_unflatten(treedef, keeps)


def _freeze_layers(tree: Any, freeze: Tuple[str, ...]) -> Any:
    """Zero pseudo-gradients of frozen layers by path-name match
    (reference zeroes ``p.grad`` for names in ``freeze_layer``,
    ``core/client.py:306-307``, ``core/strategies/fedavg.py:83-88``)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)
    paths_leaves, treedef = flat
    out = []
    for path, leaf in paths_leaves:
        name = "/".join(_key_path(path))
        if any(f in name for f in freeze):
            out.append(jnp.zeros_like(leaf))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)
