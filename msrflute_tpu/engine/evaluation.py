"""Distributed evaluation.

Parity target: reference ``core/evaluation.py`` + ``run_validation_generic``
(``core/trainer.py:690-723``) + ``Metrics.call_inference``
(``core/metrics.py:29-73``): eval users are chunked across workers
(``core/evaluation.py:185-216``), each runs the model over its shard, and
metrics are sample-weighted averaged server-side
(``core/evaluation.py:160-183``).

TPU-native: all eval samples are packed into a ``[T, B, ...]`` grid
(:func:`msrflute_tpu.data.batching.pack_eval_batches`), the batch axis T is
sharded over the mesh's ``clients`` axis, a ``lax.scan`` accumulates each
task's *sum*-form eval stats, and one ``psum`` merges shards — numerically
identical to the reference's weighted average, in one compiled program.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..models.base import BaseTask
from ..parallel.mesh import CLIENTS_AXIS
from ..telemetry import compiles as compile_spans
from ..utils.metrics import MetricsDict


def build_eval_fn(task: BaseTask, mesh: Mesh,
                  partition_mode: str = "shard_map") -> Callable:
    """Returns jitted ``eval_fn(params, batches) -> stat sums`` where
    ``batches`` is the dict from ``pack_eval_batches`` (leading axis T padded
    to a multiple of the clients-axis size).  ``partition_mode='gspmd'``
    skips the explicit shard_map/psum so model-sharded params work (XLA
    partitions the scan body itself)."""
    cspec = P(CLIENTS_AXIS)
    rspec = P()

    def shard_body(params, batches):
        batches = {k: v for k, v in batches.items() if k != "user_idx"}

        def body(carry, batch):
            sums, skipped = carry
            step = task.eval_stats(params, batch)
            # eval-side non-finite guard (fluteshield): a single client
            # batch producing a NaN/Inf stat would otherwise poison the
            # whole split's sums — and through best_val/plateau, the LR
            # schedule's history, permanently.  A poisoned step's ENTIRE
            # contribution (including its sample_count) is excluded, so
            # the surviving weighted average stays consistent; the
            # skipped-step count rides out with the sums for the
            # structured `eval_nonfinite_skipped` event.  All-finite
            # evals are numerically identical (where(True) is identity).
            finite = jnp.asarray(True)
            for leaf in jax.tree.leaves(step):
                if jnp.issubdtype(leaf.dtype, jnp.floating):
                    finite = finite & jnp.all(jnp.isfinite(leaf))
            step = jax.tree.map(
                lambda s: jnp.where(finite, s, jnp.zeros_like(s)), step)
            return (jax.tree.map(jnp.add, sums, step),
                    skipped + (1.0 - finite.astype(jnp.float32))), None

        # zero-initialize the carry; zeros_like only needs shapes, so the
        # extra eval_stats trace is dead-code-eliminated by XLA
        first = {k: v[0] for k, v in batches.items()}
        zero = jax.tree.map(jnp.zeros_like, task.eval_stats(params, first))
        (sums, skipped), _ = jax.lax.scan(
            body, (zero, jnp.zeros((), jnp.float32)), batches)
        if partition_mode == "shard_map":
            sums = jax.lax.psum(sums, CLIENTS_AXIS)
            skipped = jax.lax.psum(skipped, CLIENTS_AXIS)
        sums = dict(sums)
        sums["__eval_nonfinite_steps__"] = skipped
        return sums

    if partition_mode == "shard_map":
        fn = shard_map(shard_body, mesh=mesh,
                       in_specs=(rspec, cspec), out_specs=rspec,
                       check_vma=False)
    else:
        fn = shard_body
    return jax.jit(fn)


def build_per_user_eval_fn(task: BaseTask, mesh: Mesh, n_users: int,
                           partition_mode: str = "shard_map") -> Callable:
    """Jitted ``(params, batches) -> (correct [n_users], count [n_users])``
    classification accuracy segmented by the eval grid's ``user_idx``.

    Fairness observability (the q-FFL / AFL complement — aggregate
    accuracy hides the client dispersion those strategies optimize): one
    scan over the same packed eval grid the metric eval uses, with
    per-sample correctness scattered into per-user sums
    (``.at[].add(mode="drop")``; padding rows map out of bounds).
    Requires a classification-style task (``task.apply`` + ``y`` labels).
    """
    cspec = P(CLIENTS_AXIS)
    rspec = P()

    def shard_body(params, batches):
        def body(carry, batch):
            c, t = carry
            pred = jnp.argmax(task.apply(params, batch["x"]), axis=-1)
            correct = (pred == batch["y"].astype(jnp.int32)).astype(
                jnp.float32) * batch["sample_mask"]
            uid = batch["user_idx"]
            # -1 padding must NOT wrap to the last user: send it out of
            # bounds so mode="drop" discards it
            uid = jnp.where(uid >= 0, uid, n_users)
            c = c.at[uid].add(correct, mode="drop")
            t = t.at[uid].add(batch["sample_mask"], mode="drop")
            return (c, t), None

        zero = (jnp.zeros((n_users,), jnp.float32),
                jnp.zeros((n_users,), jnp.float32))
        (c, t), _ = jax.lax.scan(body, zero, batches)
        if partition_mode == "shard_map":
            c = jax.lax.psum(c, CLIENTS_AXIS)
            t = jax.lax.psum(t, CLIENTS_AXIS)
        return c, t

    if partition_mode == "shard_map":
        fn = shard_map(shard_body, mesh=mesh,
                       in_specs=(rspec, cspec), out_specs=rspec,
                       check_vma=False)
    else:
        fn = shard_body
    return jax.jit(fn)


def per_user_accuracy(per_user_fn: Callable, params: Any,
                      batches: Dict[str, np.ndarray], mesh: Mesh,
                      partition_mode: str = "shard_map") -> np.ndarray:
    """Per-user accuracy vector (NaN where a user had no eval samples)."""
    spec = P(CLIENTS_AXIS) if partition_mode == "shard_map" else P()
    sharding = NamedSharding(mesh, spec)
    # flint: disable=put-loop eval-boundary staging, not the per-round dispatch path
    staged = {k: jax.device_put(v, sharding) for k, v in batches.items()}
    c, t = jax.device_get(per_user_fn(params, staged))
    c, t = np.asarray(c, np.float64), np.asarray(t, np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        return np.where(t > 0, c / np.maximum(t, 1.0), np.nan)


def evaluate(task: BaseTask, eval_fn: Callable, params: Any,
             batches: Dict[str, np.ndarray], mesh: Mesh,
             partition_mode: str = "shard_map",
             telemetry=None) -> MetricsDict:
    """Run the jitted eval program and finalize metrics host-side.

    In shard_map mode the batch-step axis T is sharded over ``clients``
    (data-parallel eval); in gspmd mode batches stay replicated and the
    model axis shards the compute instead (a scan cannot iterate a sharded
    leading axis without resharding every step).

    ``telemetry``: optional flutescope scope — the device program +
    stat-sums fetch becomes its own ``eval_device`` span so a trace
    separates eval device time from the host metric finalize.
    """
    spec = P(CLIENTS_AXIS) if partition_mode == "shard_map" else P()
    sharding = NamedSharding(mesh, spec)
    # flint: disable=put-loop eval-boundary staging, not the per-round dispatch path
    staged = {k: jax.device_put(v, sharding) for k, v in batches.items()}
    if telemetry is not None:
        # the evaluation program's first launch is followed by its scope
        # map (telemetry/compiles.py); None while no tracer is attached
        before = compile_spans.programs_before(eval_fn)
        with telemetry.span("eval_device"):
            sums = eval_fn(params, staged)
            compile_spans.program_scopes(eval_fn, before, (params, staged))
            sums = jax.device_get(sums)
    else:
        sums = jax.device_get(eval_fn(params, staged))
    sums = dict(sums)
    skipped = float(sums.pop("__eval_nonfinite_steps__", 0.0))
    metrics = task.finalize_metrics(sums)
    if skipped:
        from ..telemetry import emit_event
        # structured record in the metrics stream (and trace when on):
        # the split's aggregate EXCLUDED this many poisoned batch steps
        emit_event(telemetry, "eval_nonfinite_skipped",
                   steps=int(skipped))
        if float(sums.get("sample_count", 0.0)) <= 0.0:
            # EVERY step was poisoned: the zero-sum "metrics" would read
            # as a perfect loss of 0.0 and hijack best_val — surface NaN
            # so the server's finite gate skips best/plateau updates
            from ..utils.metrics import Metric
            metrics = {name: Metric(float("nan"), m.higher_is_better)
                       for name, m in metrics.items()}
    return metrics
