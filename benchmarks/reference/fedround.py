"""Plain reference: federated rounds, written out.

Float32 under ``jax.default_matmul_precision("highest")``, a Python loop
over the round's clients in blocks (``vmap`` of one client's plain
training: a block is what fits beside nothing else on the chip and keeps
the reference shorter than the window), ``jax.grad``, plain SGD.  Imports nothing of
``msrflute_tpu`` and takes nothing the program made: the weights come
from the model reference's ``init`` (which the harness also hands to the
program), the data from the benchmark's generator, and the order of
batches from the round's packed input (an input of the program, not a
result of it).

Semantics (the reference's ``core/client.py`` / ``core/trainer.py`` /
``core/strategies``):

- a client starts from the global weights, takes one SGD step per batch
  in the packed order (loss = cross entropy averaged over the batch's
  real rows; an all-padding batch is skipped), and returns
  ``pseudo_gradient = global - trained``, ``train_loss`` = the sum of its
  batch losses and ``num_samples`` = its real rows.  A model reference
  may bring the task's own ``loss(params, batch, model_config)`` and
  ``sample_count(batch)`` over one step's batch (every array of the
  packed batch and ``sample_mask``); ``next_token_loss`` below is the
  plain one for token sequences;
- ``fedavg``: weight = ``num_samples`` capped at 100;
- ``dga``: weight = ``exp(-beta * train_loss / num_samples)`` capped at
  100; the payload is quantised per leaf (``quantise``) when the
  configuration says so; global DP adds N(0, (sigma * max_grad / K)^2)
  to the aggregate — the reference leaves the NOISE out and the check
  tests the residual's moments instead;
- aggregate = sum(w_k * payload_k) / sum(w_k); server SGD:
  ``new = global - server_lr * aggregate``.
"""

from __future__ import annotations

import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

MAX_WEIGHT = 100.0  # the reference's core/strategies/utils.py filter


def xent(logits, labels, mask):
    logp = jax.nn.log_softmax(logits, axis=-1)
    per_row = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(per_row * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def next_token_loss(forward, params, batch, model_config):
    """Cross entropy over the real target positions of ``[B, L]`` token
    rows, sum / count (the reference's seq-to-seq trainers,
    ``ignore_index`` padding): float32 log-softmax; targets = ``y`` where
    the batch has one of ``x``'s rank, else ``x`` shifted by one (the
    model then reads ``x[:, :-1]``); weight = ``tok_mask`` of the target
    positions (absent: targets that are not the padding id 0) x
    ``sample_mask``."""
    x = batch["x"]
    if "y" in batch and batch["y"].ndim == x.ndim:
        inputs, targets, real = x, batch["y"], batch.get("tok_mask")
    else:
        inputs, targets, real = x[:, :-1], x[:, 1:], batch.get("tok_mask")
        real = None if real is None else real[:, 1:]
    if real is None:
        real = (targets != 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(
        forward(params, inputs, model_config).astype(jnp.float32), axis=-1)
    per_token = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    weight = real * batch["sample_mask"][:, None]
    return jnp.sum(per_token * weight) / jnp.maximum(jnp.sum(weight), 1.0)


def seam(forward, loss=None, sample_count=None) -> tuple:
    """``(loss, sample_count)`` of one step's batch: the model
    reference's own where it defines them, else image classification
    (``xent`` of ``forward(x)`` against ``y`` over the real rows, and the
    real rows)."""
    def classification(params, batch, model_config):
        return xent(forward(params, batch["x"], model_config), batch["y"],
                    batch["sample_mask"])

    def rows(batch):
        return jnp.sum(batch["sample_mask"])

    return loss or classification, sample_count or rows


def on_device(array):
    """A packed array as the reference's program takes it: ids and labels
    int32, everything else float32."""
    array = np.asarray(array)
    return jnp.asarray(array, jnp.int32 if np.issubdtype(
        array.dtype, np.integer) else jnp.float32)


def step_arrays(batch: dict) -> dict:
    """The arrays of a round's packed input that are cut into steps
    (``[K, S, B, ...]``): all but ``client_mask`` and the round's
    numbers."""
    return {k: v for k, v in batch.items() if getattr(v, "ndim", 0) >= 3}


def quantise(g, thresh_quantile, bits):
    """The reference's ``quant_model`` on one leaf: the nearest of
    ``2**bits`` levels on ``linspace(min, max)``, zero where ``|g|`` is at
    most the ``thresh_quantile`` quantile of ``|g|``."""
    n_bins = 2 ** int(bits)
    lo, hi = jnp.min(g), jnp.max(g)
    thresh = jnp.quantile(jnp.abs(g), thresh_quantile)
    width = (hi - lo) / (n_bins - 1)
    idx = jnp.clip(jnp.round((g - lo) / jnp.maximum(width, 1e-30)),
                   0, n_bins - 1)
    return jnp.where(jnp.abs(g) > thresh, lo + idx * width, 0.0)


@functools.lru_cache(maxsize=None)
def _block_fn(forward, loss, sample_count, model_items, strategy_items):
    """One jitted function for a BLOCK of clients (``vmap`` of the plain
    per-client training below): the block's weighted payload sum and,
    per client, train loss, sample count, weight and update norm."""
    model_config = dict(model_items)
    strategy = dict(strategy_items)
    loss, sample_count = seam(forward, loss, sample_count)

    def one_client(global_params, steps, live_client, lr, quantile):
        def step(carry, batch):
            params, loss_sum = carry
            value, grads = jax.value_and_grad(loss)(params, batch,
                                                    model_config)
            live = (jnp.sum(batch["sample_mask"]) > 0).astype(jnp.float32)
            params = jax.tree.map(lambda p, g: p - live * lr * g,
                                  params, grads)
            return (params, loss_sum + live * value), None

        (trained, loss_sum), _ = jax.lax.scan(
            step, (global_params, jnp.zeros(())), steps)
        pseudo = jax.tree.map(lambda a, b: a - b, global_params, trained)
        n = jnp.sum(jax.vmap(sample_count)(steps))
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(pseudo)))
        if strategy["name"] == "dga":
            w = jnp.exp(-float(strategy["beta"]) * loss_sum /
                        jnp.maximum(n, 1.0))
            if strategy.get("quant_bits") is not None:
                pseudo = jax.tree.map(
                    lambda g: quantise(g, quantile, strategy["quant_bits"]),
                    pseudo)
        else:
            w = n
        w = jnp.clip(jnp.nan_to_num(w, nan=0.0, posinf=0.0), 0.0,
                     MAX_WEIGHT) * live_client
        return (jax.tree.map(lambda g: g * w, pseudo), loss_sum, n, w, norm)

    def block(global_params, steps, live_clients, lr, quantile):
        terms, loss_sum, n, w, norm = jax.vmap(
            one_client, in_axes=(None, 0, 0, None, None))(
                global_params, steps, live_clients, lr, quantile)
        return (jax.tree.map(lambda t: jnp.sum(t, axis=0), terms),
                loss_sum, n, w, norm)

    return jax.jit(block)


def _fetch(tree) -> dict:
    return jax.tree.map(np.asarray, tree)


def _round_on_device(fn, dev_params, batch: dict, client_lr: float,
                     server_lr: float, quantile, block: int) -> tuple:
    """One round from weights that are on the device already:
    ``(aggregate, new_params)`` still on the device, and the round's
    result without them (per live client, on the host, and the seconds
    of its two parts)."""
    steps = step_arrays(batch)
    live_all = (np.asarray(batch["client_mask"]) > 0).astype(np.float32)
    total_k = len(live_all)
    block = max(1, min(int(block), total_k))
    weighted = None
    losses, counts, weights, norms = [], [], [], []

    def padded(a, lo):
        part = np.asarray(a[lo:lo + block])
        short = block - part.shape[0]
        if short:
            part = np.concatenate(
                [part, np.zeros((short,) + part.shape[1:], part.dtype)])
        return part

    t0 = time.time()
    for lo in range(0, total_k, block):
        live = padded(live_all, lo)
        term, loss_sum, n, w, norm = fn(
            dev_params,
            {k: on_device(padded(v, lo)) for k, v in steps.items()},
            jnp.asarray(live), jnp.float32(client_lr), quantile)
        weighted = term if weighted is None else jax.tree.map(
            jnp.add, weighted, term)
        keep = live > 0
        losses += np.asarray(loss_sum)[keep].tolist()
        counts += np.asarray(n)[keep].tolist()
        weights += np.asarray(w)[keep].tolist()
        norms += np.asarray(norm)[keep].tolist()
    t1 = time.time()
    total = max(sum(weights), 1e-12)
    aggregate = jax.tree.map(lambda g: g / total, weighted)
    new_params = jax.block_until_ready(jax.tree.map(
        lambda p, g: p - server_lr * g, dev_params, aggregate))
    return aggregate, new_params, {
        "train_loss": np.asarray(losses), "num_samples": np.asarray(counts),
        "weight": np.asarray(weights), "pseudo_norm": np.asarray(norms),
        # the clients (the first block's compile with them), the server
        "seconds": {"clients": t1 - t0, "server": time.time() - t1}}


def run_rounds(forward, model_config: dict, params: dict, rounds: list,
               strategy: dict, block: int = 1,
               precision: str | None = "highest", loss=None,
               sample_count=None) -> list:
    """The rounds of one dispatch, in turn: round ``r + 1`` starts from
    round ``r``'s new weights, which stay on the device between the two.

    ``rounds`` = the packed inputs, each ``{"x": [K,S,B,...], "y":
    [K,S,B], "sample_mask": [K,S,B], "client_mask": [K]}`` (numpy; a
    token task's batch has ``tok_mask`` and may have no ``y``: every
    ``[K,S,B,...]`` array goes to ``loss``) with its ``client_lr``,
    ``server_lr`` and (quantised payloads) ``quant_quantile``; ``block``
    clients at a time (the last block is padded with masked-out clients,
    so one program serves every block).  ``loss`` / ``sample_count``: see
    ``seam``.  ``strategy`` = ``{"name": "fedavg"}`` or ``{"name": "dga",
    "beta", "quant_bits"}``.  ``precision`` = the matmul precision
    (``None``: the backend's default, which is what the program runs at
    as configured).

    Returns one result per round, host numpy: per live client the train
    loss, sample count, weight and pseudo-gradient norm, and of the trees
    what the comparison reads and no more: ROUND 0's ``aggregate`` (as
    the server optimizer gets it) and the LAST round's ``new_params``.  A
    tree that nobody reads is neither fetched nor kept: at 0.5 B
    parameters each is 1.9 GB through the host's link and on the host.
    ``seconds``, on each result: the round by part (``upload`` and
    ``fetch`` where it had one)."""
    static = {k: v for k, v in strategy.items() if k != "quant_quantile"}
    fn = _block_fn(forward, loss, sample_count,
                   tuple(sorted(model_config.items())),
                   tuple(sorted(static.items())))
    out = []
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        t0 = time.time()
        dev_params = jax.block_until_ready(
            jax.tree.map(jnp.asarray, params))
        upload = time.time() - t0
        for r, inputs in enumerate(rounds):
            quantile = jnp.float32(
                (inputs.get("quant_quantile")
                 if static.get("quant_bits") is not None else None) or 0.0)
            aggregate, dev_params, result = _round_on_device(
                fn, dev_params, inputs, inputs["client_lr"],
                inputs["server_lr"], quantile, block)
            t0 = time.time()
            if r == 0:
                result["aggregate"] = _fetch(aggregate)
            del aggregate
            if r == len(rounds) - 1:
                result["new_params"] = _fetch(dev_params)
            result["seconds"].update(
                upload=upload if r == 0 else 0.0, fetch=time.time() - t0)
            out.append(result)
    return out


def flops_per_step(forward, model_config: dict, params: dict, batch: dict,
                   count, loss=None) -> float:
    """Matmul and convolution operations of ONE forward+backward of the
    step's loss (``seam``) on one step's ``batch``, counted by ``count``
    (``benchmarks/flops.py``) on this plain model — what the algorithm
    requires, nothing recomputed."""
    loss, _ = seam(forward, loss)
    return count(jax.value_and_grad(
        lambda p, b: loss(p, b, model_config)), params, batch)
