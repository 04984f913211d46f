"""The comparison that decides ``correct``.

``compare`` returns the numbers, ``judge`` holds each to the limit the
configuration's file gives it (a number without a limit is an error, a
limit without a number is ignored: a configuration without DP has no
noise moments, a single-round dispatch no later rounds).  All on the
host, in numpy float64.

Three parties:

- the REFERENCE (``reference/fedround.py``, float32), run twice: round 0
  at ``highest``, and every round of the first timed dispatch at the
  backend's default matmul precision (what the configurations state);
- the CHECK program: the engine's own one-round dispatch of round 0's
  cohort, traced under ``highest`` — compared with the ``highest``
  reference tightly, so a lower compute precision in the program fails;
- the TIMED program's first dispatch (default precision, as users run
  it; the object the window then drives): compared with the
  default-precision reference over the same number of rounds, on both
  sides — every round's loss, round 0's aggregate norm as the server
  optimizer gets it, and what the dispatch did to the weights by length
  and by direction.  (Against the ``highest`` reference the ResNet's
  timed dispatch reads 0.82 of the movement and losses up to 32% apart
  after five rounds: on the TPU the default's bf16 passes train visibly
  differently, which is why each program is held to a reference of its
  own precision.)  These bands catch a wrong update: half a learning
  rate, a flipped sign, a shard left out of the sum, a quantiser or
  noise skipped.
"""

from __future__ import annotations

import numpy as np


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        for key in sorted(tree):
            yield from _leaves(tree[key], f"{prefix}/{key}")
    else:
        yield prefix, np.asarray(tree, np.float64)


def _delta(before, after):
    return [(name, a - b) for (name, a), (_, b) in
            zip(_leaves(before), _leaves(after))]


def _norm(leaves) -> float:
    return float(np.sqrt(sum(np.sum(v * v) for _, v in leaves)))


def _dot(a, b) -> float:
    return float(sum(np.sum(x * y) for (_, x), (_, y) in zip(a, b)))


def compare(*, init_params, ref_check: dict, refs_timed: list, rounds: list,
            check_stats: dict, check_params, timed_first: dict,
            timed_first_params, dp: dict | None) -> list:
    """``[(name, value)]``.  ``ref_check`` = the ``highest`` reference's
    round 0, ``refs_timed`` = the default-precision reference's result
    for each round of the first dispatch, ``rounds`` = those rounds'
    inputs.  ``dp`` = ``{"sigma", "max_grad"}`` where the configuration
    adds global-DP noise to the aggregate."""
    numbers = []
    ref = ref_check
    num_clients = max(float(np.sum(rounds[0]["client_mask"] > 0)), 1.0)
    server_lr = float(rounds[0]["server_lr"])
    ref_loss = float(np.mean(ref["train_loss"]))
    check_loss = float(check_stats["train_loss_sum"] /
                       max(check_stats["client_count"], 1.0))
    numbers.append(("loss_gap", abs(check_loss - ref_loss) / abs(ref_loss)))
    ref_pseudo = float(np.mean(ref["pseudo_norm"]))
    numbers.append(("pseudo_norm_gap",
                    abs(float(check_stats["grad_norm"]) - ref_pseudo) /
                    ref_pseudo))

    ref_delta = _delta(init_params, ref["new_params"])     # = lr * aggregate
    check_delta = _delta(init_params, check_params)
    ref_norm = _norm(ref_delta)
    ref_agg = _norm(list(_leaves(ref["aggregate"])))
    elements = sum(v.size for _, v in ref_delta)
    # global DP adds N(0, noise^2) to every element of the aggregate
    noise = (float(dp["sigma"]) * float(dp["max_grad"]) / num_clients
             if dp else 0.0)
    if dp is None:
        diff = [(n, a - b) for (n, a), (_, b) in zip(check_delta, ref_delta)]
        numbers.append(("update_diff", _norm(diff) / ref_norm))
        leaf_norms = [float(np.sqrt(np.sum(v * v))) for _, v in ref_delta]
        floor = float(np.median(leaf_norms))
        numbers.append(("update_gap_worst_leaf", max(
            abs(float(np.sqrt(np.sum(c * c))) - r) / max(r, floor)
            for (_, c), r in zip(check_delta, leaf_norms))))
        numbers.append(("agg_norm_gap",
                        abs(float(check_stats["agg_grad_norm"]) - ref_agg) /
                        ref_agg))
    else:
        # the program adds the noise and the reference does not: the
        # residual in units of lr * noise is N(0, 1)
        resid = np.concatenate([
            (a - b).ravel() for (_, a), (_, b) in
            zip(check_delta, ref_delta)]) / (server_lr * noise)
        centred = resid - resid.mean()
        std = float(resid.std())
        numbers += [
            ("noise_mean", abs(float(resid.mean()))),
            ("noise_std_gap", abs(std - 1.0)),
            ("noise_kurtosis_gap",
             abs(float(np.mean(centred ** 4)) / std ** 4 - 3.0)),
            # a quantisation bin or a threshold that fell the other way
            # shows as a residual far outside the noise
            ("noise_outlier_share", float(np.mean(np.abs(resid) > 6.0))),
        ]

    # -- the timed program's first dispatch against the reference --------
    ref_losses = [float(np.mean(r["train_loss"])) for r in refs_timed]
    gaps = [abs(float(t) - r) / abs(r)
            for t, r in zip(timed_first["losses"], ref_losses)]
    numbers.append(("timed_loss_gap", gaps[0]))
    if len(gaps) > 1:
        numbers.append(("timed_later_loss_gap", max(gaps[1:])))
    # a part of the cohort left out of the timed batch, in any round
    numbers.append(("timed_client_gap", float(sum(
        abs(float(c) - float(np.sum(r["client_mask"] > 0)))
        for c, r in zip(timed_first["client_count"], rounds))) +
        abs(len(timed_first["client_count"]) - len(rounds))))
    # round 0's aggregate as the server optimizer gets it, noise included
    timed_agg = _norm(list(_leaves(refs_timed[0]["aggregate"])))
    want_agg = float(np.sqrt(timed_agg ** 2 + elements * noise ** 2))
    numbers.append(("timed_agg_norm_gap",
                    abs(float(timed_first["agg_grad_norm"][0]) - want_agg) /
                    want_agg))
    # what the whole dispatch did to the weights, against the reference
    # after as many rounds: by length and by direction, on both sides
    want = _delta(init_params, refs_timed[-1]["new_params"])
    got = _delta(init_params, timed_first_params)
    want_norm = _norm(want)
    lr_noise = float(np.sqrt(sum(
        (float(r["server_lr"]) * noise) ** 2 for r in rounds)))
    numbers.append(("timed_update_norm_gap", abs(
        _norm(got) / float(np.sqrt(want_norm ** 2 +
                                   elements * lr_noise ** 2)) - 1.0)))
    numbers.append(("timed_update_projection_gap",
                    abs(_dot(got, want) / want_norm ** 2 - 1.0)))
    if dp is not None:
        resid = np.concatenate([(a - b).ravel() for (_, a), (_, b) in
                                zip(got, want)]) / lr_noise
        numbers.append(("timed_noise_std_gap",
                        abs(float(resid.std()) - 1.0)))
    return numbers


def judge(numbers: list, limits: dict) -> list:
    verdicts = []
    for name, value in numbers:
        if name not in limits:
            raise KeyError(f"the configuration gives no limit for {name!r}")
        limit = float(limits[name]["limit"])
        verdicts.append({"name": name, "value": float(value), "limit": limit,
                         "ok": bool(np.isfinite(value) and value <= limit)})
    return verdicts
