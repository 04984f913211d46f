"""The comparison that decides ``correct``.

``compare`` returns the numbers, ``judge`` holds each to the limit the
configuration's file gives it (a number without a limit is an error, a
limit without a number is ignored: a configuration without DP has no
noise moments, a single-round dispatch no later rounds).  All on the
host, in numpy float64, one leaf at a time.

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

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

LEAVES_AT_A_TIME = 4


def _leaves(tree, prefix=""):
    """``(name, leaf)`` in sorted-key order, each leaf as it is stored."""
    if isinstance(tree, dict):
        for key in sorted(tree):
            yield from _leaves(tree[key], f"{prefix}/{key}")
    else:
        yield prefix, np.atleast_1d(np.asarray(tree))


def _minus(before, after, out=None):
    """``before - after`` of one leaf in float64 (the operands are cast on
    the way in, element for element what a float64 copy of each would
    give); ``out``: a float64 buffer to reuse."""
    return np.subtract(before, after, out=out, dtype=np.float64)


def _sum_squares(trees) -> float:
    """Sum over the leaves of the sum of squares, in float64."""
    return sum(np.sum(np.multiply(leaf, leaf, dtype=np.float64))
               for _, leaf in trees)


def compare(*, init_params, ref_check: dict, refs_timed: list, rounds: list,
            check_stats: dict, check_params, timed_first: dict,
            timed_first_params, dp: dict | None,
            leaf_kinds: dict | None = None,
            leaf_gaps: dict | None = None) -> list:
    """``[(name, value)]``.  ``ref_check`` = the ``highest`` reference's
    round 0, ``refs_timed`` = the default-precision reference's result
    for each round of the first dispatch, ``rounds`` = those rounds'
    inputs.  ``dp`` = ``{"sigma", "max_grad"}`` where the configuration
    adds global-DP noise to the aggregate.  ``leaf_kinds`` = ``{kind:
    [parts of a leaf's path]}``: the leaves whose path holds one of a
    kind's parts have a worst-leaf number of their own,
    ``update_gap_worst_leaf.<kind>``, and ``update_gap_worst_leaf`` is
    the worst of the leaves of no kind (a configuration whose expert
    leaves take a discrete event that the others do not holds the two to
    limits of their own).  ``leaf_gaps``: a dict to fill
    with every leaf's own gap of update norms, ``{leaf's path: gap}``
    (what ``update_gap_worst_leaf`` is the largest of; what its limit is
    set from, ``run.py --readings 1``).

    Leaf by leaf: every norm, dot product and worst-leaf gap is
    accumulated in float64 over the leaves in sorted-key order;
    ``LEAVES_AT_A_TIME`` leaves are worked on side by side, each thread
    in two float64 buffers of the largest leaf's size, never a tree (a 600 M-parameter tree
    is 4.8 GB in float64; seventeen passes over 0.47 B elements on one
    core were 20 s of every run of the expert cell).  Only the DP branch's
    noise residuals keep all elements at once (their mean, std and
    kurtosis are taken over the whole vector): a configuration with
    global DP has to be small enough for two such vectors."""
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

    # deltas are ``init - after`` (= lr * aggregate for one round).  Per
    # leaf: the check program's against the `highest` reference's, then
    # the timed dispatch's (``got``) against the default-precision
    # reference's after as many rounds (``want``)
    elements = sum(leaf.size for _, leaf in _leaves(init_params))
    resid = np.empty(elements) if dp is not None else None
    timed_resid = np.empty(elements) if dp is not None else None
    ref_sq = diff_sq = want_sq = got_sq = got_want = 0
    ref_leaf_norms, check_leaf_norms = [], []
    leaf_names = [name for name, _ in _leaves(init_params)]
    sizes = [leaf.size for _, leaf in _leaves(init_params)]
    offsets = np.cumsum([0] + sizes[:-1])
    largest = max(sizes)
    kept = threading.local()

    def one_leaf(at, init, ref_new, check_new, want_new, got_new):
        """The leaf's six sums, in two float64 buffers that its thread
        keeps from leaf to leaf (fresh ones are mostly page faults)."""
        init, ref_new, check_new, want_new, got_new = (
            leaf.reshape(-1) for _, leaf in (init, ref_new, check_new,
                                             want_new, got_new))
        if not hasattr(kept, "pair"):
            kept.pair = np.empty(largest), np.empty(largest)
        ref_delta = _minus(init, ref_new, out=kept.pair[0][:init.size])
        buffer = np.multiply(ref_delta, ref_delta,
                             out=kept.pair[1][:init.size])
        leaf_sq = np.sum(buffer)
        check_delta = _minus(init, check_new, out=buffer)
        diff = np.subtract(check_delta, ref_delta, out=ref_delta)
        np.multiply(check_delta, check_delta, out=check_delta)
        check_sq = np.sum(check_delta)
        leaf_diff_sq = 0
        if dp is None:
            np.multiply(diff, diff, out=diff)
            leaf_diff_sq = np.sum(diff)
        else:
            resid[at:at + diff.size] = diff

        want = _minus(init, want_new, out=diff)
        np.multiply(want, want, out=buffer)
        leaf_want_sq = np.sum(buffer)
        got = _minus(init, got_new, out=buffer)
        if dp is not None:
            np.subtract(got, want, out=timed_resid[at:at + got.size])
        np.multiply(want, got, out=want)
        leaf_got_want = np.sum(want)
        np.multiply(got, got, out=got)
        return (leaf_sq, check_sq, leaf_diff_sq, leaf_want_sq,
                leaf_got_want, np.sum(got))

    # the leaves side by side on a few threads (numpy lets go of the
    # interpreter inside a pass), their sums added up in the leaves'
    # order: the digits of one leaf after the other
    with ThreadPoolExecutor(max_workers=LEAVES_AT_A_TIME) as pool:
        sums = list(pool.map(
            one_leaf, offsets, _leaves(init_params),
            _leaves(ref["new_params"]), _leaves(check_params),
            _leaves(refs_timed[-1]["new_params"]),
            _leaves(timed_first_params)))
    for (leaf_sq, check_sq, leaf_diff_sq, leaf_want_sq, leaf_got_want,
         leaf_got_sq) in sums:
        ref_sq += leaf_sq
        ref_leaf_norms.append(float(np.sqrt(leaf_sq)))
        check_leaf_norms.append(float(np.sqrt(check_sq)))
        diff_sq += leaf_diff_sq
        want_sq += leaf_want_sq
        got_want += leaf_got_want
        got_sq += leaf_got_sq

    ref_norm = float(np.sqrt(ref_sq))
    ref_agg = float(np.sqrt(_sum_squares(_leaves(ref["aggregate"]))))
    # global DP adds N(0, noise^2) to every element of the aggregate
    noise = (float(dp["sigma"]) * float(dp["max_grad"]) / num_clients
             if dp else 0.0)
    if dp is None:
        numbers.append(("update_diff",
                        float(np.sqrt(diff_sq)) / ref_norm))
        floor = float(np.median(ref_leaf_norms))
        per_leaf = [abs(c - r) / max(r, floor)
                    for c, r in zip(check_leaf_norms, ref_leaf_norms)]
        kind_of = [next((kind for kind, parts in (leaf_kinds or {}).items()
                         if any(part in name for part in parts)), None)
                   for name in leaf_names]
        for kind in [None, *(leaf_kinds or {})]:
            numbers.append((
                f"update_gap_worst_leaf.{kind}" if kind
                else "update_gap_worst_leaf",
                max(gap for gap, k in zip(per_leaf, kind_of) if k == kind)))
        if leaf_gaps is not None:
            leaf_gaps.update(zip(leaf_names, per_leaf))
        numbers.append(("agg_norm_gap",
                        abs(float(check_stats["agg_grad_norm"]) - ref_agg) /
                        ref_agg))
    else:
        # the program adds the noise and the reference does not: the
        # residual in units of lr * noise is N(0, 1)
        resid /= server_lr * noise
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
        del resid, centred

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
    timed_agg = float(np.sqrt(_sum_squares(
        _leaves(refs_timed[0]["aggregate"]))))
    want_agg = float(np.sqrt(timed_agg ** 2 + elements * noise ** 2))
    numbers.append(("timed_agg_norm_gap",
                    abs(float(timed_first["agg_grad_norm"][0]) - want_agg) /
                    want_agg))
    # what the whole dispatch did to the weights, against the reference
    # after as many rounds: by length and by direction, on both sides
    want_norm = float(np.sqrt(want_sq))
    lr_noise = float(np.sqrt(sum(
        (float(r["server_lr"]) * noise) ** 2 for r in rounds)))
    numbers.append(("timed_update_norm_gap", abs(
        float(np.sqrt(got_sq)) / float(np.sqrt(want_norm ** 2 +
                                               elements * lr_noise ** 2)) -
        1.0)))
    numbers.append(("timed_update_projection_gap",
                    abs(float(got_want) / want_norm ** 2 - 1.0)))
    if dp is not None:
        timed_resid /= lr_noise
        numbers.append(("timed_noise_std_gap",
                        abs(float(timed_resid.std()) - 1.0)))
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
