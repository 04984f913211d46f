"""``check.compare`` leaf by leaf: the numbers of the comparison over
whole float64 trees (the function as it stood at PR 25, kept below as the
oracle), digit for digit, and a host peak of a few float64 leaves."""

import os
import sys
import tracemalloc

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import check  # noqa: E402


# -- the oracle: benchmarks/check.py at PR 25, whole trees in float64 ------
def _old_leaves(tree, prefix=""):
    if isinstance(tree, dict):
        for key in sorted(tree):
            yield from _old_leaves(tree[key], f"{prefix}/{key}")
    else:
        yield prefix, np.asarray(tree, np.float64)


def _old_delta(before, after):
    return [(name, a - b) for (name, a), (_, b) in
            zip(_old_leaves(before), _old_leaves(after))]


def _old_norm(leaves) -> float:
    return float(np.sqrt(sum(np.sum(v * v) for _, v in leaves)))


def _old_dot(a, b) -> float:
    return float(sum(np.sum(x * y) for (_, x), (_, y) in zip(a, b)))


def compare_whole_trees(*, init_params, ref_check: dict, refs_timed: list,
                        rounds: list,
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

    ref_delta = _old_delta(init_params, ref["new_params"])     # = lr * aggregate
    check_delta = _old_delta(init_params, check_params)
    ref_norm = _old_norm(ref_delta)
    ref_agg = _old_norm(list(_old_leaves(ref["aggregate"])))
    elements = sum(v.size for _, v in ref_delta)
    # global DP adds N(0, noise^2) to every element of the aggregate
    noise = (float(dp["sigma"]) * float(dp["max_grad"]) / num_clients
             if dp else 0.0)
    if dp is None:
        diff = [(n, a - b) for (n, a), (_, b) in zip(check_delta, ref_delta)]
        numbers.append(("update_diff", _old_norm(diff) / ref_norm))
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
    timed_agg = _old_norm(list(_old_leaves(refs_timed[0]["aggregate"])))
    want_agg = float(np.sqrt(timed_agg ** 2 + elements * noise ** 2))
    numbers.append(("timed_agg_norm_gap",
                    abs(float(timed_first["agg_grad_norm"][0]) - want_agg) /
                    want_agg))
    # what the whole dispatch did to the weights, against the reference
    # after as many rounds: by length and by direction, on both sides
    want = _old_delta(init_params, refs_timed[-1]["new_params"])
    got = _old_delta(init_params, timed_first_params)
    want_norm = _old_norm(want)
    lr_noise = float(np.sqrt(sum(
        (float(r["server_lr"]) * noise) ** 2 for r in rounds)))
    numbers.append(("timed_update_norm_gap", abs(
        _old_norm(got) / float(np.sqrt(want_norm ** 2 +
                                   elements * lr_noise ** 2)) - 1.0)))
    numbers.append(("timed_update_projection_gap",
                    abs(_old_dot(got, want) / want_norm ** 2 - 1.0)))
    if dp is not None:
        resid = np.concatenate([(a - b).ravel() for (_, a), (_, b) in
                                zip(got, want)]) / lr_noise
        numbers.append(("timed_noise_std_gap",
                        abs(float(resid.std()) - 1.0)))
    return numbers


# --------------------------------------------------------------------------
def _case(rng, leaves=12, size=1000):
    """Seeded trees as a run hands them to ``compare``: float32 leaves of
    mixed shapes in nested dicts, a two-round dispatch."""
    def tree(make):
        out = {}
        for i in range(leaves):
            shape = (size // (i + 1), i + 1) if i % 2 else (size + i,)
            out.setdefault(f"group{i % 3}", {})[f"w{i}"] = make(
                f"group{i % 3}", f"w{i}", shape)
        return out

    def normal(scale):
        return lambda g, k, shape: (
            scale * rng.standard_normal(shape)).astype(np.float32)

    def near(base, scale):
        return tree(lambda g, k, shape: base[g][k] + normal(scale)(
            g, k, shape))

    def aggregate(new):
        return tree(lambda g, k, shape: init[g][k] - new[g][k])

    init = tree(normal(1.0))
    ref_new = near(init, 1e-2)
    timed = [near(ref_new, 1e-4), near(near(init, 2e-2), 1e-4)]
    clients = 5
    return dict(
        init_params=init,
        ref_check={"train_loss": rng.random(clients) + 1.0,
                   "pseudo_norm": rng.random(clients) + 1.0,
                   "new_params": ref_new, "aggregate": aggregate(ref_new)},
        refs_timed=[{"train_loss": rng.random(clients) + 1.0,
                     "new_params": new, "aggregate": aggregate(new)}
                    for new in timed],
        rounds=[{"client_mask": np.ones(clients), "server_lr": 1.0},
                {"client_mask": np.ones(clients), "server_lr": 0.9}],
        check_stats={"train_loss_sum": 7.3, "client_count": 5.0,
                     "grad_norm": 1.4, "agg_grad_norm": 0.3},
        check_params=near(ref_new, 1e-5),
        timed_first={"losses": [1.5, 1.4], "client_count": [5.0, 5.0],
                     "agg_grad_norm": [0.31, 0.3]},
        timed_first_params=near(timed[1], 1e-4))


@pytest.mark.parametrize("dp", [None, {"sigma": 0.01, "max_grad": 1.0}],
                         ids=["plain", "global_dp"])
def test_leaf_by_leaf_gives_the_whole_tree_numbers(dp):
    case = _case(np.random.default_rng(5))
    want = compare_whole_trees(dp=dp, **case)
    got = check.compare(dp=dp, **case)
    assert [name for name, _ in got] == [name for name, _ in want]
    assert len(got) == (11 if dp is None else 13)
    for (name, a), (_, b) in zip(got, want):
        assert a == b, (name, a, b)  # every digit


def test_leaf_kinds_split_the_worst_leaf_number_and_nothing_else():
    """``leaf_kinds``: the leaves whose path holds a kind's part are held
    apart; the worst of both numbers is the one number without kinds,
    each is the worst of its own leaves, every other number is as it
    was."""
    case = _case(np.random.default_rng(7))
    plain = dict(check.compare(dp=None, **case))
    gaps = {}
    split = dict(check.compare(dp=None, leaf_kinds={"routed": ["/group1/"]},
                               leaf_gaps=gaps, **case))
    assert list(split)[3:5] == ["update_gap_worst_leaf",
                                "update_gap_worst_leaf.routed"]
    assert len(gaps) == 12 and sum("/group1/" in name for name in gaps) == 4
    assert split["update_gap_worst_leaf.routed"] == max(
        gap for name, gap in gaps.items() if "/group1/" in name)
    assert split["update_gap_worst_leaf"] == max(
        gap for name, gap in gaps.items() if "/group1/" not in name)
    assert max(split["update_gap_worst_leaf"],
               split["update_gap_worst_leaf.routed"]) == \
        plain["update_gap_worst_leaf"] == max(gaps.values())
    split.pop("update_gap_worst_leaf.routed")
    assert {k: v for k, v in split.items()
            if k != "update_gap_worst_leaf"} == \
        {k: v for k, v in plain.items() if k != "update_gap_worst_leaf"}


def test_compare_holds_a_few_leaves_not_six_trees():
    """About 10**7 elements in a dozen leaves: the peak of host memory
    stays under two float64 copies of each LEAF being worked on, and one
    to spare (the whole-tree form held five to six float64 trees)."""
    case = _case(np.random.default_rng(6), leaves=12, size=840_000)
    sizes = [leaf.size for group in case["init_params"].values()
             for leaf in group.values()]
    assert sum(sizes) > 1e7
    tracemalloc.start()
    try:
        check.compare(dp=None, **case)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    at_a_time = 2 * check.LEAVES_AT_A_TIME + 1
    assert at_a_time < len(sizes)
    assert peak < at_a_time * 8 * max(sizes), (peak, 8 * max(sizes),
                                               8 * sum(sizes))
