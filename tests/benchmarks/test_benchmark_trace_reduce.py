"""The trace -> metrics reduction on a small recorded trace."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import trace_reduce  # noqa: E402


def test_interval_arithmetic():
    merged = trace_reduce.union([[5, 7], [0, 2], [1, 3], [7, 8]])
    assert merged == [[0, 3], [5, 8]]
    assert trace_reduce.length(merged) == 6
    assert trace_reduce.subtract([[0, 10]], merged) == [[3, 5], [8, 10]]
    assert trace_reduce.subtract(merged, [[0, 10]]) == []
    assert trace_reduce.subtract([[0, 4], [6, 9]], [[2, 7]]) == \
        [[0, 2], [7, 9]]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_small.json")) as fh:
        return json.load(fh)


def test_reduction_of_the_recorded_trace(recorded):
    out = trace_reduce.reduce_events(
        recorded["trace"], recorded["spans"], tuple(recorded["window"]),
        recorded["sync_epoch_s"])
    want = recorded["expect"]
    assert out["chips"] == want["chips"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["clock_synced"]
    assert out["collective_exposed_s"] == pytest.approx(
        want["collective_exposed_s"], rel=1e-9, abs=1e-12)
    assert [n for n, _ in out["breakdown"]["device_ops"]][:3] == \
        want["top_ops"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    gaps = dict(out["breakdown"]["idle_gaps"])
    # one chip: what is not busy in the window is idle, and every idle
    # gap is given to a host span (or to "no_host_span")
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-3)
    assert list(gaps) == want["gap_owners"]
    assert sum(out["op_counts"].values()) == want["n_ops"]
    assert not any(name.startswith("%") or " = " in name
                   for name in out["op_seconds"])
    for name, seconds in want["module_seconds"].items():
        assert out["module_seconds"][name] == pytest.approx(seconds)


def test_a_trace_without_a_device_plane_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events(
            {"planes": [{"name": "/host:CPU", "lines": []}]}, [], (0, 1), 0)
