"""The layer-metric reader of the pre-dispatch ``latest`` snapshot
(ISSUE 25) on a small recorded set of spans: milliseconds per round from
the span's own ``rounds``, and nothing (not an error) on a program whose
span lacks it or has no such span."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402

METRIC = "ckpt_presubmit_ms"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "spans_presubmit.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def reader():
    return harness.load_layer_metrics(harness.BENCH_DIR)[METRIC]


def test_reader_on_the_recorded_spans(recorded, reader):
    ctx = {"spans": recorded["spans"], "window": recorded["window"]}
    assert reader.read(ctx) == pytest.approx(
        recorded["expect"][METRIC], rel=1e-9)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert len(entry) == 1 and entry[0]["unit"] == reader.UNIT
    assert entry[0]["source"] == "program_span"
    assert entry[0]["layer"] == "checkpoint"
    assert entry[0]["moves"] == "clients_per_s"
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(METRIC) > names.index("setup_start_s"), \
        "new entries go after the ones that were there"


def test_reader_divides_by_the_chunks_rounds_not_by_dispatches(recorded,
                                                               reader):
    """Two-round chunks: per round is half of per span, and a window
    that holds one of the two spans reads that span alone."""
    spans = [s for s in recorded["spans"] if s["name"] == "ckpt_presubmit"]
    assert len(spans) == 2 and all(s["rounds"] == 2 for s in spans)
    assert all(s["leaves"] >= 1 and s["programs"] == 1 for s in spans)
    first, second = spans
    window = {"t_open": first["ts"] - 1.0, "t_close": second["ts"] - 1e-3}
    ctx = {"spans": recorded["spans"], "window": window}
    assert reader.read(ctx) == pytest.approx(1e3 * first["dur_s"] / 2)


@pytest.mark.parametrize("program", ["parent", "no_span", "no_spans"])
def test_reader_finds_nothing_on_a_program_without_it(recorded, reader,
                                                      program):
    """The driver lays this file over the parent's checkout: there
    ``ckpt_presubmit`` carries ``round`` and ``chunk`` only, and the
    line leaves the metric out."""
    spans = {
        "parent": [{k: v for k, v in s.items()
                    if k not in ("rounds", "leaves", "programs")}
                   if s["name"] == "ckpt_presubmit" else s
                   for s in recorded["spans"]],
        "no_span": [s for s in recorded["spans"]
                    if s["name"] != "ckpt_presubmit"],
        "no_spans": [],
    }[program]
    assert reader.read({"spans": spans,
                        "window": recorded["window"]}) is None
