"""The layer-metric readers of the program's own child spans (ISSUE 24),
each on a small recorded set of spans: what a traced run of the change
gives, and nothing (not an error) on a program that lacks the spans."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402

NEW = ("stage_host_ms", "h2d_ms", "launch_ms", "fence_wait_ms",
       "stats_d2h_ms", "dispatch_starved", "eval_pack_ms",
       "setup_compile_s", "setup_start_s")
#: what the parent program's events.jsonl had: no ids, no child spans
OLD_NAMES = ("pack", "dispatch", "stats_fetch", "host_tail", "eval",
             "eval_device", "ckpt_submit", "housekeeping", "round_device",
             "ckpt_async_write")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "spans_small.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def readers():
    return harness.load_layer_metrics(harness.BENCH_DIR)


@pytest.mark.parametrize("metric", NEW)
def test_reader_on_the_recorded_spans(recorded, readers, metric):
    ctx = {"spans": recorded["spans"], "window": recorded["window"]}
    value = readers[metric].read(ctx)
    assert value == pytest.approx(recorded["expect"][metric], rel=1e-9)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        entry = [m for m in json.load(fh)["per_layer"]
                 if m["name"] == metric]
    assert len(entry) == 1 and entry[0]["unit"] == readers[metric].UNIT
    assert entry[0]["source"] == "program_span"


@pytest.mark.parametrize("metric", NEW)
def test_reader_finds_nothing_on_the_parent_program(recorded, readers,
                                                    metric):
    """The driver lays these files over the parent's checkout: there the
    spans they read do not exist, and the line leaves the metric out."""
    old = [{k: v for k, v in s.items()
            if k in ("name", "ts", "dur_s", "round0", "rounds", "round",
                     "split")}
           for s in recorded["spans"] if s["name"] in OLD_NAMES]
    assert old
    ctx = {"spans": old, "window": recorded["window"]}
    assert readers[metric].read(ctx) is None
    assert readers[metric].read({"spans": [],
                                 "window": recorded["window"]}) is None


def test_children_cover_their_parents_in_the_recorded_window(recorded,
                                                             readers):
    """What the five span metrics are for: the parts add up to the whole
    (``dispatch_ms`` from its three, ``stats_fetch`` from its two)."""
    ctx = {"spans": recorded["spans"], "window": recorded["window"]}
    parts = sum(readers[m].read(ctx) for m in
                ("stage_host_ms", "h2d_ms", "launch_ms"))
    whole = readers["dispatch_ms"].read(ctx)
    assert 0.8 * whole <= parts <= whole
    from benchmarks.readers import ms_per_round
    fetch = ms_per_round(ctx, ("stats_fetch",))
    parts = readers["fence_wait_ms"].read(ctx) + \
        readers["stats_d2h_ms"].read(ctx)
    assert 0.95 * fetch <= parts <= fetch


def test_setup_compile_counts_a_nested_trace_once(readers):
    spans = [
        {"name": "jit_trace", "ts": 0.0, "dur_s": 4.0, "thread": "Main"},
        {"name": "jit_trace", "ts": 1.0, "dur_s": 1.0, "thread": "Main"},
        {"name": "compile", "ts": 4.0, "dur_s": 2.0, "thread": "Main"},
        {"name": "compile", "ts": 1.0, "dur_s": 0.5, "thread": "other"},
        {"name": "compile", "ts": 9.5, "dur_s": 1.0, "thread": "Main"},
    ]
    ctx = {"spans": spans, "window": {"t_open": 10.0, "t_close": 20.0}}
    # 0-6 on one thread, 0.5 s on the other; the last ends in the window
    assert readers["setup_compile_s"].read(ctx) == pytest.approx(6.5)
