"""The layer-metric reader of the training thread's waits on the
checkpoint writer (ISSUE 32): summed ``ckpt_wait`` seconds over the
window's saves, and ``ckpt_write_ms`` beside it still reading one whole
save now that a best-model save is the writer's (``ckpt_async_write``
with ``bytes``).  Nothing, and no error, on a program without the span:
the driver lays these files over the parent's checkout too."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402

METRIC = "ckpt_wait_ms"
WINDOW = {"t_open": 10.0, "t_close": 20.0}
BYTES = 1877142949


def _span(name, ts, dur_s, thread="MainThread", **args):
    return {"kind": "span", "name": name, "ts": ts, "dur_s": dur_s,
            "thread": thread, **args}


def _period(t0, wait_s):
    """One evaluation period of the expert cell as the change runs it:
    the best-model snapshot handed over (a wait for the slot), the file
    written on the writer's track, the tail's wait for it behind the next
    launch, the ``latest`` link."""
    return [
        _span("ckpt_wait", t0, 1e-5, **{"for": "best"}),
        _span("ckpt_async_write", t0 + 0.01, 2.4,
              thread="ckpt-latest-writer", bytes=BYTES,
              file="best_val_loss_model.msgpack"),
        _span("ckpt_wait", t0 + 0.6, wait_s, **{"for": "best"}),
        _span("ckpt_submit", t0 + 0.6 + wait_s, 0.02, round=4,
              deferred=True),
        _span("ckpt_wait", t0 + 0.6 + wait_s, 2e-5, **{"for": "best"}),
    ]


@pytest.fixture(scope="module")
def readers():
    return harness.load_layer_metrics(harness.BENCH_DIR)


def test_the_entry_is_found_by_its_name(readers):
    """By its name, not by its place: a later PR's entries go after it."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    found = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert len(found) == 1
    entry, = found
    assert entry["name"] == METRIC and entry["unit"] == readers[METRIC].UNIT
    assert entry["source"] == "program_span"
    assert entry["moves"] == "clients_per_s"
    # the layer's name, letter for letter, as ckpt_write_ms gives it
    assert entry["layer"] == next(m["layer"] for m in bench["per_layer"]
                                  if m["name"] == "ckpt_write_ms")
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}


def test_waits_are_summed_over_the_windows_saves(readers):
    spans = _period(11.0, 0.004) + _period(16.0, 1.8)
    # before the window opens and after it closes: not counted
    spans += _period(-5.0, 9.0) + _period(21.0, 9.0)
    value = readers[METRIC].read({"spans": spans, "window": WINDOW})
    assert value == pytest.approx(
        1e3 * (0.004 + 1.8 + 2 * (1e-5 + 2e-5)) / 2)


@pytest.mark.parametrize("case", ["deferred_best", "with_a_latest"])
def test_a_best_model_save_of_the_writer_is_counted_once(readers, case):
    """``ckpt_write_ms`` keeps reading one whole save: the best-model
    file is one ``ckpt_async_write`` with ``bytes``, and the ``latest``
    that is a link to it writes nothing and has no such span."""
    spans = _period(11.0, 0.004)
    expect = 2400.0
    if case == "with_a_latest":
        # a round between evaluations: the writer's own `latest`
        spans.append(_span("ckpt_async_write", 15.0, 2.0,
                           thread="ckpt-latest-writer", bytes=BYTES,
                           file="latest_model.msgpack"))
        expect = 2200.0
    assert readers["ckpt_write_ms"].read(
        {"spans": spans, "window": WINDOW}) == pytest.approx(expect)


@pytest.mark.parametrize("program", ["parent", "no_saves", "no_spans"])
def test_reader_finds_nothing_on_a_program_without_it(readers, program):
    spans = {
        # the parent: synchronous best-model saves, no wait span at all
        "parent": [_span("ckpt_write", 11.0, 2.3, bytes=BYTES),
                   _span("ckpt_submit", 13.4, 0.33, round=4)],
        # waits but no save that says its bytes inside the window
        "no_saves": [_span("ckpt_wait", 11.0, 0.1, **{"for": "latest"})],
        "no_spans": [],
    }[program]
    assert readers[METRIC].read({"spans": spans, "window": WINDOW}) is None
