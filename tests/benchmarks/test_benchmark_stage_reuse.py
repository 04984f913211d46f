"""The layer-metric reader of the staging buffers' reuse (ISSUE 34's,
added by ISSUE 35): of the window's ``stage_host`` spans that say
``reused``, the share that say true.  Nothing, and no error, on a
program whose span does not say it: the driver lays these files over the
parent's checkout too."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402

METRIC = "stage_reuse"
WINDOW = {"t_open": 10.0, "t_close": 20.0}


def _stage(ts, **args):
    return {"kind": "span", "name": "stage_host", "ts": ts, "dur_s": 0.03,
            "thread": "MainThread", "rounds": 5, **args}


@pytest.fixture(scope="module")
def reader():
    return harness.load_layer_metrics(harness.BENCH_DIR)[METRIC]


def test_the_entry_is_found_by_its_name(reader):
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert len(entry) == 1 and entry[0]["unit"] == reader.UNIT == "%"
    assert entry[0]["source"] == "program_span"
    assert entry[0]["better"] == "higher"
    assert entry[0]["moves"] == "clients_per_s"
    # the layer's name, letter for letter, as stage_host_ms gives it
    assert entry[0]["layer"] == next(m["layer"] for m in bench["per_layer"]
                                     if m["name"] == "stage_host_ms")
    assert set(entry[0]["workloads"]) == {w["name"]
                                          for w in bench["workloads"]}


@pytest.mark.parametrize("flags, share", [
    ((True, True, True, True), 100.0),
    ((False, True, True, True), 75.0),
    # shapes that change from chunk to chunk: allocated every time
    ((False, False), 0.0),
])
def test_share_of_the_windows_spans_that_say_true(reader, flags, share):
    spans = [_stage(11.0 + i, reused=flag) for i, flag in enumerate(flags)]
    # the warm-up's allocations, before the window opens: not counted;
    # nor a span after it has closed, nor one that does not say
    spans += [_stage(2.0, reused=False), _stage(3.0, reused=False),
              _stage(25.0, reused=False), _stage(12.5)]
    assert reader.read({"spans": spans, "window": WINDOW}) == \
        pytest.approx(share)


@pytest.mark.parametrize("program", ["parent", "outside", "no_spans"])
def test_reader_finds_nothing_on_a_program_without_it(reader, program):
    spans = {
        # a tree before PR 34: the span, without the word
        "parent": [_stage(11.0), _stage(12.0)],
        # said, but only while warming up
        "outside": [_stage(2.0, reused=False), _stage(3.0, reused=True)],
        "no_spans": [],
    }[program]
    assert reader.read({"spans": spans, "window": WINDOW}) is None
