"""Eval (``engine/server.py::_packed_eval_batches``): span ``eval_pack``,
host packing and staging of a split's evaluation grid (done once a
split, then served from the cache), per evaluation of the window."""
from benchmarks.readers import window_spans

UNIT = "ms/eval"


def read(ctx):
    packs = window_spans(ctx, "eval_pack")
    evals = window_spans(ctx, "eval")
    if not packs or not evals:
        return None
    return 1e3 * sum(s["dur_s"] for s in packs) / len(evals)
