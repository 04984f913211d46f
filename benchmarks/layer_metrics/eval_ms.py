"""Evaluation (``engine/evaluation.py``): span ``eval``, per evaluation."""
from benchmarks.readers import ms_per_event

UNIT = "ms/eval"


def read(ctx):
    return ms_per_event(ctx, "eval")
