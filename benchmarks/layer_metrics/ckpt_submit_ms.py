"""Checkpoint (``engine/checkpoint.py``): span ``ckpt_submit``, per save."""
from benchmarks.readers import ms_per_event

UNIT = "ms/save"


def read(ctx):
    return ms_per_event(ctx, "ckpt_submit")
