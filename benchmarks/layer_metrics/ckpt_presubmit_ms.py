"""Checkpoint (``engine/server.py::train`` -> ``engine/checkpoint.py::
_mp_submit``): span ``ckpt_presubmit``, the pending chunk's ``latest``
snapshot submitted before the next dispatch donates its buffers, per
round of that chunk (its ``rounds`` argument).  The host sits in it
between two launches, so whatever it waits for there the ring cannot
hide.  Nothing to read on a program whose span carries no ``rounds``."""
from benchmarks.readers import window_spans

UNIT = "ms/round"


def read(ctx):
    spans = [s for s in window_spans(ctx, "ckpt_presubmit")
             if "rounds" in s]
    rounds = sum(s["rounds"] for s in spans)
    return 1e3 * sum(s["dur_s"] for s in spans) / rounds if rounds else None
