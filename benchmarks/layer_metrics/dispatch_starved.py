"""Device: the share of the window's ``dispatch`` spans whose
``inflight`` arg is 0 — no chunk of the pending ring still had a
program running (``is_ready()`` of its stats, asked before any work), so
the device had nothing left to run when the host began preparing the
next chunk.  100 by construction at ``pipeline_depth: 0``; near 0 where
the ring hides the host."""
from benchmarks.readers import window_spans

UNIT = "%"


def read(ctx):
    asked = [s for s in window_spans(ctx, "dispatch") if "inflight" in s]
    if not asked:
        return None
    return 100.0 * sum(s["inflight"] == 0 for s in asked) / len(asked)
