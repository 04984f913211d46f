"""Expert layer (``ops/moe.py::held_experts_ffn``): the largest held
expert's load over the mean held expert's, averaged over the window's
expert layers and local steps.  From the counters that ride the packed
round stats and land on the ``host_tail`` span (``moe_max_load``: the
largest held expert's pairs, summed over layer-steps; ``moe_pairs_held``:
pairs on held experts; the held count is the configuration's).  1 = even
routing; the pair buffer is sized for the worst, so no value drops a
token (``moe_pairs_dropped`` reads 0).  Nothing to read on a program
without the counters."""
from benchmarks.readers import window_spans

UNIT = "ratio"


def read(ctx):
    spans = [s for s in window_spans(ctx, "host_tail")
             if "moe_pairs_held" in s]
    held = ctx["config"]["model_config"].get("experts_held")
    pairs = sum(s["moe_pairs_held"] for s in spans)
    if not spans or not held or pairs <= 0:
        return None
    return sum(s["moe_max_load"] for s in spans) * float(held) / pairs
