"""Round program (``models/token_blocks.py::BlockDiffusionLMTask``): the
share of the real positions the window's local steps trained on that
were masked, and so scored: ``100 x bd_positions_masked /
bd_positions_real`` over the window's ``host_tail`` spans, both counters
summed over rows and local steps.  The rates are uniform on [0.05, 1]:
52.5% is what the draws aim at; the loss's scale and what a step learns
from move with it.  Nothing to read on a program without the counters
(every tree before PR 41)."""
from benchmarks.readers import window_spans

UNIT = "%"


def read(ctx):
    spans = [s for s in window_spans(ctx, "host_tail")
             if "bd_positions_real" in s and "bd_positions_masked" in s]
    real = sum(s["bd_positions_real"] for s in spans)
    if real <= 0:
        return None
    return 100.0 * sum(s["bd_positions_masked"] for s in spans) / real
