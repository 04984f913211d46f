"""Expert layer (``ops/moe.py::held_experts_ffn``): the share of the
grouped products' rows that are real (token, expert) pairs.  Every held
expert's pairs are rounded up to whole tiles of 128 rows
(``ops.moe.TILE_ROWS``; at least one tile an expert), and the three
kernels run every row of every active tile: ``100 x moe_pairs_held /
(moe_tiles_active x 128)`` over the window's ``host_tail`` spans, both
counters summed over expert layers and local steps.  Small experts at a
fraction of their deployment's load are where the padding costs most.
Nothing to read on a program without the counter (every tree before
PR 36)."""
from benchmarks.readers import window_spans

UNIT = "%"
TILE_ROWS = 128


def read(ctx):
    spans = [s for s in window_spans(ctx, "host_tail")
             if "moe_tiles_active" in s and "moe_pairs_held" in s]
    rows = TILE_ROWS * sum(s["moe_tiles_active"] for s in spans)
    if rows <= 0:
        return None
    return 100.0 * sum(s["moe_pairs_held"] for s in spans) / rows
