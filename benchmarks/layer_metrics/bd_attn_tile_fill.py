"""Kernels (``ops/pallas_attention.py``): the share of the score pairs
the block-diffusion kernels' static tile map runs that the mask lets a
query see: ``100 x pairs_seen / (tiles_run x block_q x block_k)`` from
the program's ``attn_tiles`` event (the tiles a block boundary crosses,
and each noised tile's own noised tile with a diagonal of blocks in it,
are run whole).  Nothing to read on a program without the event (every
tree before PR 41)."""
from benchmarks.bd_attn_rooflines import tile_event

UNIT = "%"


def read(ctx):
    event = tile_event(ctx)
    if not event or not event.get("tiles_run"):
        return None
    return 100.0 * event["pairs_seen"] / (
        event["tiles_run"] * event["block_q"] * event["block_k"])
