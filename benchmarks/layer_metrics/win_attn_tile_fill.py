"""Kernels (``ops/pallas_attention.py``): the share of the score pairs
the window law's static tile map runs that the band lets a query see:
``100 x pairs_seen / (tiles_run x block_q x block_k)`` from the
program's ``attn_window_tiles`` event (the tiles the diagonal or the
band's lower edge crosses are run whole).  Nothing to read on a program
without the event (every tree before PR 43)."""
from benchmarks.win_attn_rooflines import tile_event

UNIT = "%"


def read(ctx):
    event = tile_event(ctx)
    if not event or not event.get("tiles_run"):
        return None
    return 100.0 * event["pairs_seen"] / (
        event["tiles_run"] * event["block_q"] * event["block_k"])
