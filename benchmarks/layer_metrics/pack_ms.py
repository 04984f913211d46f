"""Host pack (``engine/server.py::pack_chunk``): span ``pack``."""
from benchmarks.readers import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("pack",))
