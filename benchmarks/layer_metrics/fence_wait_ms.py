"""Inside ``stats_fetch`` (``engine/server.py::_drain_chunk``): span
``fence_wait``, the host waiting until the chunk's program has produced
its stats."""
from benchmarks.readers import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("fence_wait",))
