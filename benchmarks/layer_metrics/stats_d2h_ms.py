"""Inside ``stats_fetch`` (``engine/round.py::PackedStats.fetch``): span
``stats_d2h``, the ``device_get`` of the packed stats and their unpack,
once they are ready."""
from benchmarks.readers import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("stats_d2h",))
