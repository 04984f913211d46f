"""Inside ``dispatch`` (``engine/round.py::_dispatch_staged``): span
``stage_host``, stacking the rounds' arrays, fault vectors and scalars
and packing each dtype group into one buffer."""
from benchmarks.readers import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("stage_host",))
