"""Inside ``dispatch`` (``engine/round.py::_dispatch_staged``): span
``h2d``, the ``device_put``s of the packed buffers (the host's time in
the calls, which need not be the DMA's)."""
from benchmarks.readers import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("h2d",))
