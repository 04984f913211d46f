"""Inside ``dispatch`` (``engine/round.py::_dispatch_staged``): span
``launch``, the call of the staged program (trace, lower and compile too
where its ``compiled`` arg is true)."""
from benchmarks.readers import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("launch",))
