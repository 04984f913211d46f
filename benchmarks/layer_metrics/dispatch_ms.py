"""Staged transfer + dispatch (``engine/round.py::_dispatch_staged``):
span ``dispatch``."""
from benchmarks.readers import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("dispatch",))
