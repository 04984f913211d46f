"""Host tail (``engine/server.py::_drain_host_tail``): span ``host_tail``,
which holds the evaluation and the checkpoint submit of a boundary round.
(``stats_fetch`` is not added: it is the fence, where the host waits for
the device.)"""
from benchmarks.readers import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("host_tail",))
