"""Round program: device time of the round outside the clients' local
steps (the scope ``round_aggregate`` of ``engine/round.py``, its own
operations only: pseudo-gradient and its statistics, clipping, DP
noise, quantisation without the threshold's selection, the strategy's
weights, the weighted sum, the server optimizer's step, the packed
stats), per chip, over the rounds the round program ran in the traced
window (``scope_times.py``)."""
from benchmarks.scope_times import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("round_aggregate",), own=True)
