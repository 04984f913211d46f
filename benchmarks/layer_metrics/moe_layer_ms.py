"""Expert layer: device time of the routed experts (the scope
``routed_experts`` of the token models: routing, the two gathers and
the three grouped products, forward and backward), per chip, over the
rounds the round program ran in the traced window
(``scope_times.py``)."""
from benchmarks.scope_times import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("routed_experts",))
