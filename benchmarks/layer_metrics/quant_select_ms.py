"""Round program: device time of the quantiser's threshold selection
(the scope ``quant_select`` of ``ops/quantization.py``: 32
compare-and-count passes over each leaf), per chip, over the rounds the
round program ran in the traced window (``scope_times.py``)."""
from benchmarks.scope_times import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("quant_select",))
