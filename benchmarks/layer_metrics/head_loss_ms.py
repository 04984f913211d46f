"""Round program: device time of the final norm, the head's product and
the log-softmax with their backward (the scope ``lm_head_loss`` of the
token models), per chip, over the rounds the round program ran in the
traced window (``scope_times.py``)."""
from benchmarks.scope_times import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("lm_head_loss",))
