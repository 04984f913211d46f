"""Round program: device time of the attention layers' projections,
norms and RoPE (the scopes ``mla_proj`` / ``gqa_proj``), per chip, over
the rounds the round program ran in the traced window
(``scope_times.py``)."""
from benchmarks.scope_times import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("mla_proj", "gqa_proj"))
