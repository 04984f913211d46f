"""Kernels: device time of the causal attention core inside the round
program (the scopes ``mla_attn_core`` / ``gqa_attn_core``: the tiled
kernels and the layout copies around them, which have no name of their
own in the trace), per chip, over the rounds the round program ran in
the traced window (``scope_times.py``).  ``attn_kernel_ms`` beside it
is the kernels alone, the evaluation program's calls included."""
from benchmarks.scope_times import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("mla_attn_core", "gqa_attn_core"))
