"""Kernels (``ops/pallas_attention.py``): device time of the
sliding-window attention core's three Pallas kernels (``attn_win_fwd``,
``attn_win_dq``, ``attn_win_dkv``) in the traced window, per chip, over
the rounds the round program ran (the evaluation program's forward calls
are in it: they are the window's too).  Nothing to read on a program
without these kernels (every tree before PR 43)."""
from benchmarks.readers import round_program
from benchmarks.win_attn_rooflines import kernel_times

UNIT = "ms/round"


def read(ctx):
    found = kernel_times(ctx["trace"])
    _, rounds = round_program(ctx)
    if not found or not rounds:
        return None
    seconds = sum(s for s, _ in found.values())
    return 1e3 * seconds / rounds  # the trace's seconds are a chip's
