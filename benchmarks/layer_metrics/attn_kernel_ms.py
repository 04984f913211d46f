"""Kernels (``ops/pallas_attention.py``): device time of the causal
attention core's three Pallas kernels (``attn_flash_fwd``,
``attn_flash_dq``, ``attn_flash_dkv``) in the traced window, per chip,
over the rounds the round program ran (the evaluation program's forward
calls are in it: they are the window's too).  Nothing to read on a
program that runs the plain path (every tree before PR 37)."""
from benchmarks.attn_rooflines import kernel_times
from benchmarks.readers import round_program

UNIT = "ms/round"


def read(ctx):
    found = kernel_times(ctx["trace"])
    _, rounds = round_program(ctx)
    if not found or not rounds:
        return None
    seconds = sum(s for s, _ in found.values())
    return 1e3 * seconds / rounds  # the trace's seconds are a chip's
