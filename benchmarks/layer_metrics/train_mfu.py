"""Model FLOP/s utilisation of the traced window: the forward+backward
matmul and convolution operations the algorithm requires for the rounds
the round program ran (``benchmarks/flops.py`` on the plain model,
nothing recomputed) over window seconds x chips x the MXU peak of
``peaks.json``.  End to end over the window, host gaps included; not a
kernel's roofline share."""
from benchmarks.readers import peak, required_flops_per_round, round_program

UNIT = "%"


def read(ctx):
    _, rounds = round_program(ctx)
    trace = ctx["trace"]
    if not rounds or trace["window_s"] <= 0:
        return None
    return 100.0 * required_flops_per_round(ctx) * rounds / (
        trace["window_s"] * trace["chips"] * peak(ctx)["flops_per_s"])
