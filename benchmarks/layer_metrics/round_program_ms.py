"""Round program (``jit(staged)``): device time of its executions in the
traced window, per chip, over the rounds they ran."""
from benchmarks.readers import round_program

UNIT = "ms/round"


def read(ctx):
    seconds, rounds = round_program(ctx)
    return 1e3 * seconds / rounds if rounds else None
