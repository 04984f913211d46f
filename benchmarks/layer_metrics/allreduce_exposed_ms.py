"""Collectives (the psum of the shard_map round): collective time during
which no compute runs on that chip, per round.  Nothing to read on one
chip."""
from benchmarks.readers import round_program

UNIT = "ms/round"


def read(ctx):
    trace = ctx["trace"]
    _, rounds = round_program(ctx)
    if trace["collective_s"] <= 0 or not rounds:
        return None
    return 1e3 * trace["collective_exposed_s"] / rounds
