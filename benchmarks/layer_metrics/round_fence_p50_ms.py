"""Stats fetch and host tail: the median over the traced window's
dispatches of fence-to-fence milliseconds a round, which is what
``round_s_p50`` reports end to end in the cells that keep it there.  In a
cell whose host chain and round program are of nearly equal length the
dispatches fall into two groups (device-paced and host-paced) and this
median sits between them, so it swings from run to run: a layer's
reading, not a bound's (``BENCHMARK.json`` keeps it to such cells)."""
import statistics

UNIT = "ms/round"


def read(ctx):
    per_round = ctx.get("window", {}).get("per_round_s")
    if not per_round:
        return None
    return 1e3 * statistics.median(per_round)
