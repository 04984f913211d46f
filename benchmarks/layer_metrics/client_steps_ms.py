"""Round program: device time of the clients' local steps (forward,
backward, the client optimizer's update: the scope ``client_steps`` of
``engine/client_update.py`` with every model-level scope nested in it),
per chip, over the rounds the round program ran in the traced window.
Read through the program's scope map (``scope_times.py``): nothing on a
program that writes none, or whose map is stale."""
from benchmarks.scope_times import ms_per_round

UNIT = "ms/round"


def read(ctx):
    return ms_per_round(ctx, ("client_steps",))
