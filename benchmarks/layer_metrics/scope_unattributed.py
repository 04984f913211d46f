"""Round program: the share of its traced device time that lies under
no catalogue scope at all (compiler-made operations without metadata,
the staged buffers' unpacking, and whatever of the program's time is no
operation's), over the program's (``scope_times.py``)."""
from benchmarks.scope_times import read as scope_table

UNIT = "%"


def read(ctx):
    table = scope_table(ctx)
    if table is None or table["module_s"] <= 0:
        return None
    return 100.0 * table["unattributed_s"] / table["module_s"]
