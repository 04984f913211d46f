"""Set-up: seconds before the window opens during which jax was tracing,
lowering or compiling (spans ``jit_trace``, ``jit_lower``, ``compile``
from ``telemetry/compiles.py``).  The union of their intervals on each
thread, not the sum of their durations: the trace of an inner jitted
function lies inside its caller's."""
from benchmarks.trace_reduce import length, union

UNIT = "s"
NAMES = ("jit_trace", "jit_lower", "compile")


def read(ctx):
    t_open = ctx["window"]["t_open"]
    by_thread = {}
    for s in ctx["spans"]:
        if s["name"] in NAMES and s["ts"] + s["dur_s"] <= t_open:
            by_thread.setdefault(s.get("thread"), []).append(
                [s["ts"], s["ts"] + s["dur_s"]])
    if not by_thread:
        return None
    return sum(length(union(spans)) for spans in by_thread.values())
