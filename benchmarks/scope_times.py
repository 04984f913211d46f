"""The round program's device time by named scope.

The program marks its mechanisms with ``jax.named_scope`` and, while a
tracer is attached, writes for each program it launches which compiled
instruction lies in which scope (``<telemetry dir>/programs/
<module>-<k>.json``, announced by a ``program_scopes`` span:
``msrflute_tpu/telemetry/compiles.py``).  The device trace names an
operation by its instruction and nothing else (the events of a TPU's
``XLA Ops`` line carry an offset and a duration; one traced chip run of
PR 39 looked), so this joins the two:

- every ``XLA Ops`` event goes to the ``XLA Modules`` event of the same
  plane whose interval holds it: two programs share instruction names
  (``fusion.3`` of the round program is not the evaluation's);
- of the modules, the round program's (``readers.ROUND_PROGRAM``);
- joined by instruction name with the NEWEST map of that module that is
  not ``stale`` and was written before the traced window opened: the
  program timed, not an earlier compile of the same function (the
  check program under another precision);
- an operation counts under the innermost scope of its entry, with its
  OWN time: a loop, a branch or a call spans the operations it holds,
  which are events of their own, and what they take is taken off it.

Nothing is returned unless the map knows the operations that hold 99%
of the module's traced operation time: a map of another compile must
not be read as this one's.  Seconds are a chip's (summed over the
planes, over the chips).  The table goes once to standard error as one
JSON line ``scope_times``; the readers under ``layer_metrics/`` take
their numbers from it (``read(ctx)``, memoised on ``ctx``).  On an
operator's own capture (``profile_rounds``), from the repository's
root (a third argument: another module's pattern than ``^jit_staged``):

    python3 -m benchmarks.scope_times <profile dir> <telemetry dir>
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

from benchmarks import trace_reduce
from benchmarks.readers import ROUND_PROGRAM

#: the map must know the operations that hold this share of the
#: module's traced operation time
KNOWN_SHARE = 0.99
#: ``jit_staged(14982711673957171649)``: the trace's module event is
#: the module's name and the program's fingerprint
_FINGERPRINT = re.compile(r"\(\d+\)$")
_MEMO = "_scope_times"
#: operations named beside each scope's seconds
TOP_OPS = 6


def load_maps(telemetry_dir: str) -> list:
    """Every map under ``<telemetry dir>/programs``, oldest first."""
    maps = []
    for path in glob.glob(os.path.join(telemetry_dir, "programs",
                                       "*.json")):
        with open(path) as fh:
            maps.append({**json.load(fh), "file": path})
    return sorted(maps, key=lambda m: m["written_ts"])


def module_ops(trace: dict, pattern=ROUND_PROGRAM) -> dict:
    """``{module name: {"seconds", "runs", "ops": {instruction:
    [seconds, calls]}}}`` of the modules whose name matches, each
    operation put to the module event of its own plane whose interval
    holds it; summed over the device planes, not yet divided by them."""
    found = {}
    for plane in trace["planes"]:
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        modules = sorted(trace_reduce._line(plane, trace_reduce.MODULES_LINE),
                         key=lambda e: e[1])
        starts = [start for _, start, _ in modules]
        entries = []  # the module's entry beside its event, None: not ours
        for name, _, dur in modules:
            name = _FINGERPRINT.sub("", name)
            entries.append(found.setdefault(
                name, {"seconds": 0.0, "runs": 0, "ops": {}})
                if pattern.match(name) else None)
            if entries[-1] is not None:
                entries[-1]["seconds"] += dur / 1e9
                entries[-1]["runs"] += 1
        # an operation's OWN time: a loop, a branch or a call spans the
        # operations it holds, which are events of their own on this
        # line, so what they take is taken off it (what is left of a
        # loop is its condition and its bookkeeping)
        open_ops = []  # [end, cell] of the events that hold the current one
        for op, start, dur in sorted(
                trace_reduce._line(plane, trace_reduce.OPS_LINE),
                key=lambda e: (e[1], -e[2])):
            while open_ops and open_ops[-1][0] <= start:
                open_ops.pop()
            if open_ops:
                open_ops[-1][1][0] -= dur / 1e9
            at = bisect.bisect_right(starts, start) - 1
            if at < 0 or start >= modules[at][1] + modules[at][2]:
                continue  # outside every program: not an operation of one
            cell = [0.0, 0] if entries[at] is None else \
                entries[at]["ops"].setdefault(op, [0.0, 0])
            cell[0] += dur / 1e9
            cell[1] += 1
            open_ops.append([start + dur, cell])
    return found


def scope_table(trace: dict, maps: list, before_ts: float | None = None,
                pattern=ROUND_PROGRAM) -> dict | None:
    """The table, or None where there is nothing sound to read: no
    matching module in the trace, no map of it that is not stale (and
    older than ``before_ts``), or a map that does not know the
    operations."""
    chips = sum(1 for p in trace["planes"]
                if trace_reduce.DEVICE_PLANE.match(p["name"]))
    table = {"chips": chips, "module_s": 0.0, "ops_s": 0.0, "unknown_s": 0.0,
             "runs": 0.0, "modules": [], "maps": [], "scopes": {}}
    inside, largest = {}, {}
    for module, entry in sorted(module_ops(trace, pattern).items()):
        mine = [m for m in maps if m["module"] == module and
                not m.get("stale") and
                (before_ts is None or m["written_ts"] <= before_ts)]
        if not mine:
            return None
        known = mine[-1]["scopes"]  # the newest
        total = sum(secs for secs, _ in entry["ops"].values())
        unknown = sum(secs for op, (secs, _) in entry["ops"].items()
                      if op not in known)
        if total <= 0 or unknown > (1.0 - KNOWN_SHARE) * total:
            return None
        table["modules"].append(module)
        table["maps"].append(mine[-1]["file"])
        table["module_s"] += entry["seconds"] / chips
        table["ops_s"] += total / chips
        table["unknown_s"] += unknown / chips
        table["runs"] += entry["runs"] / chips
        for op, (secs, calls) in entry["ops"].items():
            chain = known.get(op, "").split("/")
            row = table["scopes"].setdefault(chain[-1],
                                             {"s": 0.0, "calls": 0})
            row["s"] += secs / chips
            row["calls"] += calls
            largest.setdefault(chain[-1], []).append(
                [op, secs / chips, calls])
            for scope in set(filter(None, chain)):
                inside[scope] = inside.get(scope, 0.0) + secs / chips
    if not table["modules"]:
        return None
    for scope in inside:  # one that only holds others has no row yet
        table["scopes"].setdefault(scope, {"s": 0.0, "calls": 0})
    for scope, row in table["scopes"].items():
        row["share"] = row["s"] / table["module_s"]
        # with everything nested in it; a scope's own seconds are ``s``
        row["inside_s"] = inside.get(scope, row["s"])
        # its largest operations, by name: what a breakdown by
        # ``fusion.N`` alone cannot put to a mechanism
        row["top"] = sorted(largest.get(scope, []),
                            key=lambda r: -r[1])[:TOP_OPS]
    # what no scope holds: the operations without one and whatever of
    # the program's time is no operation's at all
    scoped = sum(row["s"] for scope, row in table["scopes"].items() if scope)
    table["unattributed_s"] = table["module_s"] - scoped
    return table


def profile_dir_of(spans: list) -> tuple | None:
    """``(profile dir, telemetry dir)`` of a harness run, from the
    ``file`` of its ``program_scopes`` spans: the harness keeps the
    program's output under ``<work>/out`` and records its profile into
    ``<work>/profile``."""
    for span in spans:
        if span["name"] == "program_scopes" and span.get("file"):
            telemetry = os.path.dirname(os.path.dirname(span["file"]))
            work = os.path.dirname(os.path.dirname(os.path.dirname(
                telemetry)))
            return os.path.join(work, "profile"), telemetry
    return None


def read(ctx: dict) -> dict | None:
    """The traced window's table for the layer-metric readers: parsed
    once a run, printed once, None where the program wrote no map (every
    tree before PR 39), the map is stale, or the guard refuses it."""
    if _MEMO not in ctx:
        ctx[_MEMO] = None
        found = profile_dir_of(ctx["spans"])
        if found and os.path.isdir(found[0]):
            ctx[_MEMO] = scope_table(
                trace_reduce.load_profile(found[0]), load_maps(found[1]),
                before_ts=ctx["window"]["t_open"])
            if ctx[_MEMO] is not None:
                # what making each map cost the program, beside its launch
                ctx[_MEMO]["program_scopes"] = [
                    {key: span.get(key) for key in
                     ("module", "fun_name", "dur_s", "ops", "scoped",
                      "stale")}
                    for span in ctx["spans"]
                    if span["name"] == "program_scopes"]
                print(json.dumps({"scope_times": ctx[_MEMO]}),
                      file=sys.stderr, flush=True)
    return ctx[_MEMO]


def ms_per_round(ctx: dict, scopes: tuple, own: bool = False) -> float | None:
    """Device milliseconds a round of the round program under any of
    ``scopes`` with everything nested in them (``own``: the scopes' own
    operations only), over the rounds ``round_program_ms`` counts."""
    table = read(ctx)
    if table is None:
        return None
    rounds = table["runs"] * int(
        ctx["config"]["server_config"]["rounds_per_step"])
    rows = [table["scopes"][s] for s in scopes if s in table["scopes"]]
    if not rows or not rounds:
        return None
    return 1e3 * sum(r["s" if own else "inside_s"] for r in rows) / rounds


def main(argv: list) -> int:
    if len(argv) not in (2, 3):
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    pattern = re.compile(argv[2]) if len(argv) == 3 else ROUND_PROGRAM
    table = scope_table(trace_reduce.load_profile(argv[0]),
                        load_maps(argv[1]), pattern=pattern)
    if table is None:
        print("scope_times: nothing to read: no program matching "
              f"{pattern.pattern!r} in the trace, no map of it under "
              f"{argv[1]}/programs that is not stale, or a map that does "
              "not know the traced operations", file=sys.stderr)
        return 1
    print(json.dumps({"scope_times": table}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
