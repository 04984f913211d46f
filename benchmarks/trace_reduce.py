"""From a profiler trace and the program's host spans to numbers.

``load_profile`` reads the ``.xplane.pb`` the JAX profiler wrote into a
plain structure (``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}``, times in the trace's own
clock); ``reduce_events`` does the arithmetic on that structure and is
what the test drives with a small recorded trace.

On a TPU every chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per executed HLO operation and whose line ``XLA Modules``
one per executed program.  The host's own annotations (``TraceMe``) are on
the ``/host:CPU`` plane; the harness writes one, ``bench_clock_sync``, at a
known epoch time, which puts the program's host spans (epoch clock) and
the device events on one clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_EVENT = "bench_clock_sync"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
#: operations that only hold other operations (a scan's loop, a branch):
#: their own events span their children's, so they count as busy time
#: but not as an operation of their own
CONTAINER = re.compile(r"^(while|conditional|call)\b")


def op_name(event_name: str) -> str:
    """The trace names an operation by its whole HLO line
    (``%fusion.3 = f32[...] fusion(...)``); the name is the part before
    the ``=``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load_profile(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[op_name(e.name), float(e.start_ns),
                       float(e.duration_ns)]
                      for e in line.events
                      if device or e.name == SYNC_EVENT]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines,
                       "all_lines": [ln.name for ln in plane.lines]})
    return {"planes": planes}


def union(intervals: list) -> list:
    """Merged ``[start, end]`` intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def length(intervals: list) -> float:
    return sum(end - start for start, end in intervals)


def subtract(a: list, b: list) -> list:
    """The part of merged intervals ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append([cur, end])
    return out


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def reduce_events(trace: dict, spans: list, window: tuple,
                  sync_epoch_s: float | None) -> dict:
    """``window`` = (t0, t1) of the traced window on the host's epoch
    clock; ``spans`` = the program's host spans (``ts`` epoch seconds,
    ``dur_s``, ``name``)."""
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError(
            "no device plane in the trace: planes "
            f"{[p['name'] for p in trace['planes']]}")
    n = len(devices)
    op_seconds, op_counts, module_seconds, module_counts = {}, {}, {}, {}
    busy_ns = exposed_ns = collective_ns = 0.0
    busy_first = None
    for plane in devices:
        ops = _line(plane, OPS_LINE)
        if not ops:
            raise ValueError(
                f"plane {plane['name']} has no {OPS_LINE!r} line: "
                f"{plane.get('all_lines')}")
        busy = union([[s, s + d] for _, s, d in ops])
        ops = [e for e in ops if not CONTAINER.match(e[0])]
        for name, _, dur in ops:
            op_seconds[name] = op_seconds.get(name, 0.0) + dur / 1e9 / n
            op_counts[name] = op_counts.get(name, 0) + 1
        for name, _, dur in _line(plane, MODULES_LINE):
            module_seconds[name] = module_seconds.get(name, 0.0) + \
                dur / 1e9 / n
            module_counts[name] = module_counts.get(name, 0) + 1
        busy_ns += length(busy)
        if busy_first is None:
            busy_first = busy
        coll = union([[s, s + d] for name, s, d in ops
                      if COLLECTIVE.match(name)])
        compute = union([[s, s + d] for name, s, d in ops
                         if not COLLECTIVE.match(name)])
        collective_ns += length(coll)
        exposed_ns += length(subtract(coll, compute))

    # idle gaps of the first device, named by the innermost host span
    # open at the middle of the gap
    offset_ns = None
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for name, start, _ in line["events"]:
                if name == SYNC_EVENT and sync_epoch_s is not None:
                    offset_ns = sync_epoch_s * 1e9 - start
    gaps = {}
    if offset_ns is not None and busy_first:
        t0_ns = window[0] * 1e9 - offset_ns
        t1_ns = window[1] * 1e9 - offset_ns
        idle = subtract([[t0_ns, t1_ns]], busy_first)
        host = sorted(((s["ts"], s["ts"] + s["dur_s"], s["name"])
                       for s in spans), key=lambda x: x[1] - x[0])
        for start, end in idle:
            mid = ((start + end) / 2 + offset_ns) / 1e9
            owner = next((name for a, b, name in host if a <= mid <= b),
                         "no_host_span")
            gaps[owner] = gaps.get(owner, 0.0) + (end - start) / 1e9

    def top(table: dict) -> list:
        return [[k, v] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "chips": n,
        "busy_s": busy_ns / 1e9 / n,
        "window_s": window[1] - window[0],
        "op_seconds": op_seconds, "op_counts": op_counts,
        "module_seconds": module_seconds, "module_counts": module_counts,
        "collective_s": collective_ns / 1e9 / n,
        "collective_exposed_s": exposed_ns / 1e9 / n,
        "clock_synced": offset_ns is not None,
        "breakdown": {"device_ops": top(op_seconds),
                      "idle_gaps": top(gaps)},
        "summary": {
            "planes": {p["name"]: p.get("all_lines") for p in trace["planes"]},
            "modules": top(module_seconds),
            "ops": [[k, v] for k, v in sorted(
                op_seconds.items(), key=lambda kv: -kv[1])[:30]],
            "module_counts": module_counts,
            "clock_synced": offset_ns is not None,
        },
    }


def reduce_profile(profile: dict, spans: list) -> dict:
    """``profile`` as the harness recorded it: ``dir``, ``t0``, ``t1``
    (epoch seconds of the traced window) and ``sync_ts``."""
    return reduce_events(load_profile(profile["dir"]), spans,
                         (profile["t0"], profile["t1"]), profile["sync_ts"])
