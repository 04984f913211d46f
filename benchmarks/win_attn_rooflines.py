"""Operations and bytes of the sliding-window attention core's three
kernels (``msrflute_tpu/ops/pallas_attention.py``: ``attn_win_fwd``,
``attn_win_dq``, ``attn_win_dkv``), their share of the roofline in a
traced window, and what the program said of its tile map (the
``attn_window_tiles`` event).

A call is one row of ``L`` tokens in a SLIDING layer: ``H`` query heads
of ``D`` over ``KV`` key-value heads, a query over the ``W`` keys up to
its own.  Counted as the ALGORITHM needs them, whatever the tiles
compute: the SEEN pairs, ``H * (W L - W (W - 1) / 2)`` scores (the band
under the diagonal: 1,966,336 a head at 4,096 / 512), not the causal
half and not the square, and per score two operations for every width
one of the kernel's products contracts or produces, as
``attn_rooflines.py`` has them for the causal kernels: ``fwd`` ``Dqk +
Dv``, ``dq`` ``2 Dqk + Dv``, ``dkv`` ``2 Dqk + 2 Dv``.  Bytes: ``q``,
``k``, ``v`` and ``out`` once, float32.

The least time a call could take is the larger of operations over the
MXU peak and bytes over the memory bandwidth (``peaks.json``); the share
is that, summed over the calls the trace counted, over the kernels'
device seconds.  It cannot pass 100%: a kernel computes at least every
tile that holds a seen pair, whole (``tiles_run x tile >= pairs_seen``:
``tests/benchmarks/test_benchmark_laguna.py``), on an MXU no faster than
the peak.

Nothing to read on a program without these kernels or this event (every
tree before PR 43) or on a configuration without a window: the functions
return None and raise nothing."""

from __future__ import annotations

import json
import os
import re

from benchmarks.readers import peak
from benchmarks.scope_times import profile_dir_of

KERNELS = ("attn_win_fwd", "attn_win_dq", "attn_win_dkv")
ITEMSIZE = 4


def geometry(model_config: dict) -> dict | None:
    """``L``, ``W``, ``H``, ``KV``, ``D`` of the configuration's sliding
    layers; None where it has none."""
    if not model_config.get("sliding_window") or \
            "num_attention_heads_sliding" not in model_config:
        return None
    heads = int(model_config["num_attention_heads_sliding"])
    return {"L": int(model_config["seq_len"]),
            "W": int(model_config["sliding_window"]), "H": heads,
            "KV": int(model_config.get("num_key_value_heads", heads)),
            "D": int(model_config["head_dim"])}


def pairs_seen(geo: dict) -> int:
    """Seen (query, key) pairs of one head of one row."""
    w = min(geo["W"], geo["L"])
    return w * geo["L"] - w * (w - 1) // 2


def call_cost(kernel: str, geo: dict) -> tuple:
    """``(operations, bytes)`` of one call of ``kernel``."""
    widths = {"attn_win_fwd": 2, "attn_win_dq": 3,
              "attn_win_dkv": 4}[kernel] * geo["D"]
    moved = ITEMSIZE * geo["L"] * 2 * geo["D"] * (geo["H"] + geo["KV"])
    return 2.0 * geo["H"] * pairs_seen(geo) * widths, float(moved)


def kernel_times(trace: dict) -> dict:
    """``{kernel: (device seconds, calls)}`` of the kernels the trace
    holds; empty on a program that has none."""
    found = {}
    for kernel in KERNELS:
        named = re.compile(rf"(^|_){re.escape(kernel)}(_|\.|$)")
        seconds = sum(v for k, v in trace["op_seconds"].items()
                      if named.search(k))
        calls = sum(v for k, v in trace["op_counts"].items()
                    if named.search(k))
        if seconds and calls:
            found[kernel] = (seconds, calls)
    return found


def roofline_share(ctx: dict) -> float | None:
    """Per cent of the roofline that the three kernels' calls in the
    traced window reached together; None where the trace has none."""
    trace = ctx["trace"]
    found = kernel_times(trace)
    geo = geometry(ctx["config"]["model_config"])
    if not found or not geo:
        return None
    limits = peak(ctx)
    least = 0.0
    for kernel, (_, calls) in found.items():
        flops, moved = call_cost(kernel, geo)
        least += calls * max(flops / limits["flops_per_s"],
                             moved / limits["hbm_bytes_per_s"])
    seconds = sum(s for s, _ in found.values())
    return 100.0 * least / trace["chips"] / seconds


def tile_event(ctx: dict) -> dict | None:
    """The program's ``attn_window_tiles`` event for the configuration's
    row (``L``, ``W``), from the telemetry's event stream beside the
    spans; None where the program wrote none."""
    geo = geometry(ctx["config"]["model_config"])
    found = profile_dir_of(ctx["spans"])
    if not found or not geo:
        return None
    path = os.path.join(found[1], "events.jsonl")
    if not os.path.exists(path):
        return None
    event = None
    with open(path) as fh:
        for line in fh:
            if '"attn_window_tiles"' not in line:
                continue
            rec = json.loads(line)
            if rec.get("name") == "attn_window_tiles" and \
                    rec.get("L") == geo["L"] and \
                    rec.get("window") == geo["W"]:
                event = rec
    return event
