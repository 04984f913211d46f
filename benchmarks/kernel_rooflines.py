"""Operations and bytes of the expert layer's grouped-matmul kernels
(``msrflute_tpu/ops/moe.py``: ``expert_gmm_fwd``, ``expert_gmm_dx``,
``expert_gmm_dw``) and their share of the roofline in a traced window.

A call multiplies the rows of the pairs that fall on held experts (a
number the routing decides; the window's mean per expert layer and local
step is taken from the counters on the ``host_tail`` spans) with their
expert's ``[hidden, width]`` matrix.  Counted per call, whichever of the
three matrices of the SwiGLU it is (``w1``, ``w3``: hidden x width;
``w2``: width x hidden: the same products and the same bytes):

- operations: ``2 * rows * hidden * width``;
- bytes, float32: the rows read and the rows written, and every held
  expert's matrix once (read by ``fwd`` and ``dx``, written by ``dw``).
  Padding rows up to a tile, and a matrix read again by a later tile of
  columns, are not counted: they are the kernel's doing, not the
  algorithm's.

The least time a call could take is the larger of operations over the
MXU peak and bytes over the memory bandwidth (``peaks.json``); the share
is that, times the calls the trace counted, over the kernel's device
seconds."""

from __future__ import annotations

import re

from benchmarks.readers import peak, window_spans

ITEMSIZE = 4


def rows_per_call(ctx: dict) -> float | None:
    """Mean pairs on held experts per expert layer and local step."""
    spans = [s for s in window_spans(ctx, "host_tail")
             if "moe_pairs_held" in s]
    steps = sum(s["moe_layer_steps"] for s in spans)
    return sum(s["moe_pairs_held"] for s in spans) / steps if steps else None


def call_cost(kernel: str, rows: float, model_config: dict) -> tuple:
    """``(operations, bytes)`` of one call of ``kernel``."""
    hidden = int(model_config["hidden_size"])
    width = int(model_config["moe_intermediate_size"])
    held = int(model_config["experts_held"])
    matrices = held * hidden * width * ITEMSIZE
    # fwd / dx read rows of one side and write rows of the other; dw reads
    # both sides' rows and writes the matrices
    moved = rows * (hidden + width) * ITEMSIZE + matrices
    return 2.0 * rows * hidden * width, moved


def roofline_share(ctx: dict, kernel: str) -> float | None:
    """Per cent of the roofline that ``kernel``'s calls in the traced
    window reached; None where the trace has no such operation or the
    spans no counters."""
    trace = ctx["trace"]
    named = re.compile(rf"(^|_){re.escape(kernel)}(_|\.|$)")
    seconds = sum(v for k, v in trace["op_seconds"].items()
                  if named.search(k))
    calls = sum(v for k, v in trace["op_counts"].items() if named.search(k))
    rows = rows_per_call(ctx)
    if not seconds or not calls or not rows:
        return None
    flops, moved = call_cost(kernel, rows, ctx["config"]["model_config"])
    limits = peak(ctx)
    least = max(flops / limits["flops_per_s"],
                moved / limits["hbm_bytes_per_s"])
    return 100.0 * least * calls / trace["chips"] / seconds
