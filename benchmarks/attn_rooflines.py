"""Operations and bytes of the causal attention core's three kernels
(``msrflute_tpu/ops/pallas_attention.py``: ``attn_flash_fwd``,
``attn_flash_dq``, ``attn_flash_dkv``) and their share of the roofline in
a traced window.

A call is one row of ``L`` tokens (the cells train and evaluate one row a
batch), ``H`` query heads of ``Dqk`` over ``KV`` key-value heads of
``Dqk`` / ``Dv``.  Counted as the ALGORITHM needs them, whatever the
tiles compute: the causal half of the square, ``H * L^2 / 2`` scores, and
per score two operations for every width one of the kernel's products
contracts or produces:

- ``fwd``: scores and output, ``Dqk + Dv``;
- ``dq``: scores again, ``dP = dO V^T``, ``dQ = dS K``: ``2 Dqk + Dv``;
- ``dkv``: scores again, ``dP`` again, ``dV = P^T dO``, ``dK = dS^T Q``:
  ``2 Dqk + 2 Dv``

(the backward's two recomputations of the scores are the price of never
writing them; they are counted because no arrangement of two kernels
avoids them).  Widths as published (192 / 128; 64 / 64), not as padded
to the lanes.  Bytes: ``q``, ``k``, ``v`` and ``out`` once, float32.

The least time a call could take is the larger of operations over the MXU
peak and bytes over the memory bandwidth (``peaks.json``); the share is
that, summed over the calls the trace counted, over the kernels' device
seconds.  It cannot pass 100%: a kernel computes at least every tile on
or under the diagonal, whole, at widths no smaller than the published
ones, on an MXU no faster than the peak."""

from __future__ import annotations

import re

from benchmarks.readers import peak

KERNELS = ("attn_flash_fwd", "attn_flash_dq", "attn_flash_dkv")
ITEMSIZE = 4


def geometry(model_config: dict) -> dict:
    """``L``, ``H``, ``KV``, ``Dqk``, ``Dv`` of the configuration's
    attention, latent (a key head a query head, keys of ``nope + rope``)
    or grouped-query."""
    heads = int(model_config["num_attention_heads"])
    out = {"L": int(model_config["seq_len"]), "H": heads}
    if "qk_nope_head_dim" in model_config:
        out.update(KV=heads, Dv=int(model_config["v_head_dim"]),
                   Dqk=int(model_config["qk_nope_head_dim"]) +
                   int(model_config["qk_rope_head_dim"]))
    else:
        out.update(KV=int(model_config["num_key_value_heads"]),
                   Dqk=int(model_config["head_dim"]),
                   Dv=int(model_config["head_dim"]))
    return out


def call_cost(kernel: str, geo: dict) -> tuple:
    """``(operations, bytes)`` of one call of ``kernel``."""
    widths = {"attn_flash_fwd": geo["Dqk"] + geo["Dv"],
              "attn_flash_dq": 2 * geo["Dqk"] + geo["Dv"],
              "attn_flash_dkv": 2 * geo["Dqk"] + 2 * geo["Dv"]}[kernel]
    scores = geo["H"] * geo["L"] ** 2 / 2
    moved = ITEMSIZE * geo["L"] * (
        geo["H"] * (geo["Dqk"] + geo["Dv"]) +
        geo["KV"] * (geo["Dqk"] + geo["Dv"]))
    return 2.0 * scores * widths, float(moved)


def kernel_times(trace: dict) -> dict:
    """``{kernel: (device seconds, calls)}`` of the kernels the trace
    holds; empty on a program that has none (every tree before PR 37)."""
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
    if not found:
        return None
    geo = geometry(ctx["config"]["model_config"])
    limits = peak(ctx)
    least = 0.0
    for kernel, (_, calls) in found.items():
        flops, moved = call_cost(kernel, geo)
        least += calls * max(flops / limits["flops_per_s"],
                             moved / limits["hbm_bytes_per_s"])
    seconds = sum(s for s, _ in found.values())
    return 100.0 * least / trace["chips"] / seconds
