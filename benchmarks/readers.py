"""What the layer-metric readers share: the window's host spans and the
traced window's programs.  A reader is ``layer_metrics/<metric>.py`` with
``UNIT`` and ``read(ctx) -> float | None`` (None: nothing to read in this
cell, the metric is left out of the line)."""

from __future__ import annotations

import re

from benchmarks.flops import matmul_flops


def window_spans(ctx: dict, name: str) -> list:
    """The program's host spans of that name that START inside the
    measured window."""
    win = ctx["window"]
    return [s for s in ctx["spans"] if s["name"] == name and
            win["t_open"] <= s["ts"] <= win["t_close"]]


def ms_per_round(ctx: dict, names: tuple) -> float | None:
    """Host milliseconds per round in spans that carry a ``rounds``
    count (``pack``, ``dispatch``, ``stats_fetch``, ``host_tail``)."""
    total = rounds = 0.0
    for name in names:
        spans = window_spans(ctx, name)
        total += sum(s["dur_s"] for s in spans)
        if name == names[0]:
            rounds = sum(s.get("rounds", 1) for s in spans)
    return 1e3 * total / rounds if rounds else None


def ms_per_event(ctx: dict, name: str) -> float | None:
    spans = window_spans(ctx, name)
    return 1e3 * sum(s["dur_s"] for s in spans) / len(spans) \
        if spans else None


ROUND_PROGRAM = re.compile(r"^jit_staged")


def round_program(ctx: dict) -> tuple:
    """(device seconds per chip, rounds) of the round program
    (``jit(staged)``) inside the traced window."""
    trace = ctx["trace"]
    per_dispatch = int(ctx["config"]["server_config"]["rounds_per_step"])
    seconds = runs = 0.0
    for name, secs in trace["module_seconds"].items():
        if ROUND_PROGRAM.match(name):
            seconds += secs
            runs += trace["module_counts"][name] / trace["chips"]
    return seconds, runs * per_dispatch


def required_flops_per_round(ctx: dict) -> float:
    """Forward + backward matmul/convolution operations one round needs:
    the dots of the step's loss (the model reference's own, else
    classification: ``fedround.seam``) on the first step's whole batch
    and the plain model, or the model reference's own
    ``required_flops(params, batch, model_config)`` where its plain form
    computes more than the algorithm needs (experts written densely)."""
    first, model, fedround = (ctx[k] for k in
                              ("first_inputs", "model", "fedround"))
    batch = {k: fedround.on_device(v[0, 0])
             for k, v in fedround.step_arrays(first).items()}
    model_config = ctx["config"]["model_config"]
    if hasattr(model, "required_flops"):
        per_step = float(model.required_flops(ctx["weights"], batch,
                                              model_config))
    else:
        per_step = fedround.flops_per_step(
            model.forward, model_config, ctx["weights"], batch,
            matmul_flops, getattr(model, "loss", None))
    live_steps = float((first["sample_mask"].sum(axis=-1) > 0).sum())
    return per_step * live_steps


def peak(ctx: dict) -> dict:
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise KeyError(f"peaks.json has no entry for device kind {kind!r}")
    return ctx["peaks"][kind]
