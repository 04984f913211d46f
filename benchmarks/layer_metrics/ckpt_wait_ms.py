"""Checkpoint (``engine/checkpoint.py::_await_writer``): what the
training thread spends waiting for the writer thread, per save: the
summed ``ckpt_wait`` spans of the window (every wait on the writer's
slot: before a snapshot is handed over, for a best-model file to land
before the status log names it, before a link or a backup copy) over
the window's saves, counted as ``ckpt_write_ms`` counts them (the spans
``ckpt_async_write`` and ``ckpt_write`` that say their ``bytes``).
Nothing to read on a program without the span."""
from benchmarks.readers import window_spans

UNIT = "ms/save"


def read(ctx):
    waits = window_spans(ctx, "ckpt_wait")
    saves = [s for name in ("ckpt_async_write", "ckpt_write")
             for s in window_spans(ctx, name) if "bytes" in s]
    return 1e3 * sum(s["dur_s"] for s in waits) / len(saves) \
        if waits and saves else None
