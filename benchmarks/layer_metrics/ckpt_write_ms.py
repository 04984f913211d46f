"""Checkpoint (``engine/checkpoint.py``): the fetch + checksum + write
of one whole state, per save, whichever thread made it: spans
``ckpt_async_write`` (the writer thread's ``latest``) and ``ckpt_write``
(a synchronous save on the training thread: every best-model save).
Read only from spans that say how many ``bytes`` they wrote (the program
says so since PR 28); nothing to read on a program whose spans carry
none."""
from benchmarks.readers import window_spans

UNIT = "ms/save"


def read(ctx):
    spans = [s for name in ("ckpt_async_write", "ckpt_write")
             for s in window_spans(ctx, name) if "bytes" in s]
    return 1e3 * sum(s["dur_s"] for s in spans) / len(spans) \
        if spans else None
