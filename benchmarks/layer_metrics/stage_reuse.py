"""Inside ``dispatch`` (``engine/round.py::StagingPool``): of the
window's ``stage_host`` spans that say ``reused``, the share that say
true: the dispatch wrote its rounds into host buffers it had kept and
allocated none.  False only while the buffers of a staged shape are
first allocated, which a steady run does in its warm-up; a run whose
staged shapes change from chunk to chunk drops and allocates on every
change and reads low.  Nothing to read on a program whose span does not
say it (every tree before PR 34, the bucketed dispatch)."""
from benchmarks.readers import window_spans

UNIT = "%"


def read(ctx):
    said = [s for s in window_spans(ctx, "stage_host") if "reused" in s]
    if not said:
        return None
    return 100.0 * sum(bool(s["reused"]) for s in said) / len(said)
