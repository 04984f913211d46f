"""Kernels (``ops/moe.py``, Pallas kernel ``expert_gmm_dx``): share of
the roofline its calls reached in the traced window; operations and bytes
by ``benchmarks/kernel_rooflines.py``."""
from benchmarks.kernel_rooflines import roofline_share

UNIT = "%"


def read(ctx):
    return roofline_share(ctx, "expert_gmm_dx")
