"""Kernels (``ops/pallas_attention.py``: ``attn_bd_fwd``, ``attn_bd_dq``,
``attn_bd_dkv``): share of the roofline their calls reached together in
the traced window; operations and bytes by
``benchmarks/bd_attn_rooflines.py`` (the seen pairs of the doubled row,
``H x L (L + B)`` scores a call, at the published widths)."""
from benchmarks.bd_attn_rooflines import roofline_share

UNIT = "%"


def read(ctx):
    return roofline_share(ctx)
