"""Kernels (``ops/pallas_attention.py``: ``attn_flash_fwd``,
``attn_flash_dq``, ``attn_flash_dkv``): share of the roofline their calls
reached together in the traced window; operations and bytes by
``benchmarks/attn_rooflines.py`` (the causal half of the square at the
published widths)."""
from benchmarks.attn_rooflines import roofline_share

UNIT = "%"


def read(ctx):
    return roofline_share(ctx)
