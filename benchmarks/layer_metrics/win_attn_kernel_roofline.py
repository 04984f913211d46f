"""Kernels (``ops/pallas_attention.py``: ``attn_win_fwd``,
``attn_win_dq``, ``attn_win_dkv``): share of the roofline their calls
reached together in the traced window; operations and bytes by
``benchmarks/win_attn_rooflines.py`` (the seen pairs of the band, ``H x
(W L - W (W - 1) / 2)`` scores a call, at the published widths)."""
from benchmarks.win_attn_rooflines import roofline_share

UNIT = "%"


def read(ctx):
    return roofline_share(ctx)
