"""Chip peak tables: the MFU and roofline denominators.

Leaf module (imports only jax, lazily): vendor-published per-chip peak
FLOP/s and HBM bandwidth keyed by ``device.device_kind``.  A device that
is neither a CPU nor in the table is an ERROR — a chip the table does
not know can never be priced against somebody else's peak.  The CPU
figures are documented NOMINAL round numbers that keep the scorecard's
MFU column computable in the CPU test environment (ROADMAP Queue 3
item 6 removes them); they are never comparable to a chip's.
"""

from __future__ import annotations

# ----------------------------------------------------------------------
# chip peak-FLOPs table (flutescope device-truth: the MFU denominator)
# ----------------------------------------------------------------------
#: dense bf16 peak FLOP/s per TPU chip generation (vendor-published
#: per-chip numbers; keys are matched as substrings of
#: ``device.device_kind`` lowercased).  Longest key wins, so "v5e"
#: matches before "v5".
TPU_PEAK_FLOPS = {
    "v2": 45e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,   # v5e reports device_kind "TPU v5 lite"
    "v5p": 459e12,
    "v6e": 918e12,
    "v6 lite": 918e12,
}

#: the bench harness's historical headline denominator (bench.py MFU
#: columns were published against this) — now sourced from the one table
V5E_BF16_PEAK_FLOPS = TPU_PEAK_FLOPS["v5e"]

#: documented NOMINAL peak for CPU only: a fixed round number so CPU MFU
#: values exist, are deterministic, and compare across CPU runs — never
#: against a real chip's.  ~a few-core host's practical f32 throughput
#: order of magnitude.
CPU_NOMINAL_PEAK_FLOPS = 1e11


def _lookup(device, table, cpu_nominal):
    """``(kind, value)`` from ``table`` by longest substring match on the
    lowercased ``device_kind``; the CPU platform gets ``cpu_nominal``;
    anything else raises."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = str(getattr(device, "device_kind", "") or "").lower()
    matches = [key for key in table if key in kind]
    if matches:
        return kind, table[max(matches, key=len)]
    if getattr(device, "platform", None) == "cpu" or kind == "cpu":
        return kind, cpu_nominal
    raise ValueError(
        f"device kind {kind!r} is not in the chip peak table "
        "(utils/compat.py): add its published peak with a source "
        "instead of pricing it against another device's")


def chip_peak_flops(device=None):
    """``(kind, peak_flops)`` for ``device`` (default: this process's
    first jax device).  TPU kinds resolve through :data:`TPU_PEAK_FLOPS`,
    the CPU platform gets :data:`CPU_NOMINAL_PEAK_FLOPS` (the scorecard
    records the kind next to the number), and an unknown kind raises."""
    return _lookup(device, TPU_PEAK_FLOPS, CPU_NOMINAL_PEAK_FLOPS)


#: HBM bandwidth (bytes/s) per TPU chip generation (vendor-published),
#: matched like :data:`TPU_PEAK_FLOPS`.  The roofline denominator of the
#: attention dispatch gate (ops/pallas_attention.py): estimated program
#: seconds = max(flops / peak, bytes / bandwidth).
TPU_HBM_BYTES_PER_SEC = {
    "v2": 700e9,
    "v3": 900e9,
    "v4": 1228e9,
    "v5e": 819e9,
    "v5 lite": 819e9,
    "v5p": 2765e9,
    "v6e": 1640e9,
    "v6 lite": 1640e9,
}

#: documented NOMINAL bandwidth for CPU — the same fixed-round-number
#: contract as :data:`CPU_NOMINAL_PEAK_FLOPS`
CPU_NOMINAL_HBM_BYTES_PER_SEC = 5e10


def chip_hbm_bytes_per_sec(device=None):
    """``(kind, bytes_per_sec)`` for ``device`` — the memory-side twin of
    :func:`chip_peak_flops`, with the identical matching and errors."""
    return _lookup(device, TPU_HBM_BYTES_PER_SEC,
                   CPU_NOMINAL_HBM_BYTES_PER_SEC)
