"""Turn a flash_crossover.json sweep into concrete settings.

``tools/flash_crossover_sweep.py`` measures fwd+bwd wall
time of dense vs flash per length x kernel-tile choice.  This tool reads
that artifact and prints, per length: the best tile, the flash/dense
speedup, and the recommended settings —

- ``FLASH_AUTO_MIN_LEN`` (``models/ringlm.py``): the smallest measured
  length where the best flash beats dense (the constant stays STATIC in
  code by design; this tool makes the manual re-derivation mechanical
  and reviewable).
- ``flash_block_q`` / ``flash_block_k`` (model_config): the tile pair
  winning at the lengths where flash is the chosen path.

Usage::

    python tools/calibrate_flash.py [flash_crossover.json]
"""

from __future__ import annotations

import json
import os
import sys


def analyze(path: str) -> dict:
    with open(path) as fh:
        res = json.load(fh)
    lengths = {}
    for ls, row in sorted(res.get("lengths", {}).items(),
                          key=lambda kv: int(kv[0])):
        best_tile, best_ms = None, None
        for key, val in row.items():
            if key.startswith("flash_") and key.endswith("_fwd_bwd_ms") \
                    and isinstance(val, (int, float)):
                if best_ms is None or val < best_ms:
                    best_ms, best_tile = val, key[len("flash_"):
                                                  -len("_fwd_bwd_ms")]
        dense = row.get("dense_fwd_bwd_ms")
        lengths[int(ls)] = {
            "best_tile": best_tile,
            "best_flash_ms": best_ms,
            "dense_ms": dense,
            "flash_speedup": (round(dense / best_ms, 3)
                              if dense and best_ms else None),
        }
    crossover = None
    for L in sorted(lengths):
        row = lengths[L]
        if row["best_flash_ms"] is None and row["dense_ms"] is None:
            # no data at this length (both paths failed/skipped): it can
            # neither establish nor refute a crossover — leave the scan
            # state untouched instead of counting it as a flash loss
            continue
        wins = (row["flash_speedup"] or 0) > 1.0 or \
            (row["best_flash_ms"] is not None and row["dense_ms"] is None)
        if wins and crossover is None:
            crossover = L
        if not wins:
            crossover = None  # must win at every length >= the crossover
    win_tiles = [lengths[L]["best_tile"] for L in sorted(lengths)
                 if crossover is not None and L >= crossover and
                 lengths[L]["best_tile"]]
    return {
        "lengths": lengths,
        "recommended_flash_auto_min_len": crossover,
        "recommended_tiles_at_win_lengths": win_tiles,
    }


def main() -> None:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = sys.argv[1] if len(sys.argv) > 1 else \
        os.path.join(repo, "flash_crossover.json")
    out = analyze(path)
    print(json.dumps(out, indent=1))
    rec = out["recommended_flash_auto_min_len"]
    if rec is None:
        print("\n[calibrate] flash never beats dense in this sweep — "
              "FLASH_AUTO_MIN_LEN should stay above the largest measured "
              "length; kernel work needed", file=sys.stderr)
    else:
        print(f"\n[calibrate] set FLASH_AUTO_MIN_LEN = {rec} "
              f"(models/ringlm.py); winning tiles per length: "
              f"{out['recommended_tiles_at_win_lengths']}", file=sys.stderr)


if __name__ == "__main__":
    main()
