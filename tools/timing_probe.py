"""Honest on-chip wall-time measurement for the tools/ scripts.

The implementation moved to :mod:`msrflute_tpu.telemetry.timing` (the
one timing source of truth — bench.py sits on the same primitives); this module keeps the import path
``flash_crossover_sweep.py`` / ``validate_flash_auto.py`` were written
against.

The fence is a host ``float()`` of a scalar result, which completes only
after the producing program does; see the telemetry.timing docstrings
for the full methodology.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from msrflute_tpu.telemetry.timing import grad_wall, scalar_time  # noqa: E402,F401
