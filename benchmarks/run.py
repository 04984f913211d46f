"""One run of one benchmark cell:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``); every earlier line is a JSON object of its own.  Runs on
the machine it is started on, in one process, and only on a TPU with as
many chips as the cell asks for: anything else is an error with no result.
``--control <name>`` (not used by the driver) overlays
``controls/<name>.json`` on the program's configuration: the run that
``correct`` has to fail.  ``--readings 1`` (not used by the driver) stops
after the first timed dispatch and prints the compared numbers alone:
what a limit is set from, over many seeds, without paying for windows.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--readings", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path.insert(0, REPO)
    from benchmarks import harness
    cell = harness.load_cell(harness.BENCH_DIR, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != int(cell["chips"]):
        print(f"benchmarks/run.py: cell {args.workload!r} needs "
              f"{cell['chips']} TPU chip(s); jax sees {len(devices)} x "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), control=args.control,
                              t_start=T_START,
                              readings=bool(args.readings))
    # the whole run on the harness's clock, its clean-up included
    harness.say({"run_s": time.time() - T_START})
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
