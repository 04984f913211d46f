"""The compared numbers of one cell over many seeds: what a limit is set from.

    python3 benchmarks/readings.py --workload <name> --seeds 1,2,3 [--control bf16] [--out DIR]

One ``run.py --readings 1`` process per seed, one after the other (this
process stays off jax: a chip belongs to one process at a time), each
stopped after its first timed dispatch.  Prints every seed's numbers and,
last, the largest and the smallest of each.  Stops at the first seed
whose process fails: a fault costs one seed's chip time, not all of them.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    table = {}
    for seed in args.seeds.split(","):
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
               args.workload, "--seed", seed, "--seconds", "0",
               "--readings", "1"]
        if args.control:
            cmd += ["--control", args.control]
        done = subprocess.run(cmd, capture_output=True, text=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            stem = os.path.join(args.out, "_".join(
                [args.workload, args.control or "sound", seed]))
            with open(stem + ".out", "w") as fh:
                fh.write(done.stdout)
            with open(stem + ".err", "w") as fh:
                fh.write(done.stderr[-40000:])
        if done.returncode != 0:
            print(done.stderr[-3000:], file=sys.stderr)
            return done.returncode
        lines = [json.loads(line) for line in done.stdout.splitlines()
                 if line.startswith("{")]
        result = lines[-1]
        row = {v["name"]: v["value"] for v in result["compared"]}
        extra = next((r for r in lines if "reference_s" in r), {})
        print(json.dumps({"seed": int(seed), "correct": result["correct"],
                          **row, **extra}), flush=True)
        for name, value in row.items():
            table.setdefault(name, []).append(value)
    print(json.dumps({"workload": args.workload, "control": args.control,
                      "seeds": args.seeds,
                      "largest": {k: max(v) for k, v in table.items()},
                      "smallest": {k: min(v) for k, v in table.items()}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
