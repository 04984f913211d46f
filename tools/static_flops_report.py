"""Static per-op FLOP decomposition for every benchmark protocol model.

Writes PROFILE_STATIC.json: for each ``bench.build_protocols`` protocol
(the TPU geometries, incl. mlm_bert), the exact
conv/dot/elementwise/other FLOP split of one client grad step — the
round's inner loop — from the jaxpr (``msrflute_tpu/utils/flops.py``).
Configs and batches come from bench.py itself, so the report cannot
drift from what the benchmark actually runs.  Chip-independent: this is the half of the
compute-bound argument that needs no TPU — it shows the benchmark
rounds are MXU work (conv+dot), not bookkeeping.  The on-chip half
(device time by scope, MFU, the host chain by span) is
``benchmarks/run.py --trace 1``.

Usage: python tools/static_flops_report.py [--out PROFILE_STATIC.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "PROFILE_STATIC.json"))
    args = ap.parse_args()

    import bench  # repo-root harness: the protocol table of record

    import jax

    from msrflute_tpu.data.batching import steps_for
    from msrflute_tpu.models import make_task
    from msrflute_tpu.utils.flops import flops_by_op

    # the TPU protocol geometries are the benchmark; building them off-TPU
    # only affects dataset size, not the per-step shapes we analyze
    protocols = bench.build_protocols(True, np.random.default_rng(0))

    report = {}
    for name, spec in protocols.items():
        cfg, dataset = spec["cfg"], spec["data"]()
        task = make_task(cfg.model_config)
        params = task.init_params(jax.random.PRNGKey(0))
        bs = int(cfg.client_config.data_config.train["batch_size"])
        max_steps = steps_for(int(max(dataset.num_samples)), bs,
                              cfg.client_config.get("desired_max_samples"))
        # _one_client_batch already yields one step's [B, ...] arrays
        batch = bench._one_client_batch(dataset, bs, max_steps)

        def grad_step(p, _batch=batch, _task=task):
            return jax.grad(lambda pp: _task.loss(
                pp, _batch, jax.random.PRNGKey(0), True)[0])(p)

        res = flops_by_op(grad_step, params)
        report[name] = {
            "batch_shape": list(np.shape(batch["x"])),
            "total_flops": res["total"],
            "mxu_share": res["mxu_share"],
            "conv_share": res["conv_share"],
            "dot_share": res["dot_share"],
            "elementwise_share": res["elementwise_share"],
            "other_share": res["other_share"],
            "approximate": res["approximate"],
        }
        # XLA's own compiled-program numbers next to the jaxpr walk —
        # through the ONE shared helper (telemetry/xla.aot_cost, same
        # path as bench.grad_step_cost and the live device-truth layer),
        # so the two FLOP accountings can be compared without wondering
        # whether they were measured differently
        from msrflute_tpu.telemetry.xla import aot_cost
        cost = aot_cost(grad_step, params)
        if cost is not None:
            report[name]["xla_flops"] = cost.get("flops")
            report[name]["xla_bytes_accessed"] = cost.get("bytes_accessed")
            report[name]["xla_hbm_bytes"] = cost.get("hbm_bytes")
        print(f"{name}: mxu={res['mxu_share']:.3f} "
              f"(conv={res['conv_share']:.3f} dot={res['dot_share']:.3f})")

    with open(args.out, "w") as fh:
        json.dump({"note": "exact per-op FLOP split of one client grad "
                           "step per bench.build_protocols protocol "
                           "(utils/flops.py jaxpr walk; geometries taken "
                           "from bench.py itself); chip-independent "
                           "compute-bound evidence — wall-clock/MFU live "
                           "in the bench/profile artifacts",
                   "protocols": report}, fh, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
