"""One-command round profiling — where does a federated round's time go?

The reference's answer is flag-gated cProfile dumps (``core/server.py:
327-331``, SURVEY §5.1); the TPU answer is this CLI: run one benchmark
protocol for a few fused chunks, split wall-clock into host packing vs
device execution, attach the compiled program's own cost analysis
(FLOPs/bytes from XLA), optionally capture a ``jax.profiler`` trace, and
print one JSON object.

Usage::

    python tools/profile_round.py --protocol cnn_femnist --chunks 3
    python tools/profile_round.py --protocol lr_mnist --trace /tmp/trace
    BENCH_BACKEND=cpu python tools/profile_round.py ...   # force backend

Run it the moment the chip answers: ``pack_share`` (host packing as a
fraction of the round) says whether to optimize kernels or the host path
first.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--protocol", default="cnn_femnist",
                    help="one of bench.py's protocols")
    ap.add_argument("--chunks", type=int, default=3,
                    help="timed fused-round chunks after warmup")
    ap.add_argument("--trace", default=None,
                    help="directory for a jax.profiler trace of one chunk")
    args = ap.parse_args(argv)

    import bench  # repo-root harness: backend probe + protocol table

    backend, reason = bench.select_backend()
    on_tpu = backend == "tpu"
    rng = np.random.default_rng(0)
    protocols = bench.build_protocols(on_tpu, rng, with_bf16=True)
    if args.protocol not in protocols:
        raise SystemExit(f"unknown protocol {args.protocol!r}; have "
                         f"{sorted(protocols)}")
    spec = protocols[args.protocol]
    cfg, dataset = spec["cfg"], spec["data"]()

    import jax
    from msrflute_tpu.data import pack_round_batches
    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task
    from msrflute_tpu.parallel import make_mesh
    from msrflute_tpu.telemetry.timing import Stopwatch

    mesh = make_mesh()
    task = make_task(cfg.model_config)
    fuse = int(cfg.server_config.get("rounds_per_step", 1))
    out = {"protocol": args.protocol, "backend": backend,
           "backend_reason": reason, "rounds_per_step": fuse}

    with tempfile.TemporaryDirectory() as tmp:
        server = OptimizationServer(task, cfg, dataset, model_dir=tmp,
                                    mesh=mesh, seed=0)
        # ---- compile (first chunk) ----
        # telemetry.timing.Stopwatch everywhere below: the same clock
        # the server spans and bench.py use (one timing source of
        # truth); JSON field names unchanged
        with Stopwatch() as sw:
            server.config.server_config.max_iteration = fuse
            server.train()
            jax.block_until_ready(server.state.params)
        out["compile_plus_first_chunk_secs"] = round(sw.secs, 3)

        # ---- host packing cost, measured alone — with the SAME client
        # padding the server uses (pad_to_mesh), or the share is
        # understated exactly on the hardware this tool targets ----
        from msrflute_tpu.parallel.mesh import pad_to_mesh
        sampled = list(range(int(
            cfg.server_config.num_clients_per_iteration)))
        bs = int(cfg.client_config.data_config.train["batch_size"])
        pad_to = pad_to_mesh(len(sampled), mesh)
        pool_mode = server._pool_offsets is not None
        sw = Stopwatch().__enter__()
        for _ in range(5):
            if pool_mode:
                # device-resident pool: the server packs int32 indices,
                # not feature rows — measure what it actually pays
                from msrflute_tpu.data import pack_round_indices
                pack_round_indices(dataset, server._pool_offsets, sampled,
                                   bs, server.max_steps,
                                   rng=np.random.default_rng(0),
                                   pad_clients_to=pad_to)
            else:
                pack_round_batches(dataset, sampled, bs, server.max_steps,
                                   rng=np.random.default_rng(0),
                                   pad_clients_to=pad_to)
        sw.__exit__()
        pack_secs = sw.secs / 5
        out["pack_secs_per_round"] = round(pack_secs, 5)
        out["device_resident_pool"] = pool_mode

        # ---- optional trace chunk: profiler instrumentation inflates
        # wall time, so it is NOT counted into the steady-state stats ----
        if args.trace:
            jax.profiler.start_trace(args.trace)
            try:
                server.config.server_config.max_iteration += fuse
                server.train()
                jax.block_until_ready(server.state.params)
            finally:
                jax.profiler.stop_trace()
            out["trace_dir"] = args.trace

        # ---- timed chunks (the steady state) ----
        per_round = []
        for _ in range(max(args.chunks, 1)):
            server.config.server_config.max_iteration += fuse
            with Stopwatch() as sw:
                server.train()
                jax.block_until_ready(server.state.params)
            per_round.append(sw.secs / fuse)
        out["secs_per_round_p50"] = round(float(np.percentile(per_round, 50)), 5)
        out["secs_per_round_p90"] = round(float(np.percentile(per_round, 90)), 5)
        out["pack_share"] = round(pack_secs / max(np.median(per_round), 1e-9), 3)

        # ---- static per-op-type FLOP decomposition (chip-independent):
        # where the client grad step's FLOPs go — conv/dot (MXU) vs
        # elementwise/bookkeeping (VPU) — so the compute-bound argument
        # doesn't need the chip (utils/flops.py) ----
        one = bench._one_client_batch(dataset, bs, server.max_steps)
        try:
            from msrflute_tpu.utils.flops import flops_by_op

            def _grad_step(p):
                return jax.grad(lambda pp: task.loss(
                    pp, one, jax.random.PRNGKey(0), True)[0])(p)

            out["flops_by_op"] = flops_by_op(_grad_step,
                                             server.state.params)
        except Exception as exc:  # decomposition must not kill the tool
            out["flops_by_op_error"] = f"{type(exc).__name__}: {exc}"

        # ---- XLA's own cost + memory analysis of one client grad step
        # (the shared telemetry/xla.py helper — the same numbers the
        # live device-truth layer records, so this report can never
        # disagree with a scorecard) ----
        cost = bench.grad_step_cost(task, server.state.params, one)
        if cost is not None:
            from msrflute_tpu.telemetry.xla import mfu as mfu_of
            from msrflute_tpu.utils.compat import chip_peak_flops
            flops = float(cost.get("flops", 0.0))
            out["client_step_flops"] = flops
            out["client_step_bytes"] = float(cost.get("bytes_accessed",
                                                      0.0))
            if "hbm_bytes" in cost:
                out["client_step_hbm_bytes"] = cost["hbm_bytes"]
            out["round_model_flops"] = flops * server.max_steps * len(sampled)
            chip_kind, chip_peak = chip_peak_flops()
            value = mfu_of(out["round_model_flops"],
                           float(np.median(per_round)),
                           peak_flops=chip_peak)
            if value is not None:
                out["mfu_vs_chip_peak"] = {"chip": chip_kind,
                                           "mfu": round(value, 6)}
            if on_tpu:
                out["mfu_vs_bf16_peak"] = round(
                    mfu_of(out["round_model_flops"],
                           float(np.median(per_round)),
                           peak_flops=bench.V5E_BF16_PEAK_FLOPS) or 0.0, 5)
        else:
            # structured (not silently swallowed): name the helper that
            # declined so an operator knows WHICH layer has no analysis
            out["cost_analysis_error"] = (
                "telemetry.xla.aot_cost returned None — XLA cost "
                "analysis unavailable on this jax/backend")

        # ---- eval cost breakdown: bench.py's secs_eval is an absolute
        # (~0.07 s even for tiny protocols) larger than a train round;
        # split it into its parts so the absolute is explained, not just
        # amortized away by the eval cadence ----
        try:
            from msrflute_tpu.engine.evaluation import evaluate
            # the profiled server is built without a val split; use the
            # SAME val_ds bench.py times as secs_eval
            server.val_dataset = bench.make_val_ds(dataset, 8)
            server._eval_batches_cache.pop("val", None)
            with Stopwatch() as sw:
                staged = server._packed_eval_batches("val")
                # sync the staging transfers with an indexed scalar fetch
                # per leaf — block_until_ready is not a trustworthy fence
                # on the remote backend
                jax.device_get({k: v[(0,) * v.ndim]
                                for k, v in staged.items()})
            cold_pack = sw.secs
            first = next(iter(staged.values()))
            ev = {"split": "val",
                  "grid_steps_T": int(first.shape[0]),
                  "batch_B": int(first.shape[1]),
                  "grid_bytes": int(sum(int(np.prod(v.shape)) * v.dtype.itemsize
                                        for v in staged.values())),
                  "cold_pack_and_stage_secs": round(cold_pack, 5)}
            # device-only: the jitted scan+psum program on staged arrays.
            # Sync by fetching the (tiny) stat sums — block_until_ready is
            # not a trustworthy fence on the remote backend (see
            # flash_crossover.json history); evaluate() itself device_gets,
            # so this matches what the server's eval path actually pays
            # compile + first run, synced so the warm-up execution cannot
            # drain into the first timed sample
            jax.device_get(server._eval_fn(server.state.params, staged))
            times = []
            for _ in range(10):
                with Stopwatch() as sw:
                    jax.device_get(server._eval_fn(server.state.params,
                                                   staged))
                times.append(sw.secs)
            ev["device_secs_p50"] = round(float(np.percentile(times, 50)), 5)
            # full path as the server pays it each cadence hit: device_put
            # no-ops + device run + device_get + host metric finalize
            times = []
            for _ in range(10):
                with Stopwatch() as sw:
                    evaluate(task, server._eval_fn, server.state.params,
                             staged, mesh, server.engine.partition_mode)
                times.append(sw.secs)
            ev["full_eval_secs_p50"] = round(float(np.percentile(times, 50)), 5)
            ev["host_overhead_secs"] = round(
                ev["full_eval_secs_p50"] - ev["device_secs_p50"], 5)
            out["eval_breakdown"] = ev
        except Exception as exc:  # breakdown must not kill the tool
            out["eval_breakdown_error"] = f"{type(exc).__name__}: {exc}"

        # ---- per-round checkpoint cost: the faithful fullrun saves
        # ``latest`` every round (reference cadence); on a remote-attached
        # chip the full-state fetch is the suspected dominant cost.  Time
        # the synchronous save (fetch + serialize + write) and the
        # device->host fetch alone, so FULLRUN numbers decompose ----
        try:
            from msrflute_tpu.engine.checkpoint import LATEST, _payload
            state = server.state
            nbytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                         for x in jax.tree.leaves(_payload(state))
                         if hasattr(x, "shape"))
            times_f, times_s = [], []
            for _ in range(5):
                with Stopwatch() as sw:
                    jax.device_get(_payload(state))
                times_f.append(sw.secs)
                with Stopwatch() as sw:
                    server.ckpt._write(os.path.join(
                        server.ckpt.model_dir, LATEST), state)
                times_s.append(sw.secs)
            out["checkpoint_cost"] = {
                "state_bytes": int(nbytes),
                "fetch_secs_p50": round(float(np.percentile(times_f, 50)), 5),
                "sync_save_secs_p50": round(float(np.percentile(times_s, 50)), 5),
                "device_to_host_mb_per_s": round(
                    nbytes / 1e6 / max(float(np.percentile(times_f, 50)),
                                       1e-9), 2),
            }
        except Exception as exc:
            out["checkpoint_cost_error"] = f"{type(exc).__name__}: {exc}"

    print(json.dumps(out))


if __name__ == "__main__":
    main()
