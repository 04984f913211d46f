"""CLI launcher — run a federated simulation from a YAML config.

Parity target: reference ``e2e_trainer.py`` (invoked under
``torch.distributed.run`` with ``-config -dataPath -outputPath -task``,
``e2e_trainer.py:198-253``).  The TPU build is single-controller: no
process launcher, no backend flag — the mesh spans whatever devices JAX
sees (multi-host via ``jax.distributed``, see
``msrflute_tpu.parallel.mesh.maybe_init_distributed``).

Usage:
    python e2e_trainer.py -config cfg.yaml -dataPath ./data \
        -outputPath ./out -task cv_lr_mnist
"""

from __future__ import annotations

import argparse
import os
import shutil
import time

import yaml


def main():
    """Run the simulation the command line describes; returns the server
    (run statistics, final state) for an in-process caller."""
    # set-up phases as (name, start, end) on the epoch clock: they are
    # over before a telemetry scope exists, and are handed to it then
    phases, phase_start = [], time.time()

    def phase_done(name: str) -> None:
        nonlocal phase_start
        now = time.time()
        phases.append((name, phase_start, now))
        phase_start = now

    ap = argparse.ArgumentParser()
    ap.add_argument("-config", required=True)
    ap.add_argument("-dataPath", default=None)
    ap.add_argument("-outputPath", default="./output")
    ap.add_argument("-task", default=None)
    ap.add_argument("-num_skip_decoding", default=-1, type=int)  # parity arg
    ap.add_argument("-backend", default="xla")  # parity arg; always XLA here
    args = ap.parse_args()

    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.engine import select_server
    from msrflute_tpu.models import make_task
    from msrflute_tpu.parallel import make_mesh
    from msrflute_tpu.parallel.mesh import maybe_init_distributed
    from msrflute_tpu.tasks import build_server_train_dataset, build_task_datasets
    from msrflute_tpu.utils import init_logging, print_rank

    maybe_init_distributed()

    # output/models/log dir setup + config copy (reference e2e_trainer.py:222-235)
    os.makedirs(args.outputPath, exist_ok=True)
    model_dir = os.path.join(args.outputPath, "models")
    log_dir = os.path.join(args.outputPath, "log")
    os.makedirs(model_dir, exist_ok=True)
    init_logging(log_dir)
    shutil.copyfile(args.config,
                    os.path.join(args.outputPath, os.path.basename(args.config)))

    with open(args.config) as fh:
        raw = yaml.safe_load(fh)
    cfg = FLUTEConfig.from_dict(raw)
    cfg.task = args.task or cfg.task
    cfg.data_path = args.dataPath or cfg.data_path
    cfg.output_path = args.outputPath
    cfg.validate(cfg.data_path)
    from msrflute_tpu.telemetry import trace_config_enabled
    if trace_config_enabled(cfg.server_config.get("telemetry")):
        # from here on every trace / lower / compile jax reports is
        # kept for the scope (engine construction and init_state
        # included); a telemetry-off run registers nothing
        from msrflute_tpu.telemetry import compiles
        compiles.install()

    # plugin-folder resolution (reference loads experiments/<task>/ by the
    # -task name, utils/dataloaders_utils.py:9-23): an explicit
    # model_folder resolves against cwd, the config file's directory, then
    # the repo root; without one, experiments/<task>/task.py is used when
    # it exists, so `-task mytask` alone finds the plugin
    repo_root = os.path.dirname(os.path.abspath(__file__))
    folder = cfg.model_config.get("model_folder")
    if folder:
        for base in ("", os.path.dirname(os.path.abspath(args.config)),
                     repo_root):
            cand = os.path.join(base, folder) if base else folder
            if os.path.isdir(cand):
                cfg.model_config["model_folder"] = os.path.abspath(cand)
                break
    elif cfg.task:
        cand = os.path.join(repo_root, "experiments", cfg.task)
        if os.path.exists(os.path.join(cand, "task.py")):
            cfg.model_config["model_folder"] = cand

    # applied-defaults report (reference core/config.py:771-779 prints the
    # diff between the user YAML and the config with defaults filled in)
    from msrflute_tpu.schema import applied_defaults
    defaults = {k: v for k, v in applied_defaults(raw, cfg).items()
                if k not in ("task", "data_path", "output_path")}  # CLI-assigned
    if defaults:
        print_rank("config defaults applied: "
                   + ", ".join(f"{k}={v!r}" for k, v in sorted(defaults.items())))

    # persistent XLA compilation cache: repeat runs of the same protocol
    # skip the tens-of-seconds first compile — worth it on TPU, harmless
    # elsewhere.  server_config.compilation_cache_dir only switches it on;
    # the directory is JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache
    if cfg.server_config.get("compilation_cache_dir"):
        from msrflute_tpu.utils.backend import enable_compilation_cache
        print_rank(f"compilation cache: {enable_compilation_cache()}")

    phase_done("cli_config")
    task = make_task(cfg.model_config)
    train_ds, val_ds, test_ds = build_task_datasets(cfg, task)
    print_rank(f"task={cfg.task} users={len(train_ds)} "
               f"val={len(val_ds) if val_ds else 0} "
               f"test={len(test_ds) if test_ds else 0}")
    phase_done("data_load")

    # experiment properties at startup (reference log_run_properties,
    # e2e_trainer.py:40-74 — AzureML run properties become metrics.jsonl)
    from msrflute_tpu.utils import log_metric
    from msrflute_tpu.utils.backend import device_report
    log_metric("run_properties", {
        "task": cfg.task,
        # read by tools/fullrun_protocols.py, which never imports jax
        "device": device_report(),
        "model_type": cfg.model_config.get("model_type"),
        "strategy": cfg.strategy,
        "max_iteration": cfg.server_config.get("max_iteration"),
        "num_clients_per_iteration":
            cfg.server_config.get("num_clients_per_iteration"),
        "initial_lr_client": cfg.server_config.get("initial_lr_client"),
        "server_optimizer": cfg.server_config.optimizer_config.get("type"),
        "client_optimizer": cfg.client_config.optimizer_config.get("type"),
        "num_users": len(train_ds),
        "dp_enabled": bool(cfg.dp_config and
                           (cfg.dp_config.get("enable_local_dp") or
                            cfg.dp_config.get("enable_global_dp"))),
    })

    mesh = make_mesh(model_axis_size=int(cfg.mesh_config.get("model_axis_size", 1)))
    server_cls = select_server(cfg.server_config.get("type", "optimization"))
    server = server_cls(task, cfg, train_ds, val_dataset=val_ds,
                        test_dataset=test_ds,
                        server_train_dataset=build_server_train_dataset(cfg, task),
                        model_dir=model_dir, mesh=mesh)
    phase_done("server_build")
    if server.scope is not None:
        server.scope.emit_spans(phases)
    server.run()

    # graceful preemption (SIGTERM/SIGINT mid-run, or the chaos drill's
    # preempt_at_round): the server drained the in-flight round, wrote a
    # durable checkpoint + rng resume anchors, and returned.  Exit with
    # EX_TEMPFAIL (75) so schedulers re-queue the job rather than scoring
    # it as success or crash; re-launching the same command with
    # server_config.resume_from_checkpoint: true continues bit-exactly
    # (docs/RUNBOOK.md "Preemption & recovery drill").
    if getattr(server, "preempted", False):
        print_rank("exiting preempted (EX_TEMPFAIL); resume with "
                   "server_config.resume_from_checkpoint: true")
        raise SystemExit(os.EX_TEMPFAIL)
    return server


if __name__ == "__main__":
    main()
