"""Crash-point fuzzer (flutearmor leg 3): kill the run at EVERY durable
commit point and prove it always resumes bit-identical.

A training run's durable state advances through a small set of atomic
commits — ``os.replace``/``os.rename``/``os.link`` under the model dir:
the two-slot ``latest`` rotation, the orbax pointer, ``status_log.json``,
the checksum sidecars, the fleet row-store ``.npz`` spills and their
round marker.  The recovery contract says a process death at ANY point
in any of those sequences leaves the tree loadable, and a relaunch
trains on to final params bit-identical to an uninterrupted run (a hard
kill may roll back to the previous durable anchor and re-train forward;
the round-keyed RNG anchors make the replay exact).

This tool makes that claim exhaustive instead of anecdotal: it first
runs a CENSUS pass that counts every durable op a run performs, then for
each op index k re-runs from scratch, raises :class:`CrashPoint` (a
``BaseException``, so no retry ladder or best-effort ``except
Exception`` can swallow it) immediately BEFORE op k — simulating death
with the commit un-landed — relaunches with
``resume_from_checkpoint: true``, and asserts the finished params equal
the uninterrupted baseline bit for bit.  ``--phase post`` kills right
AFTER each commit instead (death with the commit landed but every
in-memory postcondition lost).  Both serial and depth-3 pipelined loops
are fuzzed; checkpointing is forced synchronous so every durable op
happens on the training thread.  ``--writer`` fuzzes the other half:
the async msgpack writer with an evaluation every second round, where a
best-model file is the WRITER's commit and the round's durable tail
(status log, ``latest`` link) follows it on the training thread behind
the next dispatch.  A kill in either thread is the process's death: from
that op on, no commit of any thread lands.  The writer's commits fall
between the training thread's in an order that one run does not owe the
next, so that matrix names its kill points (``replace:status_log.json``,
second occurrence) instead of numbering them.  Found with it, and left
standing (ROADMAP debt *presubmit-before-status*): the pipelined loop
submits a ring chunk's ``latest`` BEFORE that chunk's status entry is
written, so at ``--depths 3`` a kill between the two (this census: from
the first ``replace:latest_model.msgpack`` to the first
``replace:status_log.json``) resumes a round that the status ring does
not know, soundly but not bit for bit; the serial matrix is clean.

Run: ``python tools/crashpoint.py`` (CPU, ~minutes for the full
matrix); ``tests/test_crashpoint.py`` drives :func:`fuzz` on a small
point subset inside tier-1's budget.  Exit 0 iff every kill point
resumed bit-identical.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

#: the atomic-commit syscalls a durable-write sequence ends with
DURABLE_OPS = ("replace", "rename", "link")


class CrashPoint(BaseException):
    """Simulated process death at a durable commit point.  Derives from
    ``BaseException`` on purpose: the retry ladder and every best-effort
    ``except Exception`` in the host tail must treat it like SIGKILL,
    not like a transient IO error to absorb."""


class KillSwitch:
    """Intercepts the durable-commit syscalls, scoped to one model dir.

    ``arm(dir, kill_at=None)`` counts ops (census mode); with
    ``kill_at=k`` it raises :class:`CrashPoint` at op k — before the
    commit in phase ``pre``, after it in phase ``post``.  ``kill_at``
    may also name the op: ``("replace:status_log.json", 1)`` is the
    second rename onto that file.  Once it has fired the process is
    dead: every later op in scope, on any thread, raises too."""

    def __init__(self) -> None:
        self._orig = {name: getattr(os, name) for name in DURABLE_OPS}
        self._lock = threading.Lock()
        self.scope_dir: str | None = None
        self.kill_at: int | tuple | None = None
        self.phase = "pre"
        self.count = 0
        self.dead = False
        self.log: list = []

    def install(self) -> None:
        for name in DURABLE_OPS:
            setattr(os, name, self._wrap(name))

    def uninstall(self) -> None:
        for name, orig in self._orig.items():
            setattr(os, name, orig)

    def arm(self, scope_dir: str, kill_at: int | tuple | None = None,
            phase: str = "pre") -> None:
        self.scope_dir = os.path.abspath(scope_dir)
        self.kill_at = kill_at
        self.phase = phase
        self.count = 0
        self.dead = False
        self.log = []

    def disarm(self) -> None:
        self.scope_dir = None
        self.kill_at = None

    def _wrap(self, name):
        orig = self._orig[name]

        def wrapped(src, dst, *args, **kwargs):
            scope = self.scope_dir
            in_scope = (scope is not None and
                        os.path.abspath(str(dst)).startswith(scope))
            if not in_scope:
                return orig(src, dst, *args, **kwargs)
            rel = os.path.relpath(os.path.abspath(str(dst)), scope)
            with self._lock:  # the writer thread commits too
                if self.dead:
                    raise CrashPoint(f"dead before {name} -> {dst}")
                k = self.count
                self.count += 1
                nth = sum(entry == (name, rel) for entry in self.log)
                self.log.append((name, rel))
                mine = self.kill_at in (k, (f"{name}:{rel}", nth))
                if mine and self.phase == "pre":
                    self.dead = True
                    raise CrashPoint(
                        f"killed BEFORE durable op #{k}: {name} -> {dst}")
                out = orig(src, dst, *args, **kwargs)
                if mine and self.phase == "post":
                    self.dead = True
                    raise CrashPoint(
                        f"killed AFTER durable op #{k}: {name} -> {dst}")
            return out
        return wrapped


def _config(depth: int, rounds: int, resume: bool = False,
            writer: bool = False):
    from msrflute_tpu.config import FLUTEConfig
    if writer:
        # the async writer's half: FedAvg, an evaluation every second
        # round (young run: each improves, so each hands a best-model
        # snapshot to the writer and holds its durable tail back), the
        # rounds between going out as the writer's `latest`
        strategy, mode = "fedavg", {
            "val_freq": 2, "initial_val": True,
            "data_config": {"val": {"batch_size": 8}},
            "checkpoint_async": True}
    else:
        # fused_carry paged carry: the row-store spill + marker
        # sequences are in play
        strategy, mode = "scaffold", {
            "fused_carry": True, "val_freq": 10_000, "initial_val": False,
            "data_config": {},
            # a tiny host cache forces spill-through, so the .npz +
            # marker pairing is part of every fuzzed sequence
            "fleet": {"page_pool_slots": 16, "host_cache_rows": 2,
                      "spill_freq": 1},
            # synchronous checkpoints: every durable op on the training
            # thread, op order deterministic (the fuzz precondition)
            "checkpoint_async": False}
    return FLUTEConfig.from_dict({
        "model_config": {"model_type": "LR", "num_classes": 4,
                         "input_dim": 8},
        "strategy": strategy,
        "server_config": {
            "max_iteration": rounds, "num_clients_per_iteration": 4,
            "initial_lr_client": 0.2, "pipeline_depth": depth,
            "rounds_per_step": 1,
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "checkpoint_retry": {"retries": 2, "backoff_base_s": 0.0,
                                 "jitter": 0.0},
            **mode,
            **({"resume_from_checkpoint": True} if resume else {}),
        },
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": 0.2},
            "data_config": {"train": {"batch_size": 4}}},
    })


def _point_order(point):
    """Numbered kill points in their order, named ones after them."""
    return (isinstance(point, tuple), point)


def _dataset():
    sys.path.insert(0, os.path.join(REPO_ROOT, "tests"))
    from conftest import make_synthetic_classification
    return make_synthetic_classification()


def _run(cfg, model_dir: str, dataset):
    import jax
    import numpy as np
    from jax.flatten_util import ravel_pytree

    from msrflute_tpu.engine import OptimizationServer
    from msrflute_tpu.models import make_task

    server = OptimizationServer(
        make_task(cfg.model_config), cfg, dataset, model_dir=model_dir,
        val_dataset=(dataset if cfg.server_config.get("checkpoint_async")
                     else None), seed=7)
    state = server.train()
    return np.asarray(ravel_pytree(jax.device_get(state.params))[0])


def fuzz(depth: int = 0, rounds: int = 3, phase: str = "pre",
         kill_points=None, stride: int = 1, workdir: str | None = None,
         verbose: bool = True, writer: bool = False) -> dict:
    """Run the kill matrix for one loop mode; returns the record
    (census size, points fuzzed, per-point ops).  AssertionError on the
    first kill point whose resumed run is not bit-identical.  A kill
    point is an index into the census or ``("op:file", nth)``;
    ``writer``: the async writer's matrix (see the module docstring),
    every distinct named commit of its census by default."""
    import numpy as np

    from msrflute_tpu.utils.backend import force_cpu_backend
    force_cpu_backend()

    import tempfile
    workdir = workdir or tempfile.mkdtemp(prefix="crashpoint_")
    dataset = _dataset()

    def config(resume=False):
        return _config(depth, rounds, resume=resume, writer=writer)

    baseline = _run(config(), os.path.join(workdir, f"baseline_d{depth}"),
                    dataset)

    switch = KillSwitch()
    switch.install()
    try:
        # census: how many durable commits does this loop mode perform?
        census_dir = os.path.join(workdir, f"census_d{depth}")
        switch.arm(census_dir)
        _run(config(), census_dir, dataset)
        n_ops = switch.count
        census = list(switch.log)
        switch.disarm()

        named = [f"{op}:{rel}" for op, rel in census]
        if kill_points is not None:
            points = sorted(set(kill_points), key=_point_order)
        elif writer:
            points = sorted({(name, named[:i].count(name))
                             for i, name in enumerate(named)})
        else:
            points = list(range(n_ops))
        if stride > 1:
            # always keep the first and last commit; subsample between
            points = sorted(set(points[::stride]) | {points[-1]},
                            key=_point_order)
        for i, k in enumerate(points):
            if isinstance(k, int):
                assert 0 <= k < n_ops, \
                    f"kill point {k} outside census {n_ops}"
            else:
                assert named.count(k[0]) > k[1], \
                    f"kill point {k} not in the census:\n" + "\n".join(named)
            run_dir = os.path.join(workdir, f"d{depth}_{phase}_k{i:03d}")
            switch.arm(run_dir, kill_at=k, phase=phase)
            died = False
            try:
                _run(config(), run_dir, dataset)
            except CrashPoint as exc:
                died = True
                if verbose:
                    print(f"[crashpoint] d{depth} {phase} k={k}: {exc}")
            finally:
                switch.disarm()
            assert died, f"kill point {k} never fired (census drift?)"
            # the relaunch: resume must find a loadable tree (possibly
            # rolled back one anchor) and re-train to the same bits
            flat = _run(config(resume=True), run_dir, dataset)
            assert np.array_equal(baseline, flat), (
                f"kill at durable op {k} (phase {phase}, depth {depth}) "
                "resumed to DIFFERENT final params")
    finally:
        switch.uninstall()

    record = {
        "depth": depth, "rounds": rounds, "phase": phase,
        "durable_ops": n_ops, "points_fuzzed": len(points),
        "census": named,
    }
    if verbose:
        print(f"[crashpoint] depth {depth} phase {phase}: "
              f"{len(points)}/{n_ops} kill points resumed bit-identical")
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--depths", type=int, nargs="*", default=[0, 3],
                    help="loop modes to fuzz (0=serial, 3=depth-3 ring)")
    ap.add_argument("--phase", choices=("pre", "post", "both"),
                    default="pre",
                    help="kill before the commit, after it, or both")
    ap.add_argument("--stride", type=int, default=1,
                    help="fuzz every stride-th kill point (1 = all)")
    ap.add_argument("--writer", action="store_true",
                    help="fuzz the async writer's matrix (best-model "
                    "files through the writer, the durable tail behind "
                    "the next dispatch) instead of the synchronous one")
    ap.add_argument("--report", default=None,
                    help="write the JSON record here")
    args = ap.parse_args(argv)

    phases = ("pre", "post") if args.phase == "both" else (args.phase,)
    records = []
    for depth in args.depths:
        for phase in phases:
            records.append(fuzz(depth=depth, rounds=args.rounds,
                                phase=phase, stride=args.stride,
                                writer=args.writer))
    out = {"kill_matrix": records}
    if args.report:
        with open(args.report, "w") as fh:
            json.dump(out, fh, indent=2)
    print(json.dumps({r["phase"] + f"_d{r['depth']}":
                      f"{r['points_fuzzed']}/{r['durable_ops']}"
                      for r in records}, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
