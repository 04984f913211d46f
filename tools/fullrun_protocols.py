"""Full-length end-to-end runs of the four reference benchmark protocols.

The reference's published numbers are WHOLE-RUN wall-clocks —
100/1500/4000/1200 rounds including per-round ``latest_model``
checkpointing and the eval cadence (``/root/reference/README.md:22-41``,
``core/server.py:530-558``) — which a per-round microbench times an
extrapolation cannot stand in for.  This tool drives the REAL CLI (``e2e_trainer.py``) through
each protocol at the reference's published geometry (BASELINE.md):

    protocol             pool   K/round  batch  lr    rounds  eval freq
    lr_mnist             1000   10       10     0.03   100    20
    cnn_femnist          3400   10       20     0.1   1500    50
    resnet_fedcifar100    500   10       20     0.1   4000    50
    rnn_fedshakespeare    715   10        4     0.8   1200    50

on full-size synthetic blobs (the real datasets are unreachable — zero
egress; geometry and per-user sample counts match the real corpora), with
``rounds_per_step: 1`` so ``latest_model`` is written EVERY round exactly
like the reference, and eval at the published cadence on full-size
val/test blobs.  The measured quantity is the END-TO-END wall-clock of
the trainer process (startup + compile + all rounds + evals + checkpoint
I/O) — directly comparable to the published FLUTE NCCL totals
(1:35 / 8:22 / 1:42:01 / 21:50).

Each protocol runs as its own subprocess of the actual CLI — this parent
never imports jax, so each child has the chip to itself — and results
land in ``.scratch/fullrun_out*/FULLRUN_<PLATFORM>_<stamp>.json`` with the
total wall-clock, the vs-published ratio, and the full val-accuracy curve
parsed from the run's ``metrics.jsonl``.

``--platform`` (default ``tpu``) says where the run is meant to happen;
each child reports the device it actually ran on (``run_properties`` in
its ``metrics.jsonl``) and a child that ran elsewhere, or that failed,
makes the exit code non-zero.

Env knobs:
    FULLRUN_PROTOCOLS=lr_mnist,cnn_femnist   subset selection
    FULLRUN_SMOKE=1                          tiny geometry (CI contract)
    FULLRUN_FUSED=N                          also run a rounds_per_step=N
                                             variant per protocol (the
                                             TPU-best-practice number;
                                             checkpoint cadence then
                                             follows the fuse boundary)
    FULLRUN_DATA_DIR=...                     blob cache (default
                                             .scratch/fullrun_data)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np
import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: published FLUTE NCCL whole-run wall-clocks, seconds
#: (reference README.md:38-41)
PUBLISHED_SECS = {
    "lr_mnist": 95.0,            # 00:01:35
    "cnn_femnist": 502.0,        # 00:08:22
    "resnet_fedcifar100": 6121.0,  # 01:42:01
    "rnn_fedshakespeare": 1310.0,  # 00:21:50
}

#: reference geometry (README.md:22-27; BASELINE.md table).  spu = samples
#: per user, matched to the real corpora's averages (MNIST 60k/1000,
#: federated EMNIST ~100/user, Fed-CIFAR-100 100/user, Shakespeare lines).
#: ``tscale``/``flip`` set corpus difficulty, ridge-probed offline so the
#: attached accuracy curves live in each protocol's published
#: neighborhood instead of saturating instantly (LR ~81%, CNN ~83%,
#: ResNet ~33%, Shakespeare next-char ~57% — README.md:38-41).  The
#: probes (0.79 / 0.78 / 0.30 at 6-8k samples) are small-sample LOWER
#: bounds — full-pool training lands somewhat higher (measured: LR
#: 0.847 at 60k samples/100 rounds); the token walk's flip rate caps
#: next-char accuracy at ~1-flip.
PROTOCOLS = {
    "lr_mnist": dict(
        model={"model_type": "LR", "num_classes": 10, "input_dim": 784},
        pool=1000, spu=60, batch=10, lr=0.03, rounds=100, freq=20,
        shape=(784,), classes=10, val_users=100, val_spu=100, tscale=0.1),
    "cnn_femnist": dict(
        model={"model_type": "CNN", "num_classes": 62},
        pool=3400, spu=100, batch=20, lr=0.1, rounds=1500, freq=50,
        shape=(28, 28, 1), classes=62, val_users=340, val_spu=100,
        tscale=0.15),
    "resnet_fedcifar100": dict(
        model={"model_type": "RESNET", "num_classes": 100,
               "image_size": 32},
        pool=500, spu=100, batch=20, lr=0.1, rounds=4000, freq=50,
        shape=(32, 32, 3), classes=100, val_users=100, val_spu=100,
        tscale=0.08),
    "rnn_fedshakespeare": dict(
        model={"model_type": "RNN", "vocab_size": 90, "embed_dim": 8,
               "hidden_dim": 256, "seq_len": 80},
        pool=715, spu=50, batch=4, lr=0.8, rounds=1200, freq=50,
        shape=None, classes=90, val_users=100, val_spu=30, flip=0.45),
}

SMOKE_OVERRIDES = dict(pool=12, spu=10, rounds=4, freq=2,
                       val_users=4, val_spu=8)


def _shrink(spec: dict) -> dict:
    out = dict(spec)
    out.update(SMOKE_OVERRIDES)
    return out


# ----------------------------------------------------------------------
# synthetic full-size data, learnable (class-structured): accuracy curves
# must move, the compute per sample matches the real corpus shapes
# ----------------------------------------------------------------------
def _write_image_blob(path, pool, spu, shape, classes, seed, tscale):
    import h5py
    dim = int(np.prod(shape))
    rng = np.random.default_rng(seed)
    # ONE class template bank for every split (fixed seed, independent of
    # the per-split sample seed): train and val must share the label rule
    # or val accuracy measures an unrelated function and sits at chance
    templates = np.random.default_rng(12345).normal(
        size=(classes, dim)).astype(np.float32) * tscale
    with h5py.File(path, "w") as fh:
        users_grp = fh.create_group("user_data")
        names, counts = [], []
        for u in range(pool):
            y = rng.integers(0, classes, size=spu)
            x = (rng.normal(size=(spu, dim)).astype(np.float32)
                 + templates[y])
            g = users_grp.create_group(f"u{u:05d}")
            g.create_dataset("x", data=x.reshape((spu,) + shape))
            g.create_dataset("y", data=y.astype(np.int64))
            names.append(f"u{u:05d}")
            counts.append(spu)
        fh.create_dataset(
            "users", data=np.asarray(names, dtype=h5py.string_dtype()))
        fh.create_dataset("num_samples", data=np.asarray(counts))


def _write_token_blob(path, pool, spu, seq_len, vocab, seed, flip):
    import h5py
    rng = np.random.default_rng(seed)
    # learnable next-char rule: a FIXED random walk over the vocab (seed
    # independent of the split, same reason as the image templates) with
    # per-split sample noise, like the parity harness's synthetic
    # shakespeare; the flip rate caps next-char accuracy at ~1-flip
    step = np.random.default_rng(54321).integers(1, 7, size=vocab)
    with h5py.File(path, "w") as fh:
        users_grp = fh.create_group("user_data")
        names, counts = [], []
        for u in range(pool):
            start = rng.integers(1, vocab, size=(spu, 1))
            x = np.empty((spu, seq_len), np.int64)
            x[:, :1] = start
            for t in range(1, seq_len):
                nxt = (x[:, t - 1] + step[x[:, t - 1] % vocab]) % vocab
                flipped = rng.random(spu) < flip
                nxt = np.where(flipped, rng.integers(1, vocab, size=spu),
                               nxt)
                x[:, t] = np.maximum(nxt, 1)
            g = users_grp.create_group(f"u{u:05d}")
            g.create_dataset("x", data=x)
            names.append(f"u{u:05d}")
            counts.append(spu)
        fh.create_dataset(
            "users", data=np.asarray(names, dtype=h5py.string_dtype()))
        fh.create_dataset("num_samples", data=np.asarray(counts))


def _ensure_data(name: str, spec: dict, data_dir: str) -> dict:
    os.makedirs(data_dir, exist_ok=True)
    paths = {}
    for split, (pool, spu) in {
            "train": (spec["pool"], spec["spu"]),
            "val": (spec["val_users"], spec["val_spu"]),
            "test": (spec["val_users"], spec["val_spu"])}.items():
        # v3: shared-template corpus (split-independent label rule) at
        # ridge-probed difficulty; the version tag invalidates caches
        # from earlier generators
        hardness = spec.get("tscale", spec.get("flip"))
        fname = f"{name}_{split}_{pool}x{spu}_h{hardness}_v3.hdf5"
        fpath = os.path.join(data_dir, fname)
        # prune superseded generations of this split (a difficulty retune
        # or generator bump renames the cache; the orphans are GB-class)
        import glob as _glob
        for old in _glob.glob(os.path.join(data_dir,
                                           f"{name}_{split}_*.hdf5")):
            if os.path.basename(old) != fname:
                os.remove(old)
        if not os.path.exists(fpath):
            seed = {"train": 0, "val": 1, "test": 2}[split]
            if spec["shape"] is None:
                _write_token_blob(fpath, pool, spu,
                                  spec["model"]["seq_len"],
                                  spec["model"]["vocab_size"], seed,
                                  spec["flip"])
            else:
                _write_image_blob(fpath, pool, spu, spec["shape"],
                                  spec["classes"], seed, spec["tscale"])
        paths[split] = fname
    return paths


# ----------------------------------------------------------------------
def _config(name: str, spec: dict, paths: dict, fuse: int,
            on_tpu: bool) -> dict:
    """The six-section FLUTE config for one protocol run.

    ``rounds_per_step: 1`` (the faithful mode) makes the housekeeping
    tail — including the ``latest_model`` save — run EVERY round, the
    reference's cadence (``core/server.py:530``)."""
    return {
        "model_config": spec["model"],
        "strategy": "fedavg",
        "server_config": {
            "max_iteration": spec["rounds"],
            "num_clients_per_iteration": 10,
            "initial_lr_client": spec["lr"],
            "optimizer_config": {"type": "sgd", "lr": 1.0},
            "val_freq": spec["freq"], "rec_freq": spec["freq"],
            "initial_val": False, "initial_rec": False,
            "best_model_criterion": "acc",
            "rounds_per_step": fuse,
            # per-round latest saves overlap the next round's compute
            # (same durability contract as orbax async: a crash can lose
            # only the in-flight save) — without this the faithful fuse=1
            # mode pays a synchronous full-state device->host fetch every
            # round
            "checkpoint_async": True,
            # warm repeat compiles across protocols/runs (an on-switch:
            # utils.backend.enable_compilation_cache places the cache)
            "compilation_cache_dir": ".jax_cache",
            "data_config": {
                "val": {"batch_size": 256, "val_data": paths["val"]},
                "test": {"batch_size": 256, "test_data": paths["test"]},
            },
        },
        "client_config": {
            "optimizer_config": {"type": "sgd", "lr": spec["lr"]},
            "data_config": {"train": {
                "batch_size": spec["batch"],
                "list_of_train_data": paths["train"],
                # TPU-native data path (bit-identical to host packing,
                # tests/test_device_pool.py): the flat sample pool lives
                # in HBM, per-round only [K,S,B] indices cross the host
                "device_resident": bool(on_tpu),
            }},
        },
    }


def _parse_metrics(out_dir: str):
    """Val-acc curve, timing stats and the device the child reports it
    ran on, from the run's metrics.jsonl."""
    curve, timing, device = [], {}, None
    path = os.path.join(out_dir, "log", "metrics.jsonl")
    if not os.path.exists(path):
        return curve, timing, device
    with open(path) as fh:
        for line in fh:
            try:
                rec = json.loads(line)
            except Exception:
                continue
            if rec.get("name") == "Val acc":
                curve.append([rec.get("step"), round(float(rec["value"]), 4)])
            if str(rec.get("name", "")).startswith("secsPerRound"):
                timing[rec["name"]] = round(float(rec["value"]), 4)
            if rec.get("name") == "run_properties":
                device = (rec.get("value") or {}).get("device")
    return curve, timing, device


def run_protocol(name: str, spec: dict, data_dir: str, out_root: str,
                 fuse: int, platform: str) -> dict:
    paths = _ensure_data(name, spec, data_dir)
    tag = f"{name}_fuse{fuse}"
    out_dir = os.path.join(out_root, tag)
    # a reused output dir APPENDS to metrics.jsonl and the parsed curve
    # then interleaves runs — each invocation starts clean
    import shutil
    shutil.rmtree(out_dir, ignore_errors=True)
    cfg_path = os.path.join(out_root, f"{tag}.yaml")
    with open(cfg_path, "w") as fh:
        yaml.safe_dump(_config(name, spec, paths, fuse,
                               on_tpu=platform == "tpu"), fh)
    cmd = [sys.executable, os.path.join(REPO, "e2e_trainer.py"),
           "-config", cfg_path, "-dataPath", data_dir,
           "-outputPath", out_dir, "-task", name]
    # hang protection: the run must finish WELL under the published
    # wall-clock for the number to mean anything, so published + compile
    # headroom is a generous budget
    budget = (PUBLISHED_SECS.get(name) or 600.0) + 600.0
    if os.environ.get("FULLRUN_SMOKE"):
        budget = 300.0
    env = dict(os.environ)
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    else:
        # the default platform: jax picks the chip, and fails at start-up
        # if it cannot
        env.pop("JAX_PLATFORMS", None)
    tic = time.time()
    try:
        proc = subprocess.run(cmd, cwd=REPO, capture_output=True,
                              text=True, timeout=budget, env=env)
    except subprocess.TimeoutExpired as exc:
        total = time.time() - tic
        curve, timing, device = _parse_metrics(out_dir)
        return {
            "rounds": spec["rounds"], "total_secs": round(total, 1),
            "published_secs": PUBLISHED_SECS.get(name),
            "vs_published": None, "rounds_per_step": fuse,
            "returncode": "timeout", "device": device,
            "timing": timing, "val_acc_curve": curve,
            "stderr_tail": (exc.stderr or b"")[-2000:].decode(
                "utf-8", "replace") if isinstance(exc.stderr, bytes)
            else str(exc.stderr or "")[-2000:],
        }
    total = time.time() - tic
    curve, timing, device = _parse_metrics(out_dir)
    published = PUBLISHED_SECS.get(name)
    returncode = proc.returncode
    if returncode == 0 and (device or {}).get("platform") != platform:
        # the child ran, but not where this run was meant to happen: its
        # wall-clock must not be filed under the expected platform
        returncode = "wrong-platform"
    res = {
        "rounds": spec["rounds"],
        "total_secs": round(total, 1),
        "published_secs": published,
        "vs_published": (round(published / total, 2)
                         if published and returncode == 0 else None),
        "rounds_per_step": fuse,
        "returncode": returncode,
        "device": device,
        "secs_per_round_incl_everything": round(total / spec["rounds"], 4),
        "timing": timing,
        "final_val_acc": curve[-1][1] if curve else None,
        "val_acc_curve": curve,
    }
    if returncode != 0:
        res["stderr_tail"] = proc.stderr[-2000:]
    return res


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="where the trainers are meant to run; a child "
                         "that reports another platform fails the run")
    platform = ap.parse_args().platform
    smoke = bool(os.environ.get("FULLRUN_SMOKE"))
    data_dir = os.environ.get(
        "FULLRUN_DATA_DIR",
        os.path.join(REPO, ".scratch",
                     "fullrun_data" + ("_smoke" if smoke else "")))
    out_root = os.path.join(REPO, ".scratch",
                            "fullrun_out" + ("_smoke" if smoke else ""))
    os.makedirs(out_root, exist_ok=True)
    keep = os.environ.get("FULLRUN_PROTOCOLS")
    names = [n for n in PROTOCOLS
             if keep is None or n in keep.split(",")]
    fused_extra = int(os.environ.get("FULLRUN_FUSED", 0) or 0)

    results = {}
    for name in names:
        spec = _shrink(PROTOCOLS[name]) if smoke else PROTOCOLS[name]
        print(f"[fullrun] {name}: generating data + running "
              f"{spec['rounds']} rounds (faithful, fuse=1)", file=sys.stderr)
        results[name] = run_protocol(name, spec, data_dir, out_root,
                                     fuse=1, platform=platform)
        print(f"[fullrun] {name}: {results[name]['total_secs']}s "
              f"(vs_published {results[name]['vs_published']})",
              file=sys.stderr)
        if fused_extra > 1:
            results[f"{name}_fused{fused_extra}"] = run_protocol(
                name, spec, data_dir, out_root, fuse=fused_extra,
                platform=platform)

    stamp = time.strftime("%Y%m%d_%H%M%S")
    payload = {
        "kind": "fullrun_protocols",
        "backend": platform,
        "smoke": smoke,
        "captured_at": stamp,
        "geometry": "reference README.md:22-27; per-round latest "
                    "checkpointing (core/server.py:530-558); eval at "
                    "published cadence; synthetic full-size blobs",
        "protocols": results,
    }
    out_path = os.path.join(out_root,
                            f"FULLRUN_{platform.upper()}_{stamp}.json")
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=1)
    print(json.dumps(payload))
    print(f"[fullrun] wrote {out_path}", file=sys.stderr)
    failed = [n for n, r in results.items() if r["returncode"] != 0]
    if failed:
        print(f"[fullrun] failed: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
