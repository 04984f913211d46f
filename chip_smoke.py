"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once — ``e2e_trainer.py`` -> ``OptimizationServer.run()``
-> the ``RoundEngine`` round program -> eval -> checkpoint — in ONE process
that imports jax once and holds the chip; the trainer runs in-process through
``e2e_trainer.main()`` with ``sys.argv`` set, never in a child.  No phase is
caught and skipped: anything that raises, and any failed check, ends the run
with a non-zero exit and ``"ok": false``.

One chip (no arguments, as the driver runs it):

1. ``resnet``   ResNet-18+GroupNorm (11,227,812 parameters) on synthetic
                Fed-CIFAR-100 with ``experiments/cv_resnet_fedcifar100/
                config.yaml`` unchanged except ``max_iteration`` (two fused
                chunks of 25 rounds), the backup cadence and the data paths.
2. ``dp_quant`` 5 rounds of CNN_FEMNIST under ``strategy: dga`` with global
                DP and gradient quantization, so the Pallas noise and
                quantization kernels run inside the compiled round.
3. ``kernels``  each Pallas kernel compiled (never interpreted) at a real
                size against its jnp/dense reference.
4. ``resume``   the ResNet command again with ``resume_from_checkpoint`` and
                ``max_iteration: 75``: resumes at 50, reaches 75, and builds
                the same round program, so the persistent compile cache hits.

``--chips 4`` runs ONLY the four-chip phase: the ResNet config for 4 rounds at
``rounds_per_step: 1`` on a four-device and on a one-device mesh, same seed,
compared round by round.

Every line on stdout is one JSON object; the last is
``{"ok": ..., "device": {"platform", "kind", "count"}, "claim": null}`` with
the device as jax reports it.  Times are observations of this run, not claims.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
RESNET_CONFIG = os.path.join(REPO, "experiments", "cv_resnet_fedcifar100",
                             "config.yaml")
CNN_CONFIG = os.path.join(REPO, "experiments", "cv_cnn_femnist",
                          "config.yaml")
#: four chips against one (see phase_four_chips for the reasons)
ROUND0_TOL = 1e-5
ROUND1_TOL = 1e-3
BAND = 1e-1
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def check(cond: bool, what: str) -> None:
    """A failed check fails the run (``assert`` would vanish under -O)."""
    if not cond:
        raise RuntimeError(f"check failed: {what}")


# ----------------------------------------------------------------------
# observation: compile events (jax.monitoring) and chunk windows
# ----------------------------------------------------------------------
class CompileLog:
    """Every backend-compile request and persistent-cache hit/miss jax
    reports, with the wall-clock time it ended — counted by listener, not
    by eye.  (The backend-compile event wraps the persistent-cache lookup,
    so a cache hit is still one event: a program was requested.)"""

    def __init__(self):
        self.compiles = []  # (end_ts, seconds, fun_name)
        self.hits = []      # ts of each persistent-cache hit
        self.misses = []    # ts of each persistent-cache miss

    def install(self) -> None:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event == BACKEND_COMPILE_EVENT:
            self.compiles.append((time.time(), float(duration),
                                  str(kwargs.get("fun_name"))))

    def _event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT:
            self.hits.append(time.time())
        elif event == CACHE_MISS_EVENT:
            self.misses.append(time.time())

    def between(self, t0: float, t1: float) -> list:
        return [c for c in self.compiles if t0 < c[0] <= t1]

    def cache_between(self, t0: float, t1: float) -> dict:
        return {"hits": sum(t0 < t <= t1 for t in self.hits),
                "misses": sum(t0 < t <= t1 for t in self.misses)}


class ChunkClock:
    """Wall-clock windows of every round-program dispatch and of its stats
    fence, read by wrapping the two engine calls (observation only: same
    arguments, same results).  The fence is ``block_until_ready`` on the
    chunk's packed stats followed by the engine's own fetch."""

    def __init__(self):
        self.dispatches = []   # (start_ts, end_ts)
        self.fences = []       # end_ts of each chunk's stats fetch
        self.losses = []       # per-round mean train loss, in fence order
        self.staged_device_sets = []  # device-set sizes of device_put results

    @contextlib.contextmanager
    def watching(self, spy_device_put: bool = False):
        import jax
        from msrflute_tpu.engine import round as round_mod

        dispatch = round_mod.RoundEngine.dispatch_rounds
        fetch = round_mod.PackedStats.fetch
        device_put = jax.device_put
        clock = self

        def timed_dispatch(engine, *args, **kwargs):
            t0 = time.time()
            if spy_device_put:
                jax.device_put = spying_put
            try:
                out = dispatch(engine, *args, **kwargs)
            finally:
                jax.device_put = device_put
            clock.dispatches.append((t0, time.time()))
            return out

        def spying_put(x, *args, **kwargs):
            out = device_put(x, *args, **kwargs)
            clock.staged_device_sets.append(sorted(
                {(len(leaf.sharding.device_set),
                  bool(leaf.sharding.is_fully_replicated))
                 for leaf in jax.tree.leaves(out)}))
            return out

        def timed_fetch(stats):
            jax.block_until_ready(stats.vecs)
            out = fetch(stats)
            clock.fences.append(time.time())
            counts = np.maximum(out["client_count"], 1.0)
            clock.losses.extend(
                float(v) for v in out["train_loss_sum"] / counts)
            return out

        round_mod.RoundEngine.dispatch_rounds = timed_dispatch
        round_mod.PackedStats.fetch = timed_fetch
        try:
            yield self
        finally:
            round_mod.RoundEngine.dispatch_rounds = dispatch
            round_mod.PackedStats.fetch = fetch

    def chunk_secs(self) -> list:
        """Fence-to-fence seconds per chunk (a pipelined chunk is
        dispatched before the previous fence, so its own time starts
        there)."""
        out, prev = [], 0.0
        for (start, _), fence in zip(self.dispatches, self.fences):
            out.append(fence - max(start, prev))
            prev = fence
        return out


# ----------------------------------------------------------------------
# data: generated from a seed, labels a function of the image
# ----------------------------------------------------------------------
def write_image_blob(path: str, users: int, samples: int, shape: tuple,
                     classes: int, seed: int, scale: float) -> None:
    """hdf5 user blob whose images are ``scale`` x (class prototype plus
    noise).  The prototype bank has its own fixed seed so every split
    shares the label rule; under labels drawn at random the loss need not
    fall."""
    import h5py
    dim = int(np.prod(shape))
    prototypes = np.random.default_rng(20260926).normal(
        size=(classes, dim)).astype(np.float32)
    rng = np.random.default_rng(seed)
    names = [f"u{u:05d}" for u in range(users)]
    with h5py.File(path, "w") as fh:
        group = fh.create_group("user_data")
        for name in names:
            y = rng.integers(0, classes, size=samples)
            x = scale * (prototypes[y] + 0.5 * rng.normal(
                size=(samples, dim)).astype(np.float32))
            user = group.create_group(name)
            user.create_dataset("x", data=x.reshape((samples,) + shape))
            user.create_dataset("y", data=y.astype(np.int64))
        fh.create_dataset("users",
                          data=np.asarray(names, dtype=h5py.string_dtype()))
        fh.create_dataset("num_samples", data=np.full((users,), samples))


def write_splits(data_dir: str, shape: tuple, classes: int, users: int,
                 samples: int, val_users: int, scale: float = 1.0) -> None:
    os.makedirs(data_dir, exist_ok=True)
    for seed, (split, n) in enumerate({"train": users, "val": val_users,
                                       "test": val_users}.items()):
        write_image_blob(os.path.join(data_dir, f"{split}.hdf5"), n, samples,
                         shape, classes, seed, scale)


def load_config(path: str) -> dict:
    import yaml
    with open(path) as fh:
        return yaml.safe_load(fh)


def point_at_splits(cfg: dict) -> dict:
    cfg["server_config"]["data_config"]["val"]["val_data"] = "val.hdf5"
    cfg["server_config"]["data_config"]["test"]["test_data"] = "test.hdf5"
    cfg["client_config"]["data_config"]["train"]["list_of_train_data"] = \
        "train.hdf5"
    return cfg


# ----------------------------------------------------------------------
# the trainer, in-process through the CLI entry point
# ----------------------------------------------------------------------
def run_cli(cfg: dict, name: str, task: str, data_dir: str, out_dir: str):
    """``e2e_trainer.main()`` with ``sys.argv`` set, as a user's command
    line would; returns the server once its final params are ready."""
    import jax
    import yaml

    import e2e_trainer
    os.makedirs(out_dir, exist_ok=True)
    cfg_path = os.path.join(os.path.dirname(out_dir), f"{name}.yaml")
    with open(cfg_path, "w") as fh:
        yaml.safe_dump(cfg, fh)
    argv, sys.argv = sys.argv, [
        "e2e_trainer.py", "-config", cfg_path, "-dataPath", data_dir,
        "-outputPath", out_dir, "-task", task]
    try:
        server = e2e_trainer.main()
    finally:
        sys.argv = argv
    jax.block_until_ready(server.state.params)
    return server


def read_metrics(out_dir: str) -> list:
    with open(os.path.join(out_dir, "log", "metrics.jsonl")) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def checkpoint_facts(server) -> dict:
    """``status_log.json``'s round, and ``latest_model.msgpack`` read back
    by the checkpoint manager's own loader (integrity check included) and
    compared with the state the run ended in."""
    import jax
    restored = server.ckpt.load(server.state)
    check(restored is not None, "latest_model.msgpack is not readable")
    same = jax.tree.map(lambda a, b: bool(np.array_equal(a, b)),
                        jax.device_get(restored.params),
                        jax.device_get(server.state.params))
    model_dir = server.ckpt.model_dir
    return {
        "status_round": int(server.ckpt.read_status()["i"]),
        "latest_round": int(restored.round),
        "latest_equals_final_params": all(jax.tree.leaves(same)),
        "latest_bytes": os.path.getsize(
            os.path.join(model_dir, "latest_model.msgpack")),
        "backups": sorted(f for f in os.listdir(model_dir)
                          if f.startswith("epoch")),
    }


def run_trainer(name: str, cfg: dict, task: str, data_dir: str, out_dir: str,
                compiles: CompileLog, rounds_per_chunk: int):
    """One watched trainer run.  Returns ``(server, report)``; the
    report holds the facts every trainer phase prints: losses, set-up /
    per-chunk seconds, compile events and persistent-cache hits and misses
    (whole run, and inside the first dispatch — the round program's own),
    checkpoint."""
    clock = ChunkClock()
    t_start = time.time()
    with clock.watching():
        server = run_cli(cfg, name, task, data_dir, out_dir)
    t_end = time.time()
    first_dispatch = clock.dispatches[0]
    report = {
        "phase": name,
        "rounds": len(clock.losses),
        "loss_first_chunk_mean": float(np.mean(
            clock.losses[:rounds_per_chunk])),
        "loss_last_chunk_mean": float(np.mean(
            clock.losses[-rounds_per_chunk:])),
        "loss_all_finite": bool(np.isfinite(clock.losses).all()),
        "setup_secs": first_dispatch[0] - t_start,
        "chunk_secs": clock.chunk_secs(),
        "first_dispatch_compile_events": [
            (fun, round(dur, 3)) for _, dur, fun in
            compiles.between(first_dispatch[0], first_dispatch[1])],
        "first_dispatch_cache": compiles.cache_between(*first_dispatch),
        "total_secs": t_end - t_start,
        "compile_requests": len(compiles.between(t_start, t_end)),
        "cache": compiles.cache_between(t_start, t_end),
        "checkpoint": checkpoint_facts(server),
    }
    if len(clock.dispatches) > 1:
        # the second chunk's window: from its dispatch to its stats fence
        window = compiles.between(clock.dispatches[1][0], clock.fences[1])
        report["second_chunk_compiles"] = len(window)
        report["second_chunk_compile_names"] = [c[2] for c in window]
    return server, report


def phase_resnet(work: str, compiles: CompileLog, *, rounds: int = 50,
                 rounds_per_step: int = 25, users: int = 50,
                 samples: int = 100, val_users: int = 10) -> dict:
    """The cold ResNet run.  Returns what :func:`phase_resume` needs to
    repeat the command: the config, the directories, the cold report."""
    import jax

    from msrflute_tpu import native
    so_before = os.path.exists(native._SO_PATH)

    data_dir = os.path.join(work, "fedcifar100")
    out_dir = os.path.join(work, "resnet_out")
    t0 = time.time()
    write_splits(data_dir, (32, 32, 3), 100, users, samples, val_users)
    data_secs = time.time() - t0

    cfg = point_at_splits(load_config(RESNET_CONFIG))
    sc = cfg["server_config"]
    sc["max_iteration"] = rounds
    sc["rounds_per_step"] = rounds_per_step
    sc["val_freq"] = sc["rec_freq"] = sc["model_backup_freq"] = rounds

    server, cold = run_trainer("resnet", cfg, "cv_resnet_fedcifar100",
                               data_dir, out_dir, compiles, rounds_per_step)
    cold["data_gen_secs"] = data_secs
    cold["params"] = int(sum(
        np.prod(v.shape) for v in jax.tree.leaves(server.state.params)))
    cold["native_packer"] = (
        "numpy path (native lib unavailable)" if not native.native_available()
        else "preexisting _packer.so" if so_before
        else "built from packer.cpp in this run")
    cold["val_acc"] = val_acc = [
        (m["step"], m["value"]) for m in read_metrics(out_dir)
        if m.get("name") == "Val acc"]
    emit(cold)
    check(cold["rounds"] == rounds, f"ran {cold['rounds']} of {rounds} rounds")
    check(cold["loss_all_finite"], "a train loss is not finite")
    check(cold["loss_last_chunk_mean"] < cold["loss_first_chunk_mean"],
          "train loss did not fall from the first chunk to the last")
    check(bool(val_acc) and val_acc[-1][0] == rounds and
          val_acc[-1][1] > 1.0 / 100, f"val acc not above chance: {val_acc}")
    check(cold["checkpoint"]["status_round"] == rounds and
          cold["checkpoint"]["latest_round"] == rounds,
          "status_log.json / latest_model are not at the last round")
    check(cold["checkpoint"]["latest_equals_final_params"],
          "latest_model.msgpack does not hold the final parameters")
    check(bool(cold["checkpoint"]["backups"]), "no backup checkpoint written")
    check(cold.get("second_chunk_compiles") == 0,
          "the second chunk compiled a program: "
          f"{cold.get('second_chunk_compile_names')}")
    return {"cfg": cfg, "data_dir": data_dir, "out_dir": out_dir,
            "cold": cold, "rounds": rounds,
            "rounds_per_step": rounds_per_step}


def phase_resume(ctx: dict, compiles: CompileLog, *,
                 resume_to: int = 75) -> dict:
    """The same command with ``resume_from_checkpoint`` and a later
    ``max_iteration``.  A new engine traces the same round program, so
    this is where the persistent compile cache must hit."""
    sc = ctx["cfg"]["server_config"]
    sc["max_iteration"] = resume_to
    sc["resume_from_checkpoint"] = True
    server, warm = run_trainer(
        "resume", ctx["cfg"], "cv_resnet_fedcifar100", ctx["data_dir"],
        ctx["out_dir"], compiles, ctx["rounds_per_step"])
    cold = ctx["cold"]
    warm["resumed_at"] = resume_to - warm["rounds"]
    warm["reached"] = int(server.state.round)
    warm["first_chunk_secs_cold"] = cold["chunk_secs"][0]
    warm["first_chunk_secs_resumed"] = warm["chunk_secs"][0]
    # a machine that came with this repository's cache serves the "cold"
    # run from it too: only a cold run that MISSED has a longer chunk
    warm["cold_run_missed"] = cold["first_dispatch_cache"]["misses"] > 0
    emit(warm)
    check(warm["resumed_at"] == ctx["rounds"],
          f"resumed at {warm['resumed_at']}, not {ctx['rounds']}")
    check(warm["reached"] == resume_to and
          warm["checkpoint"]["status_round"] == resume_to,
          f"did not reach round {resume_to}")
    check(warm["loss_all_finite"], "a resumed train loss is not finite")
    check(warm["first_dispatch_cache"] == {"hits": 1, "misses": 0},
          "the resumed round program was not served from the persistent "
          f"compile cache: {warm['first_dispatch_cache']}")
    check(not warm["cold_run_missed"] or
          warm["first_chunk_secs_resumed"] < warm["first_chunk_secs_cold"],
          "the resumed first chunk was not shorter than the cold one")
    return warm


def phase_dp_quant(work: str, compiles: CompileLog, *, rounds: int = 5,
                   users: int = 30, samples: int = 60) -> dict:
    """CNN_FEMNIST for a few rounds under DGA + global DP + quantization:
    the Pallas branches of ``privacy.apply_global_dp`` and
    ``ops.quantization.quantize_array`` run inside the compiled round."""
    data_dir = os.path.join(work, "femnist")
    out_dir = os.path.join(work, "dp_quant_out")
    # the CNN has no normalisation layer: keep the pixels in FEMNIST's
    # range or lr 0.1 diverges inside the first client's steps
    write_splits(data_dir, (28, 28, 1), 62, users, samples, 6, scale=0.25)
    cfg = point_at_splits(load_config(CNN_CONFIG))
    cfg["strategy"] = "dga"
    cfg["model_config"].update(quant_bits=8, quant_threshold=0.5)
    cfg["dp_config"] = {
        "enable_global_dp": True, "enable_local_dp": False,
        "global_sigma": 0.01, "max_grad": 1.0, "eps": 100.0, "delta": 1e-7,
        "max_weight": 10.0, "min_weight": 0.0, "weight_scaler": 1.0}
    sc = cfg["server_config"]
    sc.update(max_iteration=rounds, rounds_per_step=1, val_freq=rounds,
              rec_freq=rounds, model_backup_freq=rounds,
              aggregate_median="softmax", softmax_beta=1.0,
              weight_train_loss="train_loss")
    server, report = run_trainer("dp_quant", cfg, "cv_cnn_femnist",
                                 data_dir, out_dir, compiles, 1)
    report["strategy"] = type(server.strategy).__name__
    report["quant_thresh"] = server.quant_thresh
    emit(report)
    check(report["rounds"] == rounds and
          report["checkpoint"]["status_round"] == rounds,
          f"dp_quant ran {report['rounds']} of {rounds} rounds")
    check(report["loss_all_finite"], "a dp_quant train loss is not finite")
    check(server.quant_thresh is not None, "quantization was not configured")
    return report


# ----------------------------------------------------------------------
# kernels against their references (compiled, never interpreted)
# ----------------------------------------------------------------------
def _moments(z: np.ndarray) -> dict:
    z = z.astype(np.float64)
    c = z - z.mean()
    return {"mean": float(z.mean()), "std": float(z.std()),
            "skew": float((c ** 3).mean() / z.std() ** 3),
            "kurtosis": float((c ** 4).mean() / z.std() ** 4)}


def phase_kernels(n: int = 11227812, attn_shape: tuple = (2, 2048, 4, 64),
                  interpret=False) -> dict:
    """``interpret=False`` is the chip's setting (compiled Mosaic); a CPU
    rehearsal of the control flow passes ``True`` from its scratch script."""
    import jax
    import jax.numpy as jnp

    from msrflute_tpu.ops import pallas_attention as pa
    from msrflute_tpu.ops.pallas_kernels import (fused_gaussian_noise,
                                                 fused_sgd_apply,
                                                 quant_bin_sparsify)
    from msrflute_tpu.ops.quantization import bin_sparsify

    report = {"phase": "kernels", "elements": n, "interpret": bool(interpret)}
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n,), jnp.float32)

    # fused_gaussian_noise: the first four moments of the injected noise
    scale, sigma = 2.0, 0.5
    noisy = jax.jit(lambda v: fused_gaussian_noise(
        v, jnp.float32(scale), jnp.float32(sigma), jnp.int32(42),
        interpret=interpret))(x)
    z = (np.asarray(noisy) - scale * np.asarray(x)) / sigma
    report["noise_moments"] = m = _moments(z)
    checks = []
    if not interpret:  # the interpreter stubs the on-core PRNG to zeros
        checks.append((
            abs(m["mean"]) < 5e-3 and abs(m["std"] - 1) < 5e-3 and
            abs(m["skew"]) < 2e-2 and abs(m["kurtosis"] - 3) < 5e-2,
            f"noise kernel moments off N(0,1): {m}"))

    # quant_bin_sparsify against the jnp elementwise reference
    lo, hi = jnp.min(x), jnp.max(x)
    thresh = jnp.quantile(jnp.abs(x[:1 << 20]), 0.5)
    got = jax.jit(lambda v: quant_bin_sparsify(
        v, lo, hi, thresh, 256, interpret=interpret))(x)
    want = jax.jit(lambda v: bin_sparsify(v, lo, hi, thresh, 256))(x)
    bin_width = float((hi - lo) / 255)
    diff = jnp.abs(got - want)
    report["quant_bin_width"] = bin_width
    report["quant_max_abs_err"] = err = float(jnp.max(diff))
    report["quant_mismatch_fraction"] = frac = float(jnp.mean(diff > 1e-5))
    report["quant_zero_fraction"] = float(jnp.mean(got == 0))
    # the bin index (x-lo)/width reaches 255, where one f32 ulp is 1.5e-5:
    # an element within an ulp of a .5 boundary may round either way
    # under the two compilers' divisions, so up to ~3e-5 of the elements
    # may sit exactly one bin apart; none may differ by more
    checks.append((err <= bin_width * 1.001 and frac < 1e-4,
                   f"quant kernel differs from reference: max {err} "
                   f"(bin {bin_width}), fraction {frac}"))

    # fused_sgd_apply against the optax.sgd(momentum) formula
    g = jax.random.normal(jax.random.fold_in(key, 1), (n,), jnp.float32)
    mom = jax.random.normal(jax.random.fold_in(key, 2), (n,), jnp.float32)
    p_new, m_new = jax.jit(lambda p, gg, mm: fused_sgd_apply(
        p, gg, mm, jnp.float32(0.1), jnp.float32(0.9), jnp.float32(1.0),
        interpret=interpret))(x, g, mom)
    m_ref = g + 0.9 * mom
    p_ref = x - 0.1 * m_ref
    report["sgd_max_abs_err"] = err = float(max(
        jnp.max(jnp.abs(p_new - p_ref)), jnp.max(jnp.abs(m_new - m_ref))))
    checks.append((err < 1e-5, f"sgd kernel differs from reference by {err}"))

    # flash attention forward + backward against the dense reference
    B, L, H, D = attn_shape
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, 10 + i),
                                    attn_shape, jnp.float32)
                  .astype(jnp.bfloat16) for i in range(4))

    def loss(fn):
        return lambda q_, k_, v_: jnp.sum(
            fn(q_, k_, v_).astype(jnp.float32) * w.astype(jnp.float32))

    def flash(q_, k_, v_):
        return pa.flash_attention(q_, k_, v_, causal=True, force_flash=True,
                                  interpret=interpret)

    def dense(q_, k_, v_):
        with jax.default_matmul_precision("highest"):
            return pa._dense_lse(q_.astype(jnp.float32),
                                 k_.astype(jnp.float32),
                                 v_.astype(jnp.float32), 0, 0, True)[0]

    out_f = jax.jit(flash)(q, k, v)
    out_d = jax.jit(dense)(q, k, v)
    grads_f = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    grads_d = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)

    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))

    report["flash_shape"] = list(attn_shape)
    report["flash_fwd_rel_err"] = rel(out_f, out_d)
    report["flash_bwd_rel_err"] = [rel(a, b) for a, b in zip(grads_f, grads_d)]
    # bf16 inputs and outputs (eps 2^-8 = 3.9e-3) against an f32 reference
    # at highest matmul precision: a few eps of the largest magnitude
    checks.append((out_f.shape == tuple(attn_shape) and
                   report["flash_fwd_rel_err"] < 2e-2 and
                   max(report["flash_bwd_rel_err"]) < 4e-2,
                   "flash attention differs from dense: "
                   f"fwd {report['flash_fwd_rel_err']} "
                   f"bwd {report['flash_bwd_rel_err']}"))

    # what the dispatch gate picks at this length, and both estimates;
    # a probe that raises on this backend is said here, not only logged
    pa.reset_attention_plans()
    try:
        costs = pa._probe_costs(
            B, L, L, H, D, jnp.dtype(jnp.bfloat16), True,
            list(pa._BLOCK_CANDIDATES))
        report["plan_probe_error"] = None
    except Exception as exc:  # reported in the phase's own line
        costs = None
        report["plan_probe_error"] = f"{type(exc).__name__}: {exc}"
    if costs is not None:
        plan = pa.plan_attention(B, L, L, H, D, jnp.bfloat16, True,
                                 cost_probe=lambda *a: costs)
        report["plan"] = {k_: plan[k_] for k_ in (
            "impl", "block_q", "block_k", "flash_secs_est",
            "dense_secs_est")}
    emit(report)
    for ok, what in checks:
        check(ok, what)
    return report


# ----------------------------------------------------------------------
# four chips: the clients-axis mesh against one device, same seed
# ----------------------------------------------------------------------
def phase_four_chips(work: str, *, rounds: int = 4, users: int = 50,
                     samples: int = 100, val_users: int = 10,
                     n_devices: int = 4) -> dict:
    """The ResNet config at ``rounds_per_step: 1`` on ``make_mesh()`` over
    ``n_devices`` (K=10 pads to 12, two masked) and on
    ``make_mesh(num_devices=1)``, same seed — twice.

    ``highest`` matmul precision is the comparison that decides.  With
    true f32 products the two meshes differ in reduction order only (psum
    over shards, per-shard batch of 3 instead of 10), and rounds 0 and 1
    are where that is visible: round 0 starts from identical parameters
    (``ROUND0_TOL`` = 1e-5 relative: f32 eps 1.2e-7 over ~1e2 reduction
    terms; 8e-8 seen on the chip and on four virtual CPU devices), and
    round 1's loss is a function of round 0's aggregated update, so
    agreement there covers the psum and the server step (``ROUND1_TOL``
    = 1e-3: a dropped shard or mis-weighted client shows at 1e-2).
    Training itself then amplifies the seed, up to x200 a round at lr
    0.1 on this fast-falling loss — 8e-8, 9e-8, 2e-5, 3e-3 on the chip,
    8e-8, 7e-6, 2e-3, 2e-3 in true f32 on the CPU, final parameters
    1.4e-2 and 2.1e-2 — so later rounds and the final parameters are held
    to ``BAND`` = 1e-1 only.

    At the ``default`` precision users run, the MXU multiplies f32
    operands in bf16 passes and XLA places those roundings differently for
    a per-device batch of 3 and of 10, so round 0 already differs at 2e-3
    (parameters 4.8e-2 after four rounds): that pair is reported and held
    to ``BAND`` throughout.  It shows the as-run program executes, spreads
    and tracks, not bit-agreement."""
    import jax

    from msrflute_tpu.config import FLUTEConfig
    from msrflute_tpu.engine import select_server
    from msrflute_tpu.models import make_task
    from msrflute_tpu.parallel import make_mesh
    from msrflute_tpu.tasks import build_task_datasets

    check(len(jax.devices()) >= n_devices,
          f"need {n_devices} devices, have {len(jax.devices())}")
    data_dir = os.path.join(work, "fedcifar100")
    write_splits(data_dir, (32, 32, 3), 100, users, samples, val_users)
    raw = point_at_splits(load_config(RESNET_CONFIG))
    raw["server_config"].update(
        max_iteration=rounds, rounds_per_step=1, val_freq=rounds,
        rec_freq=rounds, model_backup_freq=rounds, initial_val=False)

    def run(num_devices, precision):
        cfg = FLUTEConfig.from_dict(raw)
        cfg.task = "cv_resnet_fedcifar100"
        cfg.data_path = data_dir
        cfg.validate(data_dir)
        task = make_task(cfg.model_config)
        train, val, test = build_task_datasets(cfg, task)
        mesh = make_mesh(num_devices=num_devices)
        model_dir = os.path.join(
            work, f"mesh{num_devices}_{precision}", "models")
        os.makedirs(model_dir, exist_ok=True)
        clock = ChunkClock()
        t0 = time.time()
        with jax.default_matmul_precision(precision), \
                clock.watching(spy_device_put=True):
            server = select_server("optimization")(
                task, cfg, train, val_dataset=val, test_dataset=test,
                model_dir=model_dir, mesh=mesh, seed=0)
            server.run()
            jax.block_until_ready(server.state.params)
        return {"server": server, "clock": clock, "secs": time.time() - t0,
                "params": jax.device_get(server.state.params)}

    def compare(mesh_run, one_run):
        leaves_m = jax.tree.leaves(mesh_run["params"])
        leaves_1 = jax.tree.leaves(one_run["params"])
        scale = max(float(np.max(np.abs(v))) for v in leaves_1)
        losses_m = np.asarray(mesh_run["clock"].losses)
        losses_1 = np.asarray(one_run["clock"].losses)
        check(len(losses_m) == rounds and len(losses_1) == rounds and
              bool(np.isfinite(losses_m).all()),
              "a mesh did not run every round with a finite loss")
        return {
            "losses_mesh": losses_m.tolist(), "losses_one": losses_1.tolist(),
            "loss_rel_err": (np.abs(losses_m - losses_1) /
                             np.abs(losses_1)).tolist(),
            "param_max_rel_err": max(
                float(np.max(np.abs(a - b)))
                for a, b in zip(leaves_m, leaves_1)) / scale,
            "secs_mesh": mesh_run["secs"], "secs_one": one_run["secs"],
            "chunk_secs_mesh": mesh_run["clock"].chunk_secs(),
            "chunk_secs_one": one_run["clock"].chunk_secs(),
        }

    mesh_run = run(n_devices, "default")
    # every device's peak, read before anything runs on device 0 alone
    peaks = {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:n_devices]}
    as_run = compare(mesh_run, run(1, "default"))
    exact = compare(run(n_devices, "highest"), run(1, "highest"))

    server, clock = mesh_run["server"], mesh_run["clock"]
    spread = [s for s in clock.staged_device_sets
              if any(n == n_devices and not rep for n, rep in s)]
    report = {
        "phase": "four_chips", "devices": n_devices, "rounds": rounds,
        "round0_tol": ROUND0_TOL, "round1_tol": ROUND1_TOL, "band": BAND,
        "highest_precision": exact, "default_precision": as_run,
        "mesh_shape": dict(server.mesh.shape),
        "client_sharding_devices": len(
            server.engine._client_sharding.device_set),
        "params_devices": len(jax.tree.leaves(
            server.state.params)[0].sharding.device_set),
        "staged_puts": len(clock.staged_device_sets),
        "staged_puts_spread_over_all": len(spread),
        "peak_bytes_in_use": peaks,
    }
    emit(report)
    check(exact["loss_rel_err"][0] < ROUND0_TOL and
          exact["loss_rel_err"][1] < ROUND1_TOL,
          "rounds 0 and 1 differ beyond reduction order at highest "
          f"precision: {exact['loss_rel_err']}")
    for name, pair in (("highest", exact), ("default", as_run)):
        check(max(pair["loss_rel_err"]) < BAND and
              pair["param_max_rel_err"] < BAND,
              f"mesh and one device drifted apart at {name} precision: "
              f"loss {pair['loss_rel_err']}, "
              f"parameters {pair['param_max_rel_err']}")
    check(report["client_sharding_devices"] == n_devices and
          report["params_devices"] == n_devices,
          "the mesh's shardings do not span every device")
    check(len(spread) >= rounds,
          "the round's client-axis operands were not spread over the mesh: "
          f"{clock.staged_device_sets}")
    # params alone are 45 MB replicated; an idle device reports ~nothing
    check(all(p is not None and p > 32 * 2 ** 20 for p in peaks.values()),
          f"a device's peak memory is trivial or unreported: {peaks}")
    return report


# ----------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    args = ap.parse_args()

    summary = {"ok": False, "device": None}
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        run_phases(args.chips, work, summary)
        summary["ok"] = True
    except Exception as exc:
        import traceback
        traceback.print_exc()
        summary["error"] = f"{type(exc).__name__}: {exc}"
    finally:
        shutil.rmtree(work, ignore_errors=True)
        # the last line, whatever ended the run (an interrupt or a
        # SystemExit from the trainer propagates after it)
        summary["claim"] = None
        emit(summary)
    return 0 if summary["ok"] else 1


def run_phases(chips: int, work: str, summary: dict) -> None:
    import jax

    from msrflute_tpu.utils.backend import (device_report,
                                            enable_compilation_cache)
    from msrflute_tpu.utils.compat import chip_peak_flops
    summary["device"] = device = device_report()
    if device["platform"] != "tpu":
        raise RuntimeError(
            f"no accelerator: jax platform is {device['platform']!r} — "
            "chip_smoke.py runs on the chip only")
    check(device["count"] == chips,
          f"--chips {chips} but jax sees {device['count']} devices")

    chip_peak_flops()  # a chip the peak table lacks is an error
    compiles = CompileLog()
    compiles.install()
    emit({"phase": "start", "device": device, "jax": jax.__version__,
          "compilation_cache": enable_compilation_cache(), "work": work})
    if chips == 4:
        phase_four_chips(work)
        return
    ctx = phase_resnet(work, compiles)
    phase_dp_quant(work, compiles)
    phase_kernels()
    phase_resume(ctx, compiles)


if __name__ == "__main__":
    sys.exit(main())
