"""The SDAR-MoE configuration (``sdar_30b_a3b_ep8share``) and its cell
(``sdar_bd_k2_t4096``) as the benchmark holds them.

1. The cell's files load and run through ``harness.run_cell`` without an
   edit to the harness (``data/sdar_root``: the same base yaml, task,
   reference, generator and limits' names at CPU-test widths): ONE whole
   run on the CPU, program against ``reference/fedround.py``.
2. Planted faults in the program's model and objective, each held to the
   same comparison (``check.compare`` / ``check.judge``, the tiny root's
   limits): the sound program passes; the noised query that sees its own
   clean block, targets shifted by one, the ``1 / t`` weight left out,
   three faults of the layer and the bfloat16 control come out not
   correct.  The program's party is played by the program's own task
   loss inside ``fedround``'s plain round, so a fault costs one compile,
   not one trainer run.
3. The configuration's file against the catalog row, the shipped yaml
   against the file, ``bd_attn_rooflines.py``'s counts, the four new
   readers on a canned trace, and the reference's operation count.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import bd_attn_rooflines, check, harness  # noqa: E402
from benchmarks.reference import fedround, sdar_moe as ref  # noqa: E402
from msrflute_tpu.models import make_task, token_blocks  # noqa: E402
from msrflute_tpu.ops import moe, pallas_attention as pa  # noqa: E402

ROOT = os.path.join(HERE, "data", "sdar_root")
CELL = "tiny_sdar_cell"
REAL_CELL = "sdar_bd_k2_t4096"
REAL_CONFIG = "sdar_30b_a3b_ep8share"
SEED = 2 ** 31 + 4141
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# ----------------------------------------------------------------------
# 1. the files, through the harness
# ----------------------------------------------------------------------
def test_the_tiny_root_reuses_the_benchmarks_own_files():
    cell = harness.load_cell(ROOT, CELL)
    real = harness.load_cell(harness.BENCH_DIR, REAL_CELL)
    for key in ("base_yaml", "task"):
        assert cell["config_doc"][key] == real["config_doc"][key]
    assert cell["config_doc"]["reference"] == {
        k: v for k, v in real["config_doc"]["reference"].items()
        if k != "leaf_kinds_why"}
    assert set(cell["config_doc"]["check_limits"]) == \
        set(real["config_doc"]["check_limits"])
    assert not os.path.isdir(os.path.join(ROOT, "reference"))
    assert harness.find_module(
        ROOT, "reference", "sdar_moe").__file__.startswith(harness.BENCH_DIR)
    cfg = harness.build_config(real, False, None)
    # exactly the traffic of the cell it was made beside, under a name
    # and a ``what`` of its own
    twin = harness.read_json(os.path.join(harness.BENCH_DIR, "traffic",
                                          "k2_s2_t4096.json"))
    assert real["traffic"] == "k2_s2_t4096_bd"
    assert real["traffic_doc"]["overlay"] == twin["overlay"]
    assert real["traffic_doc"]["period_rounds"] == twin["period_rounds"] == 4
    assert real["traffic_doc"]["what"] != twin["what"]
    assert cfg["server_config"]["clients_per_chunk"] == 1
    assert cfg["server_config"]["num_clients_per_iteration"] == 2
    assert cfg["server_config"]["rounds_per_step"] == 4
    assert cfg["model_config"]["model_type"] == "SDAR_MOE"
    assert cfg["model_config"]["remat"] is True
    assert real["chips"] == 1


@pytest.fixture(scope="module")
def sound_run():
    return harness.run_cell(CELL, SEED, 0.2, False, root=ROOT)


def test_sound_run_is_correct_against_the_plain_round(sound_run):
    assert sound_run["correct"], sound_run["compared"]
    assert sound_run["failed"] == 0 and sound_run["attempted"] >= 4
    got = {v["name"]: v["value"] for v in sound_run["compared"]}
    # float32 on both sides on the CPU: rounding and summation order
    assert got["loss_gap"] < 1e-6 and got["update_diff"] < 1e-4
    assert got["timed_update_projection_gap"] < 1e-5
    assert got["window_compiles"] == 0
    assert got["update_gap_worst_leaf.routed"] < 1e-4
    assert got["update_gap_worst_leaf"] < 1e-4
    assert {"clients_per_s", "setup_s"} <= set(sound_run["metrics"])


# ----------------------------------------------------------------------
# 2. planted faults, through check.py
# ----------------------------------------------------------------------
MC = harness.build_config(harness.load_cell(ROOT, CELL), False,
                          None)["model_config"]
LIMITS = harness.load_cell(ROOT, CELL)["config_doc"]["check_limits"]


def _rounds(seed=11, clients=2, steps=2):
    length, span = MC["seq_len"], MC["block_length"]
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, MC["vocab_size"] - 1,
                       size=(clients, steps, 1, length))
    draws = [[token_blocks.bd_draws(seed, "train", k, s, length, span)
              for s in range(steps)] for k in range(clients)]
    shape = (clients, steps, 1, length)
    return [{"x": ids.astype(np.int32),
             "tok_mask": np.ones(shape, np.float32),
             "bd_mask": np.asarray([[m for m, _ in c] for c in draws]
                                   ).reshape(shape),
             "bd_weight": np.asarray([[w for _, w in c] for c in draws]
                                     ).reshape(shape),
             "sample_mask": np.ones((clients, steps, 1), np.float32),
             "client_mask": np.ones((clients,), np.float32),
             "client_lr": 0.1, "server_lr": 1.0, "quant_quantile": None}]


def _norm(tree):
    return float(np.sqrt(sum(np.sum(np.square(leaf, dtype=np.float64))
                             for leaf in jax.tree.leaves(tree))))


def _plain_round(loss, weights, rounds):
    return fedround.run_rounds(
        forward=ref.forward, loss=loss, sample_count=ref.sample_count,
        model_config=MC, params=weights, rounds=rounds,
        strategy={"name": "fedavg"}, block=1, precision="highest")


@pytest.fixture(scope="module")
def reference_round():
    weights = ref.init(np.random.default_rng(5), MC)
    rounds = _rounds()
    return weights, rounds, _plain_round(ref.loss, weights, rounds)


def _verdicts(reference_round, program_loss):
    """The program's round (its task loss in the plain round's place of
    the model) held to the reference's by ``check.py``."""
    weights, rounds, want = reference_round
    got = _plain_round(program_loss, weights, rounds)
    clients = float(len(got[0]["train_loss"]))
    numbers = check.compare(
        init_params=weights, ref_check=want[0], refs_timed=want,
        rounds=rounds,
        check_stats={"train_loss_sum": float(np.sum(got[0]["train_loss"])),
                     "client_count": clients,
                     "grad_norm": float(np.mean(got[0]["pseudo_norm"])),
                     "agg_grad_norm": _norm(got[0]["aggregate"])},
        check_params=got[0]["new_params"],
        timed_first={"losses": [float(np.mean(r["train_loss"]))
                                for r in got],
                     "client_count": [clients] * len(got),
                     "agg_grad_norm": [_norm(got[0]["aggregate"])]},
        timed_first_params=got[-1]["new_params"], dp=None,
        leaf_kinds={"routed": ["/moe/"]})
    return {v["name"]: v for v in check.judge(numbers, LIMITS)}


def _task_loss(**over):
    task = make_task({**MC, **over})

    def loss(params, batch, model_config):
        return task.loss(params, batch, None, True)[0]

    return loss


# -- the objective's three -------------------------------------------
def _own_clean_block_seen(monkeypatch):
    """The noised query sees its OWN clean block (``blk(k) <= blk(q)`` on
    the clean keys): the answer leaks."""
    def rows(q_rows, k, v, row0, own, span):
        def seen():
            q_blk = (row0 + jnp.arange(q_rows.shape[1]))[:, None] // span
            cols = jnp.arange(k.shape[1])[None, :]
            k_blk = jnp.where(cols < own, row0 + cols, cols - own) // span
            return jnp.where(cols < own, k_blk == q_blk, k_blk <= q_blk)
        return token_blocks._masked_rows(q_rows, k, v, seen)

    monkeypatch.setattr(token_blocks, "_bd_attention_rows", rows)
    # and in the kernels (the chip's path; the tile map runs the noised
    # tile's own clean tile already, masked): ``d >= 0`` where ``d >= 1``
    real_mask = pa._bd_mask

    def mask(shape, q_axis, q_tile, k_tile, **geo):
        strict = real_mask(shape, q_axis, q_tile, k_tile, **geo)
        clean_keys = k_tile >= geo["num_k"]
        same = real_mask(shape, q_axis, q_tile % geo["num_q"],
                         k_tile % geo["num_k"], **geo)
        return jnp.logical_or(strict, jnp.logical_and(
            jnp.logical_and(clean_keys, q_tile < geo["num_q"]), same))

    monkeypatch.setattr(pa, "_bd_mask", mask)


def _targets_shifted_by_one(monkeypatch):
    real = token_blocks.softmax_xent
    monkeypatch.setattr(
        token_blocks, "softmax_xent",
        lambda logits, labels: real(logits, jnp.roll(labels, -1, axis=1)))


def _weight_left_out(monkeypatch):
    real = token_blocks.BlockDiffusionLMTask._scored

    def scored(self, params, batch):
        return real(self, params, {
            **batch, "bd_weight": (batch["bd_weight"] > 0).astype(
                jnp.float32)})

    monkeypatch.setattr(token_blocks.BlockDiffusionLMTask, "_scored", scored)


# -- the layer's three -------------------------------------------------
def _halves_at_positions_of_one_row(monkeypatch):
    """The clean half rotated at positions L..2L-1, as if the doubled
    row were one row."""
    real = token_blocks.rope_half
    monkeypatch.setattr(token_blocks, "rope_half",
                        lambda x, theta, copies=1: real(x, theta, 1))


def _head_norms_dropped(monkeypatch):
    real = token_blocks._RMSNorm
    monkeypatch.setattr(
        token_blocks, "_RMSNorm",
        lambda eps, name=None: (lambda x: x) if name in ("norm_q", "norm_k")
        else real(eps, name=name))


def _sigmoid_gate(monkeypatch):
    def route(z, router_w, select_bias, per_token, scaling=1.0, **_):
        """Top k of the same order, gated by renormalised SIGMOID
        scores."""
        scores = jax.nn.sigmoid(jnp.matmul(
            z, router_w, precision=jax.lax.Precision.HIGHEST))
        picked, chosen = jax.lax.top_k(scores, per_token)
        return chosen, picked / jnp.sum(picked, -1, keepdims=True)

    monkeypatch.setattr(moe, "route_tokens", route)


FAULTS = {"own_clean_block_seen": _own_clean_block_seen,
          "targets_shifted_by_one": _targets_shifted_by_one,
          "weight_left_out": _weight_left_out,
          "halves_at_positions_of_one_row": _halves_at_positions_of_one_row,
          "head_norms_dropped": _head_norms_dropped,
          "sigmoid_gate": _sigmoid_gate}


def test_sound_program_passes_every_limit(reference_round):
    got = _verdicts(reference_round, _task_loss())
    assert all(v["ok"] for v in got.values()), got


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(reference_round, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    got = _verdicts(reference_round, _task_loss())
    assert not got["loss_gap"]["ok"] or not got["update_diff"]["ok"], got
    if fault == "own_clean_block_seen":
        # the answer leaks: after two steps the loss is already lower
        assert not got["loss_gap"]["ok"], got


def test_the_bfloat16_control_is_not_correct(reference_round):
    control = harness.read_json(os.path.join(
        harness.BENCH_DIR, "controls", "bf16.json"))["overlay"]
    got = _verdicts(reference_round, _task_loss(**control["model_config"]))
    assert not got["loss_gap"]["ok"] or not got["update_diff"]["ok"], got


# ----------------------------------------------------------------------
# 3. the configuration's file, the counts and the readers
# ----------------------------------------------------------------------
def _config_doc():
    return harness.read_json(os.path.join(
        harness.BENCH_DIR, "configs", f"{REAL_CONFIG}.json"))


def test_configuration_holds_the_catalog_row_but_for_what_it_lists():
    doc = _config_doc()
    if os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert doc["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items()
                   if k not in doc or doc[k] != v}
        assert differs == {"num_hidden_layers", "vocab_size"}, differs
        assert differs <= set(doc["reduced"])
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 32,
            "num_key_value_heads": 4, "head_dim": 128,
            "moe_intermediate_size": 768, "num_experts": 128,
            "num_experts_per_tok": 8, "norm_topk_prob": True,
            "rope_theta": 1000000, "rms_norm_eps": 1e-6,
            "decoder_sparse_step": 1, "mlp_only_layers": [],
            "use_sliding_window": False,
            "tie_word_embeddings": False}.items():
        assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "experts_held",
                              "vocab_size", "data", "noise", "max_iteration"]
    assert set(doc["reduced"]) == set(doc["reduced_why"])
    assert {"block_length", "noise_schedule", "no_shift", "mask_token_id",
            "attention", "router_scores", "init", "protocol"} <= \
        set(doc["assumed"])
    assert doc["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    assert doc["vocab_size"] * 8 == 151936
    assert doc["layers_kept"] == [0, 1, 2, 3]
    assert "8 chips" in doc["stands_for"]
    assert doc["experts_held"] * 8 == doc["num_experts"]
    listed = harness.read_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = next(c for c in listed["configs"] if c["name"] == REAL_CONFIG)
    assert entry["reduced"] == doc["reduced"]
    assert entry["source"] == doc["source"]
    assert listed["configs"][-1] == entry
    assert listed["workloads"][-1] == {
        "name": REAL_CELL, "config": REAL_CONFIG,
        "traffic": "k2_s2_t4096_bd", "chips": 1,
        "why": listed["workloads"][-1]["why"]}
    assert len(listed["workloads"][-1]["why"]) <= 200
    for limit in doc["check_limits"].values():
        assert "limit" in limit and "why" in limit


def test_what_runs_is_what_the_configuration_states():
    doc = _config_doc()
    cfg = harness.build_config(
        harness.load_cell(harness.BENCH_DIR, REAL_CELL), False, None)
    mc = cfg["model_config"]
    for key in ("hidden_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "rms_norm_eps", "rope_theta", "rope_scaling",
                "decoder_sparse_step", "num_experts", "num_experts_per_tok",
                "norm_topk_prob", "use_sliding_window",
                "tie_word_embeddings", "experts_held", "vocab_size",
                "num_hidden_layers", "block_length"):
        assert mc[key] == doc[key], key
    # the slice's last id is the mask's alone: the generator stops short
    assert doc["data"]["vocab"] == mc["vocab_size"] - 1
    assert doc["data"]["len_max"] == mc["seq_len"] == 4096
    assert (doc["data"]["train_users"], doc["data"]["val_users"],
            doc["data"]["test_users"]) == (32, 4, 4)
    # a model_config that the plain round can hash (fedround caches its
    # program by the items)
    hash(tuple(sorted(mc.items())))
    # the roofline readers take these from model_config
    assert {"hidden_size", "moe_intermediate_size", "experts_held",
            "seq_len", "head_dim", "block_length"} <= set(mc)


GEO = {"L": 4096, "B": 4, "H": 32, "KV": 4, "D": 128}


def test_the_kernels_counts_are_the_seen_pairs_at_published_widths():
    mc = harness.build_config(
        harness.load_cell(harness.BENCH_DIR, REAL_CELL), False,
        None)["model_config"]
    assert bd_attn_rooflines.geometry(mc) == GEO
    assert bd_attn_rooflines.KERNELS == (pa.BD_FWD_NAME, pa.BD_DQ_NAME,
                                         pa.BD_DKV_NAME)
    seen = 4096 * 4100
    assert bd_attn_rooflines.pairs_seen(GEO) == seen == \
        pa.bd_tile_map(4096, 4, 512, 512)["pairs_seen"]
    small = {"L": 16, "B": 4, "H": 1, "KV": 1, "D": 8}
    assert bd_attn_rooflines.pairs_seen(small) == pa.bd_seen(16, 4).sum()
    moved = 4 * 8192 * 256 * 36
    for kernel, widths in (("attn_bd_fwd", 256), ("attn_bd_dq", 384),
                           ("attn_bd_dkv", 512)):
        assert bd_attn_rooflines.call_cost(kernel, GEO) == (
            2.0 * 32 * seen * widths, float(moved))
    # a layer's forward core: 275 GFLOP (ISSUE.md's reckoning)
    assert bd_attn_rooflines.call_cost("attn_bd_fwd", GEO)[0] == \
        pytest.approx(275e9, rel=0.01)
    # the share cannot pass 100: the kernels compute at least every tile
    # that holds a seen pair, whole, at every tile shape the rule gives
    for length in (128, 1024, 4096):
        for tile in ((512, 512), (128, 128), (256, 512)):
            tile = tuple(min(t, length) for t in tile)
            tiles = pa.bd_tile_map(length, 4, *tile)
            assert tiles["tiles_run"] * tile[0] * tile[1] >= \
                tiles["pairs_seen"]


def _trace(seconds):
    names = {"attn_bd_fwd": "attn_bd_fwd.3", "attn_bd_dq": "attn_bd_dq.1",
             "attn_bd_dkv": "attn_bd_dkv.1"}
    return {"chips": 1, "window_s": 8.0,
            "op_seconds": {**{names[k]: s for k, (s, _) in seconds.items()},
                           "attn_flash_fwd.2": 9.0, "fusion.7": 1.0},
            "op_counts": {**{names[k]: c for k, (_, c) in seconds.items()},
                          "attn_flash_fwd.2": 5, "fusion.7": 100},
            "module_seconds": {"jit_staged(123)": 6.0, "jit_eval(5)": 1.0},
            "module_counts": {"jit_staged(123)": 1, "jit_eval(5)": 2}}


def _ctx(tmp_path, trace, spans=(), events=()):
    telemetry = tmp_path / "out" / "models" / "telemetry"
    (telemetry / "programs").mkdir(parents=True, exist_ok=True)
    with open(telemetry / "events.jsonl", "w") as fh:
        for record in events:
            fh.write(json.dumps(record) + "\n")
    mc = harness.build_config(
        harness.load_cell(harness.BENCH_DIR, REAL_CELL), False, None)
    return {"trace": trace, "config": mc,
            "window": {"t_open": 10.0, "t_close": 20.0},
            "device": {"kind": "TPU v5 lite"},
            "peaks": harness.read_json(os.path.join(harness.BENCH_DIR,
                                                    "peaks.json")),
            "spans": [*spans, {
                "name": "program_scopes", "ts": 1.0, "dur_s": 0.1,
                "file": str(telemetry / "programs" / "jit_staged-1.json")}]}


def test_the_four_readers_on_a_canned_trace(tmp_path):
    readers = harness.load_layer_metrics(harness.BENCH_DIR)
    seconds = {"attn_bd_fwd": (0.6, 40), "attn_bd_dq": (0.9, 32),
               "attn_bd_dkv": (1.5, 32)}
    tail = {"name": "host_tail", "ts": 12.0, "dur_s": 0.1, "rounds": 4,
            "bd_positions_masked": 34000.0, "bd_positions_real": 65536.0}
    event = {"kind": "event", "name": "attn_tiles", "ts": 2.0,
             **{k: v for k, v in pa.record_attention_tiles(
                 4096, 4, 512, 512).items() if k != "kind"}}
    pa.drain_attention_events()
    other = {**event, "L": 64, "tiles_run": 3}
    ctx = _ctx(tmp_path, _trace(seconds), [tail, dict(tail, ts=25.0)],
               [{"kind": "span", "name": "pack"}, other, event])
    # four rounds a dispatch, one dispatch in the trace
    assert readers["bd_attn_kernel_ms"].read(ctx) == pytest.approx(
        1e3 * 3.0 / 4)
    least = sum(calls * bd_attn_rooflines.call_cost(k, GEO)[0] / 197e12
                for k, (_, calls) in seconds.items())
    assert readers["bd_attn_kernel_roofline"].read(ctx) == pytest.approx(
        100.0 * least / 3.0)
    assert 0 < readers["bd_attn_kernel_roofline"].read(ctx) < 100
    assert readers["bd_attn_tile_fill"].read(ctx) == pytest.approx(
        100.0 * 4096 * 4100 / (80 * 512 * 512))
    assert readers["bd_masked_share"].read(ctx) == pytest.approx(
        100.0 * 34000 / 65536)
    assert {readers[name].UNIT for name in (
        "bd_attn_kernel_roofline", "bd_attn_tile_fill",
        "bd_masked_share")} == {"%"}
    assert readers["bd_attn_kernel_ms"].UNIT == "ms/round"
    # the causal kernels' readers do not read these kernels, nor these
    # readers the causal kernels
    causal = _trace({})
    for name in ("bd_attn_kernel_ms", "bd_attn_kernel_roofline"):
        assert readers[name].read(_ctx(tmp_path, causal)) is None


def test_the_readers_fall_silent_on_a_program_without_what_they_read(
        tmp_path):
    """The parent's program: no such kernel, counter or event.  Nothing
    is returned and nothing raised (the driver runs these readers over
    the parent's checkout too)."""
    readers = harness.load_layer_metrics(harness.BENCH_DIR)
    bare = {"name": "host_tail", "ts": 12.0, "dur_s": 0.1, "rounds": 4,
            "moe_pairs_held": 10.0}
    ctx = _ctx(tmp_path, _trace({}), [bare], [])
    for name in ("bd_attn_kernel_ms", "bd_attn_kernel_roofline",
                 "bd_attn_tile_fill", "bd_masked_share"):
        assert readers[name].read(ctx) is None, name
    # no program_scopes span at all (no telemetry directory to find)
    ctx["spans"] = [bare]
    assert readers["bd_attn_tile_fill"].read(ctx) is None
    # a configuration of another model (no block_length, no head_dim)
    # with none of the kernels in its trace
    other = harness.build_config(harness.load_cell(
        harness.BENCH_DIR, "kanana2_mla_k2_t4096"), False, None)
    assert readers["bd_attn_kernel_roofline"].read(
        {**ctx, "config": other}) is None


def test_the_new_entries_and_the_lists_the_cell_joined():
    listed = harness.read_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in listed["per_layer"]}
    new = ["bd_attn_kernel_ms", "bd_attn_kernel_roofline",
           "bd_attn_tile_fill", "bd_masked_share"]
    assert [m["name"] for m in listed["per_layer"][-4:]] == new
    for name in new:
        assert by_name[name]["workloads"] == [REAL_CELL]
        assert by_name[name]["moves"] == "clients_per_s"
    # not ``expert_tile_fill``: an accepted test holds its list to the
    # two causal token cells, letter for letter (PERF.md section 7)
    joined = {"expert_load_max_over_mean", "expert_gmm_fwd_roofline", "expert_gmm_dx_roofline",
              "expert_gmm_dw_roofline", "ckpt_write_ms", "ckpt_wait_ms",
              "stage_reuse", "client_steps_ms", "aggregate_ms",
              "moe_layer_ms", "attn_core_ms", "attn_proj_ms",
              "head_loss_ms", "scope_unattributed"}
    assert {name for name, m in by_name.items()
            if REAL_CELL in m.get("workloads", [])} == joined | set(new)
    for name in joined:
        assert by_name[name]["workloads"][-1] == REAL_CELL
    # the causal kernels' metrics count the causal half of an L square
    for name in ("attn_kernel_ms", "attn_kernel_roofline"):
        assert REAL_CELL not in by_name[name]["workloads"]


def test_required_flops_counts_what_the_algorithm_needs():
    weights = ref.init(np.random.default_rng(5), MC)
    first = _rounds()[0]
    batch = {k: jnp.asarray(v[0, 0]) for k, v in first.items()
             if getattr(v, "ndim", 0) >= 3}
    held = ref.required_flops(weights, batch, MC)
    everywhere = {**MC, "experts_held": MC["num_experts"]}
    all_held = ref.required_flops(
        ref.init(np.random.default_rng(5), everywhere), batch, everywhere)
    length, layers = MC["seq_len"], MC["num_hidden_layers"]
    per_pair = 6.0 * 3 * MC["hidden_size"] * MC["moe_intermediate_size"]
    assert all_held > held > 0
    # with every expert held, every chosen pair of BOTH halves is counted
    rest = all_held - per_pair * 2 * length * MC["num_experts_per_tok"] * \
        layers
    assert rest > 0 and (held - rest) % per_pair == 0
    # the rest: projections and router on 2 L positions, the core on the
    # seen pairs, the head on L
    heads, dim, hidden = (MC["num_attention_heads"], MC["head_dim"],
                          MC["hidden_size"])
    want = 6.0 * (layers * (
        2 * length * (2 * hidden * heads * dim +
                      2 * hidden * MC["num_key_value_heads"] * dim +
                      hidden * MC["num_experts"]) +
        heads * 2 * dim * length * (length + MC["block_length"])) +
        length * hidden * MC["vocab_size"])
    assert rest == pytest.approx(want, rel=1e-12)
    # a short row counts its own seen pairs
    short = {**batch, "tok_mask": batch["tok_mask"].at[:, 42:].set(0.0)}
    seen = ref.seen(length, MC["block_length"])
    real = np.concatenate([np.arange(length) < 42] * 2)
    pairs = seen[real][:, real].sum()
    less = ref.required_flops(weights, short, MC)
    assert less < held
    core = 6.0 * layers * heads * 2 * dim
    rest_short = 6.0 * (layers * 2 * 42 * (
        2 * hidden * heads * dim + 2 * hidden * MC["num_key_value_heads"] *
        dim + hidden * MC["num_experts"]) + 42 * hidden * MC["vocab_size"])
    assert (less - rest_short - core * pairs) % per_pair == \
        pytest.approx(0, abs=1e-3)
