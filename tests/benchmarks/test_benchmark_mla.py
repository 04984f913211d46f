"""The MLA-MoE configuration (``kanana2_30b_a3b_ep16share``) as the
benchmark holds it.

1. The cell's files load and run through ``harness.run_cell`` without an
   edit to the harness (``data/mla_root``: the same base yaml, task,
   reference, generator and limits' names at CPU-test widths): ONE whole
   run on the CPU, program against ``reference/fedround.py``.
2. Planted faults in the program's model, each held to the same
   comparison (``check.compare`` / ``check.judge``, the tiny root's
   limits): the sound program passes, every fault and the bfloat16
   control come out not correct.  The program's party is played by the
   program's own task loss inside ``fedround``'s plain round, so a fault
   costs one compile, not one trainer run.
3. The configuration's file against the catalog row, the shipped yaml
   against the file, the new layer-metric reader on spans made by hand,
   and the reference's operation count.
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

from benchmarks import check, flops, harness  # noqa: E402
from benchmarks.reference import fedround, mla_moe as ref  # noqa: E402
from msrflute_tpu.models import make_task, mla_moe  # noqa: E402
from msrflute_tpu.ops import moe  # noqa: E402

ROOT = os.path.join(HERE, "data", "mla_root")
CELL = "tiny_mla_cell"
REAL_CELL = "kanana2_mla_k2_t4096"
SEED = 2 ** 31 + 3636
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
        ROOT, "reference", "mla_moe").__file__.startswith(harness.BENCH_DIR)
    cfg = harness.build_config(real, False, None)
    assert cfg["server_config"]["clients_per_chunk"] == 1
    assert cfg["server_config"]["num_clients_per_iteration"] == 2
    assert cfg["server_config"]["rounds_per_step"] == 4
    assert real["traffic_doc"]["period_rounds"] == 4
    assert cfg["model_config"]["model_type"] == "MLA_MOE"
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
    # the routed experts' leaves and the rest, each a number of its own
    assert got["update_gap_worst_leaf.routed"] < 1e-4
    assert got["update_gap_worst_leaf"] < 1e-4
    assert {"clients_per_s", "setup_s"} <= set(sound_run["metrics"])


# ----------------------------------------------------------------------
# 2. planted faults, through check.py
# ----------------------------------------------------------------------
MC = harness.build_config(harness.load_cell(ROOT, CELL), False,
                          None)["model_config"]
LIMITS = harness.load_cell(ROOT, CELL)["config_doc"]["check_limits"]


def _rounds(seed=11, clients=2, steps=2, length=17):
    ids = np.random.default_rng(seed).integers(
        1, MC["vocab_size"], size=(clients, steps, 1, length))
    return [{"x": ids.astype(np.int32),
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


def _on_every(params, kind, change):
    """``params`` with ``change`` applied to every layer's ``kind``."""
    return {name: ({**layer, kind: change(layer[kind])}
                   if isinstance(layer, dict) and kind in layer else layer)
            for name, layer in params.items()}


def _shared_expert_left_out(params):
    return _on_every(params, "shared", lambda s: {
        **s, "w2": jnp.zeros_like(s["w2"])})


def _scale_of_the_nope_width(params):
    # scores over sqrt(128), not sqrt(128 + 64): the same as queries
    # longer by sqrt(192 / 128)
    wide = (MC["qk_nope_head_dim"] + MC["qk_rope_head_dim"]) / \
        MC["qk_nope_head_dim"]
    return _on_every(params, "attn", lambda a: {
        **a, "wq": a["wq"] * np.float32(wide ** 0.5)})


def _head_tied_to_the_embedding(params):
    return {**params, "head": params["embedding"]}


PARAM_FAULTS = {"shared_expert_left_out": _shared_expert_left_out,
                "scale_of_the_nope_width": _scale_of_the_nope_width,
                "head_tied_to_the_embedding": _head_tied_to_the_embedding}


def _shared_key_not_rotated(monkeypatch):
    real = mla_moe.rope_interleaved
    monkeypatch.setattr(
        mla_moe, "rope_interleaved",
        lambda x, theta: x if x.shape[-2] == 1 else real(x, theta))


def _latent_norm_dropped(monkeypatch):
    real = mla_moe._RMSNorm
    # the latent goes on as it came from W_kv_a (the leaf stays in the
    # tree, unused)
    monkeypatch.setattr(
        mla_moe, "_RMSNorm",
        lambda eps, name: (lambda x: x) if name == "norm_kv"
        else real(eps, name=name))


def _gate_over_held_only(monkeypatch):
    def route(z, router_w, select_bias, per_token, scaling=1.0, eps=1e-20):
        """The gate renormalised over the chosen experts that are held
        here, not over all chosen."""
        scores = jax.nn.sigmoid(jnp.matmul(
            z, router_w, precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(scores + select_bias, per_token)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        here = picked * (chosen < MC["experts_held"])
        return chosen, scaling * picked / (
            jnp.sum(here, -1, keepdims=True) + 1e-6)

    monkeypatch.setattr(moe, "route_tokens", route)


CODE_FAULTS = {"shared_key_not_rotated": _shared_key_not_rotated,
               "latent_norm_dropped": _latent_norm_dropped,
               "gate_normalised_over_held_only": _gate_over_held_only}


def test_sound_program_passes_every_limit(reference_round):
    got = _verdicts(reference_round, _task_loss())
    assert all(v["ok"] for v in got.values()), got


@pytest.mark.parametrize("fault", sorted(PARAM_FAULTS))
def test_a_fault_in_the_models_weights_path_is_not_correct(reference_round,
                                                           fault):
    sound = _task_loss()
    got = _verdicts(reference_round, lambda params, batch, mc: sound(
        PARAM_FAULTS[fault](params), batch, mc))
    assert not got["update_diff"]["ok"], got
    assert not all(v["ok"] for v in got.values())


@pytest.mark.parametrize("fault", sorted(CODE_FAULTS))
def test_a_fault_in_the_models_code_is_not_correct(reference_round, fault,
                                                   monkeypatch):
    CODE_FAULTS[fault](monkeypatch)
    got = _verdicts(reference_round, _task_loss())
    assert not got["loss_gap"]["ok"] or not got["update_diff"]["ok"], got


def test_the_scaling_factor_dropped_is_not_correct(reference_round):
    got = _verdicts(reference_round, _task_loss(routed_scaling_factor=1.0))
    assert not got["update_diff"]["ok"], got


def test_the_bfloat16_control_is_not_correct(reference_round):
    control = harness.read_json(os.path.join(
        harness.BENCH_DIR, "controls", "bf16.json"))["overlay"]
    got = _verdicts(reference_round, _task_loss(**control["model_config"]))
    assert not got["loss_gap"]["ok"] or not got["update_diff"]["ok"], got


# ----------------------------------------------------------------------
# 3. the configuration's file and the readers
# ----------------------------------------------------------------------
def _config_doc():
    return harness.read_json(os.path.join(
        harness.BENCH_DIR, "configs", "kanana2_30b_a3b_ep16share.json"))


def test_configuration_holds_the_catalog_row_but_for_what_it_lists():
    doc = _config_doc()
    if os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "kanana-2-30b-a3b-instruct-2601")
        assert doc["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items()
                   if k not in doc or doc[k] != v}
        assert differs == {"num_hidden_layers", "vocab_size"}, differs
        assert differs <= set(doc["reduced"])
    # every published width, unchanged
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 32,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "qk_head_dim": 192, "v_head_dim": 128, "kv_lora_rank": 512,
            "q_lora_rank": None, "intermediate_size": 6144,
            "moe_intermediate_size": 768, "n_shared_experts": 2,
            "n_routed_experts": 128, "num_experts_per_tok": 6,
            "routed_scaling_factor": 2.448, "rope_theta": 1000000,
            "rope_interleave": True, "rms_norm_eps": 1e-6,
            "tie_word_embeddings": False}.items():
        assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "experts_held",
                              "vocab_size", "data", "max_iteration"]
    assert set(doc["reduced"]) == set(doc["reduced_why"])
    assert {"init", "select_bias", "server_optimizer"} <= set(doc["assumed"])
    assert doc["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128,
                                "vocab_size": 128256}
    assert doc["experts_held"] == 8 and doc["vocab_size"] == 16032
    assert doc["layers_kept"] == [0, 1, 2, 3, 4]
    assert "16 chips" in doc["stands_for"] and "8 of them" in \
        doc["stands_for"]
    listed = harness.read_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = next(c for c in listed["configs"]
                 if c["name"] == "kanana2_30b_a3b_ep16share")
    assert entry["reduced"] == doc["reduced"]
    assert entry["source"] == doc["source"]
    for limit in doc["check_limits"].values():
        assert "limit" in limit and "why" in limit


def test_what_runs_is_what_the_configuration_states():
    """The shipped yaml (the configuration's ``base_yaml``) carries the
    file's widths, letter for letter, and the cut it states."""
    doc = _config_doc()
    cfg = harness.build_config(
        harness.load_cell(harness.BENCH_DIR, REAL_CELL), False, None)
    mc = cfg["model_config"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
                "q_lora_rank", "rms_norm_eps", "rope_theta",
                "rope_interleave", "first_k_dense_replace",
                "n_routed_experts", "n_shared_experts",
                "num_experts_per_tok", "routed_scaling_factor", "n_group",
                "topk_group", "tie_word_embeddings", "experts_held",
                "vocab_size", "num_hidden_layers"):
        assert mc[key] == doc[key], key
    assert mc["attention_block"] == 2048
    assert doc["data"]["vocab"] == mc["vocab_size"]
    assert doc["data"]["len_max"] == mc["seq_len"] == 4096
    assert (doc["data"]["train_users"], doc["data"]["val_users"],
            doc["data"]["test_users"]) == (32, 4, 4)
    # the roofline readers take these three from model_config
    assert {"hidden_size", "moe_intermediate_size", "experts_held"} <= \
        set(mc)


def _ctx(spans):
    return {"spans": spans, "window": {"t_open": 10.0, "t_close": 20.0},
            "config": {"model_config": {"experts_held": 8}}}


def test_expert_tile_fill_reads_the_counters_and_falls_silent_without():
    reader = harness.load_layer_metrics(harness.BENCH_DIR)[
        "expert_tile_fill"]
    assert reader.TILE_ROWS == moe.TILE_ROWS and reader.UNIT == "%"
    tail = {"name": "host_tail", "ts": 12.0, "dur_s": 0.1, "rounds": 4,
            "moe_pairs_held": 24576.0, "moe_max_load": 6000.0,
            "moe_layer_steps": 16.0, "moe_pairs_dropped": 0.0,
            "moe_tiles_active": 256.0}
    outside = dict(tail, ts=25.0, moe_tiles_active=1e9)
    # 24,576 pairs in 256 tiles of 128 rows: three quarters are real
    assert reader.read(_ctx([tail, outside, dict(tail, ts=15.0)])) == \
        pytest.approx(75.0)
    # the parent's program has no such counter: nothing to read
    bare = {k: v for k, v in tail.items() if k != "moe_tiles_active"}
    assert reader.read(_ctx([bare])) is None
    assert reader.read(_ctx([])) is None
    listed = harness.read_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = next(m for m in listed["per_layer"]
                 if m["name"] == "expert_tile_fill")
    assert entry["workloads"] == ["lfm2_moe_k4_t4096", REAL_CELL]
    assert entry["layer"] == "expert layer"
    assert entry["moves"] == "clients_per_s"


def test_required_flops_counts_what_the_algorithm_needs():
    weights = ref.init(np.random.default_rng(5), MC)
    batch = {"x": jnp.asarray(_rounds()[0]["x"][0, 0]),
             "sample_mask": jnp.ones((1,), jnp.float32)}
    held = ref.required_flops(weights, batch, MC)
    everywhere = {**MC, "experts_held": MC["n_routed_experts"]}
    all_held = ref.required_flops(
        ref.init(np.random.default_rng(5), everywhere), batch, everywhere)
    tokens = batch["x"].shape[1] - 1
    routed_layers = ref.layer_kinds(MC).count("moe")
    per_pair = 6.0 * 3 * MC["hidden_size"] * MC["moe_intermediate_size"]
    assert all_held > held > 0
    # with every expert held, every chosen pair is counted
    rest = all_held - per_pair * tokens * MC["num_experts_per_tok"] * \
        routed_layers
    assert rest > 0 and (held - rest) % per_pair == 0
    # the shared expert is counted on every token of every routed layer,
    # attention's core at half the square in all five
    shared = 6.0 * tokens * 3 * MC["hidden_size"] * \
        MC["n_shared_experts"] * MC["moe_intermediate_size"] * routed_layers
    core = 6.0 * MC["num_attention_heads"] * (
        MC["qk_nope_head_dim"] + MC["qk_rope_head_dim"] +
        MC["v_head_dim"]) * tokens * (tokens + 1) / 2 * \
        MC["num_hidden_layers"]
    assert rest > shared + core
    # far fewer than the dense products the plain form computes
    dense = fedround.flops_per_step(
        ref.forward, MC, weights, batch,
        flops.matmul_flops, ref.loss)
    assert held < dense
