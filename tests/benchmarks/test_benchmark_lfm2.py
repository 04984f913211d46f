"""The LFM2-MoE configuration as the benchmark holds it.

1. The cell's files load and run through ``harness.run_cell`` without an
   edit to the harness (``data/lfm2_root``: the same base yaml, task,
   reference, generator and limits' names at CPU-test widths): one whole
   run on the CPU, program against ``reference/fedround.py``.
2. Planted faults in the program's model, each held to the same
   comparison (``check.compare`` / ``check.judge``, the tiny root's
   limits): the sound program passes, every fault and the bfloat16
   control come out not correct.  The program's party is played by the
   program's own task loss inside ``fedround``'s plain round, so a fault
   costs one compile, not one trainer run.
3. The configuration's file against the catalog row, and the new
   layer-metric readers on spans and a trace made by hand.
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

from benchmarks import check, harness  # noqa: E402
from benchmarks.reference import fedround, lfm2_moe as ref  # noqa: E402
from msrflute_tpu.models import make_task  # noqa: E402
from msrflute_tpu.ops import moe  # noqa: E402

ROOT = os.path.join(HERE, "data", "lfm2_root")
CELL = "tiny_lfm2_cell"
SEED = 2 ** 31 + 2828
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# ----------------------------------------------------------------------
# 1. the files, through the harness
# ----------------------------------------------------------------------
def test_the_tiny_root_reuses_the_benchmarks_own_files():
    cell = harness.load_cell(ROOT, CELL)
    real = harness.load_cell(harness.BENCH_DIR, "lfm2_moe_k4_t4096")
    for key in ("base_yaml", "task"):
        assert cell["config_doc"][key] == real["config_doc"][key]
    assert cell["config_doc"]["reference"]["model"] == \
        real["config_doc"]["reference"]["model"]
    assert set(cell["config_doc"]["check_limits"]) == \
        set(real["config_doc"]["check_limits"])
    # the reference and the generator are the benchmark's: the root adds
    # a configuration, a traffic mix and a cell, nothing else
    assert not os.path.isdir(os.path.join(ROOT, "reference"))
    assert harness.find_module(
        ROOT, "reference", "lfm2_moe").__file__.startswith(harness.BENCH_DIR)
    cfg = harness.build_config(real, False, None)
    assert cfg["server_config"]["clients_per_chunk"] == 1
    assert cfg["model_config"]["model_type"] == "LFM2_MOE"


@pytest.fixture(scope="module")
def sound_run():
    return harness.run_cell(CELL, SEED, 0.2, False, root=ROOT)


def test_sound_run_is_correct_against_the_plain_round(sound_run):
    assert sound_run["correct"], sound_run["compared"]
    assert sound_run["failed"] == 0 and sound_run["attempted"] >= 8
    got = {v["name"]: v["value"] for v in sound_run["compared"]}
    # float32 on both sides on the CPU: rounding and summation order
    assert got["loss_gap"] < 1e-6 and got["update_diff"] < 1e-4
    assert got["timed_update_projection_gap"] < 1e-5
    assert got["window_compiles"] == 0
    # the expert layers' leaves and the rest, each a number of its own
    assert got["update_gap_worst_leaf.routed"] < 1e-4
    assert got["update_gap_worst_leaf"] < 1e-4
    assert {"clients_per_s", "setup_s"} <= set(sound_run["metrics"])


# ----------------------------------------------------------------------
# 2. planted faults, through check.py
# ----------------------------------------------------------------------
MC = harness.build_config(harness.load_cell(ROOT, CELL), False,
                          None)["model_config"]
LIMITS = harness.load_cell(ROOT, CELL)["config_doc"]["check_limits"]


def _rounds(seed=11, clients=4, steps=2, length=17):
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
        timed_first_params=got[-1]["new_params"], dp=None)
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


def _held_expert_left_out(params):
    return _on_every(params, "moe", lambda m: {
        **m, "w2": m["w2"].at[-1].set(0.0)})


def _conv_tap_dropped(params):
    return _on_every(params, "conv", lambda c: {
        **c, "w_conv": c["w_conv"].at[:, 2].set(0.0)})


def _gqa_groups_mismapped(params):
    # query head h on key-value head h % kv instead of h // group: the
    # same as the sound mapping on heads taken in the order 0, 2, 1, 3
    heads, dim = MC["num_attention_heads"], MC["head_dim"]
    kv = MC["num_key_value_heads"]
    order = np.arange(heads).reshape(heads // kv, kv).T.reshape(-1)
    cols = (order[:, None] * dim + np.arange(dim)[None]).reshape(-1)
    return _on_every(params, "attn", lambda a: {
        **a, "wq": a["wq"][:, cols], "wo": a["wo"][cols, :]})


PARAM_FAULTS = {"held_expert_left_out": _held_expert_left_out,
                "conv_tap_dropped": _conv_tap_dropped,
                "gqa_groups_mismapped": _gqa_groups_mismapped}


def _biased_gate(z, router_w, select_bias, per_token, scaling=1.0):
    """The selection bias let into the gate (and so given a gradient)."""
    scores = jax.nn.sigmoid(jnp.matmul(
        z, router_w, precision=jax.lax.Precision.HIGHEST)) + select_bias
    _, chosen = jax.lax.top_k(scores, per_token)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6)


def _gate_over_held_only(z, router_w, select_bias, per_token, scaling=1.0):
    """The gate renormalised over the chosen experts that are held here,
    not over all chosen."""
    scores = jax.nn.sigmoid(jnp.matmul(
        z, router_w, precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + select_bias, per_token)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    here = picked * (chosen < MC["experts_held"])
    return chosen, picked / (jnp.sum(here, -1, keepdims=True) + 1e-6)


ROUTING_FAULTS = {"bias_given_a_gradient": _biased_gate,
                  "gate_normalised_over_held_only": _gate_over_held_only}


def test_sound_program_passes_every_limit(reference_round):
    got = _verdicts(reference_round, _task_loss())
    assert all(v["ok"] for v in got.values()), got


@pytest.mark.parametrize("fault", sorted(PARAM_FAULTS))
def test_a_fault_in_the_models_layers_is_not_correct(reference_round, fault):
    sound = _task_loss()
    got = _verdicts(reference_round, lambda params, batch, mc: sound(
        PARAM_FAULTS[fault](params), batch, mc))
    assert not got["update_diff"]["ok"], got
    assert not all(v["ok"] for v in got.values())


@pytest.mark.parametrize("fault", sorted(ROUTING_FAULTS))
def test_a_fault_in_the_routing_is_not_correct(reference_round, fault,
                                               monkeypatch):
    monkeypatch.setattr(moe, "route_tokens", ROUTING_FAULTS[fault])
    got = _verdicts(reference_round, _task_loss())
    assert not got["loss_gap"]["ok"] or not got["update_diff"]["ok"], got


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
        harness.BENCH_DIR, "configs", "lfm2_24b_a2b_ep8share.json"))


def test_configuration_holds_the_catalog_row_but_for_what_it_lists():
    doc = _config_doc()
    if os.path.exists(CATALOG):
        with open(CATALOG) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "LFM2-24B-A2B")
        assert doc["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if doc.get(k) != v}
        assert differs <= set(doc["reduced"]), differs
        # no width among them (the vocabulary is sliced, not a width)
        assert not any(k.endswith(("_size", "_dim", "_rank")) or
                       k in ("num_experts_per_tok", "num_attention_heads",
                             "num_key_value_heads", "conv_L_cache")
                       for k in differs - {"vocab_size"})
    assert set(doc["reduced"]) == set(doc["reduced_why"])
    assert {"num_hidden_layers", "experts_held", "vocab_size", "data",
            "max_iteration"} <= set(doc["reduced"])
    assert {"tie_word_embeddings", "qk_norm", "router_scores",
            "select_bias", "init"} <= set(doc["assumed"])
    assert doc["published"] == {"num_hidden_layers": 40,
                                "num_dense_layers": 2, "num_experts": 64,
                                "vocab_size": 65536}
    assert "8 chips" in doc["stands_for"]


def test_what_runs_is_what_the_configuration_states():
    """The shipped yaml (the configuration's ``base_yaml``) carries the
    file's widths, letter for letter, and the cut it states."""
    doc = _config_doc()
    cfg = harness.build_config(
        harness.load_cell(harness.BENCH_DIR, "lfm2_moe_k4_t4096"), False,
        None)
    mc = cfg["model_config"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "conv_L_cache",
                "norm_eps", "num_experts", "num_experts_per_tok",
                "routed_scaling_factor", "experts_held", "vocab_size",
                "num_dense_layers"):
        assert mc[key] == doc[key], key
    assert mc["rope_theta"] == doc["rope_parameters"]["rope_theta"]
    kept = [doc["layer_types"][i] for i in doc["layers_kept"]]
    assert mc["layer_types"].split(",") == kept
    assert len(kept) == doc["num_hidden_layers"]
    assert mc["head_dim"] * mc["num_attention_heads"] == mc["hidden_size"]
    assert doc["data"]["vocab"] == mc["vocab_size"]
    assert doc["data"]["len_max"] == mc["seq_len"]
    # the tree it makes is the 0.47 B parameters the file counts
    shapes = jax.eval_shape(make_task(mc).init_params, jax.random.PRNGKey(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(shapes)) == \
        doc["parameters"]["total"]


def _ctx(spans, op_seconds=None, op_counts=None):
    return {
        "spans": spans,
        "window": {"t_open": 10.0, "t_close": 20.0},
        "config": {"model_config": {
            "hidden_size": 2048, "moe_intermediate_size": 1536,
            "experts_held": 8}},
        "trace": {"op_seconds": op_seconds or {},
                  "op_counts": op_counts or {}, "chips": 1},
        "device": {"kind": "TPU v5 lite"},
        "peaks": harness.read_json(os.path.join(harness.BENCH_DIR,
                                                "peaks.json")),
    }


def test_new_readers_read_the_counters_and_fall_silent_without_them():
    readers = harness.load_layer_metrics(harness.BENCH_DIR)
    tail = {"name": "host_tail", "ts": 12.0, "dur_s": 0.1, "rounds": 1,
            "moe_pairs_held": 64000.0, "moe_max_load": 16000.0,
            "moe_layer_steps": 32.0, "moe_pairs_dropped": 0.0}
    write = {"name": "ckpt_async_write", "ts": 13.0, "dur_s": 2.5,
             "bytes": 1877142949}
    outside = dict(write, ts=25.0, dur_s=99.0)
    # a best-model save, on the training thread
    sync = dict(write, name="ckpt_write", ts=14.0, dur_s=3.5)
    ctx = _ctx([tail, write, outside, sync, dict(tail, ts=15.0)],
               {"expert_gmm_fwd": 0.096, "expert_gmm_dx.3": 0.2,
                "fusion.7": 1.0},
               {"expert_gmm_fwd": 96, "expert_gmm_dx.3": 96, "fusion.7": 4})
    # 16,000 on the fullest of 8 held experts against a mean of 1,000
    assert readers["expert_load_max_over_mean"].read(ctx) == \
        pytest.approx(2.0)
    assert readers["ckpt_write_ms"].read(ctx) == pytest.approx(3000.0)
    # 2,000 rows a call: 2 * 2000 * 2048 * 1536 operations against
    # (2000 * 3584 + 8 * 2048 * 1536) * 4 bytes: memory bound
    least = (2000 * 3584 + 8 * 2048 * 1536) * 4 / 819e9
    assert least > 2 * 2000 * 2048 * 1536 / 197e12
    assert readers["expert_gmm_fwd_roofline"].read(ctx) == \
        pytest.approx(100.0 * least * 96 / 0.096)
    assert readers["expert_gmm_dx_roofline"].read(ctx) == \
        pytest.approx(100.0 * least * 96 / 0.2)
    assert readers["expert_gmm_dw_roofline"].read(ctx) is None  # no such op
    # a program without the counters (the parent's): nothing to read
    bare = _ctx([{"name": "host_tail", "ts": 12.0, "dur_s": 0.1},
                 {"name": "ckpt_async_write", "ts": 13.0, "dur_s": 2.5}],
                {"expert_gmm_fwd": 0.1}, {"expert_gmm_fwd": 9})
    for name in ("expert_load_max_over_mean", "ckpt_write_ms",
                 "expert_gmm_fwd_roofline"):
        assert readers[name].read(bare) is None, name


def test_required_flops_counts_held_pairs_only():
    weights = ref.init(np.random.default_rng(5), MC)
    batch = {"x": jnp.asarray(_rounds()[0]["x"][0, 0]),
             "sample_mask": jnp.ones((1,), jnp.float32)}
    held = ref.required_flops(weights, batch, MC)
    # every expert held: more pairs fall here, the count rises by exactly
    # their three products
    everywhere = {**MC, "experts_held": MC["num_experts"]}
    all_held = ref.required_flops(
        ref.init(np.random.default_rng(5), everywhere), batch, everywhere)
    tokens = batch["x"].shape[1] - 1
    moe_layers = sum(ffn == "moe" for _, ffn in ref.layer_kinds(MC))
    per_pair = 6.0 * 3 * MC["hidden_size"] * MC["moe_intermediate_size"]
    assert all_held > held > 0
    # with every expert held, every chosen pair is counted
    projections = all_held - per_pair * tokens * \
        MC["num_experts_per_tok"] * moe_layers
    assert projections > 0
    assert (held - projections) % per_pair == 0
    # far fewer than the dense products the plain form computes
    dense = fedround.flops_per_step(
        ref.forward, MC, weights, batch,
        __import__("benchmarks.flops", fromlist=["matmul_flops"]).matmul_flops,
        ref.loss)
    assert held < dense
