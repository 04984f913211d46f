"""The Laguna configuration (``laguna_xs2_33b_a3b_ep32share``) as the
benchmark holds it.

1. The cell's files load and run through ``harness.run_cell`` without an
   edit to the harness (``data/laguna_root``: the same base yaml, task,
   reference, generator and limits' names at CPU-test widths): ONE whole
   run on the CPU, program against ``reference/fedround.py``.
2. Planted faults in the program's model, each held to the same
   comparison (``check.compare`` / ``check.judge``, the tiny root's
   limits): the sound program passes, every fault and the bfloat16
   control come out not correct.  The program's party is played by the
   program's own task loss inside ``fedround``'s plain round, so a fault
   costs one compile, not one trainer run.  ``FAULTS`` is also what a
   chip run at the cell's size plants (PERF.md section 6, PR 43).
3. The configuration's file against the catalog row, the shipped yaml
   against the file, the three new readers on a canned trace, and the
   reference's operation count.
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

from benchmarks import (attn_rooflines, check, flops,  # noqa: E402
                        harness, win_attn_rooflines)
from benchmarks.reference import fedround, laguna_moe as ref  # noqa: E402
from msrflute_tpu.models import laguna, make_task  # noqa: E402
from msrflute_tpu.ops import moe, pallas_attention as pa  # noqa: E402

ROOT = os.path.join(HERE, "data", "laguna_root")
CELL = "tiny_laguna_cell"
REAL_CELL = "laguna_swa_k2_t4096"
REAL_CONFIG = "laguna_xs2_33b_a3b_ep32share"
SEED = 2 ** 31 + 4343
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
        ROOT, "reference", "laguna_moe").__file__.startswith(
        harness.BENCH_DIR)
    cfg = harness.build_config(real, False, None)
    assert cfg["server_config"]["clients_per_chunk"] == 1
    assert cfg["server_config"]["num_clients_per_iteration"] == 2
    assert cfg["server_config"]["rounds_per_step"] == 4
    # the traffic mix is Kanana's file, not a copy of it
    assert real["traffic"] == "k2_s2_t4096" == harness.load_cell(
        harness.BENCH_DIR, "kanana2_mla_k2_t4096")["traffic"]
    assert real["traffic_doc"]["period_rounds"] == 4
    assert cfg["model_config"]["model_type"] == "LAGUNA_MOE"
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


def _rounds(seed=11, clients=2, steps=2, length=33):
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


def _layer_name():
    """The name of the layer whose ``__call__`` is being traced."""
    from flax.linen import module as flax_module
    return flax_module._context.module_stack[-1].name


def _attention_with(monkeypatch, change):
    """Every attention block built with ``change(kwargs)`` applied to
    what ``models/laguna.py::_Layer`` asks for."""
    real = laguna._GQAttention

    def built(*args, **kwargs):
        change(kwargs)
        return real(*args, **kwargs)

    monkeypatch.setattr(laguna, "_GQAttention", built)


# -- the window's two (also planted at the cell's size on the chip) ------
def _window_of_513_keys(monkeypatch):
    """A sliding query sees one key more than the window (``i - j <=
    window``: the other reading of "a window of 512")."""
    def change(kwargs):
        if kwargs["window"]:
            kwargs["window"] += 1
    _attention_with(monkeypatch, change)


def _a_sliding_layer_left_causal(monkeypatch):
    """Layer 1 (the first sliding layer, here and in the cell) sees
    every key up to the query's own."""
    def change(kwargs):
        if _layer_name() == "layer_1":
            kwargs["window"] = 0
    _attention_with(monkeypatch, change)


# -- the layer types' three ---------------------------------------------
def _head_counts_swapped(monkeypatch):
    """A sliding layer computes the FULL layers' number of heads and a
    full one the sliding layers' (the weights cut or zero-padded to
    fit): two heads of every sliding layer are missing and the rest read
    key-value heads in groups of the wrong size."""
    real = laguna._GQAttention
    full, sliding = MC["num_attention_heads"], \
        MC["num_attention_heads_sliding"]

    def built(heads, *args, **kwargs):
        return real(full if kwargs["window"] else sliding, *args, **kwargs)

    monkeypatch.setattr(laguna, "_GQAttention", built)
    dim = MC["head_dim"]

    def fit(x, axis, heads):
        if x.shape[axis] >= heads * dim:
            return jax.lax.slice_in_dim(x, 0, heads * dim, axis=axis)
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, heads * dim - x.shape[axis])
        return jnp.pad(x, pad)

    def adapt(params):
        out = dict(params)
        for i, (attn, _) in enumerate(ref.layer_kinds(MC)):
            heads = full if attn == "sliding" else sliding
            a = params[f"layer_{i}"]["attn"]
            out[f"layer_{i}"] = {**params[f"layer_{i}"], "attn": {
                **a, "wq": fit(a["wq"], 1, heads),
                "wg": fit(a["wg"], 1, heads), "wo": fit(a["wo"], 0, heads)}}
        return out

    return adapt


def _rotary_laws_swapped(monkeypatch):
    laws = dict(make_task(MC).module.cfg)

    def change(kwargs):
        kwargs["rotary"] = laws["rotary_full" if kwargs["window"]
                                else "rotary_sliding"]
    _attention_with(monkeypatch, change)


def _yarn_ramp_dropped(monkeypatch):
    """Plain position interpolation: every frequency of a full layer
    divided by the factor, none kept."""
    monkeypatch.setattr(
        laguna, "yarn_inv_freq",
        lambda theta, rotated, factor, *rest: tuple(
            f / factor for f in laguna.plain_inv_freq(theta, rotated)))


def _output_gate_dropped(monkeypatch):
    def change(kwargs):
        kwargs["gate"] = False
    _attention_with(monkeypatch, change)


# -- the expert layer's two that are code --------------------------------
def _shared_expert_left_out(monkeypatch):
    def adapt(params):
        return {name: ({**layer, "shared": {
            **layer["shared"], "w2": jnp.zeros_like(layer["shared"]["w2"])}}
            if isinstance(layer, dict) and "shared" in layer else layer)
            for name, layer in params.items()}
    return adapt


def _gate_over_held_only(monkeypatch):
    def route(z, router_w, select_bias, per_token, scaling=1.0, eps=1e-6,
              **_):
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


#: ``fault(monkeypatch)`` patches the program and may return a function
#: that fits the reference's weights to the patched program
FAULTS = {"window_of_513_keys": _window_of_513_keys,
          "a_sliding_layer_left_causal": _a_sliding_layer_left_causal,
          "head_counts_swapped": _head_counts_swapped,
          "rotary_laws_swapped": _rotary_laws_swapped,
          "yarn_ramp_dropped": _yarn_ramp_dropped,
          "output_gate_dropped": _output_gate_dropped,
          "shared_expert_left_out": _shared_expert_left_out,
          "gate_renormalised_over_held_only": _gate_over_held_only}
#: faults that are another value of a key of ``model_config``
CONFIG_FAULTS = {
    "full_layers_whole_head_rotated": {"partial_rotary_factor": 1.0},
    "yarn_attention_factor_dropped": {"rope_attention_factor": 1.0},
    "routed_scaling_factor_dropped": {"moe_routed_scaling_factor": 1.0}}


def test_sound_program_passes_every_limit(reference_round):
    got = _verdicts(reference_round, _task_loss())
    assert all(v["ok"] for v in got.values()), got


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(reference_round, fault, monkeypatch):
    adapt = FAULTS[fault](monkeypatch) or (lambda params: params)
    sound = _task_loss()
    got = _verdicts(reference_round, lambda params, batch, mc: sound(
        adapt(params), batch, mc))
    assert not got["loss_gap"]["ok"] or not got["update_diff"]["ok"], got


@pytest.mark.parametrize("fault", sorted(CONFIG_FAULTS))
def test_another_value_of_a_models_key_is_not_correct(reference_round,
                                                      fault):
    got = _verdicts(reference_round, _task_loss(**CONFIG_FAULTS[fault]))
    assert not got["loss_gap"]["ok"] or not got["update_diff"]["ok"], got


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
                       if r["name"] == "Laguna-XS.2")
        assert doc["source"] == row["source_url"]
        # the lists and the rotary dict are copied whole
        differs = {k for k, v in row["config"].items()
                   if k not in doc or doc[k] != v}
        assert differs == {"num_hidden_layers", "vocab_size"}, differs
        assert differs <= set(doc["reduced"])
        for key in ("layer_types", "mlp_layer_types",
                    "num_attention_heads_per_layer"):
            assert doc["published"][key] == row["config"][key][:5]
        assert doc["published"]["rope_parameters"] == \
            row["config"]["rope_parameters"]
    # every published width, unchanged
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 48,
            "num_key_value_heads": 8, "head_dim": 128,
            "intermediate_size": 8192, "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512, "num_experts": 256,
            "num_experts_per_tok": 8, "sliding_window": 512,
            "moe_routed_scaling_factor": 2.5, "partial_rotary_factor": 0.5,
            "rms_norm_eps": 1e-6, "gating": True,
            "tie_word_embeddings": False}.items():
        assert doc[key] == value, key
    assert doc["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert doc["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert doc["mlp_layer_types"][:2] == ["dense", "sparse"]
    assert doc["reduced"] == ["num_hidden_layers", "experts_held",
                              "vocab_size", "data", "max_iteration"]
    assert set(doc["reduced"]) == set(doc["reduced_why"])
    assert {"gating", "router_scores", "select_bias", "qk_norm", "rope",
            "sliding_window", "init", "protocol"} <= set(doc["assumed"])
    assert "per-head" in doc["assumed"]["gating"] and \
        "arXiv:2505.06708" in doc["assumed"]["gating"]
    assert doc["published"]["num_hidden_layers"] == 40
    assert doc["published"]["num_experts"] == 256
    assert doc["published"]["vocab_size"] == 100352
    assert doc["experts_held"] == 8 and doc["vocab_size"] == 12544 == \
        100352 // 8
    assert doc["layers_kept"] == [0, 1, 2, 3, 4]
    assert "32 chips" in doc["stands_for"] and "8 of them" in \
        doc["stands_for"] and "thirty-second" in doc["stands_for"]
    listed = harness.read_json(os.path.join(REPO, "BENCHMARK.json"))
    # entries are found by NAME: a later cell's come after these
    entry = next(c for c in listed["configs"] if c["name"] == REAL_CONFIG)
    assert entry["reduced"] == doc["reduced"]
    assert entry["source"] == doc["source"]
    assert entry["file"] == f"benchmarks/configs/{REAL_CONFIG}.json"
    cell = next(w for w in listed["workloads"] if w["name"] == REAL_CELL)
    assert cell == {"name": REAL_CELL, "config": REAL_CONFIG,
                    "traffic": "k2_s2_t4096", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "thirty-second" in cell["why"]
    for limit in doc["check_limits"].values():
        assert "limit" in limit and "why" in limit


def test_what_runs_is_what_the_configuration_states():
    """The shipped yaml (the configuration's ``base_yaml``) carries the
    file's widths, letter for letter, the cut it states, and the
    contents of the published lists as the scalars the file names."""
    doc = _config_doc()
    cfg = harness.build_config(
        harness.load_cell(harness.BENCH_DIR, REAL_CELL), False, None)
    mc = cfg["model_config"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "shared_expert_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "sliding_window",
                "rms_norm_eps", "num_experts", "num_experts_per_tok",
                "moe_routed_scaling_factor", "partial_rotary_factor",
                "gating", "attention_bias", "tie_word_embeddings",
                "moe_apply_router_weight_on_input", "experts_held",
                "vocab_size", "num_hidden_layers"):
        assert mc[key] == doc[key], key
    scalars = doc["scalars_for_lists"]
    for key in ("full_attention_period", "num_dense_layers",
                "num_attention_heads_sliding", "rope_theta", "rope_factor",
                "rope_original_max_position_embeddings", "rope_beta_fast",
                "rope_beta_slow", "rope_attention_factor",
                "partial_rotary_factor", "rope_theta_sliding"):
        assert mc[key] == scalars[key], key
    # the scalars say what the published lists and dict say
    types = ["full_attention" if attn == "full" else "sliding_attention"
             for attn, _ in laguna.layer_types(mc)]
    assert types == doc["layer_types"][:5]
    assert [{"dense": "dense", "moe": "sparse"}[ffn]
            for _, ffn in laguna.layer_types(mc)] == \
        doc["mlp_layer_types"][:5]
    period = {**mc, "num_hidden_layers": 40}
    assert ["full_attention" if attn == "full" else "sliding_attention"
            for attn, _ in laguna.layer_types(period)] == doc["layer_types"]
    assert [mc["num_attention_heads"] if attn == "full" else
            mc["num_attention_heads_sliding"]
            for attn, _ in laguna.layer_types(period)] == \
        doc["num_attention_heads_per_layer"]
    rope = doc["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    assert (full["rope_theta"], full["factor"], full["beta_fast"],
            full["beta_slow"], full["attention_factor"],
            full["original_max_position_embeddings"],
            full["partial_rotary_factor"]) == (
        mc["rope_theta"], mc["rope_factor"], mc["rope_beta_fast"],
        mc["rope_beta_slow"], mc["rope_attention_factor"],
        mc["rope_original_max_position_embeddings"],
        mc["partial_rotary_factor"])
    assert sliding == {"rope_type": "default",
                       "rope_theta": mc["rope_theta_sliding"],
                       "partial_rotary_factor": 1}
    assert doc["data"]["vocab"] == mc["vocab_size"]
    assert doc["data"]["len_max"] == mc["seq_len"] == 4096
    assert (doc["data"]["train_users"], doc["data"]["val_users"],
            doc["data"]["test_users"]) == (32, 4, 4)
    # a model_config that the plain round can hash (fedround caches its
    # program by the items): no list, no dict
    hash(tuple(sorted(mc.items())))
    # the roofline readers take these from model_config
    assert {"hidden_size", "moe_intermediate_size", "experts_held",
            "seq_len", "head_dim", "num_attention_heads_sliding"} <= set(mc)


GEO = {"L": 4096, "W": 512, "H": 64, "KV": 8, "D": 128}
SEEN = 512 * 4096 - 512 * 511 // 2


def test_the_kernels_counts_are_the_seen_pairs_at_published_widths():
    mc = harness.build_config(
        harness.load_cell(harness.BENCH_DIR, REAL_CELL), False,
        None)["model_config"]
    assert win_attn_rooflines.geometry(mc) == GEO
    assert win_attn_rooflines.KERNELS == (pa.WIN_FWD_NAME, pa.WIN_DQ_NAME,
                                          pa.WIN_DKV_NAME)
    assert win_attn_rooflines.pairs_seen(GEO) == SEEN == 1_966_336 == \
        pa.win_tile_map(4096, 512, 512, 512)["pairs_seen"]
    small = {"L": 16, "W": 5, "H": 1, "KV": 1, "D": 8}
    assert win_attn_rooflines.pairs_seen(small) == \
        pa.window_seen(16, 5).sum()
    # a window longer than the row: the causal half
    assert win_attn_rooflines.pairs_seen({**small, "W": 40}) == 16 * 17 // 2
    moved = 4 * 4096 * 256 * 72
    for kernel, widths in (("attn_win_fwd", 256), ("attn_win_dq", 384),
                           ("attn_win_dkv", 512)):
        assert win_attn_rooflines.call_cost(kernel, GEO) == (
            2.0 * 64 * SEEN * widths, float(moved))
    # a sliding layer's forward core: 64 GFLOP (three of them 0.19 T:
    # ISSUE.md's reckoning)
    assert 3 * win_attn_rooflines.call_cost("attn_win_fwd", GEO)[0] == \
        pytest.approx(0.19e12, rel=0.02)
    # the share cannot pass 100: the kernels compute at least every tile
    # that holds a seen pair, whole, at every tile shape the rule gives
    for length in (128, 1024, 4096):
        for window in (1, 100, 512, 8192):
            for tile in ((512, 512), (128, 128), (256, 512)):
                tile = tuple(min(t, length) for t in tile)
                tiles = pa.win_tile_map(length, window, *tile)
                assert tiles["tiles_run"] * tile[0] * tile[1] >= \
                    tiles["pairs_seen"]
    # the causal kernels' readers take the FULL layers' 48 heads from the
    # published key
    assert attn_rooflines.geometry(mc) == {
        "L": 4096, "H": 48, "KV": 8, "Dqk": 128, "Dv": 128}
    # a configuration without a window has no such geometry
    other = harness.build_config(harness.load_cell(
        harness.BENCH_DIR, "sdar_bd_k2_t4096"), False, None)["model_config"]
    assert win_attn_rooflines.geometry(other) is None


def _trace(seconds):
    names = {"attn_win_fwd": "attn_win_fwd.3", "attn_win_dq": "attn_win_dq.1",
             "attn_win_dkv": "attn_win_dkv.1"}
    return {"chips": 1, "window_s": 8.0,
            "op_seconds": {**{names[k]: s for k, (s, _) in seconds.items()},
                           "attn_flash_fwd.2": 9.0, "fusion.7": 1.0},
            "op_counts": {**{names[k]: c for k, (_, c) in seconds.items()},
                          "attn_flash_fwd.2": 5, "fusion.7": 100},
            "module_seconds": {"jit_staged(123)": 6.0, "jit_eval(5)": 1.0},
            "module_counts": {"jit_staged(123)": 1, "jit_eval(5)": 2}}


def _ctx(tmp_path, trace, spans=(), events=(), cell=REAL_CELL):
    telemetry = tmp_path / "out" / "models" / "telemetry"
    (telemetry / "programs").mkdir(parents=True, exist_ok=True)
    with open(telemetry / "events.jsonl", "w") as fh:
        for record in events:
            fh.write(json.dumps(record) + "\n")
    mc = harness.build_config(
        harness.load_cell(harness.BENCH_DIR, cell), False, None)
    return {"trace": trace, "config": mc,
            "window": {"t_open": 10.0, "t_close": 20.0},
            "device": {"kind": "TPU v5 lite"},
            "peaks": harness.read_json(os.path.join(harness.BENCH_DIR,
                                                    "peaks.json")),
            "spans": [*spans, {
                "name": "program_scopes", "ts": 1.0, "dur_s": 0.1,
                "file": str(telemetry / "programs" / "jit_staged-1.json")}]}


def test_the_three_readers_on_a_canned_trace(tmp_path):
    readers = harness.load_layer_metrics(harness.BENCH_DIR)
    seconds = {"attn_win_fwd": (0.3, 60), "attn_win_dq": (0.5, 48),
               "attn_win_dkv": (0.7, 48)}
    event = {"kind": "event", "name": "attn_window_tiles", "ts": 2.0,
             **{k: v for k, v in pa.record_window_tiles(
                 4096, 512, 512, 512).items() if k != "kind"}}
    pa.drain_attention_events()
    other = {**event, "L": 512, "tiles_run": 1}
    ctx = _ctx(tmp_path, _trace(seconds), [],
               [{"kind": "span", "name": "pack"}, other, event])
    # four rounds a dispatch, one dispatch in the trace
    assert readers["win_attn_kernel_ms"].read(ctx) == pytest.approx(
        1e3 * 1.5 / 4)
    # the band is thin: the forward call is bound by the bytes of q, k,
    # v and out (0.37 ms at 819 GB/s against 0.33 ms of operations),
    # the two backward calls by their operations
    costs = {k: win_attn_rooflines.call_cost(k, GEO) for k in seconds}
    assert costs["attn_win_fwd"][1] / 819e9 > \
        costs["attn_win_fwd"][0] / 197e12
    assert costs["attn_win_dq"][1] / 819e9 < costs["attn_win_dq"][0] / 197e12
    least = sum(calls * max(costs[k][0] / 197e12, costs[k][1] / 819e9)
                for k, (_, calls) in seconds.items())
    assert readers["win_attn_kernel_roofline"].read(ctx) == pytest.approx(
        100.0 * least / 1.5)
    assert 0 < readers["win_attn_kernel_roofline"].read(ctx) < 100
    assert readers["win_attn_tile_fill"].read(ctx) == pytest.approx(
        100.0 * SEEN / (15 * 512 * 512))
    assert readers["win_attn_tile_fill"].read(ctx) == pytest.approx(
        50.0, abs=0.01)
    assert {readers[name].UNIT for name in (
        "win_attn_kernel_roofline", "win_attn_tile_fill")} == {"%"}
    assert readers["win_attn_kernel_ms"].UNIT == "ms/round"
    # the causal kernels' readers read the full layers' calls of the same
    # trace and none of these kernels; these read no causal kernel
    assert readers["attn_kernel_ms"].read(ctx) == pytest.approx(
        1e3 * 9.0 / 4)
    causal = _trace({})
    for name in ("win_attn_kernel_ms", "win_attn_kernel_roofline"):
        assert readers[name].read(_ctx(tmp_path, causal)) is None


def test_the_readers_fall_silent_on_a_program_without_what_they_read(
        tmp_path):
    """The parent's program: no such kernel, no such event.  Nothing is
    returned and nothing raised (the driver runs these readers over the
    parent's checkout too)."""
    readers = harness.load_layer_metrics(harness.BENCH_DIR)
    new = ("win_attn_kernel_ms", "win_attn_kernel_roofline",
           "win_attn_tile_fill")
    ctx = _ctx(tmp_path, _trace({}), [], [])
    for name in new:
        assert readers[name].read(ctx) is None, name
    # no program_scopes span at all (no telemetry directory to find)
    ctx["spans"] = []
    assert readers["win_attn_tile_fill"].read(ctx) is None
    # a configuration of another model (no window), whatever its trace
    other = _ctx(tmp_path, _trace({"attn_win_fwd": (0.1, 1)}), [], [],
                 cell="kanana2_mla_k2_t4096")
    assert readers["win_attn_kernel_roofline"].read(other) is None
    assert readers["win_attn_tile_fill"].read(other) is None


def test_the_new_entries_and_the_lists_the_cell_joined():
    listed = harness.read_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in listed["per_layer"]}
    new = ["win_attn_kernel_ms", "win_attn_kernel_roofline",
           "win_attn_tile_fill"]
    for name in new:
        assert by_name[name]["workloads"] == [REAL_CELL]
        assert by_name[name]["moves"] == "clients_per_s"
        assert by_name[name]["layer"] == "kernels"
    # not ``expert_tile_fill``: an accepted test holds its list to the
    # two first token cells, letter for letter (PERF.md section 7)
    joined = {"expert_load_max_over_mean", "expert_gmm_fwd_roofline",
              "expert_gmm_dx_roofline", "expert_gmm_dw_roofline",
              "ckpt_write_ms", "ckpt_wait_ms", "stage_reuse",
              "client_steps_ms", "aggregate_ms", "moe_layer_ms",
              "attn_core_ms", "attn_proj_ms", "head_loss_ms",
              "scope_unattributed", "attn_kernel_ms",
              "attn_kernel_roofline"}
    for name in joined:
        assert REAL_CELL in by_name[name]["workloads"], name
    # nothing else of an accepted entry changed: a per-layer metric
    # without a list is reported in every cell, this one too
    assert "workloads" not in by_name["train_mfu"]


def test_required_flops_counts_what_the_algorithm_needs():
    weights = ref.init(np.random.default_rng(5), MC)
    batch = {"x": jnp.asarray(_rounds()[0]["x"][0, 0]),
             "sample_mask": jnp.ones((1,), jnp.float32)}
    held = ref.required_flops(weights, batch, MC)
    everywhere = {**MC, "experts_held": MC["num_experts"]}
    all_held = ref.required_flops(
        ref.init(np.random.default_rng(5), everywhere), batch, everywhere)
    tokens = batch["x"].shape[1] - 1
    kinds = ref.layer_kinds(MC)
    routed_layers = [ffn for _, ffn in kinds].count("moe")
    per_pair = 6.0 * 3 * MC["hidden_size"] * MC["moe_intermediate_size"]
    assert all_held > held > 0
    # with every expert held, every chosen pair is counted
    rest = all_held - per_pair * tokens * MC["num_experts_per_tok"] * \
        routed_layers
    assert rest > 0 and (held - rest) % per_pair == 0
    # the rest, term by term: a full layer's core at half the square, a
    # sliding layer's at the BAND (not the causal half, not the square)
    hidden, dim, kv = MC["hidden_size"], MC["head_dim"], \
        MC["num_key_value_heads"]
    window = MC["sliding_window"]
    band = window * tokens - window * (window - 1) // 2
    want = tokens * hidden * MC["vocab_size"]
    for attn, ffn in kinds:
        heads = MC["num_attention_heads_sliding" if attn == "sliding"
                   else "num_attention_heads"]
        want += tokens * hidden * dim * (3 * heads + 2 * kv)
        want += heads * 2 * dim * (band if attn == "sliding"
                                   else tokens * (tokens + 1) // 2)
        want += tokens * 3 * hidden * (
            MC["intermediate_size"] if ffn == "dense"
            else MC["shared_expert_intermediate_size"])
        if ffn == "moe":
            want += tokens * hidden * MC["num_experts"]
    assert rest == pytest.approx(6.0 * want, rel=1e-12)
    assert band < tokens * (tokens + 1) // 2
    # far fewer than the dense products the plain form computes
    dense = fedround.flops_per_step(
        ref.forward, MC, weights, batch, flops.matmul_flops, ref.loss)
    assert held < dense
