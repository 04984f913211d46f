"""The two per-layer metrics of the causal attention core's kernels
(``attn_kernel_ms``, ``attn_kernel_roofline``: PR 37) on traces made by
hand, for both token configurations' shapes: what they read, where they
fall silent, and why a reading cannot pass 100%."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import attn_rooflines, harness  # noqa: E402
from msrflute_tpu.ops import pallas_attention as pa  # noqa: E402

PEAKS = {"TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
CELLS = {"kanana2_mla_k2_t4096": "kanana2_30b_a3b_ep16share",
         "lfm2_moe_k4_t4096": "lfm2_24b_a2b_ep8share"}
#: what the configurations' files must give the readers
SHAPES = {
    "kanana2_mla_k2_t4096": dict(L=4096, H=32, KV=32, Dqk=192, Dv=128),
    "lfm2_moe_k4_t4096": dict(L=4096, H=32, KV=8, Dqk=64, Dv=64),
}


def _model_config(cell):
    """The cell's ``model_config`` as the harness builds it (the shipped
    yaml under the configuration's overlay)."""
    loaded = harness.load_cell(harness.BENCH_DIR, cell)
    return harness.build_config(loaded, False, None)["model_config"]


def _ctx(cell, op_seconds, op_counts, rounds_run=1, chips=1):
    return {
        "config": {"model_config": _model_config(cell),
                   "server_config": {"rounds_per_step": 4}},
        "device": {"kind": "TPU v5 lite"}, "peaks": PEAKS,
        "trace": {"chips": chips, "op_seconds": op_seconds,
                  "op_counts": op_counts,
                  "module_seconds": {"jit_staged": 5.0},
                  "module_counts": {"jit_staged": rounds_run * chips}},
    }


def _readers():
    readers = harness.load_layer_metrics(harness.BENCH_DIR)
    return readers["attn_kernel_ms"], readers["attn_kernel_roofline"]


def test_the_names_are_the_kernels_own():
    assert attn_rooflines.KERNELS == (pa.FWD_NAME, pa.DQ_NAME, pa.DKV_NAME)


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_shapes_come_from_the_configurations_file(cell):
    assert attn_rooflines.geometry(_model_config(cell)) == SHAPES[cell]


@pytest.mark.parametrize("cell", list(CELLS))
def test_both_metrics_read_the_three_kernels_by_name(cell):
    ms, share = _readers()
    assert ms.UNIT == "ms/round" and share.UNIT == "%"
    geo = SHAPES[cell]
    # one dispatch of four rounds: 16 local steps x 5 layers, each layer
    # a forward, the layer's recomputed forward, a dq and a dk/dv; names
    # as the trace has them (a suffix a call site), other work beside
    seconds = {"attn_flash_fwd.1": 0.30, "attn_flash_fwd.7": 0.10,
               "attn_flash_dq": 0.35, "attn_flash_dkv.3": 0.45,
               "fusion.3422": 3.0, "expert_gmm_fwd": 0.2,
               "attn_flash_dqx": 9.0}
    counts = {"attn_flash_fwd.1": 120, "attn_flash_fwd.7": 40,
              "attn_flash_dq": 80, "attn_flash_dkv.3": 80,
              "fusion.3422": 80, "expert_gmm_fwd": 64,
              "attn_flash_dqx": 1}
    ctx = _ctx(cell, seconds, counts)
    # 1.2 s of the three kernels over the dispatch's four rounds
    assert ms.read(ctx) == pytest.approx(1e3 * 1.2 / 4)
    half = geo["H"] * geo["L"] ** 2 / 2
    least = (160 * 2 * half * (geo["Dqk"] + geo["Dv"]) +
             80 * 2 * half * (2 * geo["Dqk"] + geo["Dv"]) +
             80 * 2 * half * (2 * geo["Dqk"] + 2 * geo["Dv"])) / 197e12
    assert share.read(ctx) == pytest.approx(100 * least / 1.2)
    assert 0 < share.read(ctx) < 100
    # two chips, each running every call: seconds are a chip's, calls the
    # trace's: the share is a chip's and does not double
    both = _ctx(cell, seconds, {k: 2 * v for k, v in counts.items()},
                chips=2)
    assert share.read(both) == pytest.approx(share.read(ctx))
    assert ms.read(both) == pytest.approx(ms.read(ctx))


@pytest.mark.parametrize("cell", list(CELLS))
def test_nothing_is_read_where_the_trace_has_no_such_operation(cell):
    """The parent's program runs the plain path: the readers return
    nothing and the line leaves both metrics out."""
    ms, share = _readers()
    plain = _ctx(cell, {"fusion.3422": 3.0, "expert_gmm_fwd": 0.2},
                 {"fusion.3422": 80, "expert_gmm_fwd": 64})
    assert ms.read(plain) is None and share.read(plain) is None
    empty = _ctx(cell, {}, {})
    assert ms.read(empty) is None and share.read(empty) is None


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_share_over_100_is_impossible_at_the_causal_half(cell):
    """Why the share cannot pass 100%.  A kernel computes every tile
    that touches the causal half, whole (at 512 x 512 tiles and 4,096
    tokens: 36 of 64), at widths padded up to whole lanes (192 -> 256,
    64 -> 128), on an MXU that is at most the peak.  Give each kernel
    exactly the seconds the MXU needs for THAT at the peak — no vector
    work, no memory traffic, no grid overhead: the fastest a real call
    could be — and the share reads the ratio of the algorithm's
    operations to the computed ones, under 100.  Only a kernel that
    computed exactly the half at exactly the published widths at the
    peak would read 100, and none can compute less."""
    _, share = _readers()
    geo = SHAPES[cell]
    block, lanes = pa.causal_blocks(geo["L"])[0], 128
    n = geo["L"] // block
    computed = geo["H"] * (n * (n + 1) // 2) * block * block
    assert computed > geo["H"] * geo["L"] ** 2 / 2

    def padded(width):
        return -(-width // lanes) * lanes

    dqk, dv = padded(geo["Dqk"]), padded(geo["Dv"])
    assert dqk >= geo["Dqk"] and dv >= geo["Dv"]
    widths = {"attn_flash_fwd": dqk + dv, "attn_flash_dq": 2 * dqk + dv,
              "attn_flash_dkv": 2 * dqk + 2 * dv}
    seconds = {k: 2.0 * computed * w / 197e12 for k, w in widths.items()}
    reading = share.read(_ctx(cell, seconds, {k: 1 for k in seconds}))
    assert reading < 100
    want = 100 * (geo["H"] * geo["L"] ** 2 / 2) * (
        5 * geo["Dqk"] + 4 * geo["Dv"]) / (computed * (5 * dqk + 4 * dv))
    assert reading == pytest.approx(want)
    # the limiting kernel: the half, the published widths, the peak
    ideal = {k: attn_rooflines.call_cost(k, geo)[0] / 197e12
             for k in widths}
    assert share.read(_ctx(cell, ideal, {k: 1 for k in ideal})) == \
        pytest.approx(100.0)
    # and at these lengths the products, not the bytes, are the limit
    for kernel in widths:
        flops, moved = attn_rooflines.call_cost(kernel, geo)
        assert flops / 197e12 > moved / 819e9


def test_benchmark_json_lists_both_for_the_two_token_cells():
    """Found by name, wherever they stand: a later PR appends its own
    entries behind these, and its cells to these lists."""
    listed = harness.read_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in listed["per_layer"]}
    for name, unit, better in (("attn_kernel_ms", "ms/round", "lower"),
                               ("attn_kernel_roofline", "%", "higher")):
        entry = dict(by_name[name])
        cells = entry.pop("workloads")
        assert entry == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "kernels",
            "moves": "clients_per_s"}
        assert cells[:2] == ["lfm2_moe_k4_t4096", "kanana2_mla_k2_t4096"]
