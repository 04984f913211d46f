"""Ahead-of-time compiles for the chip, made without the chip.

The TPU's compiler is installed here and compiles for a chip that is
described (``v5e:2x2``) and not attached, so what Mosaic refuses — a slice
not aligned to the tiling, too much VMEM, a kernel that cannot be batched —
fails in tier-1 instead of in a chip call.  Nothing runs: these say nothing
about results or times.  Every kernel is called with ``interpret=False``;
the default asks ``jax.default_backend()`` and would take the interpreter.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)

from msrflute_tpu.ops import pallas_attention as pa  # noqa: E402
from msrflute_tpu.ops.pallas_kernels import (fused_gaussian_noise,  # noqa: E402
                                             fused_sgd_apply,
                                             quant_bin_sparsify)

#: a little over one CNN_FEMNIST dense layer (9216 x 128), and not a
#: multiple of the kernels' 256 x 128 block
N = 1_200_003
CLIENTS = 10


@pytest.fixture(scope="module")
def topology():
    """The described v5e:2x2 devices; skipped where the topology cannot be
    described.  The persistent compile cache is off around these
    compiles: such an entry is written but cannot be read back without a
    chip, and the next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu / no such topology here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc!r}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def chip(topology):
    return SingleDeviceSharding(topology[0])


def _noise(x):
    return fused_gaussian_noise(x, jnp.float32(1.0), jnp.float32(0.5),
                                jnp.int32(7), interpret=False)


def _quant(x):
    return quant_bin_sparsify(x, jnp.min(x), jnp.max(x),
                              jnp.float32(0.1), 256, interpret=False)


def _sgd(x):
    return fused_sgd_apply(x, x * 0.5, x * 0.25, jnp.float32(0.1),
                           jnp.float32(0.9), jnp.float32(1.0),
                           interpret=False)


@pytest.mark.parametrize("batched", [False, True],
                         ids=["plain", "vmap_clients"])
@pytest.mark.parametrize("kernel", [_noise, _quant, _sgd],
                         ids=["gaussian_noise", "quant_bin_sparsify",
                              "sgd_apply"])
def test_elementwise_kernel_compiles_for_v5e(chip, kernel, batched):
    """Plain, and under ``vmap`` over the round's clients — the form the
    quantization kernel takes inside the round program."""
    shape = (CLIENTS, N) if batched else (N,)
    fn = jax.vmap(kernel) if batched else kernel
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)
    compiled = jax.jit(fn).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_threshold_selection_compiles_for_v5e_as_one_counted_loop(chip):
    """The quantiser's exact threshold at the CNN cell's leaf and cohort,
    under the round's client ``vmap`` and a traced quantile, as the
    chip's compiler sees it: no sort, and the leaf only read — the
    program's scratch is smaller than the leaf (without the barrier in
    ``abs_order_stats`` the leaf's bitcast is hoisted out of the loop as
    an int32 copy of it, at 170 clients though not at 10)."""
    from msrflute_tpu.ops.quantization import quantile_abs
    leaf = jax.ShapeDtypeStruct((170, 9216, 128), jnp.float32,
                                sharding=chip)
    q = jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)
    compiled = jax.jit(jax.vmap(quantile_abs, (0, None))).lower(
        leaf, q).compile()
    assert not re.search(r"\bsort\(", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 170 * 9216 * 128 * 4


FLASH_SHAPES = {
    # the long-context cell's geometry: block-aligned, bf16
    "aligned_bf16": ((2, 2048, 4, 64), jnp.bfloat16),
    # L and D both need padding, f32
    "unaligned_f32": ((1, 1000, 3, 24), jnp.float32),
}


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("shape_id", list(FLASH_SHAPES))
def test_flash_attention_compiles_for_v5e(chip, shape_id, direction, causal):
    shape, dtype = FLASH_SHAPES[shape_id]
    spec = jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def forward(q, k, v):
        return pa.flash_attention(q, k, v, causal=causal, force_flash=True,
                                  interpret=False)

    def backward(q, k, v):
        return jax.grad(lambda *a: jnp.sum(forward(*a).astype(jnp.float32)),
                        argnums=(0, 1, 2))(q, k, v)

    fn = forward if direction == "forward" else backward
    compiled = jax.jit(fn).lower(spec, spec, spec).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("inside_shard_map", [True, False],
                         ids=["shard_map", "gspmd"])
def test_kernel_branch_follows_partitioning(topology, monkeypatch,
                                            inside_shard_map):
    """Mosaic kernels cannot be partitioned by GSPMD.  On a four-chip mesh
    the global-DP noise branch takes the compiled kernel inside ``shard_map``
    and the jnp path under a plain multi-device ``jit`` — where lowering
    the kernel would raise (the shipped ``mlm_bert`` config's
    ``model_axis_size: 4`` round).  ``default_backend`` is steered here,
    in the test: it still sees the CPU."""
    from msrflute_tpu.privacy import apply_global_dp
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topology).reshape(4, 1), ("clients", "model"))
    spec = P("clients")

    def noised(g):
        return apply_global_dp({"w": g}, {"global_sigma": 0.1},
                               jax.random.PRNGKey(0), jnp.float32(10.0))["w"]

    fn = (jax.shard_map(noised, mesh=mesh, in_specs=spec, out_specs=spec,
                        check_vma=False)
          if inside_shard_map else noised)
    x = jax.ShapeDtypeStruct((8, 4096), jnp.float32,
                             sharding=NamedSharding(mesh, spec))
    compiled = jax.jit(fn).lower(x).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == inside_shard_map


def test_latest_snapshot_is_one_copy_program_on_the_four_chip_mesh(topology):
    """The async writer's device snapshot (``checkpoint._copy_device_leaves``)
    on the 2x2 mesh, 64 leaves at ResNet-18 widths, replicated weights and
    a clients-sharded pool: one program, a copy for every leaf, each output
    with its input's sharding, and no collective (a gather here would wait
    for the other chips between two launches)."""
    from msrflute_tpu.engine.checkpoint import _copy_device_leaves
    mesh = Mesh(np.asarray(topology).reshape(4, 1), ("clients", "model"))
    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P("clients"))
    leaves = [jax.ShapeDtypeStruct((3, 3, 512, 512) if i % 2 else (512,),
                                   jnp.float32, sharding=replicated)
              for i in range(62)]
    leaves += [jax.ShapeDtypeStruct((1024, 4096), jnp.float32,
                                    sharding=sharded),
               jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)]
    compiled = _copy_device_leaves.lower(leaves).compile()
    text = compiled.as_text()
    assert text.count(" copy(") + text.count("copy-start(") >= 63, \
        "a leaf came back without a copy"
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text
    for want, got in zip(leaves, compiled.output_shardings):
        assert got.is_equivalent_to(want.sharding, len(want.shape))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 0, "an output aliases its input"
    assert memory.output_size_in_bytes >= memory.argument_size_in_bytes


# ----------------------------------------------------------------------
# the expert layer's grouped matmuls at LFM2-24B-A2B's widths (PR 28)
# ----------------------------------------------------------------------
#: tokens a step, experts per token, hidden, expert width, experts held,
#: experts in all, of the four token cells
EXPERT_CELLS = {
    "sdar_bd_k2_t4096": (8192, 8, 2048, 768, 16, 128),
    "lfm2_moe_k4_t4096": (4096, 4, 2048, 1536, 8, 64),
    "kanana2_mla_k2_t4096": (4096, 6, 2048, 768, 8, 128),
    "laguna_swa_k2_t4096": (4096, 8, 2048, 512, 8, 256),
}


def _expert_layer_text(chip, monkeypatch, cell, direction, dtype, precision,
                       routed=True):
    """The compiled text of one expert layer at ``cell``'s shape, forward
    or forward and backward; ``default_backend`` is steered here, in the
    test.  Not ``routed``: the scores and their top k (half of such a
    compile, and nothing of the row side) give way to a choice by
    position."""
    import contextlib
    from msrflute_tpu.ops import moe
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    tokens, per_token, hidden, width, held, experts = EXPERT_CELLS[cell]
    if not routed:
        monkeypatch.setattr(moe, "route_tokens", lambda z, *_: (
            (jnp.arange(tokens * per_token, dtype=jnp.int32) % experts
             ).reshape(tokens, per_token),
            jax.nn.sigmoid(z[:, :per_token].astype(jnp.float32))))

    def spec(*shape, kind=dtype):
        return jax.ShapeDtypeStruct(shape, kind, sharding=chip)

    def ffn(z, router, bias, w1, w3, w2):
        return moe.held_experts_ffn(z, router, bias, w1, w3, w2,
                                    experts_per_token=per_token)

    def backward(*args):
        return jax.grad(lambda *a: jnp.sum(ffn(*a)[0] ** 2),
                        argnums=(0, 1, 3, 4, 5))(*args)

    args = (spec(tokens, hidden), spec(hidden, experts, kind=jnp.float32),
            spec(experts, kind=jnp.float32), spec(held, hidden, width),
            spec(held, hidden, width), spec(held, width, hidden))
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        return jax.jit(ffn if direction == "forward" else
                       backward).lower(*args).compile().as_text()


#: what may have a whole row-side array for its result: a kernel, what
#: only names a buffer that is there, and the compiler's own move of a
#: buffer into the fast memory beside other work (where an ``[M, width]``
#: array fits there: 69-79 MB at two of the shapes), which is no pass of
#: the program's
ROW_SIDE_WRITERS = {"custom-call", "parameter", "get-tuple-element",
                    "bitcast", "copy-done"}


def _whole_row_side_results(cell, text) -> set:
    """The operations of ``text`` whose result is a whole ``[M, hidden]``
    or ``[M, width]`` array of ``cell``'s pair buffer."""
    from msrflute_tpu.ops import moe
    tokens, per_token, hidden, width, held, _ = EXPERT_CELLS[cell]
    rows = (-(-tokens * per_token // moe.TILE_ROWS) + held) * moe.TILE_ROWS
    whole = re.compile(
        rf"= f32\[{rows},(?:{hidden}|{width})\]\S* ([a-z\-]+)\(")
    found = {hit.group(1) for hit in map(whole.search, text.splitlines())
             if hit}
    assert "custom-call" in found
    return found


@pytest.mark.parametrize("dtype, precision", [
    (jnp.float32, None), (jnp.float32, "highest"),
    (jnp.bfloat16, "highest")], ids=["f32", "f32_highest", "bf16_highest"])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_expert_layer_compiles_for_v5e_at_published_widths(
        chip, monkeypatch, direction, dtype, precision):
    """One chip's share of an expert layer (8 of 64 experts, hidden 2,048,
    expert width 1,536, a 4,096-token row): the Pallas kernels are in
    the program under their stable names and fit the chip's fast
    memory, in float32 as the cell runs them, under ``highest`` as the
    benchmark's check program traces them, and with bfloat16 operands
    under ``highest`` as its lower-precision control does (Mosaic refuses
    a float32 contraction of bfloat16 operands: the kernels ask for one
    pass there).  In float32, forward and backward, no operation has a
    whole ``[M, hidden]`` or ``[M, width]`` array of the pair buffer for
    its result but the kernels: no gather, select, add or activation over
    all ``M`` rows, no loop that carries the buffer, no copy of it, no
    initial value."""
    from msrflute_tpu.ops import moe
    text = _expert_layer_text(chip, monkeypatch, "lfm2_moe_k4_t4096",
                              direction, dtype, precision)
    if direction == "backward" and dtype == jnp.float32:
        assert not _whole_row_side_results("lfm2_moe_k4_t4096", text) - \
            ROW_SIDE_WRITERS
    names = [moe.GMM_NAME, moe.SWIGLU_NAME, moe.ROWS_GATHER_NAME] \
        if direction == "forward" else \
        [moe.GMM_NAME, moe.GMM_T_NAME, moe.TGMM_NAME, moe.SWIGLU_NAME,
         moe.SWIGLU_BWD_NAME, moe.ROWS_ADD_NAME, moe.ROWS_GATHER_NAME]
    for name in names:
        assert name in text, name
    # the moves between token order and the pair buffer are gathers in
    # both directions: no scatter of [rows, hidden] (14 s of compile a
    # layer, and run row by row); the routing's own transpose, 16,384
    # scores into a flat [tokens * experts], stays a scatter
    assert not re.search(r"= (f32|bf16)\[\d+,\d+\]\S* scatter\(", text)


# ----------------------------------------------------------------------
# the expert layer's row side at the four token cells' shapes (PR 44)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("precision", [None, "highest"],
                         ids=["default", "highest"])
@pytest.mark.parametrize("cell", [c for c in EXPERT_CELLS
                                  if c != "lfm2_moe_k4_t4096"])
def test_row_side_of_the_expert_layer_compiles_for_v5e_at_the_other_cells(
        chip, monkeypatch, cell, precision):
    """Forward and backward of one expert layer at the shapes of the
    three other token cells, at the default precision as a cell runs it
    and under ``highest`` as the check program is traced: the kernels
    are in the program under their names and nothing else has a whole
    row-side array for its result (the test above holds LFM2's shape to
    the same, under its own routing)."""
    from msrflute_tpu.ops import moe
    text = _expert_layer_text(chip, monkeypatch, cell, "backward",
                              jnp.float32, precision, routed=False)
    for name in (moe.GMM_NAME, moe.GMM_T_NAME, moe.TGMM_NAME,
                 moe.SWIGLU_NAME, moe.SWIGLU_BWD_NAME, moe.ROWS_ADD_NAME,
                 moe.ROWS_GATHER_NAME):
        assert name in text, name
    assert not _whole_row_side_results(cell, text) - ROW_SIDE_WRITERS


# ----------------------------------------------------------------------
# the MLA-MoE task's whole step at Kanana-2-30B-A3B's widths (PR 36)
# ----------------------------------------------------------------------
def test_mla_moe_step_compiles_for_v5e_at_published_widths(chip,
                                                           monkeypatch):
    """One local step of ``experiments/mla_moe/config.yaml`` as the cell
    runs it (a 4,096-token row, ``remat``, attention blocks of 2,048
    rows; forward, backward and the SGD update of the 0.425 B tree),
    traced under ``highest`` at the effort of what is compared and never
    timed, as the benchmark's check program is: the expert layer's three
    kernels are in the program (``tpu_custom_call``) under their stable
    names, the four mechanisms under their scopes, and the step's scratch
    fits beside the five copies of the tree."""
    import yaml
    from jax._src import config as jax_config

    from msrflute_tpu.models import make_task
    from msrflute_tpu.ops import moe
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "experiments", "mla_moe",
                           "config.yaml")) as fh:
        mc = yaml.safe_load(fh)["model_config"]
    task = make_task({**mc, "remat": True, "attention_block": 2048})
    shapes = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        shapes)
    batch = {"x": jax.ShapeDtypeStruct((1, mc["seq_len"]), jnp.int32,
                                       sharding=chip),
             "sample_mask": jax.ShapeDtypeStruct((1,), jnp.float32,
                                                 sharding=chip)}

    def step(p, b):
        (loss, aux), grads = jax.value_and_grad(
            lambda q: task.loss(q, b, None, True)[:2], has_aux=True)(p)
        return (jax.tree.map(lambda a, g: a - 0.1 * g, p, grads), loss,
                aux["counters"])

    with jax.default_matmul_precision("highest"), \
            jax_config.exec_time_optimization_effort(-1.0):
        compiled = jax.jit(step).lower(params, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in (moe.GMM_NAME, moe.GMM_T_NAME, moe.TGMM_NAME, "mla_proj",
                 "mla_attn_core", "shared_expert", "routed_experts"):
        assert name in text, name
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= 424_961_024 * 4
    assert memory.temp_size_in_bytes < 4 * 2 ** 30
    # this is the PLAIN path (``default_backend`` says cpu here): its
    # blocks of float32 scores stand in the program
    assert SCORES.search(text)


# ----------------------------------------------------------------------
# the token models' causal core through the tiled kernels (PR 37)
# ----------------------------------------------------------------------
#: a float32 array of heads x block x L scores, as the plain path's
#: blocks of 2,048 rows have them ([1, 32, 1, 2048, 4096] for 32 heads of
#: one; [1, 8, 4, 2048, 4096] for 32 over 8)
SCORES = re.compile(
    r"f32\[(?:1,)?(?:32|32,1|8,4),(?:512|1024|2048|4096),(?:2048|4096)\]")
#: q, k, v of one 4,096-token row at the two token cells' widths
CORES = {
    # Kanana-2: 32 heads of 128 + 64 over keys of their own, values 128
    "mla_32x192_128": ((1, 4096, 32, 192), (1, 4096, 32, 192),
                       (1, 4096, 32, 128)),
    # LFM2: 32 query heads over 8 key-value heads of 64
    "gqa_32over8x64": ((1, 4096, 32, 64), (1, 4096, 8, 64),
                       (1, 4096, 8, 64)),
}


@pytest.mark.parametrize("precision", [None, "highest"],
                         ids=["default", "highest"])
@pytest.mark.parametrize("core", list(CORES))
def test_causal_core_kernels_compile_for_v5e_at_both_cells_shapes(
        chip, core, precision):
    """The three kernels under their stable names at the two token
    cells' shapes (a value width of its own; grouped key-value heads by
    the block index), with bfloat16 operands as the timed program hands
    them over and with float32 operands contracted in full as the check
    program does: what Mosaic refuses (VMEM, the tiling at width 192, a
    float32 contraction) fails here.  One program a case: the gradient's,
    which holds the forward kernel beside the two backward ones."""
    import contextlib
    specs = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)
             for shape in CORES[core]]

    def backward(q, k, v):
        return jax.grad(lambda *a: jnp.sum(pa.causal_flash_attention(
            *a, interpret=False)), argnums=(0, 1, 2))(q, k, v)

    pa.drain_attention_events()
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        compiled = jax.jit(backward).lower(*specs).compile()
    text = compiled.as_text()
    for name in (pa.FWD_NAME, pa.DQ_NAME, pa.DKV_NAME):
        assert name in text, name
    assert not SCORES.search(text)
    said = [e for e in pa.drain_attention_events()
            if e["kind"] == "attention_path"]
    assert said and all(e["impl"] == "flash" and e["block_q"] == 512
                        for e in said)


@pytest.mark.parametrize("model", ["mla_moe", "lfm2_moe"])
def test_token_model_step_compiles_for_v5e_with_the_core_in_the_kernels(
        chip, monkeypatch, model):
    """One local step of each token cell at published widths (a
    4,096-token row, ``remat``) with the kernel path steered on, here in
    the test, traced as the check program is: the three attention
    kernels are in the program under their names, no block of float32
    scores is, and the step's scratch is smaller than the plain path's
    by the two blocks of scores."""
    import functools

    import yaml
    from jax._src import config as jax_config

    from msrflute_tpu.models import make_task, mla_moe, token_blocks
    from msrflute_tpu.ops import moe
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    # LFM2's grouped-query attention is token_blocks' own
    for module in (mla_moe, token_blocks):
        monkeypatch.setattr(module, "causal_attention", functools.partial(
            token_blocks.causal_attention, interpret=False))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "experiments", model,
                           "config.yaml")) as fh:
        mc = yaml.safe_load(fh)["model_config"]
    task = make_task({**mc, "remat": True, "attention_block": 2048})
    shapes = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        shapes)
    batch = {"x": jax.ShapeDtypeStruct((1, mc["seq_len"]), jnp.int32,
                                       sharding=chip),
             "sample_mask": jax.ShapeDtypeStruct((1,), jnp.float32,
                                                 sharding=chip)}

    def step(p, b):
        loss, grads = jax.value_and_grad(
            lambda q: task.loss(q, b, None, True)[0])(p)
        return jax.tree.map(lambda a, g: a - 0.1 * g, p, grads), loss

    pa.drain_attention_events()
    with jax.default_matmul_precision("highest"), \
            jax_config.exec_time_optimization_effort(-1.0):
        compiled = jax.jit(step).lower(params, batch).compile()
    text = compiled.as_text()
    for name in (pa.FWD_NAME, pa.DQ_NAME, pa.DKV_NAME, moe.GMM_NAME):
        assert name in text, name
    assert not SCORES.search(text)
    said = pa.drain_attention_events()
    assert said and all(e["impl"] == "flash" for e in said), said
    # the plain path's step holds 2.2-2.6 GB of scratch (two blocks of
    # scores of 1.07 GB among it); this one stays under 1.5
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 2 ** 30


# ----------------------------------------------------------------------
# the sliding-window core through the tile map's second law (PR 43)
# ----------------------------------------------------------------------
#: a float32 array that holds a block of a row's scores at either of
#: Laguna's head counts (48 full: groups of 6; 64 sliding: groups of 8)
WIN_SCORES = re.compile(
    r"f32\[(?:1,)?(?:48|64|8,6|8,8),(?:512|1024|2048|4096),(?:\d{4})\]")


@pytest.mark.parametrize("precision", [None, "highest"],
                         ids=["default", "highest"])
def test_window_kernels_compile_for_v5e_at_the_cells_shape(chip, precision):
    """The three kernels under their names at ``laguna_swa_k2_t4096``'s
    sliding layers' shape (a row of 4,096, 64 query heads over 8
    key-value heads of 128, a window of 512 keys), with bfloat16
    operands and with float32 operands contracted in full: what Mosaic
    refuses fails here.  No operation of the program holds a block of a
    row's scores, and the event says what the map runs."""
    import contextlib
    specs = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)
             for shape in ((1, 4096, 64, 128), (1, 4096, 8, 128),
                           (1, 4096, 8, 128))]

    def backward(q, k, v):
        return jax.grad(lambda *a: jnp.sum(
            pa.window_flash_attention(*a, 512, interpret=False)),
            argnums=(0, 1, 2))(q, k, v)

    pa.drain_attention_events()
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        compiled = jax.jit(backward).lower(*specs).compile()
    text = compiled.as_text()
    for name in (pa.WIN_FWD_NAME, pa.WIN_DQ_NAME, pa.WIN_DKV_NAME):
        assert name in text, name
    assert pa.FWD_NAME not in text and not WIN_SCORES.search(text)
    said = {e["kind"]: e for e in pa.drain_attention_events()}
    assert said["attention_path"]["impl"] == "flash"
    tiles = said["attn_window_tiles"]
    assert (tiles["tiles_run"], tiles["tiles_masked"],
            tiles["tiles_total"], tiles["pairs_seen"]) == \
        (15, 15, 64, 1_966_336)


def test_laguna_step_compiles_for_v5e_with_both_cores_in_the_kernels(
        chip, monkeypatch):
    """One local step of ``laguna_swa_k2_t4096`` at published widths (a
    4,096-id row, ``remat``) with the kernel path steered on, traced as
    the check program is: the window law's kernels (three sliding
    layers), the causal kernels (two full layers) and the expert kernels
    are in the program under their names, and no operation holds a
    block of a row's scores."""
    import functools

    import yaml
    from jax._src import config as jax_config

    from msrflute_tpu.models import make_task, token_blocks
    from msrflute_tpu.ops import moe
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    monkeypatch.setattr(token_blocks, "causal_attention", functools.partial(
        token_blocks.causal_attention, interpret=False))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "experiments", "laguna_moe",
                           "config.yaml")) as fh:
        mc = yaml.safe_load(fh)["model_config"]
    task = make_task({**mc, "remat": True})
    shapes = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        shapes)
    batch = {"x": jax.ShapeDtypeStruct((1, mc["seq_len"]), jnp.int32,
                                       sharding=chip),
             "sample_mask": jax.ShapeDtypeStruct((1,), jnp.float32,
                                                 sharding=chip)}

    def step(p, b):
        loss, grads = jax.value_and_grad(
            lambda q: task.loss(q, b, None, True)[0])(p)
        return jax.tree.map(lambda a, g: a - 0.1 * g, p, grads), loss

    pa.drain_attention_events()
    with jax.default_matmul_precision("highest"), \
            jax_config.exec_time_optimization_effort(-1.0):
        compiled = jax.jit(step).lower(params, batch).compile()
    text = compiled.as_text()
    for name in (pa.WIN_FWD_NAME, pa.WIN_DQ_NAME, pa.WIN_DKV_NAME,
                 pa.FWD_NAME, pa.DQ_NAME, pa.DKV_NAME, moe.GMM_NAME):
        assert name in text, name
    assert not WIN_SCORES.search(text)
    said = pa.drain_attention_events()
    assert {e["kind"] for e in said} == {"attention_path",
                                         "attn_window_tiles"}
    assert all(e["impl"] == "flash" for e in said
               if e["kind"] == "attention_path"), said
    # both head counts went through the kernels
    assert {e["q_shape"][2] for e in said
            if e["kind"] == "attention_path"} == {48, 64}
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= 464_541_696 * 4
    assert memory.temp_size_in_bytes < 4 * 2 ** 30


# ----------------------------------------------------------------------
# the block-diffusion core through the kernels on a static tile map (PR 41)
# ----------------------------------------------------------------------
#: a float32 array that holds the scores of a whole doubled row, or of
#: one of the plain path's blocks of it
BD_SCORES = re.compile(
    r"f32\[(?:1,)?(?:32|4,8),(?:512|1024|2048|4096|8192),(?:\d{4})\]")


@pytest.mark.parametrize("precision", [None, "highest"],
                         ids=["default", "highest"])
def test_block_diffusion_kernels_compile_for_v5e_at_the_cells_shape(
        chip, precision):
    """The three kernels under their names at ``sdar_bd_k2_t4096``'s
    shape (a doubled row of 8,192 positions, 32 query heads over 4
    key-value heads of 128, blocks of 4), with bfloat16 operands and
    with float32 operands contracted in full: what Mosaic refuses (the
    scalar-prefetched tables, the index maps that read them, VMEM) fails
    here.  No operation of the program holds a row's scores."""
    import contextlib
    specs = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)
             for shape in ((1, 8192, 32, 128), (1, 8192, 4, 128),
                           (1, 8192, 4, 128))]

    def backward(q, k, v):
        return jax.grad(lambda *a: jnp.sum(
            pa.block_diffusion_flash_attention(*a, 4, interpret=False)),
            argnums=(0, 1, 2))(q, k, v)

    pa.drain_attention_events()
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        compiled = jax.jit(backward).lower(*specs).compile()
    text = compiled.as_text()
    for name in (pa.BD_FWD_NAME, pa.BD_DQ_NAME, pa.BD_DKV_NAME):
        assert name in text, name
    assert not BD_SCORES.search(text)
    said = {e["kind"]: e for e in pa.drain_attention_events()}
    assert said["attention_path"]["impl"] == "flash"
    assert (said["attn_tiles"]["tiles_run"],
            said["attn_tiles"]["tiles_total"]) == (80, 256)


def test_block_diffusion_step_compiles_for_v5e_with_the_core_in_the_kernels(
        chip, monkeypatch):
    """One local step of ``sdar_bd_k2_t4096`` at published widths (a
    4,096-id row doubled, ``remat``) with the kernel path steered on,
    traced as the check program is: the three block-diffusion kernels
    and the expert kernels are in the program under their names, and no
    operation holds a whole row's scores."""
    import functools

    import yaml
    from jax._src import config as jax_config

    from msrflute_tpu.models import make_task, token_blocks
    from msrflute_tpu.ops import moe
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    monkeypatch.setattr(
        token_blocks, "block_diffusion_attention", functools.partial(
            token_blocks.block_diffusion_attention, interpret=False))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "experiments", "sdar_moe",
                           "config.yaml")) as fh:
        mc = yaml.safe_load(fh)["model_config"]
    task = make_task({**mc, "remat": True})
    shapes = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        shapes)
    row = (1, mc["seq_len"])
    batch = {"x": jax.ShapeDtypeStruct(row, jnp.int32, sharding=chip),
             **{key: jax.ShapeDtypeStruct(row, jnp.float32, sharding=chip)
                for key in ("tok_mask", "bd_mask", "bd_weight")},
             "sample_mask": jax.ShapeDtypeStruct((1,), jnp.float32,
                                                 sharding=chip)}

    def step(p, b):
        loss, grads = jax.value_and_grad(
            lambda q: task.loss(q, b, None, True)[0])(p)
        return jax.tree.map(lambda a, g: a - 0.1 * g, p, grads), loss

    pa.drain_attention_events()
    with jax.default_matmul_precision("highest"), \
            jax_config.exec_time_optimization_effort(-1.0):
        compiled = jax.jit(step).lower(params, batch).compile()
    text = compiled.as_text()
    for name in (pa.BD_FWD_NAME, pa.BD_DQ_NAME, pa.BD_DKV_NAME,
                 moe.GMM_NAME):
        assert name in text, name
    assert not BD_SCORES.search(text)
    said = pa.drain_attention_events()
    assert {e["kind"] for e in said} == {"attention_path", "attn_tiles"}
    assert all(e["impl"] == "flash" for e in said
               if e["kind"] == "attention_path"), said
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= 456_346_624 * 4
    assert memory.temp_size_in_bytes < 4 * 2 ** 30


@pytest.mark.parametrize("live_taps_only", [True, False],
                         ids=["live_tap_conv", "plain_conv"])
def test_layer4_kernel_stack_is_not_moved_whole_on_v5e(chip,
                                                       live_taps_only):
    """ResNet-18's last stage at 32x32 inputs, as the fill cell's round
    sees it: 50 clients' copies of one 3x3 512->512 kernel over a 1x1
    map, local SGD steps in a scan.  Through ``ops/conv.py`` the compiled
    loop has no operation that only moves the 472 MB stack (the
    gradient's zero ``pad`` becomes the weight-gradient product's own
    padding, fused with the update); the plain call reverses it for the
    backward pass, every step."""
    from jax import lax

    from msrflute_tpu.ops.conv import live_tap_conv
    fn = live_tap_conv if live_taps_only else lax.conv_general_dilated
    clients, stack = 50, 50 * 3 * 3 * 512 * 512

    def loss(w, x):
        y = fn(x, w, (1, 1), [(1, 1), (1, 1)],
               dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.sum(y ** 2)

    def client(w, x):
        def step(w, _):
            return w - 0.1 * jax.grad(loss)(w, x), None
        return lax.scan(step, w, None, length=5)[0]

    def cohort(w0, x):
        w = jnp.broadcast_to(w0, (clients,) + w0.shape)
        return jnp.mean(jax.vmap(client)(w, x) - w0, axis=0)

    w0 = jax.ShapeDtypeStruct((3, 3, 512, 512), jnp.float32, sharding=chip)
    x = jax.ShapeDtypeStruct((clients, 20, 1, 1, 512), jnp.float32,
                             sharding=chip)
    text = jax.jit(cohort).lower(w0, x).compile().as_text()
    movers = []
    for line in text.splitlines():
        found = re.match(r"\s*%\S+ = \w+\[([\d,]+)\]\S* "
                         r"(reverse|copy|transpose)\(", line)
        if found and np.prod([int(n) for n in
                              found.group(1).split(",")]) == stack:
            movers.append(found.group(2))
    assert bool(movers) == (not live_taps_only), movers


@pytest.mark.parametrize("carry", ["windows", "whole_leaves"])
def test_local_steps_loop_makes_no_pass_over_layer4_stacks_on_v5e(
        chip, carry, monkeypatch):
    """The fill cell's cohort step (ResNet-18+GN at 32x32, 50 clients,
    five local SGD steps of 20 rows) compiled for a described v5e.  With
    the local-steps loop carrying layer4's kernels as their live windows
    (``engine/client_update.py``) no fusion and no copy anywhere in the
    program writes a ``[50, 3, 3, 512, 512]`` or ``[50, 3, 3, 256, 512]``
    stack: the update runs over ``[50, 1, 1, 512, 512]``, and the
    pseudo-gradient's padding is read by the sum over clients.  With the
    whole leaves carried, each step has the update fusions and the
    loop-carry copies the ledger names."""
    from msrflute_tpu.config import OptimizerConfig
    from msrflute_tpu.engine import client_update as cu
    from msrflute_tpu.models.resnet import make_resnet_task
    if carry == "whole_leaves":
        monkeypatch.setattr(cu, "zero_grad_is_noop", lambda cfg: False)
    task = make_resnet_task({"num_classes": 100, "image_size": 32})
    update = cu.build_client_update(
        task, OptimizerConfig(type="sgd", lr=0.1), cu.ClientHParams())
    clients, steps, rows = 50, 5, 20

    def cohort(params, arrays, mask, lr, rngs):
        pseudo, loss, _, stats = jax.vmap(
            update, in_axes=(None, 0, 0, None, 0))(
            params, arrays, mask, lr, rngs)
        mean = jax.tree.map(lambda g: jnp.mean(g, axis=0), pseudo)
        return jax.tree.map(jnp.subtract, params, mean), loss, stats

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree.map(
        lambda a: on_chip(a.shape, a.dtype),
        jax.eval_shape(task.init_params, jax.random.PRNGKey(0)))
    text = jax.jit(cohort).lower(
        params,
        {"x": on_chip((clients, steps, rows, 32, 32, 3)),
         "y": on_chip((clients, steps, rows), jnp.int32)},
        on_chip((clients, steps, rows)), on_chip(()),
        on_chip((clients, 2), jnp.uint32)).compile().as_text()
    passes = re.findall(
        r"^\s*%(\S+) = f32\[50,3,3,(?:512|256),512\]\S* (?:fusion|copy)\(",
        text, flags=re.M)
    if carry == "windows":
        assert not passes, passes
        assert re.search(r"= f32\[50,1,1,512,512\]\S* fusion\(", text)
    else:
        assert sum(name.startswith("add_select_fusion")
                   for name in passes) == 4, passes
