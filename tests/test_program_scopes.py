"""The scope map the program writes when it launches a program while a
tracer is attached (``telemetry/compiles.py``): the catalogue on the
compiled text of every round-program builder, the nesting, the stale
rule through a real compile cache, and that nothing runs with tracing
off.  Counts and structure only: nothing here is a time."""

import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "benchmarks"))

from harness_tiny_cell import harness, tiny_root  # noqa: E402,F401
from msrflute_tpu.config import FLUTEConfig, OptimizerConfig  # noqa: E402
from msrflute_tpu.data import pack_round_batches  # noqa: E402
from msrflute_tpu.engine.client_update import (ClientHParams,  # noqa: E402
                                               build_client_update)
from msrflute_tpu.engine.round import RoundEngine  # noqa: E402
from msrflute_tpu.models import make_task  # noqa: E402
from msrflute_tpu.strategies import select_strategy  # noqa: E402
from msrflute_tpu.telemetry import compiles  # noqa: E402
from msrflute_tpu.telemetry.spans import Tracer  # noqa: E402


def _spans(out_dir, name="program_scopes"):
    with open(os.path.join(str(out_dir), "events.jsonl")) as fh:
        records = [json.loads(line) for line in fh]
    return [r for r in records if r["kind"] == "span" and r["name"] == name]


@pytest.fixture
def traced(tmp_path):
    """A tracer attached to the compile spans, as a telemetry scope
    does it; detached and closed afterwards."""
    spans = compiles.install()
    tracer = Tracer(str(tmp_path / "telemetry"))
    spans.attach(tracer)
    try:
        yield tracer
    finally:
        spans.detach(tracer)
        tracer.close()


# ----------------------------------------------------------------------
# the parse
# ----------------------------------------------------------------------
@pytest.mark.parametrize("path, want", [
    ("jit(staged)/round_aggregate/client_steps/while/body/mla_proj/dot",
     ["round_aggregate", "client_steps", "mla_proj"]),
    # a backward operation's path still holds its scope
    ("jit(staged)/round_aggregate/client_steps/while/body/"
     "transpose(jvp(mla_attn_core))/pallas_call",
     ["round_aggregate", "client_steps", "mla_attn_core"]),
    # so does a rematerialised one's
    ("jit(f)/client_steps/checkpoint/rematted_computation/gqa_proj/mul",
     ["client_steps", "gqa_proj"]),
    ("jit(staged)/round_aggregate/vmap(quant_select)/while/body/reduce_sum",
     ["round_aggregate", "quant_select"]),
    # a function's own name is not a scope; nor is a transformation's
    ("jit(embed)/jit(main)/transpose(jvp())/dot_general", []),
    ("jit(staged)/add", []),
])
def test_the_scopes_of_a_path(path, want):
    assert compiles.scopes_in(path) == want


HLO = textwrap.dedent('''\
    HloModule jit_staged, is_scheduled=true, entry_computation_layout={()->f32[]}

    %fused_computation.1 (p: f32[8]) -> f32[8] {
      %p = f32[8]{0} parameter(0)
      ROOT %multiply.9 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(staged)/round_aggregate/client_steps/lm_head_loss/mul"}
    }

    %body.2 (arg: f32[8]) -> f32[8] {
      %arg = f32[8]{0} parameter(0)
      %copy.8 = f32[8]{0} copy(%arg)
      ROOT %fusion.3 = f32[8]{0} fusion(%arg), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(staged)/round_aggregate/client_steps/while/body/transpose(jvp(mla_proj))/mul"}
    }

    ENTRY %main.5 (x: f32[8]) -> f32[8] {
      %x = f32[8]{0} parameter(0)
      %copy.7 = f32[8]{0} copy(%x)
      %while.4 = f32[8]{0} while(%copy.7), condition=%cond.1, body=%body.2, metadata={op_name="jit(staged)/round_aggregate/client_steps/while"}
      ROOT %add.6 = f32[8]{0} add(%while.4, %x), metadata={op_name="jit(staged)/round_aggregate/add"}
    }
    ''')


def test_a_compiled_text_becomes_a_map():
    program = compiles.parse_program(HLO)
    assert program["module"] == "jit_staged"
    # a loop body's instructions are the device's operations; a fusion's
    # own computation is not (the device runs the fusion), but counts
    # for which scopes the executable has at all.  A copy the compiler
    # put into the loop's body has no path and takes the loop's scopes;
    # one outside every loop stays outside every scope
    assert program["scopes"] == {
        "arg": "round_aggregate/client_steps",
        "copy.8": "round_aggregate/client_steps",
        "fusion.3": "round_aggregate/client_steps/mla_proj",
        "x": "", "copy.7": "",
        "while.4": "round_aggregate/client_steps",
        "add.6": "round_aggregate"}
    assert program["present"] == {"round_aggregate", "client_steps",
                                  "mla_proj", "lm_head_loss"}
    lowered = ('#loc7 = loc("jit(staged)/round_aggregate/client_steps/'
               'embed/gather"(#loc3))\n#loc8 = loc("x")')
    assert compiles.lowering_scopes(lowered) == {
        "round_aggregate", "client_steps", "embed"}


def test_the_catalogue_is_the_documented_one():
    with open(os.path.join(REPO, "docs", "observability.md")) as fh:
        doc = fh.read()
    section = doc.split("## Named scopes", 1)[1].split("\n## ", 1)[0]
    documented = set(re.findall(r"^\| `(\w+)` \|", section, flags=re.M))
    assert documented == set(compiles.SCOPES)


# ----------------------------------------------------------------------
# (a) the catalogue on the compiled text: the engine's builders
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_engine(tiny_root):  # noqa: F811
    """The tiny cell's round engine (the CNN under DGA, DP noise and
    8-bit quantisation at three clients) and what a dispatch needs."""
    cell = harness.load_cell(tiny_root, "tiny_cell")
    cfg = FLUTEConfig.from_dict(harness.build_config(cell, False, None))
    task = make_task(cfg.model_config)
    from msrflute_tpu.parallel.mesh import make_mesh
    engine = RoundEngine(task, cfg, select_strategy(cfg.strategy)(cfg, None),
                         make_mesh(num_devices=1))
    rng = np.random.default_rng(0)
    users = [{"x": rng.normal(size=(8, 28, 28, 1)).astype(np.float32),
              "y": rng.integers(0, 62, size=8)} for _ in range(3)]
    from msrflute_tpu.data.dataset import ArraysDataset
    dataset = ArraysDataset([f"u{i}" for i in range(3)], users)
    batches = [pack_round_batches(dataset, [0, 1, 2], 4, 2,
                                  rng=np.random.default_rng(i))
               for i in range(2)]
    return engine, batches


def _chains(out_dir):
    """``{module file: set of scope chains}`` of the maps written."""
    found = {}
    for path in sorted(glob.glob(os.path.join(str(out_dir), "programs",
                                              "*.json"))):
        with open(path) as fh:
            found[os.path.basename(path)] = set(
                json.load(fh)["scopes"].values())
    return found


@pytest.mark.parametrize("builder", ["staged_one_round",
                                     "staged_scanned_rounds", "payload"])
def test_every_round_builder_carries_the_engine_scopes(tiny_engine, traced,
                                                       builder):
    engine, batches = tiny_engine
    state = engine.init_state(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)
    if builder == "payload":
        engine.client_payloads(state, batches[0], 0.1, rng)
    else:
        rounds = 1 if builder == "staged_one_round" else 2
        engine.dispatch_rounds(state, batches[:rounds], [0.1] * rounds,
                               [1.0] * rounds, rng,
                               quant_thresholds=[0.5] * rounds)
    traced.flush()
    spans = _spans(traced.out_dir)
    assert len(spans) == 1 and not spans[0]["stale"], spans
    assert spans[0]["ops"] > spans[0]["scoped"] > 0
    assert os.path.isabs(spans[0]["file"])
    (chains,) = _chains(traced.out_dir).values()
    # the local steps lie inside the round's scope, the threshold's
    # selection too, and nothing of the catalogue lies outside it
    assert "round_aggregate/client_steps" in chains
    assert "round_aggregate" in chains
    assert all(c == "" or c.startswith("round_aggregate") for c in chains)
    if builder != "payload":  # the payload program quantises nothing
        assert "round_aggregate/quant_select" in chains
    # a second launch of the same program writes nothing more
    if builder == "staged_one_round":
        engine.dispatch_rounds(engine.init_state(jax.random.PRNGKey(0)),
                               batches[:1], [0.1], [1.0], rng,
                               quant_thresholds=[0.5])
        traced.flush()
        assert len(_spans(traced.out_dir)) == 1


# ----------------------------------------------------------------------
# (a) the catalogue on the compiled text: the token models
# ----------------------------------------------------------------------
def _tiny(model):
    if model == "LFM2_MOE":
        from test_lfm2_moe import TINY, _weights
    else:
        from test_mla_moe import TINY, _weights
    return TINY, _weights


MODEL_SCOPES = {
    "LFM2_MOE": {"embed", "short_conv", "gqa_proj", "gqa_attn_core",
                 "dense_ffn", "routed_experts", "lm_head_loss"},
    "MLA_MOE": {"embed", "mla_proj", "mla_attn_core", "dense_ffn",
                "shared_expert", "routed_experts", "lm_head_loss"},
}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("model", sorted(MODEL_SCOPES))
def test_every_model_scope_lies_in_the_local_steps(model, remat):
    """The client update of both token models at test size, lowered and
    compiled: every model-level scope of the catalogue is there, forward
    and backward (under ``remat`` the recomputed forward too), and each
    lies inside ``client_steps``."""
    tiny, weights = _tiny(model)
    task = make_task({**tiny, "remat": remat})
    update = build_client_update(
        task, OptimizerConfig(type="sgd", lr=0.1), ClientHParams())
    ids = np.random.default_rng(4).integers(1, tiny["vocab_size"],
                                            size=(2, 1, 17))
    text = jax.jit(update).lower(
        weights(), {"x": jnp.asarray(ids, jnp.int32)},
        jnp.ones((2, 1), jnp.float32), jnp.float32(0.1),
        jax.random.PRNGKey(0)).compile().as_text()
    program = compiles.parse_program(text)
    assert program["present"] == MODEL_SCOPES[model] | {"client_steps"}
    chains = set(program["scopes"].values())
    for scope in MODEL_SCOPES[model]:
        assert f"client_steps/{scope}" in chains, (scope, sorted(chains))
    assert all(c in ("", "client_steps") or c.startswith("client_steps/")
               for c in chains), sorted(chains)
    # the paths of backward operations hold their scope too
    backward = {compiles.scopes_in(path)[-1]
                for path in re.findall(r'op_name="([^"]*)"', text)
                if "transpose(" in path and compiles.scopes_in(path)}
    assert MODEL_SCOPES[model] <= backward | {"embed"}, backward


# ----------------------------------------------------------------------
# (b) a scope the compile cache has never seen: the map says it is stale
# ----------------------------------------------------------------------
STALE_PROBE = textwrap.dedent('''
    import json, logging, os, sys
    import jax, jax.numpy as jnp
    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    sys.path.insert(0, sys.argv[4])
    from msrflute_tpu.telemetry import compiles
    from msrflute_tpu.telemetry.spans import Tracer
    logging.basicConfig(level=logging.WARNING)

    def f(x):
        with jax.named_scope(sys.argv[3]):
            return jnp.tanh(x @ x)

    spans = compiles.install()
    tracer = Tracer(sys.argv[2])
    spans.attach(tracer)
    jitted, x = jax.jit(f), jnp.ones((64, 64))
    before = compiles.programs_before(jitted)
    jitted(x)
    compiles.program_scopes(jitted, before, (x,))
    spans.detach(tracer)
    tracer.close()
''')


def test_a_scope_the_cache_never_saw_makes_the_map_stale(tmp_path):
    """The persistent cache keys a program without its metadata: compiled
    under one scope, traced under another in a new process, the
    executable still says the old path.  The span says so, the cure is
    logged, and the reader gives nothing."""
    from benchmarks import scope_times
    cache = tmp_path / "cache"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    runs = {}
    for name, scope in (("first", "quant_select"), ("second", "embed")):
        out = tmp_path / name
        done = subprocess.run(
            [sys.executable, "-c", STALE_PROBE, str(cache), str(out), scope,
             REPO], env=env, capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr
        (span,) = _spans(out)
        (compile_span,) = [s for s in _spans(out, "compile")
                           if s["fun_name"] == "jit(f)"]
        runs[name] = (span, compile_span, done.stderr, out)
    span, compile_span, _, _ = runs["first"]
    assert compile_span["cache"] == "miss"
    assert not span["stale"] and span["missing"] == []
    span, compile_span, stderr, out = runs["second"]
    assert compile_span["cache"] == "hit"   # the old entry, by its key
    assert span["stale"] and span["missing"] == ["embed"]
    assert "JAX_COMPILATION_CACHE_DIR" in stderr and "embed" in stderr
    with open(span["file"]) as fh:
        written = json.load(fh)
    assert written["stale"] and "quant_select" in written["scopes"].values()
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_f(7)", 0.0, 100.0]]},
        {"name": "XLA Ops", "events": [
            [op, 10.0 * i, 5.0] for i, op in enumerate(written["scopes"])
        ]}]}]}
    pattern = re.compile("^jit_f")
    assert scope_times.scope_table(
        trace, scope_times.load_maps(str(out)), pattern=pattern) is None
    # the first run's map, which is sound, reads
    table = scope_times.scope_table(
        trace, scope_times.load_maps(str(runs["first"][3])), pattern=pattern)
    assert table is not None and "quant_select" in table["scopes"]


# ----------------------------------------------------------------------
# (e) with no tracer attached nothing runs
# ----------------------------------------------------------------------
def test_with_tracing_off_nothing_is_lowered_and_nothing_written(
        tmp_path, monkeypatch):
    sys.path.insert(0, HERE)
    from test_telemetry_contract import _cfg, _run
    calls = {"lower": 0, "compile": 0, "parse": 0}
    real = compiles.parse_program

    def counted(text):
        calls["parse"] += 1
        return real(text)

    monkeypatch.setattr(compiles, "parse_program", counted)
    import jax.stages
    real_lower = jax.stages.Traced.lower

    def counted_lower(self, *args, **kwargs):
        calls["lower"] += 1
        return real_lower(self, *args, **kwargs)

    monkeypatch.setattr(jax.stages.Traced, "lower", counted_lower)
    real_compile = jax.stages.Lowered.compile

    def counted_compile(self, *args, **kwargs):
        calls["compile"] += 1
        return real_compile(self, *args, **kwargs)

    monkeypatch.setattr(jax.stages.Lowered, "compile", counted_compile)
    # off: no telemetry block at all
    server, state = _run(_cfg(0), tmp_path / "off")
    assert state.round == 6
    assert calls == {"lower": 0, "compile": 0, "parse": 0}
    assert not glob.glob(str(tmp_path / "off" / "**" / "programs"),
                         recursive=True)
    # on: the round program's first launch, once; the same through
    # telemetry.xla's ahead-of-time wrapper, whose own lower().compile()
    # is the program's compile and is counted here too
    for xla, own in ((False, 0), (True, 1)):
        calls.update(lower=0, compile=0, parse=0)
        out = tmp_path / f"on_xla_{xla}"
        server, state = _run(
            _cfg(0, telemetry={"enable": True, "xla": xla}), out)
        server.scope.close()
        assert calls == {"lower": 1 + own, "compile": 1 + own, "parse": 1}
        (span,) = _spans(out / "telemetry")
        assert span["module"] == "jit_staged" and not span["stale"]
        # that one lower().compile() came from jax's in-memory caches:
        # no compile request reached the backend while the map was made
        inside = [s for s in _spans(out / "telemetry", "compile")
                  if s["ts"] + s["dur_s"] >= span["ts"] and
                  s["ts"] <= span["ts"] + span["dur_s"]]
        assert inside == []
