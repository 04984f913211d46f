"""A token-sequence configuration added to the benchmark as files only
(``data/seq_root``: a configuration with ``data.generator: tokens``, a
traffic mix, a cell, a control and a plain causal transformer whose loss
is the plain next-token loss; nothing under ``benchmarks/`` knows it),
through ``harness.run_cell`` on the CPU: sound is correct, the
lower-precision control and two planted faults are not, and the harness
keeps nothing on the device beside the program."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402

ROOT = os.path.join(HERE, "data", "seq_root")
CELL = "tiny_seq_cell"
SEED = 2 ** 31 + 54321


def _verdicts(result):
    return {v["name"]: v for v in result["compared"]}


def _device_arrays(run) -> list:
    """Names of what ``run`` references that lives on a device (the
    program's own server aside)."""
    import jax
    found = []

    def visit(value, where, depth=0):
        if isinstance(value, jax.Array):
            found.append(where)
        elif isinstance(value, dict) and depth < 6:
            for key, item in value.items():
                visit(item, f"{where}[{key!r}]", depth + 1)
        elif isinstance(value, (list, tuple)) and depth < 6:
            for i, item in enumerate(value):
                visit(item, f"{where}[{i}]", depth + 1)

    for name, value in vars(run).items():
        if name != "server":
            visit(value, name)
    return found


def test_the_root_is_new_files_only():
    for sub, _, files in os.walk(ROOT):
        for name in files:
            twin = os.path.join(harness.BENCH_DIR,
                                os.path.relpath(sub, ROOT), name)
            # the control is a copy of the benchmark's
            assert name == "bf16.json" or not os.path.exists(twin), twin
    cell = harness.load_cell(ROOT, CELL)
    doc = cell["config_doc"]
    assert doc["data"]["generator"] == "tokens"
    # the model reference is the root's own, the generator the benchmark's
    assert not os.path.exists(os.path.join(
        harness.BENCH_DIR, "reference", f"{doc['reference']['model']}.py"))
    model = harness.find_module(ROOT, "reference", doc["reference"]["model"])
    assert callable(model.loss) and not hasattr(model, "sample_count")
    assert harness.load_generator(ROOT, doc["data"]).__file__.startswith(
        harness.BENCH_DIR)
    with pytest.raises(FileNotFoundError):
        harness.find_module(ROOT, "reference", "no_such_model")


@pytest.fixture(scope="module")
def sound():
    """One sound run, watched: what the ``Run`` object references on a
    device at the two moments the harness's footprint is stated for."""
    moments = {}
    check, fence = harness.Run.run_check_program, harness.Run.on_fence

    def watched_check(run, *args, **kwargs):
        check(run, *args, **kwargs)
        moments["after_check_program"] = _device_arrays(run)
        moments["kept"] = sorted(run.check)

    def watched_fence(run, out, rounds):
        first = not run.fences
        fence(run, out, rounds)
        if first:
            moments["after_first_fence"] = _device_arrays(run)
            moments["arrays_copied"] = sorted(run.first_rounds[0])

    harness.Run.run_check_program = watched_check
    harness.Run.on_fence = watched_fence
    try:
        result = harness.run_cell(CELL, SEED, 0.2, False, root=ROOT)
    finally:
        harness.Run.run_check_program = check
        harness.Run.on_fence = fence
    return result, moments


def test_sound_sequence_run_is_correct(sound):
    result, moments = sound
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 6
    assert list(result)[-1] == "compared"
    got = _verdicts(result)
    # float32 against float32: rounding only
    assert got["loss_gap"]["value"] < 1e-5
    assert got["timed_later_loss_gap"]["value"] < 1e-5
    assert got["window_compiles"]["value"] == 0
    # the whole packed batch reached the reference (no ``y``: the task
    # and the reference shift ``x``)
    assert moments["arrays_copied"] == [
        "client_lr", "client_mask", "quant_quantile", "sample_mask",
        "server_lr", "tok_mask", "x"]


def test_harness_keeps_no_array_on_the_device(sound):
    """After the check program has returned and after the first fence the
    harness references no ``jax.Array``: beside the program's state the
    device holds at most the one copy of the weights that the first timed
    dispatch makes, and only until that fence."""
    _, moments = sound
    assert moments["after_check_program"] == []
    assert moments["after_first_fence"] == []
    # what it keeps of the check program is on the host
    assert moments["kept"] == ["new_params", "seconds", "stats"]


def test_lower_precision_control_is_not_correct():
    result = harness.run_cell(CELL, SEED + 1, 0.2, False, root=ROOT,
                              control="bf16")
    assert not result["correct"]
    got = _verdicts(result)
    assert not got["loss_gap"]["ok"] or not got["update_diff"]["ok"]


FAULTS = {
    # fault: the number that has to catch it
    "tok_mask_ignored": "timed_loss_gap",
    "targets_one_further": "timed_update_projection_gap",
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_sequence_path_is_not_correct(monkeypatch, fault):
    """The timed program's loss broken where it is produced: it counts the
    padded positions as real (``tok_mask`` ignored), or scores position t
    against the token at t + 2.  The first dispatch call (the check
    program, traced under ``highest``) stays sound; the timed program is a
    trace of its own and takes the fault."""
    import jax.numpy as jnp

    from msrflute_tpu.engine import round as round_mod
    from msrflute_tpu.models.nlp import SequenceLMTask
    real_dispatch = round_mod.RoundEngine.dispatch_rounds
    real_targets = SequenceLMTask._logits_targets
    calls = {"n": 0}

    def counted(engine, *args, **kwargs):
        calls["n"] += 1
        return real_dispatch(engine, *args, **kwargs)

    def broken(task, params, batch):
        logits, targets, weight = real_targets(task, params, batch)
        if calls["n"] < 2:
            return logits, targets, weight
        if fault == "tok_mask_ignored":
            weight = jnp.ones_like(weight) * batch["sample_mask"][:, None]
            return logits, targets, weight
        return logits[:, :-1], targets[:, 1:], weight[:, 1:]

    monkeypatch.setattr(round_mod.RoundEngine, "dispatch_rounds", counted)
    monkeypatch.setattr(SequenceLMTask, "_logits_targets", broken)
    result = harness.run_cell(CELL, SEED + 2, 0.2, False, root=ROOT)
    assert not result["correct"]
    got = _verdicts(result)
    assert not got[FAULTS[fault]]["ok"], got[FAULTS[fault]]
    assert got["loss_gap"]["ok"]  # the check program itself was sound
