"""What a model counts inside its forward pass (``BaseTask.counter_names``,
an expert layer's load): summed over a client's local steps and a round's
live clients on the device, out with the packed round stats (no transfer
of its own: ``MSRFLUTE_STRICT_TRANSFERS=1`` stays clean), and onto the
``host_tail`` span the benchmark's readers see.  Shown on a small model
that counts what can be checked by hand; the expert layer's own counters
are ``tests/test_lfm2_moe.py``'s."""

import json
import os

import jax.numpy as jnp
import pytest

from msrflute_tpu.engine import OptimizationServer
from msrflute_tpu.models import make_task

from test_telemetry_contract import _cfg, _dataset


def _counting_task(cfg):
    task = make_task(cfg.model_config)
    plain = task.loss

    def loss(params, batch, rng=None, train=True):
        value, aux = plain(params, batch, rng, train)
        rows = jnp.sum(batch["sample_mask"])
        return value, {**aux, "counters": {"rows_seen": rows,
                                           "steps_run": jnp.ones(())}}

    task.loss = loss
    task.counter_names = ("rows_seen", "steps_run")
    return task


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["vmap_cohort", "one_client_at_a_time"])
def test_counters_ride_the_packed_stats_onto_the_host_tail_span(
        tmp_path, monkeypatch, chunked):
    monkeypatch.setenv("MSRFLUTE_STRICT_TRANSFERS", "1")
    cfg = _cfg(1, telemetry={"enable": True}, rounds=3)
    if chunked:
        cfg.server_config["clients_per_chunk"] = 1
    server = OptimizationServer(_counting_task(cfg), cfg, _dataset(),
                                model_dir=str(tmp_path), seed=0)
    server.train()
    server.scope.close()
    with open(os.path.join(str(tmp_path), "telemetry",
                           "events.jsonl")) as fh:
        tails = [r for r in map(json.loads, fh)
                 if r.get("kind") == "span" and r["name"] == "host_tail"]
    assert len(tails) == 3
    for span in tails:
        # 4 clients a round, each 8 rows in 2 steps of 4
        assert span["rows_seen"] == 32.0 and span["steps_run"] == 8.0


def test_a_model_without_counters_adds_no_field(tmp_path):
    cfg = _cfg(1, telemetry={"enable": True}, rounds=2)
    server = OptimizationServer(make_task(cfg.model_config), cfg, _dataset(),
                                model_dir=str(tmp_path), seed=0)
    server.train()
    server.scope.close()
    with open(os.path.join(str(tmp_path), "telemetry",
                           "events.jsonl")) as fh:
        tails = [r for r in map(json.loads, fh)
                 if r.get("kind") == "span" and r["name"] == "host_tail"]
    assert tails and all(set(span) <= {
        "kind", "name", "ts", "dur_s", "sid", "parent", "thread", "round0",
        "rounds", "chunk"} for span in tails)
