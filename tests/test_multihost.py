"""Multi-host federated round: two jax.distributed processes, one global
mesh — the DCN-scaling analogue of FLUTE's multi-node
``torch.distributed.run`` rendezvous (``README.md:80-87``).

Each process owns 4 virtual CPU devices; ``jax.distributed.initialize``
glues them into a global 8-device ``clients`` mesh; the round program's
psum crosses the process boundary exactly the way it crosses DCN on a
multi-host TPU slice.  Both controllers must end with identical params.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_multiprocess_supported() -> bool:
    """jax <= 0.4.x raises "Multiprocess computations aren't implemented
    on the CPU backend" the moment a cross-process collective runs, so
    on those toolchains this whole module can only fail — skip it (the
    DCN path it exercises needs either a newer jaxlib or real TPU
    hosts)."""
    import jax
    major, minor = (int(x) for x in jax.__version__.split(".")[:2])
    return (major, minor) >= (0, 5)


pytestmark = pytest.mark.skipif(
    not _cpu_multiprocess_supported(),
    reason="multiprocess CPU collectives unsupported on this jax")

WORKER = r"""
import os, sys
import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=sys.argv[1],
    num_processes=2, process_id=int(sys.argv[2]))
assert jax.device_count() == 8, jax.device_count()
assert jax.process_count() == 2

sys.path.insert(0, {repo!r})
from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.data import ArraysDataset, pack_round_batches
from msrflute_tpu.engine.round import RoundEngine
from msrflute_tpu.models import make_task
from msrflute_tpu.parallel import make_mesh
from msrflute_tpu.strategies import select_strategy

cfg = FLUTEConfig.from_dict({{
    "model_config": {{"model_type": "LR", "num_classes": 3, "input_dim": 6}},
    "strategy": "fedavg",
    "server_config": {{"max_iteration": 1, "num_clients_per_iteration": 8,
                      "optimizer_config": {{"type": "sgd", "lr": 1.0}}}},
    "client_config": {{"optimizer_config": {{"type": "sgd", "lr": 0.2}},
                      "data_config": {{"train": {{"batch_size": 4}}}}}},
}})
rng = np.random.default_rng(0)
users = [f"u{{i}}" for i in range(8)]
per_user = [{{"x": rng.normal(size=(8, 6)).astype(np.float32),
             "y": rng.integers(0, 3, 8).astype(np.int32)}} for _ in users]
ds = ArraysDataset(users, per_user)

mesh = make_mesh()  # spans both processes: 8 global devices
task = make_task(cfg.model_config)
engine = RoundEngine(task, cfg, select_strategy("fedavg")(cfg, None), mesh)
state = engine.init_state(jax.random.PRNGKey(0))
batch = pack_round_batches(ds, list(range(8)), 4, 2,
                           rng=np.random.default_rng(1), pad_clients_to=8)
state, stats = engine.run_round(state, batch, 0.2, 1.0, jax.random.PRNGKey(2))
leaves = jax.tree.leaves(jax.device_get(state.params))  # replicated
checksum = float(sum(np.abs(l).sum() for l in leaves))
print(f"CHECKSUM {{checksum:.10f}} round {{state.round}}", flush=True)
"""


WORKER_GSPMD = r"""
import os, sys
import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=sys.argv[1],
    num_processes=2, process_id=int(sys.argv[2]))
assert jax.device_count() == 8

sys.path.insert(0, {repo!r})
from msrflute_tpu.config import FLUTEConfig
from msrflute_tpu.data import ArraysDataset, pack_round_batches
from msrflute_tpu.engine.round import RoundEngine
from msrflute_tpu.models import make_task
from msrflute_tpu.parallel import make_mesh
from msrflute_tpu.strategies import select_strategy

# (clients=4, model=2) GLOBAL mesh across the two processes: tensor shards
# of the BERT params live on devices of BOTH hosts — the collectives this
# round runs are exactly the ICI/DCN mix of a real multi-host slice
cfg = FLUTEConfig.from_dict({{
    "model_config": {{"model_type": "BERT", "BERT": {{
        "model": {{"vocab_size": 96, "hidden_size": 32,
                  "num_hidden_layers": 2, "num_attention_heads": 2,
                  "intermediate_size": 64, "max_seq_length": 12,
                  "mlm_probability": 0.25, "mask_token_id": 4}},
        "training": {{"batch_size": 2, "seed": 0}}}}}},
    "strategy": "fedavg",
    "mesh_config": {{"model_axis_size": 2}},
    "server_config": {{"max_iteration": 1, "num_clients_per_iteration": 4,
                      "optimizer_config": {{"type": "sgd", "lr": 1.0}}}},
    "client_config": {{"optimizer_config": {{"type": "adamw", "lr": 0.05}},
                      "data_config": {{"train": {{"batch_size": 2}}}}}},
}})
rng = np.random.default_rng(0)
users = [f"u{{i}}" for i in range(4)]
per_user = [{{"x": rng.integers(5, 96, size=(4, 12)).astype(np.int32)}}
            for _ in users]
ds = ArraysDataset(users, per_user)

mesh = make_mesh(model_axis_size=2)
task = make_task(cfg.model_config)
engine = RoundEngine(task, cfg, select_strategy("fedavg")(cfg, None), mesh)
assert engine.partition_mode == "gspmd"
state = engine.init_state(jax.random.PRNGKey(0))
batch = pack_round_batches(ds, list(range(4)), 2, 2,
                           rng=np.random.default_rng(1), pad_clients_to=4)
state, stats = engine.run_round(state, batch, 0.05, 1.0,
                                jax.random.PRNGKey(2))
leaves = jax.tree.leaves(jax.device_get(state.params))
checksum = float(sum(np.abs(np.asarray(l, np.float64)).sum()
                     for l in leaves))
print(f"CHECKSUM {{checksum:.6f}} round {{state.round}}", flush=True)
"""


WORKER_RING = r"""
import os, sys
import numpy as np
import jax
import jax.numpy as jnp

jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=sys.argv[1],
    num_processes=2, process_id=int(sys.argv[2]))
assert jax.device_count() == 8

sys.path.insert(0, {repo!r})
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from msrflute_tpu.ops.ring_attention import ring_self_attention

# sequence axis spans BOTH processes: rotations 3->4 cross the process
# boundary — the ppermute ride over DCN on a real multi-host slice
mesh = Mesh(np.asarray(jax.devices()), ("sequence",))
B, L, H, D = 2, 32, 2, 8
rng = np.random.default_rng(0)
host = [rng.normal(size=(B, L, H, D)).astype(np.float32) for _ in range(3)]
sharding = NamedSharding(mesh, P(None, "sequence"))
q, k, v = (jax.make_array_from_callback(
    a.shape, sharding, lambda idx, a=a: a[idx]) for a in host)

out = ring_self_attention(q, k, v, mesh, causal=True)
checksum = float(jnp.abs(out).sum())  # cross-host reduce -> replicated

# dense reference on the host (numpy, no devices involved)
qh, kh, vh = host
s = np.einsum("blhd,bmhd->bhlm", qh, kh) / np.sqrt(D)
s = np.where(np.tril(np.ones((L, L), bool))[None, None], s, -np.inf)
p = np.exp(s - s.max(-1, keepdims=True))
p /= p.sum(-1, keepdims=True)
ref = np.einsum("bhlm,bmhd->blhd", p, vh)
assert abs(checksum - np.abs(ref).sum()) < 1e-3 * np.abs(ref).sum(), (
    checksum, float(np.abs(ref).sum()))
print(f"CHECKSUM {{checksum:.6f}} round 0", flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_two_process(tmp_path, worker_src: str) -> None:
    coord = f"127.0.0.1:{_free_port()}"
    script = tmp_path / "worker.py"
    script.write_text(worker_src.format(repo=REPO))
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    procs = [subprocess.Popen(
        [sys.executable, str(script), coord, str(i)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(2)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err[-3000:]
        outs.append(out)
    sums = [line.split()[1] for out in outs for line in out.splitlines()
            if line.startswith("CHECKSUM")]
    assert len(sums) == 2
    assert sums[0] == sums[1], f"processes disagree: {sums}"
    assert float(sums[0]) > 0


def test_two_process_round(tmp_path):
    _run_two_process(tmp_path, WORKER)


def test_two_process_gspmd_round(tmp_path):
    """Tensor-sharded (clients, model) round across two processes: BERT
    params shard over devices of BOTH hosts, so the round's collectives
    mix the clients-axis psum with model-axis all-reduces across the
    process boundary — the full multi-host GSPMD path."""
    _run_two_process(tmp_path, WORKER_GSPMD)


def test_two_process_ring_attention(tmp_path):
    """Sequence-parallel ring attention with the ring spanning two
    processes: the k/v ppermute rotations cross the process boundary (the
    DCN hop of a real slice) and the result must still equal dense
    attention — asserted against a host-side numpy reference inside each
    worker, plus cross-process agreement on the checksum."""
    _run_two_process(tmp_path, WORKER_RING)
