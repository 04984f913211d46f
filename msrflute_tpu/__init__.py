"""msrflute_tpu — a TPU-native federated-learning simulation framework.

A brand-new, single-controller JAX/XLA framework with the capabilities of
microsoft/msrflute (FLUTE): large-scale federated-learning simulation with
per-client local SGD producing pseudo-gradients, weighted server-side
aggregation (FedAvg / FedProx / DGA / FedLabels), differential privacy with
RDP accounting, gradient quantization, personalization, checkpoint/resume and
a plugin model/dataset zoo.

Architecture (contrast with the reference, see SURVEY.md):

- FLUTE runs one Server process (rank 0) and N-1 Worker processes that
  exchange tensors through a hand-rolled opcode protocol over
  ``torch.distributed`` P2P (reference ``core/federated.py:20-145``).
  Here there is **no message protocol at all**: a round is a single jitted
  SPMD program over a ``jax.sharding.Mesh``.  The round's sampled clients
  are a leading array axis sharded over the mesh's ``clients`` axis; the
  per-client local-SGD loop is a ``lax.scan``; client parallelism is
  ``vmap`` inside ``shard_map``; aggregation is a weighted ``psum`` riding
  ICI/DCN instead of NCCL sends.
- The Python controller keeps only host-side orchestration: client
  sampling, data staging, checkpointing, logging, LR plateau decisions —
  exactly the data-dependent parts FLUTE also keeps out of its hot loop.

Package map:

- :mod:`msrflute_tpu.config`      — typed config tree + schema validation
  (parity with reference ``core/config.py`` / ``core/schema.py``).
- :mod:`msrflute_tpu.data`        — user-blob datasets (json/hdf5), padded
  fixed-shape batching (replaces torch DataLoaders + DynamicBatchSampler).
- :mod:`msrflute_tpu.models`      — flax model zoo + ``BaseTask`` contract
  (parity with ``core/model.py`` + ``experiments/*/model.py``).
- :mod:`msrflute_tpu.engine`      — client update fn, round engine, eval,
  checkpointing (parity with ``core/client.py``, ``core/server.py``,
  ``core/trainer.py``, ``core/evaluation.py``).
- :mod:`msrflute_tpu.strategies`  — FedAvg / DGA / FedLabels aggregators
  (parity with ``core/strategies/``).
- :mod:`msrflute_tpu.privacy`     — DP mechanisms, RDP accountant, attack
  metrics (parity with ``extensions/privacy``).
- :mod:`msrflute_tpu.ops`         — quantization & fused kernels (Pallas)
  (parity with ``extensions/quantization``).
- :mod:`msrflute_tpu.optim`       — optimizer / LR-scheduler factories
  (parity with ``utils/utils.py:27-224`` + ``utils/optimizers/``).
- :mod:`msrflute_tpu.parallel`    — mesh construction, sharding specs,
  collective helpers (replaces ``core/federated.py``).
- :mod:`msrflute_tpu.rl`          — RL meta-aggregator (parity with
  ``extensions/RL``).
"""

__version__ = "0.1.0"
