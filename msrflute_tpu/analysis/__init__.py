"""fluteguard — TPU-safety static analysis for msrflute_tpu.

Nineteen checkers on one interprocedural engine, one CLI::

    python -m msrflute_tpu.analysis msrflute_tpu/     # or: tools/flint

Since flint v2 the checkers share a project-wide call graph with
per-function def-use summaries (``core.py``: :class:`~.core.Project`,
mtime-keyed summary caching), so rules reason ACROSS modules — a traced
body's helper in another file, a round path's fetch three calls deep.

- **host-sync**        implicit device->host syncs in hot-path modules
  (``engine/``, ``ops/``, ``strategies/``); the flatpack packed-stats
  fetch is the single sanctioned per-round transfer.  Taint seeding
  follows jitted bindings across modules.
- **donation-aliasing** reads of a buffer after ``donate_argnums``
  handed it to a dispatch.
- **jit-purity**       side effects / host-state reads inside traced
  function bodies (project-wide reachability: a helper imported into a
  traced body is checked in its own module).
- **pallas-shape**     TPU tile alignment of kernel block shapes and
  tracer-dependent Python loop bounds.
- **put-loop**         per-leaf ``jax.device_put`` loops in hot-path
  modules; since PR 6 the dispatch inputs cross as one staged buffer
  per dtype group (``engine/round.py::_dispatch_staged``).
- **schema-drift**     ``schema.py`` vs ``config.py`` vs docs
  cross-consistency.
- **shard-ready**      cohort-axis host logic that would break under a
  mesh-sharded client axis (ROADMAP item 1 de-risking): host
  iteration/indexing over the leading client dim of device values,
  ``.shape[0]``-conditioned branches inside traced bodies.
- **recompile-hazard** the static counterpart of the PR 7 runtime
  recompile sentinel: data-derived values in static-arg positions,
  traced closures over mutable self-state, data-dependent operand
  shapes at jitted call sites.
- **transfer-budget**  the one-fetch-per-round invariant, proven on the
  call graph: explicit ``device_get`` sites reachable from each round
  root, flagged when a round-path function splits its fetch or fetches
  in a loop.
- **guard-matrix**     the host_orchestrated/robust/bucketing/secagg/
  fused-carry refusal matrix cross-checked against ``schema.py``
  bespoke checks and ``docs/config_extensions.md``.
- **event-schema**     telemetry event names and devbus publishers
  emitted by the code vs ``docs/observability.md``'s catalogue.
- **signal-safety**    nothing reachable from a ``signal.signal``
  handler may acquire a lock, do file IO, log or block (the PR 4
  telemetry-flush deadlock class); the deferred-flush pattern (work
  gated on a ``*_from_signal`` flag) is recognized as the blessed fix.
- **lock-discipline**  consistent lock acquisition order project-wide;
  no blocking call, file IO or ``device_get`` while holding a hot-path
  lock (Tracer, dataset cache, checkpoint condition); explicit
  acquire without release.
- **thread-escape**    mutable state handed across a thread boundary
  (``threading.Thread`` roots closed over the call graph) without a
  snapshot/copy — the PR 1 torn-snapshot class; anonymous ``Thread``
  spawns in hot paths flag too (telemetry attributes by thread name).
- **atomic-write**     durable artifacts (checkpoints, scorecard,
  baseline, status log) must use tmp + ``os.replace`` or hardlink
  rotation; bare ``open(path, "w")`` and bare ``os.rename`` of a
  committed slot flag, append-only JSONL streams stay silent.
- **mesh-axis**        collectives and ``P(...)`` specs in the modules
  that own the mesh must name the canonical axis constants
  (``CLIENTS_AXIS``/``MODEL_AXIS``); bare string axis literals flag.
- **shard-locality**   the vmapped/scanned per-lane body of a round
  program must be collective-free (closures from every vmap/scan
  root), and ``shard_map`` carry-table gathers must show block-local
  evidence (the ``axis_index`` conversion idiom, a ``mode="drop"``
  sentinel scatter, or shard-local bindings).
- **spec-drift**       the page pool's slot axis must shard over the
  clients mesh axis: replicated pool-spec bindings, replicated pool
  ``device_put``s (inline or through a named spec) and UNSHARDED pool
  puts in ``engine/`` flag (subsumes shard-ready's old
  replicated-pool check).
- **collective-budget** each round program's collective sites pinned
  both ways against docs/architecture.md's "Collective budget"
  paragraph — extra code sites flag with their round-root path, stale
  doc entries flag at the doc line.

Static findings pair with a runtime strict mode: under
``MSRFLUTE_STRICT_TRANSFERS=1`` the server round loop runs inside a
``jax.transfer_guard_device_to_host("disallow")`` scope
(``utils/strict.py``), so any implicit sync the linter's static view
cannot see raises at the offending line in e2e tests.

Suppression: ``# flint: disable=RULE reason`` (linted for staleness;
unknown rule names are errors, with rename hints from
``core.RULE_RENAMES``).  Baseline: ``analysis/baseline.json`` (shipped
empty; the tier-1 gate ``tests/test_flint_clean.py`` fails on any
non-baselined finding).
"""

from .core import (RULE_RENAMES, RULES, Finding, analyze,  # noqa: F401
                   default_baseline_path, filter_baseline, load_baseline,
                   write_baseline)
