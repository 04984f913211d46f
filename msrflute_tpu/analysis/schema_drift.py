"""schema-drift — schema.py vs config.py vs docs, cross-checked.

The config surface lives in three places that historically desync:
``schema.py`` (the validation vocabulary), ``config.py`` (the dataclass
defaults), and the operator docs.  A key present in one but not the
others is a silent failure: the dataclass accepts it while validation
rejects it (or validation accepts a knob nothing reads), and an
operator copies a documented knob the schema meanwhile dropped.

Checks (all literal-extraction — no imports of the checked modules):

1. every dataclass field of ``ServerConfig`` / ``ClientConfig`` /
   ``DatasetConfig`` (minus the ``extra`` catch-all and private names)
   appears in the matching ``*_KEYS`` set in schema.py;
2. every key in ``SERVER/CLIENT/DATASET_FIELD_SPECS`` appears in the
   matching ``*_KEYS`` set (a type rule for an unknown key is dead);
3. every ``server_config.X`` / ``client_config.X`` dotted mention in
   ``docs/*.md`` + ``README.md`` names a key the schema knows;
4. the TPU-native operator knobs in :data:`DOCUMENTED_KNOBS` are
   mentioned in ``docs/RUNBOOK.md`` — the knobs whose absence from the
   runbook has already cost chip time (``pipeline_depth`` class).
"""

from __future__ import annotations

import ast
import glob
import os
import re
from typing import Dict, List, Optional, Set

from .core import Finding

RULE = "schema-drift"

#: schema key-set name -> config.py dataclass it must cover
_SECTION_MAP = {
    "SERVER_KEYS": "ServerConfig",
    "CLIENT_KEYS": "ClientConfig",
    "DATASET_KEYS": "DatasetConfig",
}
_SPEC_MAP = {
    "SERVER_FIELD_SPECS": "SERVER_KEYS",
    "CLIENT_FIELD_SPECS": "CLIENT_KEYS",
    "DATASET_FIELD_SPECS": "DATASET_KEYS",
    # resilience blocks (PR 3): their type rules must describe keys the
    # unknown-key pass knows, like every other section
    "CHAOS_FIELD_SPECS": "CHAOS_KEYS",
    "CHECKPOINT_RETRY_FIELD_SPECS": "CHECKPOINT_RETRY_KEYS",
    # flutearmor's infrastructure fault plane (PR 20): the nested
    # chaos.infra mapping has its own key set + spec table
    "CHAOS_INFRA_FIELD_SPECS": "CHAOS_INFRA_KEYS",
    # flutescope telemetry blocks (PR 4)
    "TELEMETRY_FIELD_SPECS": "TELEMETRY_KEYS",
    "WATCHDOG_FIELD_SPECS": "WATCHDOG_KEYS",
    # fluteshield screened aggregation (PR 5)
    "ROBUST_FIELD_SPECS": "ROBUST_KEYS",
    # cohort shape-bucketing (PR 8)
    "COHORT_BUCKETING_FIELD_SPECS": "COHORT_BUCKETING_KEYS",
    # megakernel local SGD (PR 12); the precision block's fields are
    # enum-typed (dtype names) so it keeps bespoke checks in validate()
    # and has no scalar spec table
    "MEGAKERNEL_FIELD_SPECS": "MEGAKERNEL_KEYS",
    # fleet mode (PR 14); `sampling` is enum-typed and keeps its
    # bespoke check in validate()
    "FLEET_FIELD_SPECS": "FLEET_KEYS",
    # cross-client megabatching (PR 16); the cohort_bucketing
    # prerequisite is a cross-block rule and stays bespoke in validate()
    "MEGABATCH_FIELD_SPECS": "MEGABATCH_KEYS",
    # straggler-tolerant secure aggregation (PR 18); `graph` is
    # enum-typed and keeps its bespoke check in validate()
    "SECURE_AGG_FIELD_SPECS": "SECURE_AGG_KEYS",
    # fluteflow arrival plane (PR 19); `mode`/`trace` are enum-typed
    # and `classes` is a list-of-mappings — those keep bespoke checks
    # in validate()
    "TRAFFIC_FIELD_SPECS": "TRAFFIC_KEYS",
}
#: structural keys docs may mention with further dotted children
_STRUCTURAL = {"data_config", "optimizer_config", "annealing_config",
               "server_replay_config", "RL", "secure_agg", "fedbuff",
               "nbest_task_scheduler"}

#: TPU-native knobs the RUNBOOK must document (each one already has an
#: operator-facing behavior difference; an undocumented one is how
#: `pipeline_depth`-class knobs silently desync from practice)
DOCUMENTED_KNOBS = (
    "pipeline_depth", "rounds_per_step", "checkpoint_async",
    "checkpoint_backend", "compilation_cache_dir", "step_bucketing",
    # universal overlap (PR 6): an operator who cannot find the carry
    # knob will keep paying the serial fallback without knowing the
    # lever exists
    "fused_carry",
    # resilience knobs: an operator who cannot find the preemption /
    # fault-injection drill in the runbook will learn about it from a
    # lost run instead
    "chaos", "checkpoint_retry",
    # flutescope: an operator who cannot find the trace/watchdog knobs
    # will keep debugging round time from log lines
    "telemetry",
    # fluteshield: an operator who cannot find the screened-aggregation
    # drill will learn about poisoned cohorts from a diverged model
    "robust",
    # cohort shape-bucketing: an operator who cannot find the bucket
    # tuning drill will keep paying masked FLOPs padding every client
    # to the slowest one
    "cohort_bucketing",
    # megakernel local SGD: an operator who cannot find the
    # pallas-apply knob will keep paying sub-MXU optimizer tails on
    # small models
    "megakernel",
    # precision policy: an operator who cannot find the bf16 drill will
    # leave the MXU's half-rate f32 path on forever — or flip dtypes
    # blind and lose bit-identity without knowing what they traded
    "precision",
    # fleet mode: an operator who cannot find the paging / O(cohort)
    # sampling drill will keep sizing HBM by population and believe
    # million-client runs are impossible
    "fleet",
    # cross-client megabatching: an operator who cannot find the lane
    # tuning drill will keep paying the padded [K, S] grid on every
    # heterogeneous cohort a coarse bucket layout produces
    "megabatch",
    # fluteflow arrival plane: an operator who cannot find the traffic
    # drill will keep benchmarking async strategies against a
    # boundary-sampled timeline where their whole reason to exist —
    # rounds-to-target under real arrivals — is unmeasurable
    "traffic",
    # flutearmor infra fault plane: an operator who cannot find the
    # infrastructure-fault drill will rehearse cohort failures but meet
    # host-service failures (dead prefetch daemon, flaky row store) for
    # the first time mid-campaign
    "infra",
)

_DOC_MENTION_RE = re.compile(
    r"\b(server_config|client_config)\.([A-Za-z_][A-Za-z0-9_]*)")


def _literal_names(node: ast.AST) -> Optional[Set[str]]:
    """String elements of a set/dict literal (dict -> its keys)."""
    if isinstance(node, ast.Set):
        out = set()
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                out.add(elt.value)
        return out
    if isinstance(node, ast.Dict):
        out = set()
        for key in node.keys:
            if isinstance(key, ast.Constant) and isinstance(key.value, str):
                out.add(key.value)
        return out
    return None


def _module_literal_sets(path: str) -> Dict[str, Set[str]]:
    with open(path, "r", encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=path)
    out: Dict[str, Set[str]] = {}
    lines: Dict[str, int] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            names = _literal_names(node.value)
            if names is not None:
                out[node.targets[0].id] = names
                lines[node.targets[0].id] = node.lineno
    out["__lines__"] = lines  # type: ignore[assignment]
    return out


def _dataclass_fields(path: str) -> Dict[str, Set[str]]:
    with open(path, "r", encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=path)
    out: Dict[str, Set[str]] = {}
    for node in tree.body:
        if isinstance(node, ast.ClassDef):
            fields = set()
            for stmt in node.body:
                if isinstance(stmt, ast.AnnAssign) and \
                        isinstance(stmt.target, ast.Name):
                    fields.add(stmt.target.id)
            out[node.name] = fields
    return out


def check_project(root: str,
                  schema_path: Optional[str] = None,
                  config_path: Optional[str] = None,
                  doc_paths: Optional[List[str]] = None,
                  runbook_path: Optional[str] = None,
                  documented_knobs=DOCUMENTED_KNOBS) -> List[Finding]:
    schema_path = schema_path or os.path.join(root, "msrflute_tpu",
                                              "schema.py")
    config_path = config_path or os.path.join(root, "msrflute_tpu",
                                              "config.py")
    if not (os.path.exists(schema_path) and os.path.exists(config_path)):
        return []  # not a tree this checker applies to
    if doc_paths is None:
        doc_paths = sorted(glob.glob(os.path.join(root, "docs", "*.md")))
        readme = os.path.join(root, "README.md")
        if os.path.exists(readme):
            doc_paths.append(readme)
    if runbook_path is None:
        runbook_path = os.path.join(root, "docs", "RUNBOOK.md")

    findings: List[Finding] = []
    rel_schema = os.path.relpath(schema_path, root).replace(os.sep, "/")
    rel_config = os.path.relpath(config_path, root).replace(os.sep, "/")

    sets = _module_literal_sets(schema_path)
    set_lines: Dict[str, int] = sets.pop("__lines__", {})  # type: ignore
    classes = _dataclass_fields(config_path)

    # 1. dataclass fields covered by the schema vocabulary
    for keys_name, cls_name in _SECTION_MAP.items():
        keys = sets.get(keys_name)
        fields = classes.get(cls_name)
        if keys is None or fields is None:
            continue
        for fname in sorted(fields):
            if fname == "extra" or fname.startswith("_"):
                continue
            if fname not in keys:
                findings.append(Finding(
                    RULE, rel_config, 1,
                    f"{cls_name}.{fname} is a dataclass field but missing "
                    f"from schema.{keys_name}",
                    hint=f"add {fname!r} to {keys_name} (or drop the "
                         "field) — validation currently rejects a key "
                         "the config tree accepts"))

    # 2. field specs must describe known keys
    for specs_name, keys_name in _SPEC_MAP.items():
        specs = sets.get(specs_name)
        keys = sets.get(keys_name)
        if specs is None or keys is None:
            continue
        for key in sorted(specs - keys):
            findings.append(Finding(
                RULE, rel_schema, set_lines.get(specs_name, 1),
                f"{specs_name}[{key!r}] has a type rule but {key!r} is "
                f"not in {keys_name}",
                hint=f"add {key!r} to {keys_name} or delete the dead "
                     "spec — as is, the key errors as unknown before "
                     "its type is ever checked"))

    # 3. doc mentions must name schema-known keys
    doc_keys = {"server_config": sets.get("SERVER_KEYS", set()),
                "client_config": sets.get("CLIENT_KEYS", set())}
    for doc in doc_paths:
        rel_doc = os.path.relpath(doc, root).replace(os.sep, "/")
        try:
            with open(doc, "r", encoding="utf-8") as fh:
                doc_lines = fh.read().splitlines()
        except OSError:
            continue
        for lineno, line in enumerate(doc_lines, start=1):
            for m in _DOC_MENTION_RE.finditer(line):
                section, key = m.group(1), m.group(2)
                known = doc_keys[section]
                if known and key not in known and \
                        key not in _STRUCTURAL:
                    findings.append(Finding(
                        RULE, rel_doc, lineno,
                        f"doc mentions `{section}.{key}` but the schema "
                        "does not know that key",
                        hint="the knob was renamed or dropped — update "
                             "the doc or restore the schema key"))

    # 4. RUNBOOK must document the operator knobs
    if os.path.exists(runbook_path):
        rel_rb = os.path.relpath(runbook_path, root).replace(os.sep, "/")
        with open(runbook_path, "r", encoding="utf-8") as fh:
            runbook = fh.read()
        server_keys = sets.get("SERVER_KEYS", set())
        client_keys = sets.get("CLIENT_KEYS", set())
        dataset_keys = sets.get("DATASET_KEYS", set())
        # nested blocks participate too: chaos.infra is an operator
        # knob even though "infra" is a CHAOS_KEYS member, not a
        # top-level section key
        chaos_keys = sets.get("CHAOS_KEYS", set())
        for knob in documented_knobs:
            if knob not in (server_keys | client_keys | dataset_keys |
                            chaos_keys):
                continue  # rule 1/2 territory, do not double-report
            if knob not in runbook:
                findings.append(Finding(
                    RULE, rel_rb, 1,
                    f"operator knob `{knob}` is in the schema but not "
                    "documented in the runbook",
                    hint="add a 'TPU knobs that matter' entry — "
                         "undocumented knobs desync from operating "
                         "practice"))
    return findings
