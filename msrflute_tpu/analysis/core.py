"""fluteguard core — findings, suppressions, baseline, runner.

Pure stdlib (``ast`` + ``json``): the analyzer must import in any
environment — it never imports jax, so it never touches a device —
and finish in seconds, because ``tests/test_flint_clean.py`` runs it
inside tier-1 on every verify.

Machinery:

- :class:`Finding` — one violation: rule id, file:line, message, fix
  hint.  The baseline key deliberately omits the line number so an
  unrelated edit above a baselined finding does not resurrect it.
- **Suppressions** — ``# flint: disable=RULE[,RULE2] reason`` on the
  offending line, or alone on the line directly above it.  A reason is
  mandatory and suppressions are themselves linted: one that stops
  matching any finding raises ``stale-suppression`` so dead pragmas
  cannot accumulate (the classic lint-rot failure mode).
- **Baseline** — ``analysis/baseline.json`` records accepted findings;
  the CLI exits non-zero only for findings outside it.  The shipped
  baseline is empty: new debt needs an inline suppression with a reason
  or a fix, never a silent baseline append.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Set, Tuple

#: modules whose per-round cost rides the TPU queue — the host-sync rule
#: only applies here (cold paths may sync freely).  telemetry/ is in the
#: set because its whole contract is zero device syncs: a devbus
#: publisher spelled `.item()`/`float(...)` would silently turn the
#: packed-stats ride-along into per-scalar transfers.
HOT_PATH_PARTS = ("engine", "ops", "strategies", "telemetry", "robust")

#: the concurrency rules' wider scope: everything above plus the layers
#: that own threads, locks and durable writes — the resilience handlers
#: and the data-cache/user-blob locks.  One tuple, shared by
#: lock-discipline and thread-escape, so a future package (fleet/?)
#: joins every concurrency checker with one edit.
CONC_HOT_PARTS = HOT_PATH_PARTS + ("resilience", "data")


def conc_hot_path(path: str) -> bool:
    segs = path.split("/")
    return any(p in segs for p in CONC_HOT_PARTS)

#: every rule id the suite can emit.  Lives here (not __init__) so the
#: suppression linter can judge pragma validity without an import cycle.
RULES = ("host-sync", "donation-aliasing", "jit-purity", "pallas-shape",
         "put-loop", "schema-drift", "shard-ready", "recompile-hazard",
         "transfer-budget", "guard-matrix", "event-schema",
         "signal-safety", "lock-discipline", "thread-escape",
         "atomic-write",
         "mesh-axis", "shard-locality", "spec-drift", "collective-budget",
         "stale-suppression", "bare-suppression", "unknown-suppression",
         "parse-error")

#: rule-rename migration map: old pragma spelling -> current rule id.  A
#: pragma naming a rule that no longer exists is an ERROR
#: (``unknown-suppression``), never silently inert; when the old name is
#: here the finding's hint names the replacement.  Seeded with the
#: underscore spellings (the one misspelling every rule accumulates).
RULE_RENAMES = {
    "host_sync": "host-sync",
    "donation_aliasing": "donation-aliasing",
    "jit_purity": "jit-purity",
    "pallas_shape": "pallas-shape",
    "put_loop": "put-loop",
    "schema_drift": "schema-drift",
    "shard_ready": "shard-ready",
    "recompile_hazard": "recompile-hazard",
    "transfer_budget": "transfer-budget",
    "guard_matrix": "guard-matrix",
    "event_schema": "event-schema",
    "signal_safety": "signal-safety",
    "lock_discipline": "lock-discipline",
    "thread_escape": "thread-escape",
    "atomic_write": "atomic-write",
    "mesh_axis": "mesh-axis",
    "shard_locality": "shard-locality",
    "spec_drift": "spec-drift",
    "collective_budget": "collective-budget",
}

#: factories whose RESULT is a compiled callable — shared by host-sync
#: (taint seeding), the summary extractor (cross-module jitted-binding
#: tracking) and recompile-hazard (static_argnums hazards)
JIT_FACTORIES = {"jax.jit", "jit", "jax.pmap", "pmap", "shard_map",
                 "jax.experimental.shard_map.shard_map", "pl.pallas_call",
                 "pallas_call"}

#: calls whose named function arguments become TRACED bodies — shared by
#: jit-purity (root discovery) and the summary extractor
TRACE_ENTRY = {"jax.jit", "jit", "jax.pmap", "pmap", "shard_map",
               "jax.experimental.shard_map.shard_map", "jax.vmap", "vmap",
               "jax.lax.scan", "lax.scan", "jax.lax.while_loop",
               "lax.while_loop", "jax.lax.fori_loop", "lax.fori_loop",
               "jax.lax.cond", "lax.cond", "jax.checkpoint", "jax.remat",
               "pl.pallas_call", "pallas_call", "jax.grad",
               "jax.value_and_grad"}

_PRAGMA_RE = re.compile(
    r"#\s*flint:\s*disable=([A-Za-z0-9_,\-]+)(?:\s+(\S.*))?")


@dataclass(frozen=True)
class Finding:
    """One rule violation at file:line."""

    rule: str      #: rule id, e.g. ``host-sync``
    path: str      #: path relative to the analysis root, '/'-separated
    line: int      #: 1-based line number
    message: str   #: what is wrong, specific to the site
    hint: str = ""  #: how to fix it

    @property
    def baseline_key(self) -> str:
        # line-free on purpose: baselines must survive edits elsewhere
        # in the file
        return f"{self.rule}::{self.path}::{self.message}"

    @property
    def id(self) -> str:
        """Stable finding id for machine consumers (``--format json`` /
        SARIF ``partialFingerprints``): the rule plus a hash of the
        line-free baseline key, so the id survives unrelated edits in
        the same file exactly like the baseline does."""
        digest = hashlib.sha1(
            self.baseline_key.encode("utf-8")).hexdigest()[:12]
        return f"{self.rule}-{digest}"

    def render(self) -> str:
        out = f"{self.path}:{self.line}: [{self.rule}] {self.message}"
        if self.hint:
            out += f"\n    hint: {self.hint}"
        return out


@dataclass
class Suppression:
    """One parsed ``# flint: disable=`` pragma."""

    path: str
    line: int            #: line the pragma sits on
    rules: Tuple[str, ...]
    reason: str
    applies_to: int      #: line the pragma suppresses (itself, or next)
    used: bool = False
    #: hygiene findings (stale/bare/unknown) are only judged for pragmas
    #: in files the caller actually asked to analyze — a project-wide
    #: summary pass may parse pragmas in files outside the request
    #: purely so cross-file checkers' findings can be suppressed there
    in_scope: bool = True


@dataclass
class ModuleInfo:
    """One parsed source file handed to every per-file checker."""

    path: str            #: relative path ('/'-separated)
    abspath: str
    src: str
    tree: ast.Module
    lines: List[str] = field(default_factory=list)

    @property
    def is_hot_path(self) -> bool:
        parts = self.path.split("/")
        return any(p in parts for p in HOT_PATH_PARTS)


# ----------------------------------------------------------------------
# suppressions
# ----------------------------------------------------------------------
def parse_suppressions(info: ModuleInfo) -> List[Suppression]:
    """Pragmas from real COMMENT tokens only — a docstring QUOTING the
    syntax (this package's own docs) must not register as a pragma."""
    import io
    import tokenize

    out: List[Suppression] = []
    if "flint:" not in info.src:
        return out  # fast path: tokenizing is ~10x a parse
    try:
        tokens = list(tokenize.generate_tokens(
            io.StringIO(info.src).readline))
    except (tokenize.TokenError, IndentationError):
        return out
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _PRAGMA_RE.search(tok.string)
        if not m:
            continue
        lineno = tok.start[0]
        rules = tuple(r.strip() for r in m.group(1).split(",") if r.strip())
        reason = (m.group(2) or "").strip()
        # a pragma-only line shields the NEXT line; a trailing pragma
        # shields its own line
        own = info.lines[lineno - 1][: tok.start[1]].strip() \
            if lineno <= len(info.lines) else ""
        applies_to = lineno + 1 if not own else lineno
        out.append(Suppression(info.path, lineno, rules, reason, applies_to))
    return out


def apply_suppressions(findings: List[Finding],
                       suppressions: List[Suppression],
                       active_rules: Optional[Set[str]] = None
                       ) -> List[Finding]:
    """Drop suppressed findings, then append the suppression-hygiene
    findings (missing reason, stale pragma).  ``active_rules`` (a
    ``--rules`` subset) limits hygiene judgment to pragmas whose rules
    actually ran — a jit-purity pragma is not stale just because this
    invocation only ran host-sync."""
    by_site: Dict[Tuple[str, int], List[Suppression]] = {}
    for sup in suppressions:
        by_site.setdefault((sup.path, sup.applies_to), []).append(sup)

    kept: List[Finding] = []
    for f in findings:
        sups = [s for s in by_site.get((f.path, f.line), [])
                if f.rule in s.rules]
        if sups:
            for s in sups:
                s.used = True
            continue
        kept.append(f)

    for sup in suppressions:
        if not sup.in_scope:
            continue
        # pragma validity is judged regardless of any --rules subset: a
        # pragma naming a rule that no longer exists must be an ERROR,
        # not silently inert (the rule-rename failure mode)
        unknown = [r for r in sup.rules if r not in RULES]
        for r in unknown:
            renamed = RULE_RENAMES.get(r)
            kept.append(Finding(
                "unknown-suppression", sup.path, sup.line,
                f"suppression names unknown rule `{r}`"
                + (f" (renamed to `{renamed}`)" if renamed else ""),
                hint=(f"update the pragma to `disable={renamed}`"
                      if renamed else
                      "no such rule — fix the spelling or delete the "
                      "pragma (tools/flint --list-rules)")))
        if unknown and not (set(sup.rules) & set(RULES)):
            continue  # nothing valid left to judge for staleness
        if active_rules is not None and \
                not set(sup.rules) & active_rules:
            continue
        if not sup.reason:
            kept.append(Finding(
                "bare-suppression", sup.path, sup.line,
                f"suppression of {','.join(sup.rules)} has no reason",
                hint="write `# flint: disable=RULE why it is safe here`"))
        if not sup.used:
            kept.append(Finding(
                "stale-suppression", sup.path, sup.line,
                f"suppression of {','.join(sup.rules)} matches no finding",
                hint="the code it shielded is gone or fixed — delete the "
                     "pragma"))
    return kept


# ----------------------------------------------------------------------
# baseline
# ----------------------------------------------------------------------
def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def load_baseline(path: Optional[str]) -> Set[str]:
    if not path or not os.path.exists(path):
        return set()
    with open(path, "r", encoding="utf-8") as fh:
        raw = json.load(fh)
    keys = set()
    for entry in raw.get("entries", []):
        keys.add(f"{entry['rule']}::{entry['path']}::{entry['message']}")
    return keys


def write_baseline(path: str, findings: Iterable[Finding]) -> None:
    entries = [{"rule": f.rule, "path": f.path, "line": f.line,
                "message": f.message} for f in findings]
    entries.sort(key=lambda e: (e["path"], e["rule"], e["line"]))
    # tmp + replace: the committed baseline is a durable artifact — a
    # crash mid-write must not leave a torn JSON that makes
    # every later run fail to parse it (the atomic-write discipline)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump({"version": 1, "entries": entries}, fh, indent=2,
                  sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)


def filter_baseline(findings: List[Finding],
                    baseline: Set[str]) -> List[Finding]:
    return [f for f in findings if f.baseline_key not in baseline]


# ----------------------------------------------------------------------
# AST helpers shared by the checkers
# ----------------------------------------------------------------------
def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(node: ast.Call) -> Optional[str]:
    return dotted_name(node.func)


def const_int(node: ast.AST,
              consts: Optional[Dict[str, int]] = None) -> Optional[int]:
    """Fold an int literal, a module-constant Name, or +-* of those."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.Name) and consts and node.id in consts:
        return consts[node.id]
    if isinstance(node, ast.BinOp) and isinstance(
            node.op, (ast.Add, ast.Sub, ast.Mult, ast.FloorDiv)):
        lhs = const_int(node.left, consts)
        rhs = const_int(node.right, consts)
        if lhs is None or rhs is None:
            return None
        if isinstance(node.op, ast.Add):
            return lhs + rhs
        if isinstance(node.op, ast.Sub):
            return lhs - rhs
        if isinstance(node.op, ast.Mult):
            return lhs * rhs
        return lhs // rhs if rhs else None
    return None


def module_int_constants(tree: ast.Module) -> Dict[str, int]:
    """Top-level ``NAME = <int expr>`` bindings (folded iteratively so
    constants may reference earlier ones)."""
    consts: Dict[str, int] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            val = const_int(node.value, consts)
            if val is not None:
                consts[node.targets[0].id] = val
    return consts


# ----------------------------------------------------------------------
# interprocedural engine (flint v2)
#
# One pass per file extracts a JSON-serializable :class:`ModuleSummary`
# (functions + their call sites / fetch sites / self-state reads &
# writes, imports, jitted bindings, traced roots, class markers, event
# emissions).  :class:`Project` stitches the summaries into a project-
# wide call graph with cross-module resolution, and exposes the two
# reachability queries the checkers need: trace-context closure
# (jit-purity, shard-ready, recompile-hazard) and round-path closure
# (transfer-budget).  Summaries are cached per file keyed by
# (mtime_ns, size) — in memory for repeated in-process runs (the tier-1
# gate + test suite), and optionally on disk for ``tools/flint
# --changed`` so an incremental run re-parses only the edited files.
# ----------------------------------------------------------------------
@dataclass
class FunctionSummary:
    """Def-use facts for one function/method, enough for every project
    checker to reason about it WITHOUT re-parsing its file."""

    module: str                 #: rel path of the defining file
    qual: str                   #: dotted qualname ("Cls.meth", "f.inner")
    name: str                   #: bare name
    cls: Optional[str]          #: immediately enclosing class, if any
    line: int
    #: every call site: (dotted name as written, line)
    calls: List[Tuple[str, int]] = field(default_factory=list)
    #: explicit fetches: (line, arg source, lexically-inside-loop)
    device_gets: List[Tuple[int, str, bool]] = field(default_factory=list)
    #: ``self.X`` attribute loads / stores (recompile-hazard's
    #: mutable-capture cross-check)
    self_reads: List[str] = field(default_factory=list)
    self_writes: List[str] = field(default_factory=list)
    # -- concurrency fact layer (signal-safety / lock-discipline /
    # -- thread-escape ride these; see the module comment) -------------
    #: lock-held regions: (lock id, start line, end line) from ``with``
    #: statements whose context expression names a lock/condition
    lock_regions: List[Tuple[str, int, int]] = field(default_factory=list)
    #: concurrency-relevant operations: (kind, line, detail); kind one of
    #: lock-acquire / lock-release / file-io / log / blocking-join /
    #: blocking-wait / blocking-sleep
    conc_ops: List[Tuple[str, int, str]] = field(default_factory=list)
    #: line spans of ``if`` statements whose test names a
    #: ``*_from_signal``-style flag — the deferred-flush idiom
    #: signal-safety blesses (work gated on the flag runs outside
    #: signal context)
    deferred_spans: List[Tuple[int, int]] = field(default_factory=list)
    #: direct ``self.X = <expr>`` assignments: (attr, line, value src)
    self_assigns: List[Tuple[str, int, str]] = field(default_factory=list)
    #: simple local ``name = <expr>`` bindings (last wins) — one level
    #: of value provenance for thread-escape's snapshot check
    local_assigns: Dict[str, str] = field(default_factory=dict)
    # -- mesh fact layer (mesh-axis / shard-locality /
    # -- collective-budget ride these; see the module comment) ----------
    #: collective call sites: (op tail, line, axis desc); axis desc is
    #: :func:`axis_desc_of`'s classification of the axis argument
    collectives: List[Tuple[str, int, str]] = field(default_factory=list)
    #: pool-table gathers: (base name, slice source, line) — Subscript
    #: loads whose base names a slot-axis table and whose slice looks
    #: like slot ids (``.at[...]`` update chains are scatters, not
    #: gathers, and are excluded)
    slot_gathers: List[Tuple[str, str, int]] = field(default_factory=list)
    #: sentinel-padded scatters: (base name, line) from
    #: ``pool.at[slots].set(..., mode="drop")`` — the fixed-shape
    #: page-in idiom shard-locality accepts as shard-local evidence
    drop_scatters: List[Tuple[str, int]] = field(default_factory=list)

    def to_dict(self) -> Dict[str, Any]:
        return {"module": self.module, "qual": self.qual,
                "name": self.name, "cls": self.cls, "line": self.line,
                "calls": [list(c) for c in self.calls],
                "device_gets": [list(d) for d in self.device_gets],
                "self_reads": self.self_reads,
                "self_writes": self.self_writes,
                "lock_regions": [list(r) for r in self.lock_regions],
                "conc_ops": [list(o) for o in self.conc_ops],
                "deferred_spans": [list(s) for s in self.deferred_spans],
                "self_assigns": [list(a) for a in self.self_assigns],
                "local_assigns": self.local_assigns,
                "collectives": [list(c) for c in self.collectives],
                "slot_gathers": [list(g) for g in self.slot_gathers],
                "drop_scatters": [list(s) for s in self.drop_scatters]}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FunctionSummary":
        return cls(d["module"], d["qual"], d["name"], d.get("cls"),
                   d["line"],
                   [tuple(c) for c in d.get("calls", [])],
                   [tuple(g) for g in d.get("device_gets", [])],
                   list(d.get("self_reads", [])),
                   list(d.get("self_writes", [])),
                   [tuple(r) for r in d.get("lock_regions", [])],
                   [tuple(o) for o in d.get("conc_ops", [])],
                   [tuple(s) for s in d.get("deferred_spans", [])],
                   [tuple(a) for a in d.get("self_assigns", [])],
                   dict(d.get("local_assigns", {})),
                   [tuple(c) for c in d.get("collectives", [])],
                   [tuple(g) for g in d.get("slot_gathers", [])],
                   [tuple(s) for s in d.get("drop_scatters", [])])


@dataclass
class ModuleSummary:
    """One file's interprocedural facts (see module comment)."""

    path: str                               #: rel path
    functions: Dict[str, FunctionSummary] = field(default_factory=dict)
    #: local name -> (target rel path, attr or None for module imports);
    #: only imports that resolve INSIDE the analyzed project are kept
    imports: Dict[str, Tuple[str, Optional[str]]] = \
        field(default_factory=dict)
    #: bare name -> qual of the LAST def with that name (runtime
    #: shadowing semantics, matching the old jit-purity index)
    name_index: Dict[str, str] = field(default_factory=dict)
    #: names / self-attrs bound to a jit-factory result
    jit_names: List[str] = field(default_factory=list)
    jit_attrs: List[str] = field(default_factory=list)
    #: trace roots: (function ref as written, enclosing class or None)
    traced_roots: List[Tuple[str, Optional[str]]] = \
        field(default_factory=list)
    #: jit factories declaring static args: binding name/attr ->
    #: {"argnums": [...], "argnames": [...], "line": n}
    static_jit: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    #: class -> list of base-class names (dotted, as written)
    class_bases: Dict[str, List[str]] = field(default_factory=dict)
    #: class -> {attr: constant} for simple class-level constants
    #: (``host_rounds = True`` markers, guard-matrix's strategy scan)
    class_markers: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    #: telemetry emissions: (event name, line, api); api one of
    #: log_event / emit_event / event / kind-literal; a trailing ``*``
    #: in the name marks an f-string prefix family (``watchdog_*``)
    events: List[Tuple[str, int, str]] = field(default_factory=list)
    #: devbus publishes: (metric name, line, publish|devbus_host)
    devbus: List[Tuple[str, int, str]] = field(default_factory=list)
    #: thread spawns: (target ref as written or "", line, has name= kw,
    #: enclosing class or None, enclosing function qual or "")
    thread_spawns: List[Tuple[str, int, bool, Optional[str], str]] = \
        field(default_factory=list)
    #: ``signal.signal(sig, handler)`` registrations:
    #: (handler ref as written, line, enclosing class or None)
    signal_handlers: List[Tuple[str, int, Optional[str]]] = \
        field(default_factory=list)
    # -- mesh fact layer ------------------------------------------------
    #: per-lane trace roots — refs handed to vmap / lax.scan:
    #: (ref as written, enclosing class or None, enclosing function
    #: qual or "" — nested lane bodies resolve in their BUILDER's
    #: scope, not via the module-wide last-def name index)
    lane_roots: List[Tuple[str, Optional[str], str]] = \
        field(default_factory=list)
    #: shard_map roots: (ref, enclosing class or None, enclosing
    #: function qual or "", line) — the enclosing qual lets
    #: shard-locality read the BUILDER's locals for shard-local markers
    shardmap_roots: List[Tuple[str, Optional[str], str, int]] = \
        field(default_factory=list)
    #: sharding-spec bindings: (bound name — ``x`` or ``self.x`` —,
    #: kind per :func:`spec_kind_of`, line)
    spec_bindings: List[Tuple[str, str, int]] = \
        field(default_factory=list)
    #: ``P("...")`` string-literal axis specs: (axis string, line)
    spec_literals: List[Tuple[str, int]] = field(default_factory=list)
    #: device_put sites: (target source, spec desc, line, enclosing
    #: function qual or ""); spec desc is ``none`` (no sharding arg), a
    #: :func:`spec_kind_of` kind, or ``name:<dotted>`` for a spec passed
    #: by name (resolved against spec_bindings by spec-drift)
    device_puts: List[Tuple[str, str, int, str]] = \
        field(default_factory=list)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "path": self.path,
            "functions": {q: f.to_dict()
                          for q, f in self.functions.items()},
            "imports": {k: list(v) for k, v in self.imports.items()},
            "name_index": self.name_index,
            "jit_names": self.jit_names, "jit_attrs": self.jit_attrs,
            "traced_roots": [list(t) for t in self.traced_roots],
            "static_jit": self.static_jit,
            "class_bases": self.class_bases,
            "class_markers": self.class_markers,
            "events": [list(e) for e in self.events],
            "devbus": [list(d) for d in self.devbus],
            "thread_spawns": [list(t) for t in self.thread_spawns],
            "signal_handlers": [list(h) for h in self.signal_handlers],
            "lane_roots": [list(t) for t in self.lane_roots],
            "shardmap_roots": [list(t) for t in self.shardmap_roots],
            "spec_bindings": [list(b) for b in self.spec_bindings],
            "spec_literals": [list(s) for s in self.spec_literals],
            "device_puts": [list(p) for p in self.device_puts],
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ModuleSummary":
        out = cls(d["path"])
        out.functions = {q: FunctionSummary.from_dict(f)
                         for q, f in d.get("functions", {}).items()}
        out.imports = {k: (v[0], v[1])
                       for k, v in d.get("imports", {}).items()}
        out.name_index = dict(d.get("name_index", {}))
        out.jit_names = list(d.get("jit_names", []))
        out.jit_attrs = list(d.get("jit_attrs", []))
        out.traced_roots = [(t[0], t[1])
                            for t in d.get("traced_roots", [])]
        out.static_jit = dict(d.get("static_jit", {}))
        out.class_bases = {k: list(v)
                           for k, v in d.get("class_bases", {}).items()}
        out.class_markers = {k: dict(v)
                             for k, v in d.get("class_markers", {}).items()}
        out.events = [(e[0], e[1], e[2]) for e in d.get("events", [])]
        out.devbus = [(e[0], e[1], e[2]) for e in d.get("devbus", [])]
        out.thread_spawns = [(t[0], t[1], bool(t[2]), t[3], t[4])
                             for t in d.get("thread_spawns", [])]
        out.signal_handlers = [(h[0], h[1], h[2])
                               for h in d.get("signal_handlers", [])]
        out.lane_roots = [(t[0], t[1], t[2])
                          for t in d.get("lane_roots", [])]
        out.shardmap_roots = [(t[0], t[1], t[2], t[3])
                              for t in d.get("shardmap_roots", [])]
        out.spec_bindings = [(b[0], b[1], b[2])
                             for b in d.get("spec_bindings", [])]
        out.spec_literals = [(s[0], s[1])
                             for s in d.get("spec_literals", [])]
        out.device_puts = [(p[0], p[1], p[2], p[3])
                           for p in d.get("device_puts", [])]
        return out


_EVENT_APIS = {"log_event": 0, "emit_event": 1}
_DEVGET_NAMES = ("jax.device_get", "device_get")
_LOOP_NODES = (ast.For, ast.AsyncFor, ast.While, ast.ListComp,
               ast.SetComp, ast.DictComp, ast.GeneratorExp)

# -- concurrency fact layer --------------------------------------------
#: a with-statement context expression whose final name segment matches
#: this is treated as a lock acquisition (threading.Lock / RLock /
#: Condition / Semaphore attribute naming conventions)
_LOCK_NAME_RE = re.compile(r"(lock|cond|mutex|sem)", re.I)
#: an ``if`` test naming one of these flags marks its body as DEFERRED
#: out of signal context — the blessed deferred-flush idiom (the
#: handler sets a flag; the loop's next poll does the unsafe work)
_SIGNAL_FLAG_RE = re.compile(r"from_signal|in_signal|signal_ctx", re.I)
_THREAD_FACTORIES = {"threading.Thread", "Thread"}

# -- mesh fact layer ---------------------------------------------------
#: collective primitives whose second argument (first for axis_index)
#: names a mesh axis.  ``axis_index`` rides along because
#: shard-locality treats it as the global->block-local slot-id
#: conversion evidence, not as a cross-shard collective.
COLLECTIVE_OPS = {"psum", "pmean", "pmax", "pmin", "all_gather",
                  "ppermute", "all_to_all", "psum_scatter", "pshuffle"}
#: names/attrs whose FINAL segment is a canonical axis constant — the
#: only sanctioned way to spell an axis in engine//parallel//strategies/
_AXIS_CONST_RE = re.compile(r"(CLIENTS_AXIS|MODEL_AXIS)$")
#: per-lane trace entries (the vmapped/scanned per-client body) vs the
#: per-shard ones (shard_map): shard-locality prohibits collectives in
#: the former and audits gathers in the latter
_LANE_ENTRIES = {"jax.vmap", "vmap", "jax.lax.scan", "lax.scan"}
_SHARD_MAP_ENTRIES = {"shard_map", "jax.experimental.shard_map.shard_map"}
_PARTITION_SPEC_TAILS = ("P", "PartitionSpec")
#: parallel/-helper tails that construct a sharding of known kind
_SPEC_HELPER_KINDS = {"slot_pool_sharding": "clients",
                      "client_axis_sharding": "clients",
                      "replicated_sharding": "replicated"}
_DEVICE_PUT_NAMES = ("jax.device_put", "device_put")

#: identifier tokens that mark a SLOT-AXIS table (the fleet page pool,
#: carry-row buffers).  Shared by the summary extractor (slot-gather /
#: drop-scatter / device_put facts) and spec-drift's replicated-pool
#: check (moved here from shard-ready).
POOL_TOKENS = frozenset({"row", "rows", "pool", "slot", "slots",
                         "table", "tables"})
_TOKEN_SPLIT = re.compile(r"[^a-zA-Z0-9]+")
#: a Subscript slice that looks like slot ids (directly or through one
#: local binding) marks a pool gather
_SLOT_SLICE_RE = re.compile(r"(slot|idx|ids|indices)", re.I)


def pool_name(name: Optional[str]) -> bool:
    """``rows`` / ``page_pool`` / ``self._tables`` — a slot-axis table
    name by its identifier tokens."""
    if not name:
        return False
    return any(tok in POOL_TOKENS
               for tok in _TOKEN_SPLIT.split(name.lower()))


def axis_desc_of(node: Optional[ast.AST]) -> str:
    """Classify a collective's axis argument: ``const:<NAME>`` for the
    canonical constants, ``literal:<s>`` for a bare string, ``dynamic``
    for everything else (parameterized axis-library kernels)."""
    if node is None:
        return "dynamic"
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return f"literal:{node.value}"
    name = dotted_name(node)
    if name is not None:
        m = _AXIS_CONST_RE.search(name.rsplit(".", 1)[-1])
        if m:
            return f"const:{m.group(1)}"
    if isinstance(node, (ast.Tuple, ast.List)):
        descs = [axis_desc_of(e) for e in node.elts]
        lit = next((d for d in descs if d.startswith("literal:")), None)
        if lit:
            return lit
        if descs and all(d.startswith("const:") for d in descs):
            return descs[0]
    return "dynamic"


def spec_kind_of(node: Optional[ast.AST]) -> Optional[str]:
    """Classify a sharding-spec expression — ``NamedSharding(mesh,
    P(...))``, a bare ``P(...)`` literal, or a parallel/ helper call —
    as replicated / clients / model / dynamic.  None when the
    expression is not a spec construction at all."""
    if not isinstance(node, ast.Call):
        return None
    tail = (call_name(node) or "").split(".")[-1]
    if tail in _SPEC_HELPER_KINDS:
        return _SPEC_HELPER_KINDS[tail]
    if tail == "NamedSharding":
        if len(node.args) < 2:
            return "dynamic"
        return spec_kind_of(node.args[1]) or "dynamic"
    if tail in _PARTITION_SPEC_TAILS:
        if any(isinstance(a, ast.Starred) for a in node.args):
            return "dynamic"
        if not node.args and not node.keywords:
            return "replicated"
        descs = [axis_desc_of(a) for a in node.args]
        if any(d == "const:CLIENTS_AXIS" for d in descs):
            return "clients"
        if any(d == "const:MODEL_AXIS" for d in descs):
            return "model"
        return "dynamic"
    return None
#: logger-receiver names whose level-method calls count as logging
_LOGGER_RECV_RE = re.compile(r"(^|\.)(_?logger|log)$", re.I)
_LOG_LEVEL_TAILS = {"debug", "info", "warning", "warn", "error",
                    "exception", "critical", "log"}


def lock_id_of(expr: ast.AST) -> Optional[str]:
    """Normalized lock identity for a with-item / acquire receiver:
    ``self._mp_cond`` -> ``_mp_cond``; inline ``threading.Lock()`` keeps
    its dotted factory name.  None when the expression does not look
    like a lock."""
    name = dotted_name(expr)
    if name is None and isinstance(expr, ast.Call):
        name = call_name(expr)
    if name is None:
        return None
    if not _LOCK_NAME_RE.search(name.rsplit(".", 1)[-1]):
        return None
    return name[5:] if name.startswith("self.") else name


def open_mode(call: ast.Call) -> Optional[str]:
    """The literal mode of an ``open(...)`` call (positional or
    ``mode=``), or None when absent/non-literal.  Shared by the summary
    extractor and atomic-write."""
    mode: Optional[str] = None
    if len(call.args) > 1 and isinstance(call.args[1], ast.Constant):
        mode = str(call.args[1].value)
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            mode = str(kw.value.value)
    return mode


def _module_rel_for(dotted: str, importer: str, level: int,
                    known: Set[str]) -> Optional[str]:
    """Map an import to a rel path inside the project file set.

    ``known`` holds the project's rel paths.  Handles relative imports
    (``from ..telemetry import metrics``) by walking up from the
    importer's package, and absolute ones by trying the dotted path both
    as-is and package-qualified (``msrflute_tpu.engine.round``)."""
    candidates: List[str] = []
    if level > 0:
        base = importer.split("/")[:-1]           # importer's package dir
        base = base[: len(base) - (level - 1)] if level > 1 else base
        if len(importer.split("/")) - 1 >= level - 1:
            candidates.append("/".join(base + dotted.split("."))
                              if dotted else "/".join(base))
    else:
        candidates.append("/".join(dotted.split(".")))
    out = []
    for cand in candidates:
        if not cand:
            continue
        if cand + ".py" in known:
            return cand + ".py"
        if cand + "/__init__.py" in known:
            return cand + "/__init__.py"
        out.append(cand)
    return None


class _SummaryVisitor(ast.NodeVisitor):
    """One walk of a module AST building its :class:`ModuleSummary`."""

    def __init__(self, info: ModuleInfo, summary: ModuleSummary):
        self.info = info
        self.s = summary
        self.class_stack: List[str] = []
        self.fn_stack: List[FunctionSummary] = []
        self.loop_depth = 0

    # -- context ----------------------------------------------------
    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.class_stack.append(node.name)
        self.s.class_bases[node.name] = [
            n for n in (dotted_name(b) for b in node.bases) if n]
        markers = {}
        for stmt in node.body:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) and \
                    isinstance(stmt.value, ast.Constant):
                markers[stmt.targets[0].id] = stmt.value.value
        if markers:
            self.s.class_markers[node.name] = markers
        self.generic_visit(node)
        self.class_stack.pop()

    def _enter_fn(self, node) -> None:
        prefix = ""
        if self.fn_stack:
            prefix = self.fn_stack[-1].qual + "."
        elif self.class_stack:
            prefix = ".".join(self.class_stack) + "."
        qual = prefix + node.name
        fn = self.s.functions.get(qual)
        if fn is None:
            fn = FunctionSummary(self.info.path, qual, node.name,
                                 self.class_stack[-1] if self.class_stack
                                 else None, node.lineno)
            self.s.functions[qual] = fn
        # else: conditional redefinition (`if mode: def f ... else:
        # def f`) — accumulate into ONE summary so the facts are the
        # UNION of the branches (either def may be the one traced;
        # round.py's gather_axis all_gather lives in one branch only)
        self.s.name_index[node.name] = qual
        for dec in node.decorator_list:
            dec_call = dec.func if isinstance(dec, ast.Call) else dec
            if dotted_name(dec_call) in TRACE_ENTRY:
                self.s.traced_roots.append(
                    (node.name, fn.cls))
        self.fn_stack.append(fn)
        outer_loop, self.loop_depth = self.loop_depth, 0
        self.generic_visit(node)
        self.loop_depth = outer_loop
        self.fn_stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._enter_fn(node)

    def visit_AsyncFunctionDef(self, node) -> None:
        self._enter_fn(node)

    # -- imports ----------------------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            target = _module_rel_for(alias.name, self.info.path, 0,
                                     self._known())
            if not target:
                continue
            if alias.asname:
                self.s.imports[alias.asname] = (target, None)
            elif "." not in alias.name:
                self.s.imports[alias.name] = (target, None)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        target = _module_rel_for(node.module or "", self.info.path,
                                 node.level or 0, self._known())
        if target is None:
            return
        for alias in node.names:
            # `from pkg import mod` where pkg/mod.py exists binds the
            # MODULE, not an attr of pkg/__init__.py
            dotted = alias.name if not node.module \
                else node.module + "." + alias.name
            sub = _module_rel_for(dotted, self.info.path,
                                  node.level or 0, self._known())
            if sub and sub != target:
                self.s.imports[alias.asname or alias.name] = (sub, None)
            else:
                self.s.imports[alias.asname or alias.name] = \
                    (target, alias.name)

    def _known(self) -> Set[str]:
        return getattr(self, "_known_paths", set())

    # -- loops (lexical, for loop-fetch detection) -------------------
    def _loop(self, node) -> None:
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = visit_AsyncFor = visit_While = _loop
    visit_ListComp = visit_SetComp = visit_DictComp = _loop
    visit_GeneratorExp = _loop

    # -- concurrency facts -------------------------------------------
    def visit_If(self, node: ast.If) -> None:
        # `if not _from_signal:` BODIES are the deferred-flush idiom:
        # signal-safety prunes call edges inside them from the handler
        # closure.  Polarity matters — the guard must be the NEGATION
        # of the flag, and only the body (never the orelse) is blessed:
        # `if _from_signal: flush()` runs the flush IN signal context
        # and must keep flagging.
        if self.fn_stack and isinstance(node.test, ast.UnaryOp) and \
                isinstance(node.test.op, ast.Not) and node.body:
            for sub in ast.walk(node.test.operand):
                ident = sub.id if isinstance(sub, ast.Name) else (
                    sub.attr if isinstance(sub, ast.Attribute) else None)
                if ident and _SIGNAL_FLAG_RE.search(ident):
                    self.fn_stack[-1].deferred_spans.append(
                        (node.body[0].lineno,
                         node.body[-1].end_lineno or
                         node.body[-1].lineno))
                    break
        self.generic_visit(node)

    def _with(self, node) -> None:
        if self.fn_stack:
            for item in node.items:
                lock = lock_id_of(item.context_expr)
                if lock is not None:
                    self.fn_stack[-1].lock_regions.append(
                        (lock, node.lineno,
                         node.end_lineno or node.lineno))
        self.generic_visit(node)

    visit_With = visit_AsyncWith = _with

    def _record_conc_op(self, name: str, node: ast.Call) -> None:
        """Classify one call as a concurrency-relevant operation on the
        enclosing function (caller guarantees ``self.fn_stack``)."""
        fn = self.fn_stack[-1]
        tail = name.rsplit(".", 1)[-1]
        recv = name[: -(len(tail) + 1)] if "." in name else ""
        if name == "open":
            fn.conc_ops.append(("file-io", node.lineno,
                                open_mode(node) or ""))
        elif name == "print" or name.endswith("print_rank") or \
                name.startswith("logging."):
            fn.conc_ops.append(("log", node.lineno, name))
        elif tail in _LOG_LEVEL_TAILS and recv and \
                _LOGGER_RECV_RE.search(recv):
            fn.conc_ops.append(("log", node.lineno, name))
        elif tail == "join" and not node.args:
            # zero-arg `.join()` is a thread/process join; str.join
            # always takes its iterable positionally
            fn.conc_ops.append(("blocking-join", node.lineno, recv))
        elif tail == "wait" and recv:
            lock = recv[5:] if recv.startswith("self.") else recv
            fn.conc_ops.append(("blocking-wait", node.lineno, lock))
        elif name in ("time.sleep", "sleep"):
            fn.conc_ops.append(("blocking-sleep", node.lineno, ""))
        elif tail in ("acquire", "release") and recv and \
                _LOCK_NAME_RE.search(recv.rsplit(".", 1)[-1]):
            # same filter as with-statements: only lock-looking
            # receivers register (`pool_slot.acquire()` is not a lock)
            lock = recv[5:] if recv.startswith("self.") else recv
            fn.conc_ops.append((f"lock-{tail}", node.lineno, lock))

    # -- statements -------------------------------------------------
    def visit_Assign(self, node: ast.Assign) -> None:
        value = node.value
        if isinstance(value, ast.Call) and \
                call_name(value) in JIT_FACTORIES:
            static = self._static_spec(value)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    self.s.jit_names.append(tgt.id)
                    if static:
                        self.s.static_jit[tgt.id] = static
                elif isinstance(tgt, ast.Attribute) and \
                        isinstance(tgt.value, ast.Name) and \
                        tgt.value.id == "self":
                    self.s.jit_attrs.append(tgt.attr)
                    if static:
                        self.s.static_jit["self." + tgt.attr] = static
        kind = spec_kind_of(value)
        if kind is not None:
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    self.s.spec_bindings.append(
                        (tgt.id, kind, node.lineno))
                elif isinstance(tgt, ast.Attribute) and \
                        isinstance(tgt.value, ast.Name) and \
                        tgt.value.id == "self":
                    self.s.spec_bindings.append(
                        ("self." + tgt.attr, kind, node.lineno))
        if self.fn_stack:
            for tgt in node.targets:
                self._record_self_write(tgt)
            fn = self.fn_stack[-1]
            for tgt in node.targets:
                # direct `self.X = expr` / `name = expr` bindings carry
                # their value source for the thread-escape snapshot check
                if isinstance(tgt, ast.Attribute) and \
                        isinstance(tgt.value, ast.Name) and \
                        tgt.value.id == "self":
                    fn.self_assigns.append(
                        (tgt.attr, node.lineno, self._src_of(value)))
                elif isinstance(tgt, ast.Name):
                    fn.local_assigns[tgt.id] = self._src_of(value)
        self.generic_visit(node)

    @staticmethod
    def _src_of(node: ast.AST, limit: int = 200) -> str:
        try:
            src = ast.unparse(node)
        except Exception:  # pragma: no cover - unparse is total on 3.9+
            return ""
        return src if len(src) <= limit else src[:limit]

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        if self.fn_stack:
            self._record_self_write(node.target)
        self.generic_visit(node)

    def _record_self_write(self, tgt: ast.AST) -> None:
        """``self.X`` / ``self.X[...]`` / ``self.X.Y`` store targets
        count as writes of attr ``X`` (mutation of its object)."""
        if not isinstance(tgt, (ast.Attribute, ast.Subscript)):
            return
        attr_node = tgt
        while isinstance(attr_node, ast.Subscript):
            attr_node = attr_node.value
        if not isinstance(attr_node, ast.Attribute):
            return
        while isinstance(attr_node.value, (ast.Attribute, ast.Subscript)):
            attr_node = attr_node.value
            while isinstance(attr_node, ast.Subscript):
                attr_node = attr_node.value
            if not isinstance(attr_node, ast.Attribute):
                return
        if isinstance(attr_node.value, ast.Name) and \
                attr_node.value.id == "self":
            self.fn_stack[-1].self_writes.append(attr_node.attr)

    @staticmethod
    def _static_spec(call: ast.Call) -> Optional[Dict[str, Any]]:
        for kw in call.keywords:
            if kw.arg == "static_argnums":
                nums = []
                for elt in (kw.value.elts if isinstance(
                        kw.value, (ast.Tuple, ast.List)) else [kw.value]):
                    if isinstance(elt, ast.Constant) and \
                            isinstance(elt.value, int):
                        nums.append(elt.value)
                return {"argnums": nums, "argnames": [],
                        "line": call.lineno}
            if kw.arg == "static_argnames":
                names = []
                for elt in (kw.value.elts if isinstance(
                        kw.value, (ast.Tuple, ast.List)) else [kw.value]):
                    if isinstance(elt, ast.Constant) and \
                            isinstance(elt.value, str):
                        names.append(elt.value)
                return {"argnums": [], "argnames": names,
                        "line": call.lineno}
        return None

    # -- expressions ------------------------------------------------
    def visit_Attribute(self, node: ast.Attribute) -> None:
        if self.fn_stack and isinstance(node.ctx, ast.Load) and \
                isinstance(node.value, ast.Name) and \
                node.value.id == "self":
            self.fn_stack[-1].self_reads.append(node.attr)
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        # mesh fact layer: a Load of `pool[slot_ids]` is a pool-table
        # gather.  `.at[...]` chains are scatter TARGETS (recorded as
        # drop_scatters in visit_Call), not gathers — a chain through
        # `.at` is skipped.
        if self.fn_stack and isinstance(node.ctx, ast.Load) and \
                not isinstance(node.slice, ast.Constant):
            base = node.value
            while isinstance(base, ast.Subscript):
                base = base.value
            if not (isinstance(base, ast.Attribute) and
                    base.attr == "at"):
                bname = dotted_name(base)
                if pool_name(bname):
                    fn = self.fn_stack[-1]
                    slice_src = self._src_of(node.slice, 80)
                    prov = slice_src
                    if isinstance(node.slice, ast.Name):
                        prov += " " + fn.local_assigns.get(
                            node.slice.id, "")
                    if _SLOT_SLICE_RE.search(prov):
                        fn.slot_gathers.append(
                            (bname.rsplit(".", 1)[-1], slice_src,
                             node.lineno))
        self.generic_visit(node)

    def visit_Dict(self, node: ast.Dict) -> None:
        # telemetry event records built as dict literals ({"kind": ...})
        # — the xla.py drain-queue pattern
        for key, val in zip(node.keys, node.values):
            if isinstance(key, ast.Constant) and key.value == "kind":
                for arm in ([val.body, val.orelse]
                            if isinstance(val, ast.IfExp) else [val]):
                    if isinstance(arm, ast.Constant) and \
                            isinstance(arm.value, str):
                        self.s.events.append(
                            (arm.value, node.lineno, "kind-literal"))
        self.generic_visit(node)

    def _event_name(self, arg: ast.AST) -> Optional[str]:
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg.value
        if isinstance(arg, ast.JoinedStr) and arg.values and \
                isinstance(arg.values[0], ast.Constant):
            return str(arg.values[0].value) + "*"
        return None

    def visit_Call(self, node: ast.Call) -> None:
        name = call_name(node)
        if name is not None and self.fn_stack:
            self.fn_stack[-1].calls.append((name, node.lineno))
            self._record_conc_op(name, node)
        if name in _THREAD_FACTORIES:
            target = ""
            named = False
            for kw in node.keywords:
                if kw.arg == "target":
                    target = dotted_name(kw.value) or ""
                elif kw.arg == "name":
                    named = True
            self.s.thread_spawns.append(
                (target, node.lineno, named,
                 self.class_stack[-1] if self.class_stack else None,
                 self.fn_stack[-1].qual if self.fn_stack else ""))
        if name == "signal.signal" and len(node.args) >= 2:
            handler = dotted_name(node.args[1])
            if handler:
                self.s.signal_handlers.append(
                    (handler, node.lineno,
                     self.class_stack[-1] if self.class_stack else None))
        if name in _DEVGET_NAMES and self.fn_stack:
            arg_src = ast.unparse(node.args[0]) if node.args else ""
            self.fn_stack[-1].device_gets.append(
                (node.lineno, arg_src, self.loop_depth > 0))
        # trace roots from named function args (incl. functools.partial)
        if name in TRACE_ENTRY:
            cls = self.class_stack[-1] if self.class_stack else None
            for arg in node.args:
                ref = dotted_name(arg)
                if ref is None and isinstance(arg, ast.Call) and \
                        call_name(arg) in ("functools.partial", "partial"):
                    ref = arg.args and dotted_name(arg.args[0]) or None
                if not ref:
                    continue
                self.s.traced_roots.append((ref, cls))
                # mesh fact layer: the lane/shard_map split rides along
                # (shard-locality prohibits collectives in the former
                # and audits pool gathers in the latter)
                if name in _LANE_ENTRIES:
                    self.s.lane_roots.append(
                        (ref, cls,
                         self.fn_stack[-1].qual if self.fn_stack
                         else ""))
                elif name in _SHARD_MAP_ENTRIES:
                    self.s.shardmap_roots.append(
                        (ref, cls,
                         self.fn_stack[-1].qual if self.fn_stack
                         else "", node.lineno))
        # telemetry emissions
        tail = name.rsplit(".", 1)[-1] if name else None
        # -- mesh fact layer -------------------------------------------
        if self.fn_stack and tail in COLLECTIVE_OPS:
            axis: Optional[ast.AST] = \
                node.args[1] if len(node.args) > 1 else None
            for kw in node.keywords:
                if kw.arg == "axis_name":
                    axis = kw.value
            self.fn_stack[-1].collectives.append(
                (tail, node.lineno, axis_desc_of(axis)))
        elif self.fn_stack and tail == "axis_index":
            axis = node.args[0] if node.args else None
            for kw in node.keywords:
                if kw.arg == "axis_name":
                    axis = kw.value
            self.fn_stack[-1].collectives.append(
                ("axis_index", node.lineno, axis_desc_of(axis)))
        if tail in _PARTITION_SPEC_TAILS:
            for arg in node.args:
                if isinstance(arg, ast.Constant) and \
                        isinstance(arg.value, str):
                    self.s.spec_literals.append(
                        (arg.value, node.lineno))
        if name in _DEVICE_PUT_NAMES and node.args:
            spec: Optional[ast.AST] = \
                node.args[1] if len(node.args) > 1 else None
            for kw in node.keywords:
                if kw.arg in ("device", "sharding"):
                    spec = kw.value
            if spec is None:
                desc = "none"
            else:
                desc = spec_kind_of(spec)
                if desc is None:
                    dn = dotted_name(spec)
                    desc = f"name:{dn}" if dn else "dynamic"
            self.s.device_puts.append(
                (self._src_of(node.args[0], 80), desc, node.lineno,
                 self.fn_stack[-1].qual if self.fn_stack else ""))
        if self.fn_stack and isinstance(node.func, ast.Attribute) and \
                node.func.attr == "set":
            # `pool.at[slots].set(rows, mode="drop")` — the donated
            # fixed-shape page-in scatter
            mode = next((kw.value for kw in node.keywords
                         if kw.arg == "mode"), None)
            recv = node.func.value
            if isinstance(mode, ast.Constant) and mode.value == "drop" \
                    and isinstance(recv, ast.Subscript) and \
                    isinstance(recv.value, ast.Attribute) and \
                    recv.value.attr == "at":
                base = recv.value.value
                while isinstance(base, ast.Subscript):
                    base = base.value
                bname = dotted_name(base)
                if pool_name(bname):
                    self.fn_stack[-1].drop_scatters.append(
                        (bname.rsplit(".", 1)[-1], node.lineno))
        if tail in _EVENT_APIS:
            idx = _EVENT_APIS[tail]
            if len(node.args) > idx:
                ev = self._event_name(node.args[idx])
                if ev:
                    self.s.events.append((ev, node.lineno, tail))
        elif name and name.endswith(".event") and node.args:
            ev = self._event_name(node.args[0])
            if ev:
                self.s.events.append((ev, node.lineno, "event"))
        elif name and name.endswith("on_event") and node.args:
            ev = self._event_name(node.args[0])
            if ev:
                self.s.events.append((ev, node.lineno, "event"))
        if name and node.args:
            if name.endswith(".publish"):
                ev = self._event_name(node.args[0])
                if ev:
                    self.s.devbus.append((ev, node.lineno, "publish"))
            elif name.endswith("devbus_host"):
                ev = self._event_name(node.args[0])
                if ev:
                    self.s.devbus.append((ev, node.lineno,
                                          "devbus_host"))
        self.generic_visit(node)


def compute_module_summary(info: ModuleInfo,
                           known_paths: Optional[Set[str]] = None
                           ) -> ModuleSummary:
    """Extract ``info``'s :class:`ModuleSummary` (one AST walk)."""
    summary = ModuleSummary(info.path)
    visitor = _SummaryVisitor(info, summary)
    visitor._known_paths = known_paths or set()
    visitor.visit(info.tree)
    return summary


#: in-process summary cache: abspath -> (mtime_ns, size, summary).
#: Shared across analyze() calls so the tier-1 gate and the test suite
#: never re-summarize an unchanged file twice in one process.
_SUMMARY_CACHE: Dict[str, Tuple[int, int, ModuleSummary]] = {}


def _file_stamp(abspath: str) -> Tuple[int, int]:
    st = os.stat(abspath)
    return (st.st_mtime_ns, st.st_size)


class Project:
    """The project-wide call graph + reachability queries."""

    def __init__(self, root: str,
                 modules: Dict[str, ModuleSummary]):
        self.root = root
        self.modules = modules
        self._traced: Optional[Set[Tuple[str, str]]] = None

    # -- resolution --------------------------------------------------
    def resolve(self, module: str, ref: str,
                cls: Optional[str] = None
                ) -> Optional[Tuple[str, str]]:
        """Resolve a call/ref string written in ``module`` (optionally
        inside class ``cls``) to a ``(module, qual)`` function, or None
        when it points outside the project / cannot be proven."""
        mod = self.modules.get(module)
        if mod is None:
            return None
        if ref.startswith("self."):
            attr = ref.split(".", 1)[1]
            if "." in attr:
                return None  # self.a.b: attribute-of-attribute dispatch
            return self._resolve_method(module, cls, attr, set())
        if "." not in ref:
            qual = mod.name_index.get(ref)
            if qual is not None:
                return (module, qual)
            imp = mod.imports.get(ref)
            if imp is not None and imp[1] is not None:
                target_mod = self.modules.get(imp[0])
                if target_mod is not None:
                    qual = target_mod.name_index.get(imp[1])
                    if qual is not None:
                        return (imp[0], qual)
            return None
        head, rest = ref.split(".", 1)
        imp = mod.imports.get(head)
        if imp is not None and imp[1] is None and "." not in rest:
            target_mod = self.modules.get(imp[0])
            if target_mod is not None:
                qual = target_mod.name_index.get(rest)
                if qual is not None:
                    return (imp[0], qual)
        return None

    def _resolve_method(self, module: str, cls: Optional[str],
                        attr: str, seen: Set[Tuple[str, str]]
                        ) -> Optional[Tuple[str, str]]:
        """``self.attr`` -> the method, walking same-named base classes
        (resolved through imports) with a cycle guard."""
        if cls is None or (module, cls) in seen:
            return None
        seen.add((module, cls))
        mod = self.modules.get(module)
        if mod is None:
            return None
        qual = f"{cls}.{attr}"
        if qual in mod.functions:
            return (module, qual)
        for base in mod.class_bases.get(cls, []):
            base_name = base.rsplit(".", 1)[-1]
            if base_name in mod.class_bases or \
                    any(q.startswith(base_name + ".")
                        for q in mod.functions):
                found = self._resolve_method(module, base_name, attr,
                                             seen)
                if found:
                    return found
            imp = mod.imports.get(base.split(".")[0])
            if imp is not None:
                # both `from .base import BaseStrategy` (attr import)
                # and `from . import base` + `base.BaseStrategy`
                # (module import) resolve the base's METHODS in imp[0]
                found = self._resolve_method(imp[0], base_name, attr,
                                             seen)
                if found:
                    return found
        return None

    def function(self, key: Tuple[str, str]) -> Optional[FunctionSummary]:
        mod = self.modules.get(key[0])
        return mod.functions.get(key[1]) if mod else None

    # -- jitted bindings ---------------------------------------------
    def imported_jit_names(self, module: str) -> Set[str]:
        """Local names of ``module`` that are module-level jit-factory
        bindings in their DEFINING module — the cross-module half of
        host-sync's taint seeding."""
        mod = self.modules.get(module)
        if mod is None:
            return set()
        out: Set[str] = set()
        for local, (target, attr) in mod.imports.items():
            if attr is None:
                continue
            target_mod = self.modules.get(target)
            if target_mod is not None and attr in target_mod.jit_names:
                out.add(local)
        return out

    # -- trace-context closure ---------------------------------------
    def traced_reachable(self) -> Set[Tuple[str, str]]:
        """Every function that runs INSIDE a trace: named roots handed
        to jit/vmap/scan/... (including ``self._fn = jax.jit(body)``
        method bindings and decorator form), closed over the project
        call graph.  Cycles are fine (seen-set)."""
        if self._traced is not None:
            return self._traced
        frontier: List[Tuple[str, str]] = []
        for path, mod in self.modules.items():
            for ref, cls in mod.traced_roots:
                resolved = self.resolve(path, ref, cls)
                if resolved:
                    frontier.append(resolved)
        seen: Set[Tuple[str, str]] = set()
        while frontier:
            key = frontier.pop()
            if key in seen:
                continue
            seen.add(key)
            fn = self.function(key)
            if fn is None:
                continue
            for ref, _line in fn.calls:
                callee = self.resolve(key[0], ref, fn.cls)
                if callee and callee not in seen:
                    frontier.append(callee)
        self._traced = seen
        return seen

    # -- round-path closure (transfer-budget, signal-safety, ...) ----
    def reachable_from(self, roots: Iterable[Tuple[str, str]],
                       stop: Optional[re.Pattern] = None,
                       skip_edge: Optional[Any] = None
                       ) -> Dict[Tuple[str, str], Tuple[str, str]]:
        """BFS closure over the host call graph from ``roots``; returns
        ``{function: caller}`` back-edges (roots map to themselves).
        ``stop`` prunes callees whose BARE NAME matches (cadence
        boundaries: eval/checkpoint-class functions).  ``skip_edge`` is
        an optional ``(caller FunctionSummary, call line) -> bool``
        predicate pruning individual call edges (signal-safety's
        deferred-flush spans) — ONE closure walk serves every checker,
        so resolution improvements can never make them disagree."""
        parents: Dict[Tuple[str, str], Tuple[str, str]] = {}
        frontier = []
        for key in roots:
            if key not in parents:
                parents[key] = key
                frontier.append(key)
        while frontier:
            key = frontier.pop()
            fn = self.function(key)
            if fn is None:
                continue
            for ref, line in fn.calls:
                if skip_edge is not None and skip_edge(fn, line):
                    continue
                callee = self.resolve(key[0], ref, fn.cls)
                if callee is None or callee in parents:
                    continue
                callee_fn = self.function(callee)
                if callee_fn is None:
                    continue
                if stop is not None and stop.search(callee_fn.name):
                    continue
                parents[callee] = key
                frontier.append(callee)
        return parents

    def call_path(self, parents: Dict[Tuple[str, str], Tuple[str, str]],
                  key: Tuple[str, str]) -> List[str]:
        """Human-readable root -> ... -> key chain from a
        :meth:`reachable_from` result."""
        chain = [key]
        while parents.get(chain[-1]) not in (None, chain[-1]):
            chain.append(parents[chain[-1]])
        return [f"{m}::{q}" for m, q in reversed(chain)]


def build_project(root: str, project_files: List[str],
                  infos: Optional[Dict[str, ModuleInfo]] = None,
                  cache: Optional[Dict[str, Any]] = None) -> Project:
    """Summarize ``project_files`` (abs paths) into a :class:`Project`.

    ``infos`` carries already-parsed modules (the analyzed set) so no
    file is parsed twice.  ``cache`` is an optional disk-cache dict (see
    :func:`load_summary_cache`): entries whose (mtime_ns, size) stamp
    still matches are reused WITHOUT re-reading the file — the
    ``--changed`` incremental contract."""
    known = {os.path.relpath(p, root).replace(os.sep, "/")
             for p in project_files}
    modules: Dict[str, ModuleSummary] = {}
    for abspath in project_files:
        rel = os.path.relpath(abspath, root).replace(os.sep, "/")
        try:
            stamp = _file_stamp(abspath)
        except OSError:
            continue
        hit = _SUMMARY_CACHE.get(abspath)
        if hit is not None and (hit[0], hit[1]) == stamp:
            modules[rel] = hit[2]
            continue
        if cache is not None:
            entry = cache.get(rel)
            if entry is not None and \
                    tuple(entry.get("stamp", ())) == stamp:
                summary = ModuleSummary.from_dict(entry["summary"])
                modules[rel] = summary
                _SUMMARY_CACHE[abspath] = (stamp[0], stamp[1], summary)
                continue
        info = infos.get(rel) if infos else None
        if info is None:
            info = load_module(abspath, root)
        if getattr(info, "parse_error", None) is not None:
            continue
        summary = compute_module_summary(info, known)
        modules[rel] = summary
        _SUMMARY_CACHE[abspath] = (stamp[0], stamp[1], summary)
        if cache is not None:
            cache[rel] = {"stamp": list(stamp),
                          "summary": summary.to_dict()}
    return Project(os.path.abspath(root), modules)


# ----------------------------------------------------------------------
# disk summary cache (tools/flint --changed)
# ----------------------------------------------------------------------
_CACHE_VERSION = 1

#: version of the SUMMARY EXTRACTOR's output shape.  Disk-cache entries
#: are keyed by (mtime_ns, size) — stamps that do not change when the
#: ANALYZER changes — so without this key a new PR's extractor could be
#: served stale summaries missing its new fact fields and silently
#: report nothing.  Bump it whenever ModuleSummary/FunctionSummary gain,
#: lose or reinterpret a field; a mismatch discards the cache wholesale.
#: History: 1 = flint v2 (PR 9); 2 = concurrency fact layer
#: (lock regions, conc ops, thread spawns, signal handlers, assigns);
#: 3 = mesh fact layer (collectives, slot gathers/scatters, lane and
#: shard_map roots, sharding-spec bindings, device_put sites).
SUMMARY_SCHEMA_VERSION = 3


def default_cache_path(root: str) -> str:
    return os.path.join(root, ".flint_cache.json")


def load_summary_cache(path: str,
                       root: Optional[str] = None) -> Dict[str, Any]:
    """Entries are keyed by ROOT-relative path and their summaries
    carry root-relative module paths, so a cache warmed under a
    different analysis root must be discarded wholesale — reusing it
    would report findings at the wrong paths."""
    try:
        with open(path, "r", encoding="utf-8") as fh:
            raw = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return {}
    if raw.get("version") != _CACHE_VERSION:
        return {}
    if raw.get("schema") != SUMMARY_SCHEMA_VERSION:
        return {}  # summaries written by a different extractor: recompute
    if root is not None and raw.get("root") not in (None,
                                                   os.path.abspath(root)):
        return {}
    entries = raw.get("entries")
    return entries if isinstance(entries, dict) else {}


def save_summary_cache(path: str, cache: Dict[str, Any],
                       root: Optional[str] = None) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump({"version": _CACHE_VERSION,
                   "schema": SUMMARY_SCHEMA_VERSION,
                   "root": os.path.abspath(root) if root else None,
                   "entries": cache}, fh)
    os.replace(tmp, path)


def function_nodes(info: ModuleInfo) -> Dict[str, ast.AST]:
    """AST def nodes of ``info`` keyed by the SAME qualnames the
    summary extractor assigns — the bridge from a reachability answer
    back to a body to walk.  Memoized on the info (three checkers ask
    per file)."""
    cached = getattr(info, "_fn_nodes", None)
    if cached is not None:
        return cached
    out: Dict[str, ast.AST] = {}

    def walk(node: ast.AST, prefix: str, in_fn: bool) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                walk(child, prefix if in_fn else prefix + child.name + ".",
                     in_fn)
            elif isinstance(child, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                qual = prefix + child.name
                out[qual] = child
                walk(child, qual + ".", True)
            else:
                walk(child, prefix, in_fn)

    walk(info.tree, "", False)
    info._fn_nodes = out  # type: ignore[attr-defined]
    return out


# ----------------------------------------------------------------------
# runner
# ----------------------------------------------------------------------
def _iter_py_files(paths: List[str]) -> List[str]:
    files: List[str] = []
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            files.append(os.path.abspath(p))
        elif os.path.isdir(p):
            for base, dirs, names in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git")]
                for name in sorted(names):
                    if name.endswith(".py"):
                        files.append(os.path.abspath(
                            os.path.join(base, name)))
    return sorted(set(files))


def load_module(abspath: str, root: str) -> ModuleInfo:
    rel = os.path.relpath(abspath, root).replace(os.sep, "/")
    with open(abspath, "r", encoding="utf-8") as fh:
        src = fh.read()
    try:
        tree = ast.parse(src, filename=abspath)
    except SyntaxError as exc:
        info = ModuleInfo(rel, abspath, src, ast.Module(body=[],
                                                        type_ignores=[]),
                          src.splitlines())
        info.parse_error = exc  # type: ignore[attr-defined]
        return info
    return ModuleInfo(rel, abspath, src, tree, src.splitlines())


def analyze(paths: List[str], root: Optional[str] = None,
            rules: Optional[Set[str]] = None,
            project_paths: Optional[List[str]] = None,
            cache: Optional[Dict[str, Any]] = None,
            with_project_checkers: bool = True) -> List[Finding]:
    """Run every checker over ``paths``; returns suppression-filtered
    findings (baseline NOT applied — that is the caller's policy).

    ``project_paths`` widens the CALL-GRAPH scope beyond the analyzed
    set (``--changed`` analyzes the edited files against the whole
    package's summaries); findings are only emitted for ``paths``.
    ``cache`` is a disk-cache dict (:func:`load_summary_cache`) updated
    in place.  ``with_project_checkers=False`` skips the project-level
    checkers (schema-drift, guard-matrix, event-schema,
    transfer-budget) — the incremental mode's call when none of their
    inputs changed."""
    from . import (atomic_write, collective_budget, donation,
                   event_schema, guard_matrix, host_sync, jit_purity,
                   lock_discipline, mesh_axis, pallas_shape, put_loop,
                   recompile_hazard, schema_drift, shard_locality,
                   shard_ready, signal_safety, spec_drift,
                   thread_escape, transfer_budget)

    root = os.path.abspath(root or os.getcwd())
    files = _iter_py_files(paths)
    proj_files = sorted(set(files) | set(
        _iter_py_files(project_paths or [])))

    # parse the analyzed set once; summaries for the rest come from the
    # caches (or a fresh parse on a cold run)
    infos: Dict[str, ModuleInfo] = {}
    findings: List[Finding] = []
    suppressions: List[Suppression] = []
    analyzed_rel: Set[str] = set()
    for abspath in files:
        info = load_module(abspath, root)
        analyzed_rel.add(info.path)
        if getattr(info, "parse_error", None) is not None:
            exc = info.parse_error  # type: ignore[attr-defined]
            findings.append(Finding("parse-error", info.path,
                                    exc.lineno or 1, str(exc.msg)))
            continue
        infos[info.path] = info
        suppressions.extend(parse_suppressions(info))

    project = build_project(root, proj_files, infos=infos, cache=cache)

    # project-level findings can land in files OUTSIDE the analyzed set
    # (a transfer-budget finding in an unchanged engine file whose
    # round path a changed helper joined; an event-schema finding in a
    # telemetry module a subset run never named) — their pragmas must
    # still suppress, so parse the WHOLE package's pragmas too, out of
    # hygiene scope
    if with_project_checkers:
        pragma_files = set(proj_files)
        pkg_dir = os.path.join(root, "msrflute_tpu")
        if os.path.isdir(pkg_dir):
            pragma_files |= set(_iter_py_files([pkg_dir]))
        for abspath in sorted(pragma_files):
            rel = os.path.relpath(abspath, root).replace(os.sep, "/")
            if rel in analyzed_rel:
                continue
            info = load_module(abspath, root)
            if getattr(info, "parse_error", None) is not None:
                continue
            for sup in parse_suppressions(info):
                sup.in_scope = False
                suppressions.append(sup)

    per_file_checkers = [
        (host_sync.RULE, lambda i: host_sync.check(i, project)),
        (donation.RULE, donation.check),
        (jit_purity.RULE, lambda i: jit_purity.check(i, project)),
        (pallas_shape.RULE, pallas_shape.check),
        (put_loop.RULE, put_loop.check),
        (shard_ready.RULE, lambda i: shard_ready.check(i, project)),
        (recompile_hazard.RULE,
         lambda i: recompile_hazard.check(i, project)),
        (atomic_write.RULE, atomic_write.check),
        (mesh_axis.RULE, lambda i: mesh_axis.check(i, project)),
        (spec_drift.RULE, lambda i: spec_drift.check(i, project)),
    ]
    for rel in sorted(infos):
        info = infos[rel]
        for rule, check in per_file_checkers:
            if rules and rule not in rules:
                continue
            findings.extend(check(info))

    if with_project_checkers:
        if rules is None or transfer_budget.RULE in rules:
            findings.extend(transfer_budget.check_project(
                project, emit_paths=analyzed_rel
                if project_paths else None))
        if rules is None or schema_drift.RULE in rules:
            findings.extend(schema_drift.check_project(root))
        if rules is None or guard_matrix.RULE in rules:
            findings.extend(guard_matrix.check_project(
                root, trees={rel: i.tree for rel, i in infos.items()}))
        if rules is None or event_schema.RULE in rules:
            findings.extend(event_schema.check_project(
                root, modules=project.modules))
        emit = analyzed_rel if project_paths else None
        if rules is None or signal_safety.RULE in rules:
            findings.extend(signal_safety.check_project(
                project, emit_paths=emit))
        if rules is None or lock_discipline.RULE in rules:
            findings.extend(lock_discipline.check_project(
                project, emit_paths=emit))
        if rules is None or thread_escape.RULE in rules:
            findings.extend(thread_escape.check_project(
                project, emit_paths=emit))
        if rules is None or shard_locality.RULE in rules:
            findings.extend(shard_locality.check_project(
                project, emit_paths=emit))
        if rules is None or collective_budget.RULE in rules:
            findings.extend(collective_budget.check_project(
                root, project))
        # project-checker findings live in .py/.md files that may carry
        # inline pragmas; .md pragmas are not a thing, which is fine
        # because the actionable end of a doc drift is the doc itself.

    # staleness is judged only for rules that RAN AND APPLIED: a
    # doc-vs-code checker that returned early (tree without its doc /
    # schema inputs, or a --changed run that skipped project checkers)
    # must not mark its pragmas stale
    active = set(rules) if rules is not None else set(RULES)
    project_rules = {transfer_budget.RULE, schema_drift.RULE,
                     guard_matrix.RULE, event_schema.RULE,
                     signal_safety.RULE, lock_discipline.RULE,
                     thread_escape.RULE, shard_locality.RULE,
                     collective_budget.RULE}
    if not with_project_checkers:
        active -= project_rules
    else:
        pkg = os.path.join(root, "msrflute_tpu")
        if not (os.path.exists(os.path.join(pkg, "schema.py")) and
                os.path.exists(os.path.join(pkg, "config.py"))):
            active.discard(schema_drift.RULE)
        if not (os.path.exists(os.path.join(pkg, "engine", "server.py"))
                and os.path.exists(os.path.join(pkg, "schema.py"))):
            active.discard(guard_matrix.RULE)
        if not os.path.exists(os.path.join(root, "docs",
                                           "observability.md")):
            active.discard(event_schema.RULE)
        if not os.path.exists(os.path.join(root, "docs",
                                           "architecture.md")):
            active.discard(collective_budget.RULE)
    return apply_suppressions(findings, suppressions,
                              active_rules=active)
