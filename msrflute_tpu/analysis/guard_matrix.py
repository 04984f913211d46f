"""guard-matrix — the refusal matrix, cross-checked layer by layer.

The features that only work on the fused round path (``robust``
screening, ``chaos`` client faults, ``cohort_bucketing``) are guarded
by THREE layers that historically desync:

1. **runtime refusals** — ``raise ValueError`` guards in
   ``engine/server.py`` / ``engine/round.py`` / ``strategies/*.py``
   keyed off the ``host_orchestrated`` predicate and per-feature
   incompatibility checks;
2. **schema bespoke checks** — config-load-time errors in ``schema.py``
   for the incompatibilities already decidable from the raw config
   (robust x strategy, fedbuff x strategy);
3. **documentation** — the per-feature compatibility tables in
   ``docs/config_extensions.md`` ("Refused with ...", "Incompatible
   with ...").

A new strategy or config block can dodge ONE layer silently; it cannot
dodge this rule:

- every strategy-class host marker (a class-level ``*_rounds = True``
  in ``strategies/``) must be consulted by the ``host_orchestrated``
  predicate in ``engine/server.py``;
- every guarded block must have a runtime refusal naming it;
- every incompatibility a runtime refusal names (tokens from
  :data:`VOCAB`) must appear in that block's
  ``docs/config_extensions.md`` section — the operator-facing table
  can't silently lag the code;
- every incompatibility the DOCS promise ("Refused/Incompatible with
  `X`") must appear in some runtime refusal or schema check for that
  block — the code can't silently drop a documented guard;
- every COMPOSITION the docs promise ("Composes with `X` ...
  (`tests/test_y.py`)") must cite a test file, and the cited file must
  actually exercise each composed :data:`VOCAB` token — a compatibility
  claim nobody tests is the refusal matrix's mirror-image failure
  (the pair runs, silently wrong, instead of refusing);
- a documented composition must not be CONTRADICTED by a live runtime
  refusal: if the block's docs claim it composes with token `X` while
  one of the block's refusal messages still says `X` "does not compose
  with"/"is incompatible with" it, one of the two layers is stale —
  exactly what happens when a refusal is lifted in docs but a guard
  site is missed (or re-introduced by a revert);
- blocks in :data:`SCHEMA_GUARDED` must keep their config-load-time
  strategy check in ``schema.py``.

All literal extraction (raise-message string constants, doc sections);
no imports of the checked modules.
"""

from __future__ import annotations

import ast
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from .core import Finding

RULE = "guard-matrix"

#: config blocks that require the fused round path at runtime
GUARDED_BLOCKS = ("robust", "chaos", "cohort_bucketing", "megabatch",
                  # fluteflow arrival plane (PR 19): the refusal ladder
                  # covers host-orchestrated rounds, the buffer==cohort
                  # geometry, fleet sampling modes, the secure_agg
                  # liveness floor, and megabatch x traced staleness
                  "traffic",
                  # the chunk scan over the cohort (PR 28 made
                  # `clients_per_chunk: 1` the path of a 1.9 GB tree):
                  # every path that needs the whole cohort's payloads at
                  # once still refuses it, and the docs say which
                  "clients_per_chunk")

#: the incompatibility vocabulary the matrix is checked over: config
#: keys, strategy names and flags that appear in refusal messages and
#: compatibility tables.  A token outside this list is prose, not a
#: matrix cell.
VOCAB = ("wantRL", "scaffold", "ef_quant", "personalization",
         "clients_per_chunk", "adaptive_clipping", "dump_norm_stats",
         "secure_agg", "fused_carry", "stale_prob",
         "fedavg", "fedprox",
         # cross-client megabatching refusal tokens (PR 16)
         "apply_metrics", "fedlabels", "pallas_apply",
         # fleet/mesh-era composition tokens (PR 17): strategies that
         # pre-bucket their cohort and the paged-carry interplay
         "wants_cohort",
         # fluteflow arrival-plane token (PR 19): the traffic block
         # itself, so other blocks' traffic refusals are matrix cells
         "traffic")

#: blocks whose strategy incompatibility is decidable at config load —
#: schema.py must carry the bespoke check (the quiet-failure rule)
SCHEMA_GUARDED = ("robust", "fedbuff", "megabatch")

#: class-attr suffix marking a strategy as host-orchestrated; every
#: marker any strategy sets must appear in the predicate
MARKER_SUFFIX = "_rounds"

_DOC_REFUSAL_RE = re.compile(
    r"(refused with|incompatible with|rejected under)", re.I)

#: composition-claim sentence start / end-of-claim boundaries (the
#: refusal sentence usually follows in the SAME paragraph)
_COMPOSE_RE = re.compile(r"composes with", re.I)
_COMPOSE_END_RE = re.compile(
    r"Refused with|Requires |Incompatible with|Rejected under")
_TEST_CITE_RE = re.compile(r"`(tests/[\w\-/]+\.py)`")

#: refusal phrasings that flatly deny a composition — a raise carrying
#: one of these next to a token the docs CLAIM to compose with marks a
#: stale guard site (refusal lifted in docs, missed in code).  Refusals
#: that merely constrain HOW a pair composes ("use aggregator: mean")
#: must avoid this phrasing — that's the convention this layer enforces.
_CONTRADICT_RE = re.compile(
    r"(does not compose with|incompatible with)", re.I)


def _parse(path: str, trees: Optional[Dict[str, ast.Module]],
           root: str) -> Optional[ast.Module]:
    if trees is not None:
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        tree = trees.get(rel)
        if tree is not None:
            return tree
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return ast.parse(fh.read(), filename=path)
    except (OSError, SyntaxError):
        return None


def _raise_texts(tree: Optional[ast.Module]) -> List[Tuple[int, str]]:
    """(line, concatenated-constant-text) for every ``raise X(msg)``."""
    if tree is None:
        return []
    out: List[Tuple[int, str]] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Raise) and
                isinstance(node.exc, ast.Call) and node.exc.args):
            continue
        parts: List[str] = []
        for sub in ast.walk(node.exc.args[0]):
            if isinstance(sub, ast.Constant) and \
                    isinstance(sub.value, str):
                parts.append(sub.value)
        if parts:
            out.append((node.lineno, " ".join(parts)))
    return out


def _string_constants(tree: Optional[ast.Module]) -> List[str]:
    if tree is None:
        return []
    return [node.value for node in ast.walk(tree)
            if isinstance(node, ast.Constant) and
            isinstance(node.value, str)]


def _doc_section(doc_lines: List[str], block: str
                 ) -> Optional[Tuple[int, List[str]]]:
    """The config_extensions section for ``block``: from the heading
    mentioning ``server_config.<block>`` (or the block's table row) to
    the next heading of the same or higher level."""
    needle = f"server_config.{block}"
    start = level = None
    for i, line in enumerate(doc_lines):
        if line.lstrip().startswith("#") and needle in line:
            start = i
            level = len(line) - len(line.lstrip("#"))
            break
    if start is None:
        return None
    end = len(doc_lines)
    for i in range(start + 1, len(doc_lines)):
        line = doc_lines[i]
        if line.startswith("#") and \
                len(line) - len(line.lstrip("#")) <= (level or 1):
            end = i
            break
    return (start + 1, doc_lines[start:end])


def _tokens_in(text: str) -> List[str]:
    low = text.lower()
    return [t for t in VOCAB if t.lower() in low]


def check_project(root: str,
                  trees: Optional[Dict[str, ast.Module]] = None
                  ) -> List[Finding]:
    """``trees`` optionally carries already-parsed module ASTs keyed by
    rel path (the analyze() fast path); files absent from it are parsed
    from disk."""
    pkg = os.path.join(root, "msrflute_tpu")
    server_path = os.path.join(pkg, "engine", "server.py")
    schema_path = os.path.join(pkg, "schema.py")
    doc_path = os.path.join(root, "docs", "config_extensions.md")
    if not (os.path.exists(server_path) and os.path.exists(schema_path)):
        return []  # not a tree this checker applies to

    rel_server = os.path.relpath(server_path, root).replace(os.sep, "/")
    rel_schema = os.path.relpath(schema_path, root).replace(os.sep, "/")
    findings: List[Finding] = []

    with open(server_path, "r", encoding="utf-8") as fh:
        server_src = fh.read()

    # ---- 1. strategy host markers all reach the predicate ------------
    strategy_files = sorted(
        glob.glob(os.path.join(pkg, "strategies", "*.py")))
    markers: Dict[str, str] = {}  # marker attr -> defining file::class
    for spath in strategy_files:
        tree = _parse(spath, trees, root)
        if tree is None:
            continue
        rel = os.path.relpath(spath, root).replace(os.sep, "/")
        for node in ast.walk(tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for stmt in node.body:
                if isinstance(stmt, ast.Assign) and \
                        len(stmt.targets) == 1 and \
                        isinstance(stmt.targets[0], ast.Name) and \
                        stmt.targets[0].id.endswith(MARKER_SUFFIX) and \
                        isinstance(stmt.value, ast.Constant) and \
                        stmt.value.value is True:
                    markers.setdefault(stmt.targets[0].id,
                                       f"{rel}::{node.name}")
    for marker, where in sorted(markers.items()):
        if marker not in server_src:
            findings.append(Finding(
                RULE, rel_server, 1,
                f"strategy host marker `{marker}` (set by {where}) is "
                "not consulted by engine/server.py — its strategy "
                "dodges the host_orchestrated refusal matrix",
                hint="add `getattr(self.strategy, '" + marker + "', "
                     "False)` to the host_orchestrated predicate (and "
                     "to _pipeline_capable if it forces serial)"))

    # ---- gather runtime refusal texts per guarded block --------------
    guard_files = sorted(
        glob.glob(os.path.join(pkg, "engine", "*.py")) +
        glob.glob(os.path.join(pkg, "strategies", "*.py")) +
        glob.glob(os.path.join(pkg, "robust", "*.py")))
    block_raises: Dict[str, List[Tuple[str, int, str]]] = \
        {b: [] for b in GUARDED_BLOCKS}
    for gpath in guard_files:
        rel = os.path.relpath(gpath, root).replace(os.sep, "/")
        for line, text in _raise_texts(_parse(gpath, trees, root)):
            for block in GUARDED_BLOCKS:
                if block in text:
                    block_raises[block].append((rel, line, text))

    doc_lines: List[str] = []
    if os.path.exists(doc_path):
        with open(doc_path, "r", encoding="utf-8") as fh:
            doc_lines = fh.read().splitlines()
    rel_doc = os.path.relpath(doc_path, root).replace(os.sep, "/") \
        if doc_lines else None

    schema_tree = _parse(schema_path, trees, root)
    schema_strings = _string_constants(schema_tree)
    # the matrix only covers blocks this tree's schema actually knows —
    # a fork that dropped cohort_bucketing owes no guard for it
    server_keys: set = set()
    if schema_tree is not None:
        for node in schema_tree.body:
            if isinstance(node, ast.Assign) and \
                    len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name) and \
                    node.targets[0].id == "SERVER_KEYS" and \
                    isinstance(node.value, ast.Set):
                server_keys = {e.value for e in node.value.elts
                               if isinstance(e, ast.Constant)}

    for block in GUARDED_BLOCKS:
        if server_keys and block not in server_keys:
            continue
        raises = block_raises[block]
        # ---- 2. runtime layer exists ---------------------------------
        if not raises:
            findings.append(Finding(
                RULE, rel_server, 1,
                f"guarded block `{block}` has no runtime refusal in "
                "engine/ or strategies/ — a host-orchestrated config "
                "would silently run it degraded",
                hint="raise at server construction when "
                     f"server_config.{block} meets an incompatible "
                     "path, like the robust/chaos guards"))
            continue
        if not doc_lines:
            continue
        section = _doc_section(doc_lines, block)
        if section is None:
            findings.append(Finding(
                RULE, rel_doc or rel_server, 1,
                f"guarded block `{block}` has runtime refusals but no "
                "docs/config_extensions.md section",
                hint="add the per-key table + compatibility notes the "
                     "other blocks carry"))
            continue
        sec_line, sec_lines = section
        sec_text = "\n".join(sec_lines)
        # ---- 3. code -> docs: every refusal token is documented ------
        code_tokens = sorted({t for _, _, text in raises
                              for t in _tokens_in(text)})
        for token in code_tokens:
            if token.lower() not in sec_text.lower():
                src = ", ".join(sorted({f"{rel}:{line}"
                                        for rel, line, text in raises
                                        if token in _tokens_in(text)}))
                findings.append(Finding(
                    RULE, rel_doc, sec_line,
                    f"`server_config.{block}` refuses `{token}` at "
                    f"runtime ({src}) but its config_extensions "
                    "section never mentions it",
                    hint="add the incompatibility to the section's "
                         "'Refused with'/'Incompatible with' list"))
        # ---- 4. docs -> code: every documented refusal is enforced ---
        doc_tokens: List[Tuple[int, str]] = []
        for i, line in enumerate(sec_lines):
            if not _DOC_REFUSAL_RE.search(line):
                continue
            # the refusal sentence may wrap: scan to the next blank line
            chunk: List[str] = []
            for j in range(i, len(sec_lines)):
                if not sec_lines[j].strip():
                    break
                chunk.append(sec_lines[j])
            joined = " ".join(chunk)
            # a composition sentence sharing the paragraph is NOT part
            # of the refusal list (layer 5 owns its tokens)
            comp = _COMPOSE_RE.search(joined)
            if comp is not None:
                joined = joined[:comp.start()]
            for token in _tokens_in(joined):
                doc_tokens.append((sec_line + i, token))
        enforced = " ".join(text for _, _, text in raises) + " " + \
            " ".join(s for s in schema_strings if block in s)
        enforced_tokens = set(_tokens_in(enforced))
        for line_no, token in sorted(set(doc_tokens)):
            if token not in enforced_tokens:
                findings.append(Finding(
                    RULE, rel_doc, line_no,
                    f"docs promise `server_config.{block}` is refused "
                    f"with `{token}`, but no runtime guard or schema "
                    "check enforces it",
                    hint="re-add the refusal or fix the doc — an "
                         "unenforced compatibility table is how silent "
                         "corruption ships"))

        # ---- 5. composition claims are exercised by the cited test ---
        # "Composes with A, B (`tests/test_x.py`)" is a promise with the
        # same weight as a refusal: each VOCAB token in the claim must
        # appear in the cited test file (the composition-case suite),
        # and the claim must cite one at all.
        blob = " ".join(sec_lines)
        claimed_tokens: set = set()
        for m in _COMPOSE_RE.finditer(blob):
            end = _COMPOSE_END_RE.search(blob, m.end())
            chunk = blob[m.start():end.start() if end else len(blob)]
            comp_tokens = _tokens_in(chunk)
            claimed_tokens.update(comp_tokens)
            claim_line = sec_line
            for i, line in enumerate(sec_lines):
                if _COMPOSE_RE.search(line):
                    claim_line = sec_line + i
                    break
            cite = _TEST_CITE_RE.search(chunk)
            if cite is None:
                if comp_tokens:
                    findings.append(Finding(
                        RULE, rel_doc, claim_line,
                        f"`server_config.{block}` claims to compose "
                        f"with {', '.join(f'`{t}`' for t in comp_tokens)}"
                        " but cites no test file for the claim",
                        hint="append the composition suite citation "
                             "(`tests/test_<block>.py`) the other "
                             "blocks carry — an uncited composition "
                             "claim is unfalsifiable"))
                continue
            cite_path = os.path.join(root, cite.group(1))
            if not os.path.exists(cite_path):
                findings.append(Finding(
                    RULE, rel_doc, claim_line,
                    f"`server_config.{block}`'s composition claim "
                    f"cites `{cite.group(1)}`, which does not exist",
                    hint="fix the citation or add the suite"))
                continue
            with open(cite_path, "r", encoding="utf-8") as fh:
                cite_src = fh.read()
            for token in comp_tokens:
                if token not in cite_src:
                    findings.append(Finding(
                        RULE, rel_doc, claim_line,
                        f"docs promise `server_config.{block}` composes "
                        f"with `{token}`, but the cited "
                        f"`{cite.group(1)}` never exercises that "
                        "config-key combination",
                        hint="add the composition case (the suite's "
                             "COMPOSE_CASES pattern: run the pair, "
                             "assert bitwise parity with the unfused "
                             "path) or drop the claim — an untested "
                             "composition promise ships the silent "
                             "version of a missing refusal"))

        # ---- 5b. claims vs refusals: no contradiction ----------------
        # a composition the docs promise for this block must not still
        # be flatly refused by one of the block's own guard sites — the
        # config would raise on exactly the pair the docs advertise.
        for token in sorted(claimed_tokens):
            for rel, line, text in raises:
                if token in _tokens_in(text) and \
                        _CONTRADICT_RE.search(text):
                    findings.append(Finding(
                        RULE, rel, line,
                        f"docs claim `server_config.{block}` composes "
                        f"with `{token}`, but this refusal still says "
                        "it does not — a stale guard site (or a stale "
                        "claim)",
                        hint="lift the refusal (and cover the pair in "
                             "the cited composition suite) or retract "
                             "the docs claim; a refusal that only "
                             "constrains HOW the pair composes should "
                             "avoid 'does not compose with'/"
                             "'incompatible with' phrasing"))

    # ---- 6. schema bespoke layer -------------------------------------
    for block in SCHEMA_GUARDED:
        if server_keys and block not in server_keys:
            continue  # a fork that dropped the block owes no guard
        held = any(block in s and "strategy" in s
                   for s in schema_strings)
        if not held:
            findings.append(Finding(
                RULE, rel_schema, 1,
                f"`server_config.{block}` has no config-load-time "
                "strategy check in schema.py — the refusal only fires "
                "at server construction",
                hint="add the bespoke validate() error (the "
                     "secure_agg/fedbuff quiet-failure rule): the "
                     "strategy incompatibility is decidable from the "
                     "raw config"))
    return findings
