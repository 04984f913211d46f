"""host-sync — implicit device->host syncs in hot-path modules.

The whole PR-1 pipeline story rests on one invariant: a faithful-mode
round pays exactly ONE explicit ``jax.device_get`` per dtype group (the
flatpack fetch) and nothing else crosses the device->host boundary.  An
accidental ``float(device_scalar)`` blocks the host on the in-flight
program and costs a device->host round trip per scalar
(``tools/dispatch_cost_probe.py`` measures it).

Flagged, in ``engine/``, ``ops/``, ``strategies/`` modules only:

- ``x.item()`` — the canonical per-scalar sync;
- ``float(x)`` / ``int(x)`` / ``bool(x)`` where ``x`` is device-tainted;
- ``np.asarray(x)`` / ``np.array(x)`` where ``x`` is device-tainted
  (implicit transfer; ``jax.device_get`` is the explicit spelling);
- ``jax.device_get(tree[field])`` — a per-field fetch: fetching members
  of one device tree in separate calls pays one transfer each; fetch
  the whole tree once (the flatpack discipline);
- ``print``/``print_rank``/``log_metric``/``logging`` of a
  device-tainted value — stringification forces the sync.

Device taint is tracked per function scope, seeded by:

- calls to ``jnp.*`` / ``jax.random.*`` / ``jax.lax.*`` / ``jax.nn.*``;
- calls through bindings created from ``jax.jit(...)`` /
  ``shard_map(...)`` / ``jax.pmap(...)`` / ``pl.pallas_call(...)``
  anywhere in the module — including ``self._fn = jax.jit(...)`` in one
  method called as ``self._fn(...)`` in another;
- subscripts/attributes of tainted values; tuple-unpacks of tainted
  calls taint every target.

``jax.device_get(...)`` results are host values and CLEAR taint, as
does rebinding a name to an untainted value.  Since flint v2 the taint
seeding is interprocedural: a name IMPORTED from another project module
where it is bound to a jit-factory result taints its call results here
too (``Project.imported_jit_names``).  VALUE flows across modules are
still the runtime strict mode's job (``MSRFLUTE_STRICT_TRANSFERS=1``,
docs/RUNBOOK.md).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from .core import (JIT_FACTORIES, Finding, ModuleInfo, Project,
                   call_name, dotted_name)

RULE = "host-sync"

#: call-name prefixes whose results live on device
_DEVICE_PREFIXES = ("jnp.", "jax.numpy.", "jax.random.", "jax.lax.",
                    "jax.nn.", "optax.")
#: factories whose RESULT is a compiled callable (module-level tracking)
_JIT_FACTORIES = JIT_FACTORIES
_LOG_SINKS = {"print", "print_rank", "log_metric"}


def _collect_jitted_bindings(tree: ast.Module):
    """Names / ``self.<attr>``s bound to a jit-factory result anywhere in
    the module (method boundaries deliberately ignored: ``__init__``
    builds the callable, the round method calls it)."""
    names: Set[str] = set()
    attrs: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        if not (isinstance(value, ast.Call) and
                call_name(value) in _JIT_FACTORIES):
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                names.add(tgt.id)
            elif isinstance(tgt, ast.Attribute) and \
                    isinstance(tgt.value, ast.Name) and \
                    tgt.value.id == "self":
                attrs.add(tgt.attr)
    return names, attrs


class _ScopeTaint(ast.NodeVisitor):
    """One function scope's device-taint walk (statement order)."""

    def __init__(self, info: ModuleInfo, jit_names: Set[str],
                 jit_attrs: Set[str], findings: List[Finding]):
        self.info = info
        self.jit_names = jit_names
        self.jit_attrs = jit_attrs
        self.findings = findings
        self.tainted: Set[str] = set()
        #: per-field device_get candidates, flagged at scope end only if
        #: the scope fetches more than once (a lone string-key pick out
        #: of a host dict is one honest transfer)
        self.devget_count = 0
        self.devget_field_picks: List[Finding] = []

    # -- taint queries --------------------------------------------------
    def _is_jitted_callable(self, func: ast.AST) -> bool:
        name = dotted_name(func)
        if name is None:
            return False
        if name in self.jit_names:
            return True
        return name.startswith("self.") and \
            name.split(".", 1)[1] in self.jit_attrs

    def is_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, (ast.Name, ast.Attribute)):
            name = dotted_name(node)
            if name is None:
                return False
            # any tainted prefix taints the whole chain (state.params
            # when `state` is tainted)
            parts = name.split(".")
            return any(".".join(parts[:i]) in self.tainted
                       for i in range(1, len(parts) + 1))
        if isinstance(node, ast.Subscript):
            return self.is_tainted(node.value)
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name is None:
                return False
            if name in ("jax.device_get", "device_get"):
                return False  # explicit fetch: result is host memory
            if name.startswith(_DEVICE_PREFIXES):
                return True
            return self._is_jitted_callable(node.func)
        if isinstance(node, ast.BinOp):
            return self.is_tainted(node.left) or self.is_tainted(node.right)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.is_tainted(e) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return self.is_tainted(node.body) or self.is_tainted(node.orelse)
        return False

    # -- assignments update taint ---------------------------------------
    def _bind(self, target: ast.AST, tainted: bool) -> None:
        if isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind(elt, tainted)
            return
        name = dotted_name(target)
        if name is None:
            return
        if tainted:
            self.tainted.add(name)
        else:
            self.tainted.discard(name)

    def visit_Assign(self, node: ast.Assign) -> None:
        self.visit(node.value)
        tainted = self.is_tainted(node.value)
        for tgt in node.targets:
            self._bind(tgt, tainted)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self.visit(node.value)
        if self.is_tainted(node.value):
            self._bind(node.target, True)

    def visit_For(self, node: ast.For) -> None:
        self.visit(node.iter)
        self._bind(node.target, self.is_tainted(node.iter))
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        pass  # nested scopes get their own walk

    visit_AsyncFunctionDef = visit_FunctionDef

    # -- the flags ------------------------------------------------------
    def _flag(self, node: ast.AST, message: str, hint: str) -> None:
        self.findings.append(Finding(RULE, self.info.path, node.lineno,
                                     message, hint))

    def visit_Call(self, node: ast.Call) -> None:
        name = call_name(node)
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr == "item" and not node.args:
            self._flag(node,
                       f"`{ast.unparse(node.func.value)}.item()` forces a "
                       "per-scalar device->host sync",
                       "batch the value into the packed-stats fetch "
                       "(utils/flatpack.py) or one explicit "
                       "jax.device_get of the whole tree")
        elif name in ("float", "int", "bool") and len(node.args) == 1 and \
                self.is_tainted(node.args[0]):
            self._flag(node,
                       f"`{name}({ast.unparse(node.args[0])})` blocks the "
                       "host on an in-flight device value",
                       "keep it on device, or fetch explicitly with "
                       "jax.device_get bundled with the round's other "
                       "host reads")
        elif name in ("np.asarray", "np.array", "numpy.asarray",
                      "numpy.array") and node.args and \
                self.is_tainted(node.args[0]):
            self._flag(node,
                       f"`{name}(...)` on a device value is an implicit "
                       "transfer",
                       "use jax.device_get (explicit, and visible to "
                       "jax.transfer_guard strict mode)")
        elif name in ("jax.device_get", "device_get"):
            self.devget_count += 1
            if node.args and isinstance(node.args[0], ast.Subscript) and \
                    isinstance(node.args[0].slice, ast.Constant) and \
                    isinstance(node.args[0].slice.value, str):
                # string-key subscript = picking ONE member out of a
                # stats dict (`stats["mag"]`); an array index
                # (`table[ids]`) is an on-device gather whose
                # device_get is one honest transfer
                self.devget_field_picks.append(Finding(
                    RULE, self.info.path, node.lineno,
                    f"per-field fetch "
                    f"`{name}({ast.unparse(node.args[0])})` pays one "
                    "transfer per member",
                    "device_get the whole tree once and index on host "
                    "(the flatpack single-transfer discipline)"))
        elif name in _LOG_SINKS or (name or "").startswith(
                ("logging.", "logger.", "_LOGGER.")):
            for arg in node.args:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.FormattedValue):
                        val = sub.value
                    elif isinstance(sub, (ast.Name, ast.Attribute)) and \
                            sub is arg:
                        val = sub
                    else:
                        continue
                    if self.is_tainted(val):
                        self._flag(
                            node,
                            f"logging `{ast.unparse(val)}` stringifies a "
                            "device value (hidden sync)",
                            "jax.device_get it first (bundled with the "
                            "round's other host reads)")
                        break
        self.generic_visit(node)


def check(info: ModuleInfo,
          project: Optional[Project] = None) -> List[Finding]:
    if not info.is_hot_path:
        return []
    summary = project.modules.get(info.path) if project else None
    if summary is not None:
        # flint v2: the module summary already extracted the bindings,
        # and imported compiled callables (module-level
        # ``step = jax.jit(...)`` in another project file) seed taint
        # exactly like locally-built ones
        jit_names = set(summary.jit_names) | \
            project.imported_jit_names(info.path)
        jit_attrs = set(summary.jit_attrs)
    else:
        jit_names, jit_attrs = _collect_jitted_bindings(info.tree)
    findings: List[Finding] = []
    for node in ast.walk(info.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            walker = _ScopeTaint(info, jit_names, jit_attrs, findings)
            for stmt in node.body:
                walker.visit(stmt)
            if walker.devget_count >= 2:
                findings.extend(walker.devget_field_picks)
    return findings
