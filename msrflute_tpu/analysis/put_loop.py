"""put-loop — per-leaf ``jax.device_put`` loops in hot-path modules.

The dispatch half of the flatpack discipline (PR 6): a faithful round's
host inputs cross the host->device boundary as ONE staged buffer per
dtype group (``utils/flatpack.py`` ``AxisPacker``/``ScalarStager``, one
``device_put`` per group).  A ``device_put`` inside a loop or
comprehension pays one transfer per iteration instead — exactly the
~8-10 per-leaf puts per dispatch that ``tools/dispatch_cost_probe.py``
measured (~88 ms suspect on a remote-attached chip) and that the staged
dispatch (``engine/round.py::_dispatch_staged``) removed.

Flagged, in hot-path modules only (``engine/``, ``ops/``,
``strategies/``, ``telemetry/``, ``robust/``): any
``jax.device_put(...)`` / ``device_put(...)`` call lexically inside a
``for``/``while`` body or a list/set/dict comprehension / generator
expression.

Deliberately lexical (no data-flow): a put whose operand is a packed
per-dtype dict is ONE call on the whole tree and never sits in a loop;
the loop shape IS the smell.  Function/lambda bodies reset the loop
context — a staging closure defined inside a loop is called elsewhere
and judged there.  Legitimate loops (one-time pool uploads, one staged
put per bucket program) carry a ``# flint: disable=put-loop reason``
pragma.
"""

from __future__ import annotations

import ast
from typing import List

from .core import Finding, ModuleInfo, call_name

RULE = "put-loop"

_PUT_NAMES = {"jax.device_put", "device_put"}
_LOOPS = (ast.For, ast.AsyncFor, ast.While, ast.ListComp, ast.SetComp,
          ast.DictComp, ast.GeneratorExp)
_SCOPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def check(info: ModuleInfo) -> List[Finding]:
    if not info.is_hot_path:
        return []
    findings: List[Finding] = []

    def walk(node: ast.AST, in_loop: bool) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _SCOPES):
                # new call boundary: the body runs when the function is
                # called, not per iteration of any enclosing loop
                walk(child, False)
                continue
            child_in_loop = in_loop or isinstance(child, _LOOPS)
            if isinstance(child, ast.Call) and child_in_loop and \
                    call_name(child) in _PUT_NAMES:
                findings.append(Finding(
                    RULE, info.path, child.lineno,
                    "device_put inside a loop/comprehension pays one "
                    "host->device transfer per iteration",
                    hint="pack the leaves into one staged buffer per "
                         "dtype group (utils/flatpack.py AxisPacker/"
                         "ScalarStager) and device_put once, or put the "
                         "whole tree in a single call"))
            walk(child, child_in_loop)

    walk(info.tree, False)
    return findings
