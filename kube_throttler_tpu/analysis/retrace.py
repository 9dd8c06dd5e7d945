"""Checker 11: retrace budgets — jit entries must see padded shapes.

The whole device story rests on *one compiled program per ladder rung*:
arrays are padded to pow2/capacity sizes (``_next_pow2``, the
``ensure_capacity`` ladders) so object churn never changes array shapes
and XLA never recompiles on the serving path (a recompile is a
100ms-class stall). This checker pins the host→device boundary shape
discipline; the runtime half (``utils/retrace.py``,
``KT_JIT_RETRACE_BUDGET``) counts actual XLA compilations per entry and
fails a tick that recompiles after warmup.

Rules, per call site of a ``@jax.jit`` entry (entries discovered the
same way the purity checker finds them, call sites resolved through the
package import-alias index):

- **unpadded dynamic shape**: an argument that is (or names) a host
  allocation (``np.zeros``/``empty``/``full``/``ones``/``jnp.*``) whose
  shape expression is data-dependent — contains ``len(...)``,
  ``.shape``, ``.size``, or ``np.nonzero`` — without passing through a
  sanctioned padder (a ``*pow2*``/``*pad*`` call or a capacity-named
  value: ``*cap*``/``capacity``). Every distinct live count then
  compiles a fresh program;
- **data-dependent static arg**: a value bound to a ``static_argnames``
  parameter that is data-dependent by the same test. Static args key
  the compile cache by *value* — ``num_groups=len(groups)`` recompiles
  per distinct group count; ``num_groups=_next_pow2(len(groups))``
  amortizes onto the ladder.

Name resolution is one-hop flow-insensitive (``B = len(pods)`` taints
``B``; ``Bp = _next_pow2(B)`` launders it) — the committed padding
idiom is exactly one hop, and deeper flows belong to the runtime
counter.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding, Module, iter_classes, iter_methods, unparse
from .purity import _decorator_jit_static, _FnIndex

_ALLOCATORS = {"zeros", "empty", "ones", "full"}
_DATADEP_CALLS = {"len", "nonzero", "count_nonzero", "flatnonzero"}
_DATADEP_ATTRS = {"shape", "size"}
_SANCTION_SUBSTRINGS = ("pow2", "pad", "cap", "ladder", "bucket")


def _entry_table(
    modules: Sequence[Module],
) -> Dict[Tuple[str, str], Tuple[List[str], Set[str]]]:
    """(modname, fn) -> (param names, static_argnames) for jit entries."""
    out: Dict[Tuple[str, str], Tuple[List[str], Set[str]]] = {}
    for m in modules:
        for node in ast.walk(m.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for dec in node.decorator_list:
                res = _decorator_jit_static(dec)
                if res:
                    params = [
                        a.arg
                        for a in list(node.args.posonlyargs) + list(node.args.args)
                    ]
                    out[(m.modname, node.name)] = (params, res[1])
                    break
    return out


def _name_of(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


class _Taint:
    """Flow-insensitive local env: name -> value expr (last assignment)."""

    def __init__(self, fn: ast.AST):
        self.env: Dict[str, ast.AST] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if isinstance(t, ast.Name):
                    self.env[t.id] = node.value

    def _scan(self, expr: ast.AST, pred, depth: int) -> bool:
        for sub in ast.walk(expr):
            if pred(sub):
                return True
            if depth > 0 and isinstance(sub, ast.Name) and sub.id in self.env:
                resolved = self.env[sub.id]
                if resolved is not expr and self._scan(resolved, pred, depth - 1):
                    return True
        return False

    def data_dependent(self, expr: ast.AST, depth: int = 2) -> bool:
        def pred(sub: ast.AST) -> bool:
            if isinstance(sub, ast.Call):
                n = _name_of(sub.func)
                if n in _DATADEP_CALLS:
                    return True
            if isinstance(sub, ast.Attribute) and sub.attr in _DATADEP_ATTRS:
                return True
            return False

        return self._scan(expr, pred, depth)

    def sanctioned(self, expr: ast.AST, depth: int = 2) -> bool:
        def pred(sub: ast.AST) -> bool:
            n = None
            if isinstance(sub, ast.Call):
                n = _name_of(sub.func)
            elif isinstance(sub, (ast.Name, ast.Attribute)):
                n = _name_of(sub)
            if n is None:
                return False
            low = n.lower()
            return any(s in low for s in _SANCTION_SUBSTRINGS)

        return self._scan(expr, pred, depth)


def _alloc_shape(call: ast.Call) -> Optional[ast.AST]:
    name = _name_of(call.func)
    if name in _ALLOCATORS and call.args:
        return call.args[0]
    return None


def check(modules: Sequence[Module]) -> List[Finding]:
    entries = _entry_table(modules)
    if not entries:
        return []
    index = _FnIndex(modules)
    by_name: Dict[str, List[Tuple[str, str]]] = {}
    for key in entries:
        by_name.setdefault(key[1], []).append(key)

    findings: List[Finding] = []

    def resolve(modname: str, call: ast.Call) -> Optional[Tuple[str, str]]:
        f = call.func
        if isinstance(f, ast.Name):
            r = index.resolve(modname, f.id)
            if r in entries:
                return r
            cands = by_name.get(f.id, [])
            return cands[0] if len(cands) == 1 else None
        if isinstance(f, ast.Attribute):
            cands = by_name.get(f.attr, [])
            return cands[0] if len(cands) == 1 else None
        return None

    def scan_function(module: Module, fn: ast.AST, where: str) -> None:
        taint = _Taint(fn)

        def check_traced_arg(expr: ast.AST, entry, pname, line: int) -> None:
            """An array arg: flag if it is/names an allocation with an
            unpadded data-dependent shape."""
            alloc = None
            if isinstance(expr, ast.Call):
                alloc = _alloc_shape(expr)
            elif isinstance(expr, ast.Name) and expr.id in taint.env:
                v = taint.env[expr.id]
                if isinstance(v, ast.Call):
                    alloc = _alloc_shape(v)
            if alloc is None:
                return
            if taint.data_dependent(alloc) and not taint.sanctioned(alloc):
                findings.append(
                    Finding(
                        checker="retrace",
                        path=module.path,
                        relpath=module.relpath,
                        line=line,
                        message=(
                            f"arg '{pname}' of jit entry {entry[0]}.{entry[1]} "
                            f"is allocated with a data-dependent shape "
                            f"({unparse(alloc)}) in {where} — every distinct "
                            "size recompiles; route through _next_pow2/"
                            "capacity padding"
                        ),
                    )
                )

        def check_static_arg(expr: ast.AST, entry, pname, line: int) -> None:
            if taint.data_dependent(expr) and not taint.sanctioned(expr):
                findings.append(
                    Finding(
                        checker="retrace",
                        path=module.path,
                        relpath=module.relpath,
                        line=line,
                        message=(
                            f"static arg '{pname}' of jit entry "
                            f"{entry[0]}.{entry[1]} is data-dependent "
                            f"({unparse(expr)}) in {where} — static args key "
                            "the compile cache by value; pad to the ladder "
                            "first"
                        ),
                    )
                )

        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            entry = resolve(module.modname, node)
            if entry is None:
                continue
            params, static = entries[entry]
            for i, a in enumerate(node.args):
                pname = params[i] if i < len(params) else f"arg{i}"
                if pname in static:
                    check_static_arg(a, entry, pname, node.lineno)
                else:
                    check_traced_arg(a, entry, pname, node.lineno)
            for kw in node.keywords:
                if kw.arg is None:
                    continue
                if kw.arg in static:
                    check_static_arg(kw.value, entry, kw.arg, node.lineno)
                else:
                    check_traced_arg(kw.value, entry, kw.arg, node.lineno)

    for m in modules:
        for node in m.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if (m.modname, node.name) in entries:
                    continue
                scan_function(m, node, f"{m.modname}.{node.name}")
        for cls in iter_classes(m):
            for method in iter_methods(cls):
                scan_function(m, method, f"{m.modname}.{cls.name}.{method.name}")

    uniq = {}
    for f in findings:
        uniq.setdefault((f.key(), f.line), f)
    return sorted(uniq.values(), key=lambda f: (f.relpath, f.line))
