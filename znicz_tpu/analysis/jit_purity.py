"""jit-purity: Python side effects, tracer leaks, and recompile hazards
inside jit/custom_vjp/pallas traced functions.

A function handed to ``jax.jit`` / ``jax.custom_vjp`` / ``pallas_call``
runs ONCE per compilation, not once per step: a ``print``, a telemetry
``.inc()``, or a ``self.x = ...`` inside it fires at trace time only
(silently wrong accounting), and ``float(x)`` / ``x.item()`` /
``np.asarray(x)`` on a traced value raises ``TracerConversionError`` at
best or silently constant-folds at worst.  Static-arg hygiene is the
recompile side of the same coin: an unhashable literal passed as a
static arg raises, and an f-string-derived static arg recompiles on
every new value.

Discovery (module-local, name-based):

  - defs decorated ``@jax.jit`` / ``@jit`` / ``@partial(jax.jit, ...)``
    / ``@jax.custom_vjp`` / ``@custom_vjp``;
  - ``g = jax.jit(f, ...)`` marks ``f`` (and records ``g``'s
    ``static_argnums``/``static_argnames`` for call-site checks);
  - ``pallas_call(kernel, ...)`` / ``pl.pallas_call(...)`` marks
    ``kernel``;
  - ``f.defvjp(fwd, bwd)`` marks ``fwd`` and ``bwd``.

Inside a marked function we flag:

  - side effects: ``print(...)``, telemetry ``.inc(...)``/
    ``.observe(...)``, and any attribute store ``obj.x = ...``;
  - tracer leaks: ``.item()`` calls, and ``float(...)``/``int(...)``/
    ``np.asarray(...)``/``np.array(...)`` whose argument is not a
    literal constant.

At call sites of a name wrapped by ``jax.jit`` in the same module we
flag list/dict/set literals bound to a declared static arg (unhashable
-> ``TypeError`` per call) and f-strings passed anywhere (a string
argument must be static, and an f-string derives a fresh value ->
recompile per call).

Functions that intentionally break the rules (host callbacks, debug
paths) carry ``# znicz: ignore[jit-purity]`` on the offending line, or
get baselined with a justification.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import Checker, Finding, Module

RULE = "jit-purity"

_TRACING_WRAPPERS = {"jit", "custom_vjp", "pallas_call"}
_NUMPY_LEAKS = {"asarray", "array"}


def _terminal_name(expr: ast.expr) -> Optional[str]:
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        return expr.attr
    return None


def _wrapper_kind(expr: ast.expr) -> Optional[str]:
    """'jit' / 'custom_vjp' / 'pallas_call' if this expression is (a
    partial over) one of the tracing wrappers, else None."""
    name = _terminal_name(expr)
    if name in _TRACING_WRAPPERS:
        return name
    if isinstance(expr, ast.Call) and _terminal_name(expr.func) in (
            "partial",):
        if expr.args:
            return _wrapper_kind(expr.args[0])
    return None


def _static_names(call: ast.Call) -> Tuple[Set[str], Set[int]]:
    """Declared static argnames / argnums of a jit(...) wrap call."""
    names: Set[str] = set()
    nums: Set[int] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    names.add(n.value)
        elif kw.arg == "static_argnums":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, int):
                    nums.add(n.value)
    return names, nums


class _TracedBodyScan(ast.NodeVisitor):
    """Flag impurities inside one traced function body."""

    def __init__(self, module: Module, numpy_aliases: Set[str],
                 fn_name: str, out: List[Finding]) -> None:
        self.module = module
        self.np = numpy_aliases
        self.fn = fn_name
        self.out = out

    def _emit(self, line: int, what: str) -> None:
        self.out.append(Finding(
            RULE, self.module.rel, line,
            f"{what} inside jit-traced '{self.fn}' — runs at trace "
            f"time only (or leaks a tracer), not per step"))

    # -- side effects --------------------------------------------------------

    def visit_Assign(self, node: ast.Assign) -> None:
        for target in node.targets:
            for t in ast.walk(target):
                if isinstance(t, ast.Attribute) and isinstance(
                        t.ctx, ast.Store):
                    self._emit(t.lineno,
                               f"attribute mutation '{ast.unparse(t)} ="
                               " ...' (Python side effect)")
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        if isinstance(node.target, ast.Attribute):
            self._emit(node.lineno,
                       f"attribute mutation "
                       f"'{ast.unparse(node.target)} op= ...' "
                       f"(Python side effect)")
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        if isinstance(func, ast.Name) and func.id == "print":
            self._emit(node.lineno, "print() (Python side effect)")
        elif isinstance(func, ast.Name) and func.id in ("float", "int") \
                and node.args and not isinstance(node.args[0],
                                                 ast.Constant):
            self._emit(node.lineno,
                       f"{func.id}() on a non-literal value "
                       f"(tracer leak)")
        elif isinstance(func, ast.Attribute):
            if func.attr in ("inc", "observe"):
                self._emit(node.lineno,
                           f".{func.attr}() telemetry mutation "
                           f"(Python side effect)")
            elif func.attr == "item" and not node.args:
                self._emit(node.lineno, ".item() (tracer leak)")
            elif (func.attr in _NUMPY_LEAKS
                  and isinstance(func.value, ast.Name)
                  and func.value.id in self.np
                  and node.args
                  and not isinstance(node.args[0], ast.Constant)):
                self._emit(node.lineno,
                           f"{func.value.id}.{func.attr}() on a "
                           f"non-literal value (tracer leak)")
        self.generic_visit(node)


class JitPurityChecker(Checker):
    name = RULE

    def check(self, module: Module):
        numpy_aliases = self._numpy_aliases(module)
        # names referenced INTO a wrapper (g = jax.jit(f) / defvjp /
        # pallas_call(kernel)) are matched by name module-wide; defs
        # carrying the decorator themselves are marked by NODE, so a
        # public wrapper that shares its name with an inner decorated
        # def is not swept in by the collision
        marked: Dict[str, str] = {}        # referenced name -> kind
        marked_nodes: List[Tuple[ast.AST, str, str]] = []  # (fn, name, kind)
        statics: Dict[str, Tuple[Set[str], Set[int]]] = {}  # callee name
        jitted_names: Set[str] = set()     # for call-site hazards

        for node in ast.walk(module.tree):
            # decorated defs
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    kind = _wrapper_kind(dec)
                    if kind:
                        marked_nodes.append((node, node.name, kind))
                        if kind == "jit":
                            jitted_names.add(node.name)
                        if isinstance(dec, ast.Call):
                            statics[node.name] = _static_names(dec)
            # g = jax.jit(f, ...): remember g's static args for the
            # call-site hazard checks
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call):
                kind = _wrapper_kind(node.value.func)
                if kind == "jit" and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    statics[node.targets[0].id] = _static_names(
                        node.value)
            if isinstance(node, ast.Call):
                # jax.jit(f) / custom_vjp(f) / pallas_call(kernel, ...)
                # in ANY position (assignment, return, nested call)
                # marks the referenced function
                kind = _wrapper_kind(node.func)
                if kind and node.args:
                    inner = node.args[0]
                    if isinstance(inner, ast.Name):
                        marked.setdefault(inner.id, kind)
                    elif isinstance(inner, ast.Lambda):
                        marked_nodes.append((inner, "<lambda>", kind))
                # f.defvjp(fwd, bwd)
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "defvjp":
                    for arg in node.args:
                        if isinstance(arg, ast.Name):
                            marked.setdefault(arg.id, "custom_vjp")

        findings: List[Finding] = []
        seen: Set[Tuple[str, int, str]] = set()

        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in marked:
                marked_nodes.append((node, node.name, marked[node.name]))
        for fn, name, _kind in marked_nodes:
            out: List[Finding] = []
            scan = _TracedBodyScan(module, numpy_aliases, name, out)
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            for stmt in body:
                scan.visit(stmt)
            for f in out:
                if (f.rule, f.line, f.message) not in seen:
                    seen.add((f.rule, f.line, f.message))
                    findings.append(f)

        jitted_names |= {n for n, k in marked.items() if k == "jit"}
        findings.extend(
            self._call_site_hazards(module, statics, jitted_names))
        return findings

    @staticmethod
    def _numpy_aliases(module: Module) -> Set[str]:
        out: Set[str] = set()
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "numpy":
                        out.add(alias.asname or "numpy")
        return out

    def _call_site_hazards(self, module: Module,
                           statics: Dict[str, Tuple[Set[str], Set[int]]],
                           jitted_names: Set[str]) -> List[Finding]:
        jitted = set(statics) | jitted_names
        findings: List[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _terminal_name(node.func)
            if callee not in jitted:
                continue
            names, nums = statics.get(callee, (set(), set()))
            for i, arg in enumerate(node.args):
                if isinstance(arg, ast.JoinedStr):
                    findings.append(Finding(
                        RULE, module.rel, arg.lineno,
                        f"f-string argument to jitted '{callee}' — "
                        f"derives a fresh static value per call "
                        f"(recompile hazard)"))
                elif i in nums and isinstance(
                        arg, (ast.List, ast.Dict, ast.Set)):
                    findings.append(Finding(
                        RULE, module.rel, arg.lineno,
                        f"unhashable {type(arg).__name__.lower()} "
                        f"literal as static arg {i} of jitted "
                        f"'{callee}' (recompile hazard: TypeError "
                        f"at call time)"))
            for kw in node.keywords:
                if isinstance(kw.value, ast.JoinedStr):
                    findings.append(Finding(
                        RULE, module.rel, kw.value.lineno,
                        f"f-string argument to jitted '{callee}' — "
                        f"derives a fresh static value per call "
                        f"(recompile hazard)"))
                elif kw.arg in names and isinstance(
                        kw.value, (ast.List, ast.Dict, ast.Set)):
                    findings.append(Finding(
                        RULE, module.rel, kw.value.lineno,
                        f"unhashable "
                        f"{type(kw.value).__name__.lower()} literal "
                        f"as static arg '{kw.arg}' of jitted "
                        f"'{callee}' (recompile hazard: TypeError "
                        f"at call time)"))
        return findings
