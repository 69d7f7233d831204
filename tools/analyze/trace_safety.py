"""brelint pass: trace-safety (`trace-host-op`, `trace-branch-on-array`).

The PR 6 bench outage class: a host-only operation (``np.*`` coercion,
``float()``/``bool()``/``int()`` on runtime values, ``.item()``,
``jax.device_get``) reachable through the call graph from a traced region
(``jax.jit`` / ``vmap`` / ``shard_map`` / ``lax.scan`` / ``lax.cond`` /
``pallas_call``) without a ``validate=False``-style opt-out.

Mechanics:

* every project function is scanned for host markers and project-internal
  call edges, each tagged with the parameter guards (``if validate:``)
  enclosing it;
* taint propagates caller-ward to a fixpoint, translating guard
  conditions through call sites — passing the constant ``False``/``None``
  for a guard parameter *discharges* the taint (the opt-out), forwarding
  a caller parameter re-conditions it on that parameter;
* at each trace root, conditioned taint survives unless every condition
  parameter defaults to ``False``/``None`` (i.e. host work is opt-in).

Functions jitted with ``static_argnames`` may coerce those (static)
parameters with ``int()``/``float()``/``bool()`` — that is trace-time
Python on static values, not a leak, and is not flagged.

A second check flags Python ``if``/``while`` tests built directly from
``jnp.*`` calls inside the traced region (implicit bool() on a tracer).
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import NamedTuple

from .common import Finding, FunctionInfo, ModuleInfo, Project, \
    dotted_name, is_const

HOST_OP = "trace-host-op"
BRANCH_ON_ARRAY = "trace-branch-on-array"

# wrapper canonical name -> positions of the traced callee argument(s)
_WRAPPERS = {
    "jax.jit": (0,), "jax.pmap": (0,), "jax.vmap": (0,),
    "jax.grad": (0,), "jax.value_and_grad": (0,),
    "jax.checkpoint": (0,), "jax.remat": (0,),
    "jax.lax.map": (0,), "jax.lax.scan": (0,),
    "jax.lax.while_loop": (0, 1), "jax.lax.fori_loop": (2,),
    "jax.lax.cond": (1, 2), "jax.lax.switch": (1,),
}
# wrappers matched on the final attribute regardless of module prefix
# (dist/sharding wraps shard_map; pallas is imported as ``pl``).
_WRAPPER_ATTRS = {"shard_map": (0,), "pallas_call": (0,)}

_COERCIONS = {"float", "int", "bool"}
# annotation words that mark a parameter as host-static (config values,
# shapes, section tuples): trace-time Python on these is fine.  Anything
# array-ish — or unannotated — is presumed traced.
_STATIC_ANN = {"int", "float", "bool", "str", "bytes", "tuple", "list",
               "dict", "type", "None", "Literal"}
_ARRAY_ANN = {"Array", "ndarray", "ArrayLike", "Any", "object"}
# builtins/modules whose results stay static when their inputs are static
_STATIC_CALLS = {"int", "float", "bool", "len", "min", "max", "range",
                 "tuple", "str", "sorted", "abs", "sum", "round", "divmod"}
# attribute reads that are trace-time metadata even on traced arrays
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "itemsize"}
_HOST_ATTR_CALLS = {"item", "tolist"}
_HOST_CANONICAL = {"jax.device_get", "jax.block_until_ready"}
# numpy attrs that are static/metadata at trace time, not array coercions
_NP_SAFE = {"dtype", "iinfo", "finfo", "result_type", "issubdtype",
            "ndim", "shape", "size", "errstate", "seterr", "isdtype"}
_JNP_STATIC = {"issubdtype", "result_type", "iinfo", "finfo", "dtype",
               "ndim", "shape", "size", "isdtype"}


def _ann_static(annotation: ast.expr) -> bool:
    """Non-array annotation => host-static parameter."""
    words = re.findall(r"[A-Za-z_][A-Za-z0-9_]*", ast.unparse(annotation))
    if any(w in _ARRAY_ANN for w in words):
        return False
    return any(w in _STATIC_ANN or w.endswith("Config") for w in words)


class TaintItem(NamedTuple):
    origin: str      # qualname of the function containing the marker
    line: int
    desc: str
    conds: frozenset  # caller-param names that must all be truthy


@dataclasses.dataclass
class _FnFacts:
    markers: list  # [(line, desc, frozenset(guard params))]
    edges: list    # [(callee qualname, ast.Call, frozenset(guard params))]
    branchy: list  # [(line, desc)] python-branch-on-jnp sites


class _BodyScan(ast.NodeVisitor):
    """Markers + edges + guard tracking for one function body."""

    def __init__(self, project: Project, mod: ModuleInfo,
                 fn: FunctionInfo, statics: frozenset):
        self.project = project
        self.mod = mod
        self.fn = fn
        self.statics = statics
        self.params = set(fn.params)
        self.guards: list[str] = []
        self.facts = _FnFacts([], [], [])
        # params that are host-static: declared via static_argnames, or
        # carrying a non-array annotation (config/shape/tuple values)
        self.static_names = set(statics)
        if not isinstance(fn.node, ast.Lambda):
            a = fn.node.args
            for p in a.posonlyargs + a.args + a.kwonlyargs:
                if p.annotation is not None and _ann_static(p.annotation):
                    self.static_names.add(p.arg)
        self.runtime_locals: set[str] = set()

    # -- guard bookkeeping -------------------------------------------------

    def _guard_params(self, test: ast.expr) -> set[str]:
        if isinstance(test, ast.Name) and test.id in self.params:
            return {test.id}
        if (isinstance(test, ast.Compare) and len(test.ops) == 1
                and isinstance(test.ops[0], (ast.IsNot, ast.NotEq))
                and isinstance(test.left, ast.Name)
                and test.left.id in self.params
                and is_const(test.comparators[0], None)):
            return {test.left.id}
        if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
            out: set[str] = set()
            for v in test.values:
                out |= self._guard_params(v)
            return out
        return set()

    def visit_If(self, node: ast.If) -> None:
        self._note_branch(node)
        self.visit(node.test)
        extra = sorted(self._guard_params(node.test))
        self.guards.extend(extra)
        for stmt in node.body:
            self.visit(stmt)
        del self.guards[len(self.guards) - len(extra):len(self.guards)]
        for stmt in node.orelse:
            self.visit(stmt)

    # nested defs and lambdas are separate functions (or trace roots,
    # handled by the root extractor) — their bodies are not part of this
    # function's host-op surface.
    def visit_FunctionDef(self, node):  # noqa: ARG002
        return

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    # -- markers and edges -------------------------------------------------

    def _mark(self, node: ast.expr, desc: str) -> None:
        guards = frozenset(g for g in self.guards if g in self.params)
        self.facts.markers.append((node.lineno, desc, guards))

    def _expr_static(self, exprs: list[ast.expr]) -> bool:
        """True when the expressions only touch host-static values:
        static/config params, locals derived from them, constants,
        shape/dtype metadata (static at trace time even on tracers), and
        static-preserving calls (numpy/math/builtins on static inputs)."""
        return all(self._static(e) for e in exprs)

    def _static(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return True              # x.shape is trace-time metadata
        if isinstance(node, ast.Name):
            if node.id in self.runtime_locals:
                return False
            return not (node.id in self.params
                        and node.id not in self.static_names)
        if isinstance(node, ast.Call):
            canon = self.project.canonical(self.mod, node.func) or ""
            named_static = (
                canon.startswith(("numpy.", "math."))
                or (isinstance(node.func, ast.Name)
                    and node.func.id in _STATIC_CALLS))
            if not named_static:
                return False         # jnp/lax/project calls: runtime
            return all(self._static(a) for a in node.args) and all(
                self._static(kw.value) for kw in node.keywords)
        if isinstance(node, ast.Constant):
            return True
        return all(self._static(c) for c in ast.iter_child_nodes(node)
                   if isinstance(c, (ast.expr, ast.keyword,
                                     ast.comprehension)))

    def visit_Assign(self, node: ast.Assign) -> None:
        self.generic_visit(node)     # marker checks inside the value first
        static = self._expr_static([node.value])
        for target in node.targets:
            for sub in ast.walk(target):
                if isinstance(sub, ast.Name):
                    if static:
                        self.runtime_locals.discard(sub.id)
                    else:
                        self.runtime_locals.add(sub.id)

    def visit_For(self, node: ast.For) -> None:
        for sub in ast.walk(node.target):
            if isinstance(sub, ast.Name):
                self.runtime_locals.add(sub.id)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        canon = self.project.canonical(self.mod, func)
        if isinstance(func, ast.Name) and func.id in _COERCIONS:
            if node.args and not self._expr_static(node.args):
                self._mark(node, f"host coercion `{func.id}()` on a "
                                 "runtime value")
        elif isinstance(func, ast.Attribute) \
                and func.attr in _HOST_ATTR_CALLS and not node.args:
            self._mark(node, f"host sync `.{func.attr}()`")
        elif canon in _HOST_CANONICAL:
            self._mark(node, f"host sync `{canon}`")
        elif canon is not None and canon.startswith("numpy."):
            name = canon.split(".", 1)[1]
            if name not in _NP_SAFE and not self._expr_static(node.args):
                self._mark(node, f"numpy call `{canon}` (host-only)")
        target = self.project.resolve_call(self.mod, node, self.fn)
        if target is not None:
            guards = frozenset(g for g in self.guards if g in self.params)
            self.facts.edges.append((target, node, guards))
        self.generic_visit(node)

    # -- implicit bool() on a tracer ---------------------------------------

    def _test_touches_jnp(self, test: ast.expr) -> int | None:
        for sub in ast.walk(test):
            if isinstance(sub, ast.Call):
                canon = self.project.canonical(self.mod, sub.func) or ""
                if canon.startswith(("jax.numpy.", "jax.lax.")):
                    attr = canon.rsplit(".", 1)[1]
                    if attr not in _JNP_STATIC:
                        return sub.lineno
        return None

    def visit_While(self, node: ast.While) -> None:
        line = self._test_touches_jnp(node.test)
        if line is not None:
            self.facts.branchy.append(
                (line, "python `while` on a jax array expression"))
        self.generic_visit(node)

    def _note_branch(self, node: ast.If) -> None:
        line = self._test_touches_jnp(node.test)
        if line is not None:
            self.facts.branchy.append(
                (line, "python `if` on a jax array expression"))

    def run(self) -> _FnFacts:
        body = self.fn.node.body
        if isinstance(self.fn.node, ast.Lambda):
            self.visit(self.fn.node.body)
            return self.facts
        for stmt in body:
            self.visit(stmt)
        return self.facts


@dataclasses.dataclass
class _Root:
    fn: FunctionInfo
    site: str            # human description of the traced site
    statics: frozenset   # declared static param names


def _decorator_root(project: Project, mod: ModuleInfo,
                    fn: FunctionInfo) -> _Root | None:
    node = fn.node
    if isinstance(node, ast.Lambda):
        return None
    for deco in node.decorator_list:
        canon = project.canonical(mod, deco) if not isinstance(
            deco, ast.Call) else project.canonical(mod, deco.func)
        if not isinstance(deco, ast.Call):
            if canon in ("jax.jit", "jax.pmap"):
                return _Root(fn, f"@{canon}", frozenset())
            continue
        if canon == "functools.partial" and deco.args:
            inner = project.canonical(mod, deco.args[0])
            if inner in ("jax.jit", "jax.pmap"):
                return _Root(fn, f"@partial({inner})",
                             _static_names(project, mod, deco.keywords, fn))
        elif canon in ("jax.jit", "jax.pmap"):
            return _Root(fn, f"@{canon}(...)",
                         _static_names(project, mod, deco.keywords, fn))
    return None


def _static_names(project: Project, mod: ModuleInfo, keywords,
                  fn: FunctionInfo) -> frozenset:
    for kw in keywords:
        if kw.arg == "static_argnames":
            val = kw.value
            names = []
            if isinstance(val, ast.Constant) and isinstance(val.value, str):
                names = [val.value]
            elif isinstance(val, (ast.Tuple, ast.List)):
                names = [e.value for e in val.elts
                         if isinstance(e, ast.Constant)]
            return frozenset(names)
        if kw.arg == "static_argnums":
            val = kw.value
            nums = []
            if isinstance(val, ast.Constant) and isinstance(val.value, int):
                nums = [val.value]
            elif isinstance(val, (ast.Tuple, ast.List)):
                nums = [e.value for e in val.elts
                        if isinstance(e, ast.Constant)]
            pos = fn.positional_params()
            return frozenset(pos[i] for i in nums if i < len(pos))
    return frozenset()


def _resolve_func_expr(project: Project, mod: ModuleInfo, expr: ast.expr,
                       scope: FunctionInfo | None) -> FunctionInfo | None:
    fake = ast.Call(func=expr, args=[], keywords=[])
    qual = project.resolve_call(mod, fake, scope)
    return project.functions.get(qual) if qual else None


def _wrapper_positions(project: Project, mod: ModuleInfo,
                       call: ast.Call) -> tuple | None:
    canon = project.canonical(mod, call.func)
    if canon in _WRAPPERS:
        return _WRAPPERS[canon]
    dotted = dotted_name(call.func) or ""
    attr = dotted.rsplit(".", 1)[-1]
    if attr in _WRAPPER_ATTRS and "." in dotted:
        return _WRAPPER_ATTRS[attr]
    return None


def run(ctx) -> list[Finding]:
    project: Project = ctx.project
    facts: dict[str, _FnFacts] = {}
    all_fns: dict[str, FunctionInfo] = dict(project.functions)
    roots: list[_Root] = []

    # decorated roots + per-function statics
    statics: dict[str, frozenset] = {}
    for mod in project.modules.values():
        for fn in list(mod.functions.values()):
            root = _decorator_root(project, mod, fn)
            if root is not None:
                statics[fn.qualname] = root.statics
                roots.append(root)

    def scan(fn: FunctionInfo) -> _FnFacts:
        if fn.qualname not in facts:
            facts[fn.qualname] = _BodyScan(
                project, fn.module, fn,
                statics.get(fn.qualname, frozenset())).run()
        return facts[fn.qualname]

    # wrapper-call roots (jax.vmap(f), lax.scan(step, ...), shard_map, ...)
    lambda_n = 0
    for mod in project.modules.values():
        scopes: list[FunctionInfo | None] = [None]
        scopes += list(mod.functions.values())
        for scope in scopes:
            body = mod.tree if scope is None else scope.node
            if isinstance(body, ast.Lambda):
                continue
            for node in ast.walk(body):
                if not isinstance(node, ast.Call):
                    continue
                positions = _wrapper_positions(project, mod, node)
                if positions is None:
                    continue
                canon = project.canonical(mod, node.func) or \
                    dotted_name(node.func) or "?"
                for pos in positions:
                    if pos >= len(node.args):
                        continue
                    cands = [node.args[pos]]
                    if isinstance(node.args[pos], (ast.Tuple, ast.List)):
                        cands = list(node.args[pos].elts)   # lax.switch
                    for cand in cands:
                        if isinstance(cand, ast.Lambda):
                            lambda_n += 1
                            owner = scope.qualname if scope else mod.name
                            lf = FunctionInfo(
                                qualname=(f"{owner}.<lambda@"
                                          f"{cand.lineno}>"),
                                name=f"<lambda@{cand.lineno}>",
                                module=mod, node=cand,
                                cls=scope.cls if scope else None)
                            all_fns[lf.qualname] = lf
                            facts[lf.qualname] = _BodyScan(
                                project, mod, lf, frozenset()).run()
                            roots.append(_Root(
                                lf, f"{canon}(<lambda>)", frozenset()))
                        else:
                            target = _resolve_func_expr(
                                project, mod, cand, scope)
                            if target is not None:
                                st = _static_names(project, mod,
                                                   node.keywords, target)
                                roots.append(_Root(
                                    target, f"{canon}({target.name})", st))

    for fn in project.functions.values():
        scan(fn)

    # -- taint fixpoint ----------------------------------------------------
    taint: dict[str, set[TaintItem]] = {q: set() for q in all_fns}
    for qual, f in facts.items():
        for line, desc, guards in f.markers:
            taint[qual].add(TaintItem(qual, line, desc, guards))

    changed = True
    while changed:
        changed = False
        for qual, f in facts.items():
            fn = all_fns[qual]
            for callee_qual, call, guards in f.edges:
                for item in taint.get(callee_qual, ()):
                    moved = _translate(item, call, all_fns.get(callee_qual),
                                       fn, guards)
                    if moved is not None and moved not in taint[qual]:
                        taint[qual].add(moved)
                        changed = True

    # -- report at roots ---------------------------------------------------
    findings: dict[tuple, Finding] = {}
    reachable: set[str] = set()
    frontier = []
    for root in roots:
        if root.fn.qualname not in reachable:
            reachable.add(root.fn.qualname)
            frontier.append(root.fn.qualname)
        for item in taint.get(root.fn.qualname, ()):
            if item.conds and all(
                    is_const(root.fn.default_of(c), False, None)
                    for c in item.conds):
                continue   # opt-in host path: off by default at this root
            origin = all_fns.get(item.origin)
            path = origin.module.path if origin else root.fn.module.path
            cond_txt = (" [enabled unless "
                        + "/".join(f"{c}=False" for c in sorted(item.conds))
                        + "]") if item.conds else ""
            key = (HOST_OP, str(path), item.line, root.fn.qualname)
            findings[key] = Finding(
                HOST_OP, path, item.line, item.origin,
                f"{item.desc} reachable from traced "
                f"`{root.fn.qualname}` ({root.site}){cond_txt}")

    while frontier:
        here = frontier.pop()
        for callee, _call, _g in facts.get(here, _FnFacts([], [], [])).edges:
            if callee not in reachable:
                reachable.add(callee)
                frontier.append(callee)

    for qual in sorted(reachable):
        fn = all_fns.get(qual)
        if fn is None:
            continue
        for line, desc in facts.get(qual, _FnFacts([], [], [])).branchy:
            key = (BRANCH_ON_ARRAY, str(fn.module.path), line, qual)
            findings[key] = Finding(
                BRANCH_ON_ARRAY, fn.module.path, line, qual,
                f"{desc} inside the traced region")

    return list(findings.values())


def _translate(item: TaintItem, call: ast.Call,
               callee: FunctionInfo | None, caller: FunctionInfo,
               guards: frozenset) -> TaintItem | None:
    """Re-express a callee taint item in the caller's parameter space."""
    conds = set(guards)
    if callee is None:
        return TaintItem(item.origin, item.line, item.desc,
                         frozenset(conds | item.conds))
    pos = callee.positional_params()
    offset = 1 if (pos and pos[0] in ("self", "cls")
                   and isinstance(call.func, ast.Attribute)) else 0
    caller_params = set(caller.params)
    for p in item.conds:
        expr = None
        for kw in call.keywords:
            if kw.arg == p:
                expr = kw.value
                break
        else:
            if p in pos:
                idx = pos.index(p) - offset
                if 0 <= idx < len(call.args) and not isinstance(
                        call.args[idx], ast.Starred):
                    expr = call.args[idx]
        if expr is None:
            default = callee.default_of(p)
            if is_const(default, False, None):
                return None         # discharged by default
            continue                # enabled (required/truthy default)
        if is_const(expr, False, None):
            return None             # explicit opt-out at this call site
        if isinstance(expr, ast.Name) and expr.id in caller_params:
            conds.add(expr.id)      # condition forwarded upward
        # any other expression: enabled unconditionally
    return TaintItem(item.origin, item.line, item.desc, frozenset(conds))
