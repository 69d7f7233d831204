"""Shared AST project model for the brelint passes.

Builds a whole-tree view of ``src/`` once (parsed modules, import alias
maps, every function/method with a stable qualified name) so the passes
can resolve call targets without importing any repo code.  Everything is
stdlib ``ast`` — brelint must run in the dependency-free CI jobs.

Resolution is deliberately best-effort: a call we cannot resolve simply
contributes no edge, so the passes stay quiet rather than noisy when the
tree grows new idioms.  The contract each pass enforces is documented in
docs/static_analysis.md.
"""

from __future__ import annotations

import ast
import dataclasses
from pathlib import Path


@dataclasses.dataclass
class Finding:
    """One violation: stable id + location + suppression key."""

    invariant: str      # e.g. "trace-host-op"
    path: Path          # absolute path of the offending file
    line: int
    symbol: str         # qualname used as the baseline suppression key
    message: str

    def key(self, root: Path) -> tuple[str, str, str]:
        return (self.invariant, self.relpath(root), self.symbol)

    def relpath(self, root: Path) -> str:
        try:
            return self.path.relative_to(root).as_posix()
        except ValueError:
            return self.path.as_posix()

    def render(self, root: Path) -> str:
        return (f"{self.relpath(root)}:{self.line}: [{self.invariant}] "
                f"{self.message}  (key: {self.symbol})")


@dataclasses.dataclass
class FunctionInfo:
    """A def/lambda anywhere in the tree, with a stable qualname."""

    qualname: str                    # repro.core.search.knn / ...Cls.meth
    name: str                        # last component
    module: "ModuleInfo"
    node: ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda
    cls: str | None = None           # enclosing class, if a method

    @property
    def line(self) -> int:
        return self.node.lineno

    @property
    def args(self) -> ast.arguments:
        return self.node.args

    @property
    def params(self) -> list[str]:
        a = self.args
        return [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]

    def positional_params(self) -> list[str]:
        a = self.args
        return [p.arg for p in a.posonlyargs + a.args]

    def default_of(self, param: str) -> ast.expr | None:
        """The default expression for ``param``, or None if required."""
        a = self.args
        pos = a.posonlyargs + a.args
        for p, d in zip(pos[len(pos) - len(a.defaults):], a.defaults):
            if p.arg == param:
                return d
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if p.arg == param and d is not None:
                return d
        return None

    def has_kwargs(self) -> bool:
        return self.args.kwarg is not None


@dataclasses.dataclass
class ModuleInfo:
    """One parsed source file plus its import alias maps."""

    name: str                        # dotted, e.g. repro.core.search
    path: Path
    tree: ast.Module
    # local alias -> dotted module name ("np" -> "numpy")
    imports: dict[str, str] = dataclasses.field(default_factory=dict)
    # local name -> (source module, original name) for from-imports
    from_imports: dict[str, tuple[str, str]] = dataclasses.field(
        default_factory=dict)
    functions: dict[str, FunctionInfo] = dataclasses.field(
        default_factory=dict)   # qualname -> info
    classes: dict[str, ast.ClassDef] = dataclasses.field(
        default_factory=dict)

    @property
    def package(self) -> str:
        return self.name.rsplit(".", 1)[0] if "." in self.name else ""


def dotted_name(node: ast.expr) -> str | None:
    """'a.b.c' for a pure Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def is_const(node: ast.expr | None, *values) -> bool:
    return isinstance(node, ast.Constant) and any(
        node.value is v for v in values)


class Project:
    """All parsed modules under ``src_root`` with cross-module resolution."""

    def __init__(self, src_root: Path):
        self.src_root = src_root
        self.modules: dict[str, ModuleInfo] = {}
        self.functions: dict[str, FunctionInfo] = {}
        for path in sorted(src_root.rglob("*.py")):
            rel = path.relative_to(src_root).with_suffix("")
            parts = list(rel.parts)
            if parts[-1] == "__init__":
                parts = parts[:-1]
            name = ".".join(parts) if parts else "__root__"
            try:
                tree = ast.parse(path.read_text(encoding="utf-8"))
            except SyntaxError:
                continue
            mod = ModuleInfo(name=name, path=path, tree=tree)
            self._index_module(mod)
            self.modules[name] = mod
        self.packages = {m.rsplit(".", 1)[0] for m in self.modules
                         if "." in m} | set(self.modules)

    # -- indexing ----------------------------------------------------------

    def _index_module(self, mod: ModuleInfo) -> None:
        for node in mod.tree.body:
            if isinstance(node, ast.Import):
                for alias in node.names:
                    mod.imports[alias.asname or alias.name.split(".")[0]] = (
                        alias.name if alias.asname else
                        alias.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom):
                src = self._resolve_from(mod, node)
                if src is None:
                    continue
                for alias in node.names:
                    mod.from_imports[alias.asname or alias.name] = (
                        src, alias.name)
        for node in mod.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._add_function(mod, node, prefix=mod.name, cls=None)
            elif isinstance(node, ast.ClassDef):
                mod.classes[node.name] = node
                for item in node.body:
                    if isinstance(item,
                                  (ast.FunctionDef, ast.AsyncFunctionDef)):
                        self._add_function(
                            mod, item, prefix=f"{mod.name}.{node.name}",
                            cls=node.name)

    def _resolve_from(self, mod: ModuleInfo,
                      node: ast.ImportFrom) -> str | None:
        if node.level == 0:
            return node.module
        base = mod.name.split(".")
        # level 1 == current package; the module's own last component is
        # not part of the package unless this file is an __init__.
        if not mod.path.name == "__init__.py":
            base = base[:-1]
        drop = node.level - 1
        if drop:
            base = base[:-drop] if drop <= len(base) else []
        return ".".join(base + ([node.module] if node.module else [])) or None

    def _add_function(self, mod: ModuleInfo, node, prefix: str,
                      cls: str | None) -> None:
        qual = f"{prefix}.{node.name}"
        info = FunctionInfo(qualname=qual, name=node.name, module=mod,
                            node=node, cls=cls)
        mod.functions[qual] = info
        self.functions[qual] = info
        # nested defs get qualnames too (trace roots are often closures)
        for child in ast.walk(node):
            if child is node:
                continue
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nested_qual = f"{qual}.{child.name}"
                if nested_qual not in self.functions:
                    ninfo = FunctionInfo(qualname=nested_qual,
                                         name=child.name, module=mod,
                                         node=child, cls=cls)
                    mod.functions[nested_qual] = ninfo
                    self.functions[nested_qual] = ninfo

    # -- resolution --------------------------------------------------------

    def canonical(self, mod: ModuleInfo, node: ast.expr) -> str | None:
        """Alias-expanded dotted name of an expression, if nameable.

        ``np.asarray`` -> ``numpy.asarray``; ``shd.shard_map`` ->
        ``repro.dist.sharding.shard_map``; plain names resolve through
        from-imports (``partial`` -> ``functools.partial``).
        """
        dotted = dotted_name(node)
        if dotted is None:
            return None
        head, _, rest = dotted.partition(".")
        if head in mod.imports:
            base = mod.imports[head]
            return f"{base}.{rest}" if rest else base
        if head in mod.from_imports:
            src, orig = mod.from_imports[head]
            base = f"{src}.{orig}"
            return f"{base}.{rest}" if rest else base
        return dotted

    def resolve_call(self, mod: ModuleInfo, call: ast.Call,
                     scope: FunctionInfo | None = None) -> str | None:
        """Project qualname for a call target, if it lives in the tree."""
        func = call.func
        if isinstance(func, ast.Name):
            name = func.id
            # innermost enclosing scope wins: try the scope itself, then
            # each enclosing function, then the module top level.
            prefix = scope.qualname if scope is not None else mod.name
            while True:
                cand = f"{prefix}.{name}"
                if cand in self.functions:
                    return cand
                if prefix == mod.name or "." not in prefix:
                    break
                prefix = prefix.rsplit(".", 1)[0]
            local = f"{mod.name}.{name}"
            if local in self.functions:
                return local
            if name in mod.from_imports:
                src, orig = mod.from_imports[name]
                target = f"{src}.{orig}"
                if target in self.functions:
                    return target
            return None
        if isinstance(func, ast.Attribute):
            base = func.value
            if (isinstance(base, ast.Name) and base.id == "self"
                    and scope is not None and scope.cls is not None):
                cand = f"{mod.name}.{scope.cls}.{func.attr}"
                return cand if cand in self.functions else None
            canon = self.canonical(mod, func)
            if canon is not None and canon in self.functions:
                return canon
            # ``module_alias.fn`` where the alias names a project module
            if canon is not None:
                head, _, fn = canon.rpartition(".")
                if head in self.modules:
                    cand = f"{head}.{fn}"
                    return cand if cand in self.functions else None
        return None

    def constants(self, mod: ModuleInfo) -> dict[str, object]:
        """Module-level constant tuples/dicts, shallowly evaluated."""
        out: dict[str, object] = {}
        for node in mod.tree.body:
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                val = _const_eval(node.value, out)
                if val is not None:
                    out[node.targets[0].id] = val
        return out


def _const_eval(node: ast.expr, env: dict[str, object]):
    """Tuples, string/number constants, + concatenation, dict literals."""
    if isinstance(node, ast.Constant):
        return node.value
    if isinstance(node, ast.Name):
        return env.get(node.id)
    if isinstance(node, ast.Tuple):
        items = [_const_eval(e, env) for e in node.elts]
        return None if any(i is None for i in items) else tuple(items)
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = _const_eval(node.left, env)
        right = _const_eval(node.right, env)
        if isinstance(left, tuple) and isinstance(right, tuple):
            return left + right
        return None
    if isinstance(node, ast.Dict):
        out = {}
        for k, v in zip(node.keys, node.values):
            if k is None:          # {**other, ...} expansion
                expanded = _const_eval(v, env)
                if not isinstance(expanded, dict):
                    return None
                out.update(expanded)
                continue
            key = _const_eval(k, env)
            if key is None:
                return None
            out[key] = _const_eval(v, env)
        return out
    return None
