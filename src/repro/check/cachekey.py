"""cache-key: every axis that reaches lowering joins the cache key.

A plan/placement axis that changes what gets compiled but is missing
from the cache key makes the warm path serve a stale executable as fresh
data — the worst failure mode a benchmarking suite has, because the
numbers still look plausible. This rule makes "added an axis, forgot the
key" a CI failure:

* every non-``self`` parameter of ``Engine._bucket_key``, the one key
  builder, must be referenced inside the function (a parameter that
  does not reach the key is an axis that was plumbed in and then
  dropped);
* every field of the ``Placement`` dataclass (parsed from ``plan.py``)
  must appear as ``placement.<field>`` in the key builder;
* every ``*.disk_cache.<load/store/...>`` call site must pass a key that
  was produced by ``_bucket_key`` (or arrived as a parameter named
  ``key``), never an ad-hoc tuple;
* ``HloDiskCache._path`` must hash ``repr(key)`` of the whole key —
  subscripting the key there would silently drop axes from the digest.
"""

from __future__ import annotations

import ast

from repro.check.core import Context, Finding, checker, dotted_name

RULE = "cache-key"

_ENGINE_FILE = "src/repro/core/engine.py"
_PLAN_FILE = "src/repro/core/plan.py"
_HLOCACHE_FILE = "src/repro/core/hlocache.py"

_KEY_BUILDERS = ("_bucket_key",)
_DISK_CACHE_METHODS = {"load", "store", "load_tuned", "store_tuned"}


def _finding(file: str, line: int, message: str) -> Finding:
    return Finding(rule=RULE, severity="error", file=file, line=line, message=message)


def _placement_fields(ctx: Context) -> set[str]:
    tree = ctx.tree(_PLAN_FILE)
    if tree is None:
        return set()
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == "Placement":
            return {
                stmt.target.id
                for stmt in node.body
                if isinstance(stmt, ast.AnnAssign)
                and isinstance(stmt.target, ast.Name)
            }
    return set()


def _find_methods(tree: ast.Module, names: tuple[str, ...]) -> dict[str, ast.FunctionDef]:
    out: dict[str, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name in names:
            out[node.name] = node
    return out


def _check_builder(
    fn: ast.FunctionDef, placement_fields: set[str]
) -> list[Finding]:
    """Findings for one key-builder."""
    findings: list[Finding] = []

    params = [
        a.arg
        for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
        if a.arg != "self"
    ]
    used_names = {
        n.id for n in ast.walk(fn) if isinstance(n, ast.Name)
    }
    for p in params:
        if p not in used_names:
            findings.append(
                _finding(
                    _ENGINE_FILE,
                    fn.lineno,
                    f"{fn.name}() parameter {p!r} never reaches the key — "
                    "an axis was plumbed in and then dropped",
                )
            )

    attrs = {
        d
        for n in ast.walk(fn)
        if isinstance(n, ast.Attribute) and (d := dotted_name(n)) is not None
    }
    for field in sorted(placement_fields):
        if f"placement.{field}" not in attrs:
            findings.append(
                _finding(
                    _ENGINE_FILE,
                    fn.lineno,
                    f"{fn.name}() omits Placement.{field} — every Placement "
                    "axis must join the cache key",
                )
            )
    return findings


def _own_nodes(fn: ast.FunctionDef):
    """Nodes of a function body excluding nested function subtrees
    (nested defs are scanned separately, inheriting captured names)."""
    stack: list[ast.AST] = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            stack.extend(ast.iter_child_nodes(node))


def _check_disk_cache_sites(tree: ast.Module) -> list[Finding]:
    """Every disk_cache call's key argument must come from a key builder
    or a parameter literally named ``key``/``base_key``. Closures see the
    enclosing function's key bindings (captured names)."""
    findings: list[Finding] = []

    def scan_fn(fn: ast.FunctionDef, inherited: frozenset[str]) -> None:
        key_vars = set(inherited)
        key_vars.update(
            a.arg
            for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
            if a.arg in ("key", "base_key")
        )
        for node in _own_nodes(fn):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                callee = dotted_name(node.value.func) or ""
                if callee.split(".")[-1] in _KEY_BUILDERS:
                    key_vars.update(
                        t.id for t in node.targets if isinstance(t, ast.Name)
                    )
        for node in _own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = dotted_name(node.func)
            if callee is None:
                continue
            parts = callee.split(".")
            if len(parts) < 3 or parts[-2] != "disk_cache":
                continue
            if parts[-1] not in _DISK_CACHE_METHODS:
                continue
            if not node.args:
                continue
            first = node.args[0]
            ok = isinstance(first, ast.Name) and first.id in key_vars
            if not ok:
                findings.append(
                    _finding(
                        _ENGINE_FILE,
                        node.lineno,
                        f"disk_cache.{parts[-1]}() key must be bound from "
                        "_bucket_key(), not built ad hoc — "
                        "ad-hoc keys drift from the compile-cache key",
                    )
                )
        for node in _own_nodes(fn):
            if isinstance(node, ast.FunctionDef):
                scan_fn(node, frozenset(key_vars))

    for node in tree.body:
        if isinstance(node, ast.FunctionDef):
            scan_fn(node, frozenset())
        elif isinstance(node, ast.ClassDef):
            for item in node.body:
                if isinstance(item, ast.FunctionDef):
                    scan_fn(item, frozenset())
    return findings


def _check_hlocache_path(ctx: Context) -> list[Finding]:
    findings: list[Finding] = []
    tree = ctx.tree(_HLOCACHE_FILE)
    if tree is None:
        return findings
    fn = _find_methods(tree, ("_path",)).get("_path")
    if fn is None:
        return findings
    key_params = {
        a.arg
        for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
        if a.arg != "self"
    }
    has_repr_of_key = any(
        isinstance(n, ast.Call)
        and isinstance(n.func, ast.Name)
        and n.func.id == "repr"
        and n.args
        and isinstance(n.args[0], ast.Name)
        and n.args[0].id in key_params
        for n in ast.walk(fn)
    )
    if not has_repr_of_key:
        findings.append(
            _finding(
                _HLOCACHE_FILE,
                fn.lineno,
                "HloDiskCache._path must digest repr(key) of the whole key "
                "tuple so every axis reaches the on-disk path",
            )
        )
    for n in ast.walk(fn):
        if (
            isinstance(n, ast.Subscript)
            and isinstance(n.value, ast.Name)
            and n.value.id in key_params
        ):
            findings.append(
                _finding(
                    _HLOCACHE_FILE,
                    n.lineno,
                    "HloDiskCache._path must not subscript the key — "
                    "selecting elements drops axes from the digest",
                )
            )
    return findings


@checker(
    RULE,
    "every ExecutionPlan/Placement axis joins the key shared by the "
    "compile cache and the HLO disk cache; disk-cache call sites use "
    "builder-produced keys",
)
def check_cache_key(ctx: Context) -> list[Finding]:
    findings: list[Finding] = []
    tree = ctx.tree(_ENGINE_FILE)
    if tree is not None:
        placement_fields = _placement_fields(ctx)
        builders = _find_methods(tree, _KEY_BUILDERS)
        for name in _KEY_BUILDERS:
            fn = builders.get(name)
            if fn is None:
                findings.append(
                    _finding(
                        _ENGINE_FILE,
                        1,
                        f"engine.py must define {name}() — it is the single "
                        "source of executable identity",
                    )
                )
                continue
            findings.extend(_check_builder(fn, placement_fields))
        findings.extend(_check_disk_cache_sites(tree))
    findings.extend(_check_hlocache_path(ctx))
    return findings
