"""Pass 1 — jit-safety: no host round-trips on traced hot paths.

CRAIG's device-resident speedup (DESIGN.md §3.6/§9) dies silently: a
``.item()``, an ``np.asarray``, or a Python ``if`` on an array value inside
a jitted selection loop doesn't crash — it inserts a blocking device→host
transfer per greedy round and the 2–3x engine wins quietly evaporate (or,
under ``jax.jit``, a TracerConversionError only on the code path a test
happens to execute).  This pass finds them statically, repo-wide.

Roots — functions whose bodies are traced:
  * defs decorated ``@jax.jit`` / ``@functools.partial(jax.jit, ...)``;
  * callees handed to ``jax.jit(...)``, ``lax.scan``, ``lax.while_loop``,
    ``lax.fori_loop``, ``lax.cond``, ``lax.switch``, ``lax.map``,
    ``jax.vmap`` and ``shard_map`` (resolved through local defs, lambdas
    and factories);
  * ``select`` methods of engines whose registry ``Capabilities`` declare
    ``jit_safe=True`` — the capability *is* the contract the trainer's
    zero-copy handoff relies on, so the linter holds the method to it.
    (``select_cover`` is exempt: cover mode is data-dependently sized and
    documented host-side.)

From the roots the pass walks the project call graph (same-module calls,
``self.method``, and cross-module calls resolved through imports) and
flags, anywhere reachable:

  * ``.item()`` / ``.tolist()``                — host materialization;
  * ``jax.device_get``                         — explicit transfer;
  * ``np.asarray`` / ``np.array``              — host materialization;
  * ``float()``/``int()``/``bool()`` over an expression that contains a
    jax/jnp call or an array-reduction method — concretization sync;
  * ``if``/``while``/``assert``/ternary tests containing one — Python
    control flow on a traced value.

Static-config jax calls (``jax.default_backend()`` etc.) are exempt: they
return Python scalars at trace time.
"""
from __future__ import annotations

import ast
from typing import Iterable, Iterator

from repro.analysis.engine import Rule
from repro.analysis.findings import Finding
from repro.analysis.index import FileIndex, ModuleInfo, resolve_callable

RULE_ID = "jit-host-sync"

# Call sites whose function-valued arguments are traced.
_TRACING_CALLERS = frozenset(
    {
        "jax.jit",
        "jax.vmap",
        "jax.pmap",
        "jax.lax.scan",
        "jax.lax.while_loop",
        "jax.lax.fori_loop",
        "jax.lax.cond",
        "jax.lax.switch",
        "jax.lax.map",
        "jax.lax.associative_scan",
        "jax.shard_map",
    }
)

# jax.* calls that return host scalars/objects at trace time — NOT traced
# values, so branching on them is fine.
_STATIC_JAX_CALLS = frozenset(
    {
        "jax.default_backend",
        "jax.devices",
        "jax.local_devices",
        "jax.device_count",
        "jax.local_device_count",
        "jax.process_index",
        "jax.process_count",
        "jax.dtypes.canonicalize_dtype",
        "jax.numpy.dtype",
        "jax.eval_shape",
    }
)

# Array-producing namespaces: a call into one of these yields a traced value.
_TRACED_PREFIXES = ("jax.numpy.", "jax.lax.", "jax.random.", "jax.nn.")

# Methods that reduce/convert arrays — `bool(x.any())` style.
_ARRAY_METHODS = frozenset(
    {"sum", "max", "min", "mean", "prod", "any", "all", "argmax", "argmin",
     "dot", "astype"}
)

_HOST_METHODS = frozenset({"item", "tolist"})
_HOST_CALLS = {
    "jax.device_get": "jax.device_get forces a device->host transfer",
    "numpy.asarray": "np.asarray materializes a traced value on the host",
    "numpy.array": "np.array materializes a traced value on the host",
}


class JitSafetyRule(Rule):
    rule_ids = (RULE_ID,)
    description = (
        "host round-trips (.item, np.asarray, device_get, scalar coercion, "
        "Python branching on arrays) reachable from jit/scan/while_loop "
        "roots and jit_safe=True engine select paths"
    )

    def run(self, index: FileIndex) -> Iterable[Finding]:
        roots = _collect_roots(index)
        reachable = _reachable(index, roots)
        findings: list[Finding] = []
        seen: set[tuple[str, int, str]] = set()
        for mod, fn, why in reachable:
            for f in _scan_function(mod, fn, why):
                key = (f.path, f.line, f.message)
                if key not in seen:
                    seen.add(key)
                    findings.append(f)
        return findings


# ---------------------------------------------------------------------------
# Roots
# ---------------------------------------------------------------------------


def _collect_roots(
    index: FileIndex,
) -> list[tuple[ModuleInfo, ast.AST, str]]:
    roots: list[tuple[ModuleInfo, ast.AST, str]] = []
    for mod in index.modules:
        # 1. @jax.jit-decorated defs
        for fn in mod.functions.values():
            for dec in fn.decorator_list:
                if _is_jit_decorator(mod, dec):
                    roots.append((mod, fn, f"@jax.jit {mod.qualname_of(fn)}"))
                    break
        # 2. callees of tracing transforms
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fq = mod.qualify(node.func)
            if fq not in _TRACING_CALLERS:
                continue
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, (ast.Name, ast.Lambda)) or (
                    isinstance(arg, ast.Call)
                ):
                    hit = resolve_callable(index, mod, arg, node)
                    if hit is not None:
                        roots.append(
                            (hit[0], hit[1], f"callee of {fq.split('.')[-1]}")
                        )
        # 3. select() of jit_safe=True engines
        for cls in mod.classes.values():
            if not _declares_jit_safe(mod, cls):
                continue
            for stmt in cls.body:
                if (
                    isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and stmt.name == "select"
                ):
                    roots.append(
                        (mod, stmt,
                         f"{cls.name}.select (capabilities jit_safe=True)")
                    )
    return roots


def _is_jit_decorator(mod: ModuleInfo, dec: ast.AST) -> bool:
    if mod.qualify(dec) == "jax.jit":
        return True
    if isinstance(dec, ast.Call):
        fq = mod.qualify(dec.func)
        if fq == "jax.jit":
            return True
        if fq == "functools.partial" and dec.args:
            return mod.qualify(dec.args[0]) == "jax.jit"
    return False


def _declares_jit_safe(mod: ModuleInfo, cls: ast.ClassDef) -> bool:
    for stmt in cls.body:
        if not isinstance(stmt, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == "capabilities"
            for t in stmt.targets
        ):
            continue
        call = stmt.value
        if not isinstance(call, ast.Call):
            continue
        fq = mod.qualify(call.func) or ""
        if not fq.endswith("Capabilities"):
            continue
        for kw in call.keywords:
            if kw.arg == "jit_safe" and isinstance(kw.value, ast.Constant):
                return bool(kw.value.value)
    return False


# ---------------------------------------------------------------------------
# Reachability
# ---------------------------------------------------------------------------


def _reachable(
    index: FileIndex, roots: list[tuple[ModuleInfo, ast.AST, str]]
) -> list[tuple[ModuleInfo, ast.AST, str]]:
    out: list[tuple[ModuleInfo, ast.AST, str]] = []
    visited: set[tuple[str, int]] = set()
    stack = list(roots)
    while stack:
        mod, fn, why = stack.pop()
        key = (mod.path, fn.lineno)
        if key in visited:
            continue
        visited.add(key)
        out.append((mod, fn, why))
        for cmod, callee, cname in _callees(index, mod, fn):
            stack.append(
                (cmod, callee, f"{why} -> {cname}")
            )
    return out


def _callees(
    index: FileIndex, mod: ModuleInfo, fn: ast.AST
) -> Iterator[tuple[ModuleInfo, ast.AST, str]]:
    """Project-internal functions ``fn``'s body may call."""
    encl_class = mod.enclosing_class(fn)
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        # self.method() / cls.method()
        if (
            isinstance(func, ast.Attribute)
            and isinstance(func.value, ast.Name)
            and func.value.id in ("self", "cls")
            and encl_class is not None
        ):
            target = mod.functions.get(f"{encl_class.name}.{func.attr}")
            if target is not None:
                yield mod, target, func.attr
            continue
        if isinstance(func, ast.Name):
            hit = resolve_callable(index, mod, func, node)
            if hit is not None:
                yield hit[0], hit[1], func.id
            continue
        fq = mod.qualify(func)
        if fq is None or not fq.startswith("repro."):
            continue
        target_mod, _, fn_name = fq.rpartition(".")
        hit = index.lookup_function(target_mod, fn_name)
        if hit is not None:
            yield hit[0], hit[1], fn_name


# ---------------------------------------------------------------------------
# Violation scan
# ---------------------------------------------------------------------------


def _scan_function(
    mod: ModuleInfo, fn: ast.AST, why: str
) -> Iterator[Finding]:
    ctx = f" [traced: {why}]"
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            func = node.func
            if (
                isinstance(func, ast.Attribute)
                and func.attr in _HOST_METHODS
                and not node.args
            ):
                yield Finding(
                    mod.path, node.lineno, RULE_ID,
                    f".{func.attr}() blocks on a device->host copy of a "
                    f"traced value{ctx}",
                )
                continue
            fq = mod.qualify(func)
            if fq in _HOST_CALLS:
                yield Finding(
                    mod.path, node.lineno, RULE_ID,
                    _HOST_CALLS[fq] + ctx,
                )
                continue
            if (
                isinstance(func, ast.Name)
                and func.id in ("float", "int", "bool")
                and func.id not in mod.imports
                and node.args
                and any(_contains_traced(mod, a) for a in node.args)
            ):
                yield Finding(
                    mod.path, node.lineno, RULE_ID,
                    f"{func.id}() concretizes a traced value (host sync); "
                    f"keep it an array or hoist it to static config{ctx}",
                )
        elif isinstance(node, (ast.If, ast.While, ast.IfExp)):
            if _contains_traced(mod, node.test):
                yield Finding(
                    mod.path, node.test.lineno, RULE_ID,
                    "Python control flow on a traced value (host sync); "
                    f"use lax.cond/jnp.where{ctx}",
                )
        elif isinstance(node, ast.Assert):
            if _contains_traced(mod, node.test):
                yield Finding(
                    mod.path, node.lineno, RULE_ID,
                    "assert on a traced value (host sync); use static "
                    f"shapes or checkify{ctx}",
                )


def _contains_traced(mod: ModuleInfo, expr: ast.AST) -> bool:
    """Does this expression contain a call that yields a traced array?"""
    for node in ast.walk(expr):
        if not isinstance(node, ast.Call):
            continue
        fq = mod.qualify(node.func)
        if fq is not None:
            if fq in _STATIC_JAX_CALLS:
                continue
            if fq.startswith(_TRACED_PREFIXES):
                return True
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _ARRAY_METHODS
            and not _is_module_call(mod, node.func)
        ):
            return True
    return False


def _is_module_call(mod: ModuleInfo, func: ast.Attribute) -> bool:
    """True when the attribute chain's root name is an import — then the
    qualified-prefix test above is authoritative and the array-method
    heuristic must not fire (``np.prod`` on Python ints is host math, not
    a traced reduction)."""
    node: ast.AST = func
    while isinstance(node, ast.Attribute):
        node = node.value
    return isinstance(node, ast.Name) and node.id in mod.imports
