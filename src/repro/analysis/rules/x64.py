"""RPL002 — x64-hygiene: keep float64 a *scoped* choice.

PR 7 established the convention: the fleet's jitted kernels run under
``with jax.enable_x64(True):`` at their call sites, so x64 is
an explicitly scoped property of the fleet fast path — never a
process-global flip that silently changes every other kernel's dtypes
(the Pallas kernels and the fed training loop are f32).

Two checks:

  * a module-level ``jax.config.update(...)`` anywhere in the linted
    tree (the global flip: importing the module changes numerics for
    the whole process);
  * in ``edge/fleet/`` files, any call to a function the same module
    decorated with ``jax.jit`` must sit lexically inside a
    ``with jax.enable_x64(True):`` block.
"""
from __future__ import annotations

import ast

from repro.analysis.core import ModuleSource, Rule, register

JIT_NAMES = {"jax.jit", "jit"}
PARTIAL_NAMES = {"partial", "functools.partial"}
ENABLE_X64 = {"jax.enable_x64"}


def jit_decorated_functions(mod: ModuleSource) -> dict:
    """{name: FunctionDef} for every function the module decorates with
    ``@jax.jit`` or ``@partial(jax.jit, ...)``."""
    out = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and jit_static_argnames(mod, node) is not None:
            out[node.name] = node
    return out


def jit_static_argnames(mod: ModuleSource, fn: ast.FunctionDef):
    """None if ``fn`` is not jit-decorated, else the set of
    ``static_argnames`` its decorator declares (possibly empty)."""
    for dec in fn.decorator_list:
        if mod.resolve(dec) in JIT_NAMES:
            return set()
        if isinstance(dec, ast.Call):
            if mod.resolve(dec.func) in JIT_NAMES:
                return _static_names(dec)
            if mod.resolve(dec.func) in PARTIAL_NAMES and dec.args \
                    and mod.resolve(dec.args[0]) in JIT_NAMES:
                return _static_names(dec)
    return None


def _static_names(call: ast.Call) -> set:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            names = set()
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    names.add(el.value)
            return names
    return set()


def under_enable_x64(mod: ModuleSource, node: ast.AST) -> bool:
    for anc in mod.ancestors(node):
        if isinstance(anc, ast.With):
            for item in anc.items:
                expr = item.context_expr
                target = expr.func if isinstance(expr, ast.Call) else expr
                if mod.resolve(target) in ENABLE_X64:
                    return True
    return False


@register
class X64HygieneRule(Rule):
    id = "RPL002"
    title = "x64-hygiene"
    description = ("no module-level jax.config.update; calls to "
                   "jit-decorated fleet kernels must sit under "
                   "`with jax.enable_x64(True):` (the PR-7 scoping)")

    def check(self, mod: ModuleSource) -> list:
        out = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if mod.resolve(node.func) == "jax.config.update" \
                    and mod.at_module_level(node):
                out.append(self.finding(
                    mod, node,
                    "module-level jax.config.update flips numerics for "
                    "the whole process on import — scope x64 with `with "
                    "jax.enable_x64(True):` at the call site instead"))
        if "edge/fleet/" in mod.path:
            out.extend(self._check_fleet_scoping(mod))
        return out

    def _check_fleet_scoping(self, mod: ModuleSource) -> list:
        jitted = jit_decorated_functions(mod)
        if not jitted:
            return []
        out = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = node.func.id if isinstance(node.func, ast.Name) else None
            if name not in jitted:
                continue
            # the decorated def itself references jax.jit, not the kernel
            if under_enable_x64(mod, node):
                continue
            out.append(self.finding(
                mod, node,
                f"call to jitted kernel {name}() outside `with "
                "jax.enable_x64(True):` — fleet kernels must match the float64 "
                "numpy references (PR-7 scoping)"))
        return out
