"""Rule family 2: jit-stability — silent-recompile and retrace hazards.

The compile-count guard tests (tests/test_flash_decode.py,
tests/test_streaming.py) exist because one stray shape or a re-wrapped
``jax.jit`` silently recompiles per step and the only symptom is a slow
sweep. These rules catch the three static precursors:

- ``jit-static-argnames``: ``static_argnames`` naming a parameter the
  wrapped function doesn't have (jax errors only at first CALL, which for
  a cold bucket can be mid-serving), and out-of-range ``donate_argnums``;
- ``jit-in-loop``: ``jax.jit`` / ``partial(jax.jit, ...)`` evaluated
  inside a loop or inside the hot call graph — every evaluation is a
  fresh cache, i.e. a recompile per iteration/request;
- ``jit-unbucketed-shape``: array constructors in hot-path functions
  whose shape derives from ``len(...)`` without passing through the pow2
  bucket helpers (``_next_bucket`` / ``_pow2_buckets``) — one compiled
  program per observed size instead of per bucket.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from . import callgraph as cg
from .core import Finding, ModuleInfo, Project, Rule, register

_BUCKET_HELPERS = ("_next_bucket", "_pow2_buckets", "next_bucket",
                   "pow2_buckets")
_ARRAY_CTORS = ("zeros", "ones", "full", "empty", "arange")
_ARRAY_MODULES = ("np", "numpy", "jnp")


def _is_jax_jit(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "jit"
            and isinstance(node.value, ast.Name)
            and node.value.id == "jax") or (
        isinstance(node, ast.Name) and node.id == "jit")


def _jit_call_info(call: ast.Call) -> Optional[ast.Call]:
    """The Call carrying jit kwargs if ``call`` is ``jax.jit(...)`` or
    ``partial(jax.jit, ...)``, else None."""
    if _is_jax_jit(call.func):
        return call
    if isinstance(call.func, ast.Name) and call.func.id == "partial" and \
            call.args and _is_jax_jit(call.args[0]):
        return call
    return None


def _literal_strings(node: ast.AST) -> Optional[List[str]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el.value)
            else:
                return None
        return out
    return None


def _literal_ints(node: ast.AST) -> Optional[List[int]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, int):
                out.append(el.value)
            else:
                return None
        return out
    return None


def _fn_param_names(fn: ast.AST) -> Set[str]:
    a = fn.args
    names = [p.arg for p in
             getattr(a, "posonlyargs", []) + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return set(names)


@register
class JitStaticArgnames(Rule):
    id = "jit-static-argnames"
    family = "jit"
    severity = "error"
    doc = ("static_argnames must name real parameters of the jitted "
           "function; donate_argnums must be in range — jax only checks "
           "at first call, which for a cold bucket is mid-serving")

    def check_module(self, mod: ModuleInfo,
                     project: Project) -> Iterable[Finding]:
        if mod.tree is None:
            return ()
        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            params = _fn_param_names(node)
            n_pos = len(getattr(node.args, "posonlyargs", [])) + \
                len(node.args.args)
            for dec in node.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                jc = _jit_call_info(dec)
                if jc is None:
                    continue
                for kw in jc.keywords:
                    if kw.arg == "static_argnames":
                        names = _literal_strings(kw.value)
                        for nm in names or []:
                            if nm not in params:
                                out.append(self.finding(
                                    mod, dec.lineno,
                                    f"static_argnames names {nm!r} but "
                                    f"`{node.name}` has no such parameter"
                                    f" (params: {sorted(params)})"))
                    elif kw.arg in ("donate_argnums", "static_argnums"):
                        nums = _literal_ints(kw.value)
                        for i in nums or []:
                            if not (0 <= i < n_pos):
                                out.append(self.finding(
                                    mod, dec.lineno,
                                    f"{kw.arg} index {i} out of range for"
                                    f" `{node.name}` ({n_pos} positional "
                                    f"parameters)"))
        return out


@register
class JitInLoop(Rule):
    id = "jit-in-loop"
    family = "jit"
    severity = "error"
    doc = ("jax.jit evaluated inside a loop or a hot-path function: each "
           "evaluation is a fresh wrapper with a fresh compile cache — a "
           "recompile per iteration/request. Wrap once at init.")

    def check_module(self, mod: ModuleInfo,
                     project: Project) -> Iterable[Finding]:
        if mod.tree is None:
            return ()
        out: List[Finding] = []

        def walk(node: ast.AST, loop_depth: int) -> None:
            for child in ast.iter_child_nodes(node):
                d = loop_depth
                if isinstance(child, (ast.For, ast.While, ast.AsyncFor)):
                    d += 1
                if isinstance(child, ast.Call) and \
                        _jit_call_info(child) is not None and d > 0:
                    out.append(self.finding(
                        mod, child.lineno,
                        "jax.jit wrapped inside a loop — hoist the wrap "
                        "out; the jit cache dies with the wrapper"))
                walk(child, d)

        walk(mod.tree, 0)
        return out

    def check_project(self, project: Project) -> Iterable[Finding]:
        # jit-wrapping anywhere in the hot graph is a per-request retrace
        # even without a lexical loop (the loop is the serving loop itself)
        graph = cg.build_call_graph(project)
        hot = cg.hot_reachable(project)
        out: List[Finding] = []
        for fi in graph.funcs:
            if fi.qual not in hot or fi.name == "__init__":
                continue
            for node in cg.iter_own_nodes(fi.node):
                if isinstance(node, ast.Call) and \
                        _jit_call_info(node) is not None:
                    out.append(self.finding(
                        fi.mod, node.lineno,
                        f"jax.jit evaluated inside hot-path function "
                        f"`{fi.name}` — a fresh compile cache per call; "
                        f"build the wrapper at engine init"))
        return out


@register
class JitUnbucketedShape(Rule):
    id = "jit-unbucketed-shape"
    family = "jit"
    severity = "error"
    doc = ("array constructed in a hot-path function with a len()-derived "
           "dimension that never passed _next_bucket/_pow2_buckets: feeds "
           "jitted dispatch one compiled program per observed size")

    def check_project(self, project: Project) -> Iterable[Finding]:
        graph = cg.build_call_graph(project)
        hot = cg.hot_reachable(project)
        out: List[Finding] = []
        for fi in graph.funcs:
            if fi.qual not in hot:
                continue
            dynamic = self._dynamic_names(fi.node)
            if not dynamic:
                continue
            for node in cg.iter_own_nodes(fi.node):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in _ARRAY_CTORS
                        and cg._expr_root_name(node.func)
                        in _ARRAY_MODULES and node.args):
                    continue
                bad = self._dynamic_dims(node.args[0], dynamic)
                if bad:
                    out.append(self.finding(
                        fi.mod, node.lineno,
                        f"shape dimension(s) {sorted(bad)} derive from "
                        f"len() without a pow2 bucket "
                        f"(_next_bucket/_pow2_buckets) in hot-path "
                        f"function `{fi.name}` — one compile per size"))
        return out

    @staticmethod
    def _dynamic_names(fn: ast.AST) -> Set[str]:
        """Names assigned from len()-containing expressions that never
        route through a bucket helper."""

        def has_call(node: ast.AST, names) -> bool:
            for n in ast.walk(node):
                if isinstance(n, ast.Call):
                    fnode = n.func
                    nm = fnode.id if isinstance(fnode, ast.Name) else \
                        getattr(fnode, "attr", "")
                    if nm in names:
                        return True
            return False

        def inline_bucketed(node: ast.AST) -> bool:
            # the repo's inline pow2 idiom: 1 << (n - 1).bit_length()
            return has_call(node, ("bit_length",))

        dyn: Set[str] = set()
        for node in cg.iter_own_nodes(fn):
            if not isinstance(node, ast.Assign):
                continue
            v = node.value
            uses_len = has_call(v, ("len",)) or any(
                isinstance(n, ast.Name) and n.id in dyn
                for n in ast.walk(v))
            bucketed = has_call(v, _BUCKET_HELPERS) or inline_bucketed(v)
            if uses_len and not bucketed:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        dyn.add(tgt.id)
            elif bucketed:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        dyn.discard(tgt.id)
        return dyn

    @staticmethod
    def _dynamic_dims(shape: ast.AST, dynamic: Set[str]) -> Set[str]:
        bad: Set[str] = set()
        dims = shape.elts if isinstance(shape, (ast.Tuple, ast.List)) \
            else [shape]
        for d in dims:
            if any(isinstance(n, ast.Call)
                   and getattr(n.func, "attr", "") == "bit_length"
                   for n in ast.walk(d)):
                continue                      # inline pow2 bucket
            for n in ast.walk(d):
                if isinstance(n, ast.Name) and n.id in dynamic:
                    bad.add(n.id)
                if isinstance(n, ast.Call) and \
                        isinstance(n.func, ast.Name) and n.func.id == "len":
                    bad.add("len(...)")
        return bad
