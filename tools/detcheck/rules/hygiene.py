"""Cache/kernel hygiene rules (HYG family) and the meta rules whose
logic lives in the engine (MAN/SUP) but whose catalog entries — id,
tier, rationale — are registered here so docs/ANALYSIS.md can diff a
complete rule set.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set

from tools.detcheck.core import FileContext, ProjectContext, rule, Violation

# Producers whose outputs are fp32-accumulated *tolerance* results: a
# kernel route's flat-batch dispatch, `_kernel_batch`, should one be
# brought back into the engine. The engine's own batch path
# (`_execute_batch`) is exact and is not one of them. The last element
# of a producer's returned tuple is the exactness flag; anything it
# produces must not reach the byte-exact engine cache unless that flag
# gates the write.
KERNEL_PRODUCERS = {"_kernel_batch"}
EXACTNESS_GUARD_HINTS = ("approximate", "exact")


@rule("HYG001", name="kernel-output-cache-guard", tier="deterministic",
      rationale="Kernel-routed outputs are tolerance-compared, not "
                "byte-exact; writing one into the exact-path engine "
                "cache poisons every later warm hit with bytes that "
                "differ from the reference semantics.",
      example="out, auxs, approx = _kernel_batch(...); "
              "cache.put(t.sub_root, out[0], nb)")
def hyg001(ctx: FileContext) -> Iterator[Violation]:
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        tainted, guards = _kernel_taint(fn)
        if not tainted:
            continue
        yield from _unguarded_puts(ctx, fn, tainted, guards)


def _kernel_taint(fn: ast.AST) -> tuple:
    """(kernel-tainted names, exactness-guard names) in one function."""
    tainted: Set[str] = set()
    guards: Set[str] = set()
    for _ in range(5):
        n0 = (len(tainted), len(guards))
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and _is_producer(node.value):
                for t in node.targets:
                    if isinstance(t, (ast.Tuple, ast.List)):
                        elts = t.elts
                        for e in elts[:-1]:
                            if isinstance(e, ast.Name):
                                tainted.add(e.id)
                        if elts and isinstance(elts[-1], ast.Name):
                            guards.add(elts[-1].id)
                    elif isinstance(t, ast.Name):
                        tainted.add(t.id)
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                if _refs_tainted(node.iter, tainted):
                    for e in ast.walk(node.target):
                        if isinstance(e, ast.Name):
                            tainted.add(e.id)
        if (len(tainted), len(guards)) == n0:
            break
    return tainted, guards


def _is_producer(value: ast.expr) -> bool:
    return (isinstance(value, ast.Call)
            and isinstance(value.func, ast.Name)
            and value.func.id in KERNEL_PRODUCERS)


def _refs_tainted(node: ast.expr, tainted: Set[str]) -> bool:
    return any(isinstance(n, ast.Name) and n.id in tainted
               for n in ast.walk(node))


def _unguarded_puts(ctx: FileContext, fn: ast.AST, tainted: Set[str],
                    guards: Set[str]) -> Iterator[Violation]:
    # walk with an explicit if-stack so each cache write knows the
    # conditions dominating it
    def visit(node: ast.AST, conds: List[ast.expr]):
        if isinstance(node, ast.If):
            for child in node.body:
                visit(child, conds + [node.test])
            for child in node.orelse:
                visit(child, conds)       # else-branch: guard inverted
            return
        # only the *stored value* arguments must be exact — args[0] is
        # the cache key, which legitimately derives from task metadata
        # that shares names with kernel-loop variables
        stored = list(node.args[1:]) + [kw.value for kw in node.keywords] \
            if isinstance(node, ast.Call) and _is_cache_put(node) else []
        if stored and any(_refs_tainted(a, tainted) for a in stored):
            guard_names = guards | set(EXACTNESS_GUARD_HINTS)
            if not any(_mentions(c, guard_names) for c in conds):
                yield_list.append(ctx.violation(
                    "HYG001", node,
                    "kernel-routed output written to the exact-path "
                    "engine cache without an exactness guard (`if not "
                    "approximate`): kernel results are tolerance-"
                    "compared fp32 accumulations, never byte-exact"))
        for child in ast.iter_child_nodes(node):
            visit(child, conds)

    yield_list: List[Violation] = []
    for stmt in getattr(fn, "body", []):
        visit(stmt, [])
    yield from yield_list


def _is_cache_put(call: ast.Call) -> bool:
    return (isinstance(call.func, ast.Attribute)
            and call.func.attr == "put"
            and isinstance(call.func.value, ast.Name)
            and "cache" in call.func.value.id)


def _mentions(cond: ast.expr, names: Set[str]) -> bool:
    for n in ast.walk(cond):
        if isinstance(n, ast.Name) and any(
                h in n.id for h in names):
            return True
    return False


@rule("HYG002", name="deprecation-warn-once-helper", tier="global",
      rationale="Deprecation shims must warn exactly once per caller "
                "and stay byte-identical; routing every warn through a "
                "stacklevel-carrying _warn* helper is what makes the "
                "once-semantics (and the CI -W error policy) uniform.",
      example="warnings.warn('x is deprecated', DeprecationWarning)")
def hyg002(ctx: FileContext) -> Iterator[Violation]:
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        is_helper = fn.name.startswith("_warn")
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and ctx.dotted(node.func) == "warnings.warn"):
                continue
            if not any(isinstance(a, ast.Name)
                       and a.id == "DeprecationWarning"
                       for a in list(node.args)
                       + [kw.value for kw in node.keywords]):
                continue
            has_stacklevel = any(kw.arg == "stacklevel"
                                 for kw in node.keywords)
            if not is_helper:
                yield ctx.violation(
                    "HYG002", node,
                    f"direct warnings.warn(DeprecationWarning) in "
                    f"{fn.name}; route it through a module _warn* "
                    "helper that passes stacklevel so every shim "
                    "dedups and blames the caller uniformly")
            elif not has_stacklevel:
                yield ctx.violation(
                    "HYG002", node,
                    f"deprecation helper {fn.name} must pass an "
                    "explicit stacklevel= so the warning (and its "
                    "once-per-site dedup) lands on the caller")


# ----------------------------------------------------- meta / manifest ---


@rule("MAN001", name="tier-manifest-declared", tier="global",
      rationale="Determinism rules only bind where a tier is declared; "
                "an undeclared package silently opts out of the SEC "
                "obligations, so the manifest itself is checked.",
      example="src/repro/newpkg/__init__.py without DETCHECK_TIER",
      project=True)
def man001(project: ProjectContext) -> Iterator[Violation]:
    seen: Dict[str, FileContext] = {}
    for f in project.files:
        if f.rel.endswith("__init__.py") and "src/repro" in f.rel:
            seen[f.rel] = f
    for rel, f in sorted(seen.items()):
        declared: Optional[str] = None
        for node in f.tree.body:
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == "DETCHECK_TIER"
                    and isinstance(node.value, ast.Constant)):
                declared = str(node.value.value)
        if declared is None:
            yield Violation(
                "MAN001", rel, 1,
                "package declares no DETCHECK_TIER "
                "(\"deterministic\" | \"environment\") — every "
                "src/repro package must choose its determinism tier "
                "explicitly")
        elif declared not in ("deterministic", "environment"):
            yield Violation(
                "MAN001", rel, 1,
                f"unknown DETCHECK_TIER {declared!r}; use "
                "\"deterministic\" or \"environment\"")


def _noop(_ctx) -> Iterator[Violation]:
    return iter(())


# SUP001/SUP002 fire from the engine's suppression pass (core.run);
# registered here so the rule catalog (DOC002) covers them.
rule("SUP001", name="suppression-needs-reason", tier="global",
     rationale="An allow[...] with no written reason is an audit hole: "
               "the next reader cannot tell a justified exemption from "
               "a silenced bug.",
     example="x = time.time()  # detcheck: allow[DET001]")(_noop)
rule("SUP002", name="suppression-staleness", tier="global",
     rationale="A suppression whose rule no longer fires on that line "
               "is dead weight that will silently swallow the next "
               "real violation there — stale allows are violations.",
     example="y = 1  # detcheck: allow[DET001] leftover comment")(_noop)
