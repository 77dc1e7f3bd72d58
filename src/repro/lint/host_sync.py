"""host-sync / retrace hygiene pass.

Flags device→host synchronization and recompilation hazards:

  * host-sync calls (``float()``, ``.item()``, ``np.asarray``,
    ``jax.device_get``, ``print``, ``.block_until_ready()``) inside
    jit-traced code — these either fail at trace time or silently insert a
    blocking transfer per step;
  * the same calls inside host-side hot loops and per-arrival callbacks
    (the scheduler's ``execute=`` path) when they touch values produced by
    a jitted step — a per-round device sync defeating async dispatch;
  * jit closures rebuilt per call: a ``@jax.jit`` function defined *and
    called* inside another function gets a fresh cache on every invocation,
    i.e. a full retrace per round;
  * ``static_argnames`` naming parameters the wrapped function does not
    have, and ``static_argnums``/``donate_argnums`` out of range — silent
    cache-miss churn on newer JAX, errors on older;
  * hand-rolled timing (``time.perf_counter`` & friends) and ``print``
    in the ``repro/federated`` / ``repro/core`` hot paths — telemetry
    there goes through ``repro.obs`` spans/events so host and virtual
    time lanes stay aligned in one exportable log (benchmarks, tests and
    the obs package itself are exempt; ``# fedlint: disable=`` works as
    everywhere).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from repro.lint.core import (Finding, LintContext, LintPass, Module,
                             call_name, dotted_name, keyword_arg)

_JIT_NAMES = {"jit", "jax.jit"}
_TRACE_WRAPPERS = {"shard_map", "jax.shard_map",
                   "jax.experimental.shard_map.shard_map", "pmap", "jax.pmap", "vmap", "jax.vmap"}
_NP_HOST = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
            "onp.asarray", "onp.array"}
_SYNC_ATTRS = {"item", "block_until_ready"}

# the hot paths where ad-hoc timing/printing is banned in favor of
# repro.obs spans/events (repro/obs itself is deliberately outside)
_HOT_PATH_RE = re.compile(r"(^|[/\\])repro[/\\](federated|core)[/\\]")
_TEST_PATH_RE = re.compile(r"(^|[/\\])(tests?[/\\]|test_)")
_RAW_TIMERS = {"time.perf_counter", "time.monotonic", "time.process_time",
               "time.perf_counter_ns", "time.monotonic_ns",
               "perf_counter", "monotonic", "process_time",
               "perf_counter_ns", "monotonic_ns"}


def _is_jit_expr(node: ast.expr) -> bool:
    """True for ``jax.jit``, ``jax.jit(...)`` and
    ``functools.partial(jax.jit, ...)`` decorator/value expressions."""
    name = dotted_name(node)
    if name in _JIT_NAMES:
        return True
    if isinstance(node, ast.Call):
        fname = call_name(node)
        if fname in _JIT_NAMES:
            return True
        if fname in ("functools.partial", "partial") and node.args \
                and dotted_name(node.args[0]) in _JIT_NAMES:
            return True
    return False


def _jit_call_params(node: ast.expr) -> Optional[ast.Call]:
    """The Call carrying jit kwargs (static_argnames etc.), if any."""
    if isinstance(node, ast.Call):
        fname = call_name(node)
        if fname in _JIT_NAMES:
            return node
        if fname in ("functools.partial", "partial") and node.args \
                and dotted_name(node.args[0]) in _JIT_NAMES:
            return node
    return None


def _own_nodes(fn: ast.AST) -> Iterable[ast.AST]:
    """Walk ``fn`` without descending into nested function/class defs."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(node))


def _params(fn) -> List[str]:
    a = fn.args
    return ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
            + [p.arg for p in a.kwonlyargs])


def _contains_shape_access(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in ("shape", "size",
                                                       "ndim", "itemsize"):
            return True
        if isinstance(n, ast.Call) and call_name(n) in ("len", "ord"):
            return True
    return False


def _banned(call: ast.Call, *, in_jit: bool,
            dynamic_params: Optional[Set[str]] = None) -> Optional[str]:
    """A human description if ``call`` is a host sync in this context.

    In jit context ``float()``/``int()`` is only flagged when the argument
    references a *traced* (non-static) parameter — ``float(levels)`` of a
    Python scalar derived from static args is legitimate and common."""
    name = call_name(call)
    if isinstance(call.func, ast.Attribute) and call.func.attr in _SYNC_ATTRS \
            and not call.args:
        return f".{call.func.attr}() blocks on a device value"
    if name and (name in ("device_get", "jax.device_get")
                 or name.endswith(".device_get")):
        return "jax.device_get blocks on device values"
    if name in _NP_HOST and in_jit:
        return f"{name} materializes the traced value on the host"
    if name == "print" and in_jit:
        return "print() inside traced code runs at trace time only " \
               "(use jax.debug.print)"
    if name in ("float", "int") and len(call.args) == 1:
        arg = call.args[0]
        if isinstance(arg, ast.Constant) or _contains_shape_access(arg):
            return None
        if in_jit:
            refs = {n.id for n in ast.walk(arg) if isinstance(n, ast.Name)}
            if not refs & (dynamic_params or set()):
                return None
        return f"{name}() forces a blocking device→host transfer"
    return None


class HostSyncPass(LintPass):
    name = "host-sync"
    rules = {
        "host-sync-in-jit":
            "host sync (float/.item/np.asarray/device_get/print) reachable "
            "from jit-traced code",
        "host-sync-in-loop":
            "per-iteration device sync on a jitted step's output inside a "
            "host loop",
        "host-sync-in-callback":
            "device sync inside a per-arrival callback (scheduler "
            "execute=/sample_cohort= path)",
        "jit-closure-rebuild":
            "@jax.jit closure defined and called in the same function: a "
            "fresh jit cache (full retrace) per call",
        "jit-static-args":
            "static_argnames/static_argnums/donate_argnums inconsistent "
            "with the wrapped function's signature",
        "raw-timing-in-hot-path":
            "hand-rolled time.perf_counter()/print() instrumentation in a "
            "repro/federated or repro/core hot path; record through "
            "repro.obs spans/events instead",
    }

    # ---- module facts ------------------------------------------------------

    def _module_facts(self, module: Module):
        tree = module.tree
        defs: Dict[str, List[ast.FunctionDef]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)

        jit_roots: Set[ast.AST] = set()
        jitted_names: Set[str] = set()
        for fns in defs.values():
            for fn in fns:
                if any(_is_jit_expr(d) for d in fn.decorator_list):
                    jit_roots.add(fn)
                    jitted_names.add(fn.name)
        # functions passed to jax.jit(f, ...)/shard_map(f, ...) by name
        for call in ast.walk(tree):
            if not isinstance(call, ast.Call):
                continue
            cname = call_name(call)
            is_wrap = cname in _JIT_NAMES \
                or (cname and cname.split(".")[-1] in
                    {n.split(".")[-1] for n in _TRACE_WRAPPERS})
            if is_wrap and call.args and isinstance(call.args[0], ast.Name):
                target = call.args[0].id
                jitted_names.add(target)
                jit_roots.update(defs.get(target, []))
        # g = jax.jit(...) style assignments
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and _is_jit_expr(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        jitted_names.add(t.id)
        # factories whose return value is a jitted function: calling them
        # yields a jitted callable, so assignments from those calls taint
        for fns in defs.values():
            for fn in fns:
                for node in _own_nodes(fn):
                    if not isinstance(node, ast.Return) or node.value is None:
                        continue
                    if _is_jit_expr(node.value):
                        jitted_names.add(fn.name)
                    elif isinstance(node.value, ast.Name) \
                            and node.value.id in jitted_names:
                        jitted_names.add(fn.name)

        imports_jax = any(
            (isinstance(n, ast.Import)
             and any(a.name.split(".")[0] == "jax" for a in n.names))
            or (isinstance(n, ast.ImportFrom) and n.module
                and n.module.split(".")[0] == "jax")
            for n in ast.walk(tree))
        return defs, jit_roots, jitted_names, imports_jax

    # ---- checks ------------------------------------------------------------

    def check(self, module: Module, ctx: LintContext) -> Iterable[Finding]:
        defs, jit_roots, jitted_names, imports_jax = \
            self._module_facts(module)
        findings: List[Finding] = []

        # 1. host syncs inside traced code (roots + everything nested)
        for root in jit_roots:
            static = self._static_argnames(root)
            dynamic: Set[str] = set()
            for node in ast.walk(root):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    dynamic.update(p for p in _params(node)
                                   if p not in static and p != "self")
            for node in ast.walk(root):
                if isinstance(node, ast.Call):
                    why = _banned(node, in_jit=True, dynamic_params=dynamic)
                    if why:
                        findings.append(self.finding(
                            module, node, "host-sync-in-jit",
                            f"{why} — this code is traced by jax.jit "
                            f"(via {getattr(root, 'name', '<fn>')!r})"))

        all_fns = [fn for fns in defs.values() for fn in fns]
        for fn in all_fns:
            if fn in jit_roots:
                continue
            findings.extend(self._check_loops(module, fn, jitted_names,
                                              imports_jax))
            findings.extend(self._check_closure_rebuild(module, fn))
            findings.extend(self._check_callbacks(module, fn))
        findings.extend(self._check_static_args(module, defs))
        findings.extend(self._check_raw_timing(module))
        return findings

    def _check_raw_timing(self, module: Module) -> Iterable[Finding]:
        """Ban ad-hoc wall-clock timing and print() in the hot paths.

        `repro.obs.span` records the same interval into the run's event
        log (host lane, aligned with the scheduler's virtual lane) at
        near-zero cost when telemetry is off — a bare ``perf_counter``
        pair or a ``print`` is measurement that vanishes when the run
        ends. Scoped to ``repro/federated`` and ``repro/core`` (not
        benchmarks, tests, or ``repro/obs`` itself, which legitimately
        owns the clock)."""
        if not _HOT_PATH_RE.search(module.path) \
                or _TEST_PATH_RE.search(module.path):
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if name in _RAW_TIMERS:
                yield self.finding(
                    module, node, "raw-timing-in-hot-path",
                    f"{name}() hand-rolls wall-clock timing in a hot "
                    "path; wrap the region in repro.obs.span(...) so the "
                    "measurement lands in the run's event log alongside "
                    "the scheduler's virtual clock")
            elif name == "print":
                yield self.finding(
                    module, node, "raw-timing-in-hot-path",
                    "print() in a hot path is unstructured and serializes "
                    "stdout; emit repro.obs.event(...) (or logging) so "
                    "the record survives in the run's event log")

    @staticmethod
    def _static_argnames(root) -> Set[str]:
        static: Set[str] = set()
        for dec in getattr(root, "decorator_list", []):
            c = _jit_call_params(dec)
            if c is not None:
                kw = keyword_arg(c, "static_argnames")
                if kw is not None:
                    static.update(s for s, _ in _iter_str_elems(kw))
        return static

    def _check_loops(self, module: Module, fn, jitted_names: Set[str],
                     imports_jax: bool) -> Iterable[Finding]:
        if not imports_jax:
            return
        for loop in _own_nodes(fn):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            tainted: Set[str] = set()
            for node in ast.walk(loop):
                if isinstance(node, ast.Assign) \
                        and isinstance(node.value, ast.Call) \
                        and isinstance(node.value.func, ast.Name) \
                        and node.value.func.id in jitted_names:
                    for t in node.targets:
                        names = t.elts if isinstance(t, ast.Tuple) else [t]
                        tainted.update(e.id for e in names
                                       if isinstance(e, ast.Name))
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                name = call_name(node)
                if name and (name.endswith(".device_get")
                             or name == "device_get"):
                    yield self.finding(
                        module, node, "host-sync-in-loop",
                        "jax.device_get inside a loop syncs every "
                        "iteration; batch values and transfer once after "
                        "the loop")
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _SYNC_ATTRS and not node.args \
                        and isinstance(node.func.value, ast.Name) \
                        and node.func.value.id in tainted:
                    yield self.finding(
                        module, node, "host-sync-in-loop",
                        f"per-iteration .{node.func.attr}() on "
                        f"{node.func.value.id!r} (output of a jitted step) "
                        "blocks the dispatch pipeline")
                elif call_name(node) in ("float", "int") and node.args \
                        and isinstance(node.args[0], ast.Name) \
                        and node.args[0].id in tainted:
                    yield self.finding(
                        module, node, "host-sync-in-loop",
                        f"{call_name(node)}({node.args[0].id}) syncs a "
                        "jitted step's output every iteration; accumulate "
                        "device values and jax.device_get once after the "
                        "loop")

    def _check_closure_rebuild(self, module: Module, fn) -> Iterable[Finding]:
        nested_jits = [c for c in _own_nodes(fn)
                       if isinstance(c, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))
                       and any(_is_jit_expr(d) for d in c.decorator_list)]
        if not nested_jits:
            return
        called = {call_name(n) for n in _own_nodes(fn)
                  if isinstance(n, ast.Call)}
        for c in nested_jits:
            if c.name in called:
                yield self.finding(
                    module, c, "jit-closure-rebuild",
                    f"@jax.jit {c.name!r} is defined inside "
                    f"{fn.name!r} and called there: every call of "
                    f"{fn.name!r} builds a fresh jit cache and retraces — "
                    "hoist the jitted function (or build it once in a "
                    "factory and reuse it)")

    def _check_callbacks(self, module: Module, fn) -> Iterable[Finding]:
        nested = {c.name: c for c in _own_nodes(fn)
                  if isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef))
                  and not any(_is_jit_expr(d) for d in c.decorator_list)}
        if not nested:
            return
        passed: Set[str] = set()
        for node in _own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name) and arg.id in nested:
                    passed.add(arg.id)
        for name in passed:
            for node in ast.walk(nested[name]):
                if isinstance(node, ast.Call):
                    why = _banned(node, in_jit=False)
                    if why:
                        yield self.finding(
                            module, node, "host-sync-in-callback",
                            f"{why} — {name!r} is a per-arrival callback; "
                            "syncing here serializes every round "
                            "(keep device values, transfer after the run)",
                            severity="warning")

    def _check_static_args(self, module: Module, defs) -> Iterable[Finding]:
        for node in ast.walk(module.tree):
            target_fn = None
            jit_call = None
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    c = _jit_call_params(dec)
                    if c is not None:
                        target_fn, jit_call = node, c
                        break
            elif isinstance(node, ast.Call):
                c = _jit_call_params(node)
                if c is not None and c.args \
                        and isinstance(c.args[0], ast.Name):
                    cands = defs.get(c.args[0].id, [])
                    if len(cands) == 1:
                        target_fn, jit_call = cands[0], c
            if target_fn is None:
                continue
            params = _params(target_fn)
            has_var = target_fn.args.vararg or target_fn.args.kwarg
            names_kw = keyword_arg(jit_call, "static_argnames")
            if names_kw is not None and not has_var:
                literals = [v for v, _ in _iter_str_elems(names_kw)]
                for bad in [s for s in literals if s not in params]:
                    yield self.finding(
                        module, jit_call, "jit-static-args",
                        f"static_argnames names {bad!r} but "
                        f"{target_fn.name!r} has no such parameter "
                        f"(params: {params})")
            for kw in ("static_argnums", "donate_argnums"):
                nums_kw = keyword_arg(jit_call, kw)
                if nums_kw is None or has_var:
                    continue
                for idx in _iter_int_elems(nums_kw):
                    if idx >= len(params) or idx < -len(params):
                        yield self.finding(
                            module, jit_call, "jit-static-args",
                            f"{kw} index {idx} is out of range for "
                            f"{target_fn.name!r} ({len(params)} parameters)")


def _iter_str_elems(node: ast.expr):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        yield node.value, node.lineno
    elif isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                yield e.value, e.lineno


def _iter_int_elems(node: ast.expr):
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        yield node.value
    elif isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                yield e.value
