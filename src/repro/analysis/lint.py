"""AST lint engine with JAX-aware rules (ISSUE 7, layer 1).

Rules (see DESIGN.md §12 for the catalog with rationale):

==================== =========================================================
rule id              fires on
==================== =========================================================
host-sync-in-jit     ``np.asarray``/``np.array``/``.item()``/``.tolist()``/
                     ``float()``/``bool()`` applied to non-constant values
                     inside jit-traced code — each forces a device→host sync
                     (or, on statics, work that belongs before the jit
                     boundary) in the middle of a fused launch.
retrace-hazard       jit signatures that recompile per call: float-annotated
                     or mutable-default ``static_argnames``, and ``jax.jit``
                     invoked inside a function body without a signature cache.
np-jnp-mixing        ``np.*`` ops or module-level ``np.*`` constants
                     referenced inside traced code — constant-folds host
                     arrays into device programs and breaks dtype discipline.
frozen-mutation      writes to ``RecordBatch`` columns or frozen-dataclass
                     fields (``object.__setattr__`` outside ``__post_init__``,
                     column element stores, column rebinds).
unordered-iteration  ``for``/comprehension iteration directly over set-valued
                     expressions — hash-seed order feeding routing, scatter,
                     or ring mutation order.
exactness-contract   local redefinitions of ``EXACT_SCHEMES`` /
                     ``BANDED_SCHEMES`` / ``DRIFT_SCHEMES`` / ``EXACTNESS``
                     instead of importing :mod:`repro.analysis.contracts`.
topology-config      literal ``config_for``/``Stage``/``Edge``/``Topology``
                     constructs that the runtime validators would reject —
                     the build error, promoted to before the run.
registry-counter-    direct stores to registry-backed counters (ISSUE 9):
mutation             ``TRACE_COUNT``/``dispatches`` through an imported-module
                     alias, or ``self.shed``/``queue_depth_peak``/
                     ``in_flight_peak``/``dispatches`` inside an Engine/Runner
                     class — writes that bypass the MetricsRegistry cell.
int32-overflow       narrow-int accumulators whose magnitude scales with
                     stream length — wrap past 2³¹ at ``SCALE_TARGET``
                     (:mod:`repro.analysis.numerics`).
unseeded-rng         global-state ``np.random.*`` / stdlib ``random`` calls
                     and seedless Generator construction — destroys seeded
                     replay (:mod:`repro.analysis.determinism`).
wall-clock-leak      ``time.*``/``datetime.now`` values escaping a function
                     outside the declared obs stamp points.
unbounded-signature  jit caches keyed by tuples with statically unbounded
                     elements — recompile per distinct value.
interproc-unordered- ``for``/comprehension over a *call* to a set-returning
iteration            function, same-module or imported
                     (:mod:`repro.analysis.callgraph`).
==================== =========================================================

The engine is a two-pass design: pass 1 builds a :class:`ModuleInfo`
(scopes, function defs, jit roots, the traced-set closure, numpy aliases);
pass 2 runs each rule over the annotated tree.  The traced set is the
transitive closure of jit roots over same-module references, including
free-variable aliases (``fifo = _fifo_scan if ... else _fifo_assoc``) and
nested defs, so rules see exactly the code that runs under ``jax.jit``.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .findings import Finding

__all__ = ["RULES", "lint_file", "lint_paths", "iter_python_files"]

RULES: Tuple[str, ...] = (
    "host-sync-in-jit",
    "retrace-hazard",
    "np-jnp-mixing",
    "frozen-mutation",
    "unordered-iteration",
    "exactness-contract",
    "topology-config",
    "registry-counter-mutation",
    # ISSUE 10: numerics + determinism (see numerics.py / determinism.py /
    # callgraph.py; registered below through late-import wrappers)
    "int32-overflow",
    "unseeded-rng",
    "wall-clock-leak",
    "unbounded-signature",
    "interproc-unordered-iteration",
)

_HOST_SYNC_BUILTINS = {"float", "bool"}
_HOST_SYNC_METHODS = {"item", "tolist"}
_NP_HOST_FUNCS = {"asarray", "array"}
_RECORDBATCH_COLS = {"keys", "values", "timestamps"}
_CONTRACT_NAMES = {"EXACT_SCHEMES", "BANDED_SCHEMES", "DRIFT_SCHEMES",
                   "EXACTNESS"}
_SET_METHODS = {"difference", "union", "intersection",
                "symmetric_difference"}
_ORDER_NEUTRAL_SINKS = {"sorted", "set", "frozenset", "len", "sum", "min",
                        "max", "any", "all"}


# ---------------------------------------------------------------------------
# pass 1: module annotation
# ---------------------------------------------------------------------------


def _annotate(tree: ast.Module) -> None:
    """Attach ``_parent`` and ``_scope`` (enclosing qualname) to every node."""

    def walk(node: ast.AST, parent: Optional[ast.AST], scope: str) -> None:
        node._parent = parent          # type: ignore[attr-defined]
        node._scope = scope            # type: ignore[attr-defined]
        child_scope = scope
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            child_scope = (node.name if scope == "<module>"
                           else f"{scope}.{node.name}")
            node._scope = child_scope  # the def itself fingerprints inward
        for child in ast.iter_child_nodes(node):
            walk(child, node, child_scope)

    walk(tree, None, "<module>")


def _is_jit_ref(node: ast.AST) -> bool:
    """``jit`` / ``jax.jit`` (any attribute path ending in ``.jit``)."""
    if isinstance(node, ast.Name):
        return node.id == "jit"
    return isinstance(node, ast.Attribute) and node.attr == "jit"


def _is_partial_ref(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return node.id == "partial"
    return isinstance(node, ast.Attribute) and node.attr == "partial"


def _literal(node: ast.AST):
    """(True, value) when the node is a pure literal, else (False, None)."""
    try:
        return True, ast.literal_eval(node)
    except (ValueError, TypeError, SyntaxError, MemoryError):
        return False, None


class ModuleInfo:
    def __init__(self, path: Path, rel: str, tree: ast.Module) -> None:
        self.path = path
        self.rel = rel
        self.tree = tree
        _annotate(tree)

        # every function def, by bare name (nested included; last wins)
        self.funcs: Dict[str, ast.AST] = {}
        # names of callables aliased through plain / conditional assignment
        self.aliases: Dict[str, Set[str]] = {}
        # numpy import aliases in this module
        self.np_aliases: Set[str] = set()
        # module-level names whose value is built from numpy
        self.np_globals: Dict[str, int] = {}
        # jit call sites: (call node, resolved target def or None)
        self.jit_calls: List[Tuple[ast.Call, Optional[ast.AST]]] = []

        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.funcs[node.name] = node
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "numpy":
                        self.np_aliases.add(a.asname or "numpy")
            elif isinstance(node, ast.Assign):
                self._record_assign(node)

        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and _is_jit_ref(node.func):
                target = None
                if node.args and isinstance(node.args[0], ast.Name):
                    target = self.funcs.get(node.args[0].id)
                self.jit_calls.append((node, target))

        self.traced_roots = self._traced_roots()
        self.traced = self._traced_closure(self.traced_roots)

    # -- assignment bookkeeping -------------------------------------------

    def _record_assign(self, node: ast.Assign) -> None:
        names = [t.id for t in node.targets if isinstance(t, ast.Name)]
        if not names:
            return
        referenced = self._callable_refs(node.value)
        for n in names:
            if referenced:
                self.aliases.setdefault(n, set()).update(referenced)
            if (node._scope == "<module>"  # type: ignore[attr-defined]
                    and self._uses_numpy(node.value)):
                self.np_globals[n] = node.lineno

    def _callable_refs(self, value: ast.AST) -> Set[str]:
        """Function names a value expression could evaluate to (plain name
        or conditional expression over names)."""
        if isinstance(value, ast.Name):
            return {value.id}
        if isinstance(value, ast.IfExp):
            return self._callable_refs(value.body) | \
                self._callable_refs(value.orelse)
        return set()

    def _uses_numpy(self, value: ast.AST) -> bool:
        for sub in ast.walk(value):
            if isinstance(sub, ast.Name) and sub.id in self.np_aliases:
                return True
        return False

    # -- the traced set ----------------------------------------------------

    def _traced_roots(self) -> List[ast.AST]:
        roots: List[ast.AST] = []
        for fn in sorted(set(self.funcs.values()), key=lambda f: f.lineno):
            for dec in getattr(fn, "decorator_list", []):
                if _is_jit_ref(dec):
                    roots.append(fn)
                elif (isinstance(dec, ast.Call)
                      and (_is_jit_ref(dec.func)
                           or (_is_partial_ref(dec.func) and dec.args
                               and _is_jit_ref(dec.args[0])))):
                    roots.append(fn)
        for _, target in self.jit_calls:
            if target is not None:
                roots.append(target)
        return roots

    def _traced_closure(self, roots: Sequence[ast.AST]) -> Set[ast.AST]:
        traced: Set[ast.AST] = set()
        work = list(roots)
        while work:
            fn = work.pop()
            if fn in traced:
                continue
            traced.add(fn)
            for sub in ast.walk(fn):
                if not (isinstance(sub, ast.Name)
                        and isinstance(sub.ctx, ast.Load)):
                    continue
                for name in (sub.id,
                             *sorted(self.aliases.get(sub.id, ()))):
                    ref = self.funcs.get(name)
                    if ref is not None and ref not in traced:
                        work.append(ref)
        return traced

    def traced_walk(self):
        """Yield every node inside traced code, visiting each subtree once
        (skipping traced functions nested inside other traced functions)."""
        tops = [fn for fn in sorted(self.traced, key=lambda f: f.lineno)
                if not any(p in self.traced for p in _ancestors(fn))]
        for fn in tops:
            yield from ast.walk(fn)

    def finding(self, rule: str, node: ast.AST, severity: str,
                message: str, hint: str) -> Finding:
        return Finding(
            rule=rule, path=self.rel,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            severity=severity, message=message, hint=hint,
            scope=getattr(node, "_scope", "<module>"))


def _ancestors(node: ast.AST):
    cur = getattr(node, "_parent", None)
    while cur is not None:
        yield cur
        cur = getattr(cur, "_parent", None)


def _call_name(node: ast.Call) -> Optional[str]:
    if isinstance(node.func, ast.Name):
        return node.func.id
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return None


# ---------------------------------------------------------------------------
# pass 2: the rules
# ---------------------------------------------------------------------------


def _rule_host_sync_in_jit(mod: ModuleInfo) -> List[Finding]:
    out = []
    for node in mod.traced_walk():
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if (isinstance(f, ast.Name) and f.id in _HOST_SYNC_BUILTINS
                and node.args
                and not all(isinstance(a, ast.Constant) for a in node.args)):
            out.append(mod.finding(
                "host-sync-in-jit", node, "error",
                f"`{f.id}(...)` on a non-constant inside jit-traced code "
                f"forces a trace-time concretization (host sync on traced "
                f"values)",
                f"convert before the jit boundary, or use "
                f"jnp.float32/jnp.asarray inside the trace"))
        elif isinstance(f, ast.Attribute) and f.attr in _HOST_SYNC_METHODS:
            out.append(mod.finding(
                "host-sync-in-jit", node, "error",
                f"`.{f.attr}()` inside jit-traced code is a device→host "
                f"sync",
                "return the array and read it at a sanctioned sync point "
                "(pane flush / host_sync)"))
        elif (isinstance(f, ast.Attribute)
              and isinstance(f.value, ast.Name)
              and f.value.id in mod.np_aliases
              and f.attr in _NP_HOST_FUNCS):
            out.append(mod.finding(
                "host-sync-in-jit", node, "error",
                f"`{f.value.id}.{f.attr}(...)` inside jit-traced code pulls "
                f"the operand to the host",
                "use jnp.asarray / keep the value device-resident"))
    return out


def _rule_np_jnp_mixing(mod: ModuleInfo) -> List[Finding]:
    out = []
    seen_globals: Set[Tuple[str, str]] = set()
    for node in mod.traced_walk():
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in mod.np_aliases
                and node.func.attr not in _NP_HOST_FUNCS):
            out.append(mod.finding(
                "np-jnp-mixing", node, "error",
                f"`{node.func.value.id}.{node.func.attr}(...)` inside "
                f"jit-traced code mixes host numpy into a device program",
                "use the jnp equivalent so the op stays on device"))
        elif (isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load)
              and node.id in mod.np_globals):
            key = (node._scope, node.id)  # type: ignore[attr-defined]
            if key not in seen_globals:
                seen_globals.add(key)
                out.append(mod.finding(
                    "np-jnp-mixing", node, "error",
                    f"module-level numpy value `{node.id}` (defined at line "
                    f"{mod.np_globals[node.id]}) is referenced inside "
                    f"jit-traced code",
                    f"make `{node.id}` a Python scalar (or build it with "
                    f"jnp inside the traced function): traced code never "
                    f"closes over host arrays, and no device array is "
                    f"built at import"))
    return out


def _static_argnames(call: ast.Call) -> List[str]:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            ok, val = _literal(kw.value)
            if ok:
                if isinstance(val, str):
                    return [val]
                return [v for v in val if isinstance(v, str)]
    return []


def _jit_decorator_calls(mod: ModuleInfo):
    """(call-like node carrying jit kwargs, target def) for decorators."""
    for fn in sorted(set(mod.funcs.values()), key=lambda f: f.lineno):
        for dec in getattr(fn, "decorator_list", []):
            if not isinstance(dec, ast.Call):
                continue
            if _is_jit_ref(dec.func):
                yield dec, fn
            elif (_is_partial_ref(dec.func) and dec.args
                  and _is_jit_ref(dec.args[0])):
                yield dec, fn


def _rule_retrace_hazard(mod: ModuleInfo) -> List[Finding]:
    out = []
    sites = list(_jit_decorator_calls(mod)) + mod.jit_calls
    for call, target in sites:
        statics = _static_argnames(call)
        if not statics or target is None:
            continue
        params = {a.arg: a for a in
                  list(target.args.posonlyargs) + list(target.args.args)
                  + list(target.args.kwonlyargs)}
        defaults = _param_defaults(target)
        for name in statics:
            arg = params.get(name)
            if arg is None:
                continue
            ann = getattr(arg, "annotation", None)
            ann_name = ann.id if isinstance(ann, ast.Name) else None
            if ann_name == "float":
                out.append(mod.finding(
                    "retrace-hazard", call, "warn",
                    f"static_argnames includes float-valued `{name}` "
                    f"(annotated float) on `{target.name}` — every distinct "
                    f"value is a fresh trace",
                    f"pass `{name}` as a traced jnp scalar, or document the "
                    f"bounded value set feeding it"))
            elif ann_name in ("list", "dict", "set") or isinstance(
                    defaults.get(name), (ast.List, ast.Dict, ast.Set)):
                out.append(mod.finding(
                    "retrace-hazard", call, "error",
                    f"static_argnames includes unhashable `{name}` on "
                    f"`{target.name}` — jit statics must be hashable",
                    f"use a tuple / frozen value for `{name}`"))
    # jax.jit(f)(x) immediately invoked inside a function body: the jitted
    # callable (and its trace cache) is rebuilt on every call of the
    # enclosing function — the classic retrace storm.  A jit assigned to a
    # name and reused, or one cached by signature, is fine.
    for call, _ in mod.jit_calls:
        scope = getattr(call, "_scope", "<module>")
        if scope == "<module>":
            continue
        parent = getattr(call, "_parent", None)
        if isinstance(parent, ast.Call) and parent.func is call:
            out.append(mod.finding(
                "retrace-hazard", call, "warn",
                f"jax.jit(...)(...) immediately invoked inside `{scope}` "
                f"rebuilds the compiled callable — and retraces — on "
                f"every call",
                "hoist the jitted fn to module level, or cache it keyed "
                "by the static signature (see feed_fused._SEG_CACHE)"))
    return out


def _param_defaults(fn: ast.AST) -> Dict[str, ast.AST]:
    args = fn.args
    pos = list(args.posonlyargs) + list(args.args)
    out: Dict[str, ast.AST] = {}
    for a, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
        out[a.arg] = d
    for a, d in zip(args.kwonlyargs, args.kw_defaults):
        if d is not None:
            out[a.arg] = d
    return out


def _rule_frozen_mutation(mod: ModuleInfo) -> List[Finding]:
    out = []
    for node in ast.walk(mod.tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "__setattr__"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "object"):
            scope = getattr(node, "_scope", "<module>")
            in_post_init = scope.split(".")[-1] == "__post_init__"
            out.append(mod.finding(
                "frozen-mutation", node,
                "note" if in_post_init else "error",
                "object.__setattr__ bypasses the frozen-dataclass contract"
                + (" (inside __post_init__: the sanctioned freeze "
                   "escape hatch)" if in_post_init else ""),
                "keep frozen instances immutable; use dataclasses.replace "
                "for derived values"
                if not in_post_init else
                "acceptable only for canonicalization during construction"))
            continue
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AugAssign):
            targets = [node.target]
        for t in targets:
            attr = None
            if isinstance(t, ast.Attribute):
                attr = t
                kind = "rebinds"
            elif (isinstance(t, ast.Subscript)
                  and isinstance(t.value, ast.Attribute)):
                attr = t.value
                kind = "writes into"
            else:
                continue
            obj = attr.value
            if (attr.attr in _RECORDBATCH_COLS
                    and isinstance(obj, ast.Name) and obj.id != "self"):
                out.append(mod.finding(
                    "frozen-mutation", node, "error",
                    f"{kind} `{obj.id}.{attr.attr}` — RecordBatch columns "
                    f"are frozen (copy-on-write, writeable=False)",
                    "build a new RecordBatch (dataclasses.replace / "
                    "with_columns) instead of mutating columns"))
    return out


class _SetTracker(ast.NodeVisitor):
    """Track local names bound to set-valued expressions, per scope."""

    def __init__(self, mod: ModuleInfo) -> None:
        self.mod = mod
        self.findings: List[Finding] = []
        self.set_vars: Set[Tuple[str, str]] = set()  # (scope, name)

    def _is_set_expr(self, node: ast.AST, scope: str) -> bool:
        if isinstance(node, (ast.Set, ast.SetComp)):
            return True
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Name) and node.func.id in (
                    "set", "frozenset"):
                return True
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _SET_METHODS
                    and self._is_set_expr(node.func.value, scope)):
                return True
            return False
        if isinstance(node, ast.BinOp) and isinstance(
                node.op, (ast.Sub, ast.BitOr, ast.BitAnd, ast.BitXor)):
            return (self._is_set_expr(node.left, scope)
                    or self._is_set_expr(node.right, scope))
        if isinstance(node, ast.Name):
            return (scope, node.id) in self.set_vars
        return False

    def visit_Assign(self, node: ast.Assign) -> None:
        scope = getattr(node, "_scope", "<module>")
        is_set = self._is_set_expr(node.value, scope)
        for t in node.targets:
            if isinstance(t, ast.Name):
                key = (scope, t.id)
                if is_set:
                    self.set_vars.add(key)
                else:
                    self.set_vars.discard(key)
        self.generic_visit(node)

    def _flag(self, iter_node: ast.AST, where: str) -> None:
        self.findings.append(self.mod.finding(
            "unordered-iteration", iter_node, "warn",
            f"{where} iterates a set — hash-seed order leaks into whatever "
            f"this loop builds or mutates (routing, scatter, ring ops)",
            "iterate sorted(...) (or an insertion-ordered dict) when "
            "downstream effects are order-sensitive"))

    def visit_For(self, node: ast.For) -> None:
        scope = getattr(node, "_scope", "<module>")
        if self._is_set_expr(node.iter, scope):
            self._flag(node.iter, "for-loop")
        self.generic_visit(node)

    def _visit_comp(self, node) -> None:
        scope = getattr(node, "_scope", "<module>")
        order_sensitive = not isinstance(node, (ast.SetComp, ast.DictComp))
        parent = getattr(node, "_parent", None)
        if (isinstance(parent, ast.Call)
                and isinstance(parent.func, ast.Name)
                and parent.func.id in _ORDER_NEUTRAL_SINKS):
            order_sensitive = False
        if order_sensitive:
            for gen in node.generators:
                if self._is_set_expr(gen.iter, scope):
                    self._flag(gen.iter, "comprehension")
        self.generic_visit(node)

    visit_ListComp = _visit_comp
    visit_GeneratorExp = _visit_comp


def _rule_unordered_iteration(mod: ModuleInfo) -> List[Finding]:
    tracker = _SetTracker(mod)
    tracker.visit(mod.tree)
    return tracker.findings


def _rule_exactness_contract(mod: ModuleInfo) -> List[Finding]:
    if mod.rel.replace("\\", "/").endswith("repro/analysis/contracts.py"):
        return []
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Assign):
            continue
        for t in node.targets:
            if (isinstance(t, ast.Name) and t.id in _CONTRACT_NAMES
                    and isinstance(node.value,
                                   (ast.Tuple, ast.List, ast.Dict))):
                out.append(mod.finding(
                    "exactness-contract", node, "error",
                    f"local redefinition of `{t.id}` shadows the exactness "
                    f"contract — a test asserting the wrong contract "
                    f"becomes a flake instead of a lint finding",
                    f"from repro.analysis.contracts import {t.id}"))
    return out


def _kwarg_map(call: ast.Call) -> Optional[Dict[str, object]]:
    """Literal kwargs of a call, or None when any is non-literal/starred."""
    out: Dict[str, object] = {}
    for kw in call.keywords:
        if kw.arg is None:
            return None
        ok, val = _literal(kw.value)
        if not ok:
            return None
        out[kw.arg] = val
    return out


def _pos_literal(call: ast.Call, i: int):
    if i < len(call.args) and not isinstance(call.args[i], ast.Starred):
        return _literal(call.args[i])
    return False, None


def _rule_topology_config(mod: ModuleInfo) -> List[Finding]:
    from . import contracts

    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if name == "config_for" and name not in mod.funcs:
            ok, scheme = _pos_literal(node, 0)
            if not ok or not isinstance(scheme, str):
                continue
            if scheme not in contracts.SCHEMES:
                out.append(mod.finding(
                    "topology-config", node, "error",
                    f"unknown scheme {scheme!r} — config_for raises at "
                    f"runtime",
                    f"one of {', '.join(contracts.SCHEMES)}"))
                continue
            kwargs = _kwarg_map(node)
            if kwargs is not None and len(node.args) == 1:
                err = contracts.validate_config_literal(scheme, kwargs)
                if err:
                    out.append(mod.finding(
                        "topology-config", node, "error",
                        f"config_for({scheme!r}, ...) rejects these "
                        f"arguments at build time: {err}",
                        "fix the literal config (the typed SchemeConfig "
                        "validates eagerly)"))
        elif name == "Stage" and name not in mod.funcs:
            okn, sname = _pos_literal(node, 0)
            okp, par = _pos_literal(node, 1)
            kwargs = _kwarg_map(node) or {}
            if not okn and "name" in kwargs:
                okn, sname = True, kwargs["name"]
            if not okp and "parallelism" in kwargs:
                okp, par = True, kwargs["parallelism"]
            if okn or okp:
                err = contracts.validate_stage_literal(
                    sname if okn else "?", par if okp else 1,
                    cost=kwargs.get("cost"),
                    capacities=kwargs.get("capacities"))
                if err:
                    out.append(mod.finding(
                        "topology-config", node, "error",
                        f"Stage(...) rejects this at build time: {err}",
                        "fix the stage literal"))
        elif name == "Edge" and name not in mod.funcs:
            oks, src = _pos_literal(node, 0)
            okd, dst = _pos_literal(node, 1)
            grouping_is_config: Optional[bool] = None
            g = node.args[2] if len(node.args) > 2 else next(
                (kw.value for kw in node.keywords if kw.arg == "grouping"),
                None)
            if g is not None and _literal(g)[0]:
                grouping_is_config = False  # a bare literal is never a config
            if oks and okd:
                err = contracts.validate_edge_literal(
                    src, dst, grouping_is_config)
                if err:
                    out.append(mod.finding(
                        "topology-config", node, "error",
                        f"Edge(...) rejects this at build time: {err}",
                        "fix the edge literal"))
        elif name == "Topology" and name not in mod.funcs:
            extracted = _extract_topology(node)
            if extracted is not None:
                stage_names, edge_pairs = extracted
                for err in contracts.validate_topology_literal(
                        stage_names, edge_pairs):
                    out.append(mod.finding(
                        "topology-config", node, "error",
                        f"Topology(...) rejects this at build time: {err}",
                        "fix the stage/edge wiring"))
    return out


def _extract_topology(call: ast.Call
                      ) -> Optional[Tuple[List[str], List[Tuple[str, str]]]]:
    """Stage names + (src, dst) pairs from a fully literal Topology call."""
    stages_node = call.args[0] if len(call.args) > 0 else next(
        (kw.value for kw in call.keywords if kw.arg == "stages"), None)
    edges_node = call.args[1] if len(call.args) > 1 else next(
        (kw.value for kw in call.keywords if kw.arg == "edges"), None)
    if not isinstance(stages_node, (ast.List, ast.Tuple)) or \
            not isinstance(edges_node, (ast.List, ast.Tuple)):
        return None
    names: List[str] = []
    for el in stages_node.elts:
        if not (isinstance(el, ast.Call) and _call_name(el) == "Stage"):
            return None
        ok, v = _pos_literal(el, 0)
        if not ok and el.keywords:
            kw = next((k.value for k in el.keywords if k.arg == "name"),
                      None)
            if kw is not None:
                ok, v = _literal(kw)
        if not ok or not isinstance(v, str):
            return None
        names.append(v)
    pairs: List[Tuple[str, str]] = []
    for el in edges_node.elts:
        if not (isinstance(el, ast.Call) and _call_name(el) == "Edge"):
            return None
        oks, s = _pos_literal(el, 0)
        okd, d = _pos_literal(el, 1)
        if not (oks and okd and isinstance(s, str) and isinstance(d, str)):
            return None
        pairs.append((s, d))
    return names, pairs


# ISSUE 9: counters whose single source of truth is a MetricsRegistry cell.
# The legacy attribute names survive as properties (read) / setters (external
# write-compat); *internal* mutation must go through the cell, or enabled and
# disabled runs drift apart.
_REGISTRY_BACKED = {"TRACE_COUNT", "shed", "queue_depth_peak",
                    "in_flight_peak", "dispatches"}
_REGISTRY_CLASS_MARKERS = ("Engine", "Runner")


def _rule_registry_counter_mutation(mod: ModuleInfo) -> List[Finding]:
    # names this module imported — a store through one of them reaches into
    # another module's registry-backed counter from the outside
    imported: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                imported.add(a.asname or a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name != "*":
                    imported.add(a.asname or a.name)
    out = []
    for node in ast.walk(mod.tree):
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AugAssign):
            targets = [node.target]
        for t in targets:
            if not (isinstance(t, ast.Attribute)
                    and t.attr in _REGISTRY_BACKED
                    and isinstance(t.value, ast.Name)):
                continue
            base = t.value.id
            scope = getattr(node, "_scope", "<module>")
            if base == "self":
                # only Engine/Runner classes hold registry-backed cells;
                # `self.dispatches` on a plain report row is a data field
                if not any(m in part for part in scope.split(".")
                           for m in _REGISTRY_CLASS_MARKERS):
                    continue
                out.append(mod.finding(
                    "registry-counter-mutation", node, "error",
                    f"direct store to registry-backed `self.{t.attr}` in "
                    f"`{scope}` bypasses the MetricsRegistry cell — enabled "
                    f"and disabled telemetry runs would disagree",
                    f"mutate through the cell (`self._m_*.add/.set/.peak`); "
                    f"the `{t.attr}` attribute is a read property"))
            elif base in imported and t.attr in ("TRACE_COUNT", "dispatches"):
                out.append(mod.finding(
                    "registry-counter-mutation", node, "error",
                    f"store to `{base}.{t.attr}` mutates another module's "
                    f"registry-backed counter from the outside",
                    "use the owning registry's cell (or the sanctioned "
                    "reset helper) instead of assigning the attribute"))
    return out


# ISSUE 10 rules live in sibling modules that import helpers from this one;
# late-import wrappers keep the registration cycle-free in both import orders.


def _rule_int32_overflow(mod: ModuleInfo) -> List[Finding]:
    from .numerics import rule_int32_overflow
    return rule_int32_overflow(mod)


def _rule_unseeded_rng(mod: ModuleInfo) -> List[Finding]:
    from .determinism import rule_unseeded_rng
    return rule_unseeded_rng(mod)


def _rule_wall_clock_leak(mod: ModuleInfo) -> List[Finding]:
    from .determinism import rule_wall_clock_leak
    return rule_wall_clock_leak(mod)


def _rule_unbounded_signature(mod: ModuleInfo) -> List[Finding]:
    from .determinism import rule_unbounded_signature
    return rule_unbounded_signature(mod)


def _rule_interproc_unordered(mod: ModuleInfo) -> List[Finding]:
    from .callgraph import single_module_interproc
    return single_module_interproc(mod)


_RULE_FNS = {
    "host-sync-in-jit": _rule_host_sync_in_jit,
    "retrace-hazard": _rule_retrace_hazard,
    "np-jnp-mixing": _rule_np_jnp_mixing,
    "frozen-mutation": _rule_frozen_mutation,
    "unordered-iteration": _rule_unordered_iteration,
    "exactness-contract": _rule_exactness_contract,
    "topology-config": _rule_topology_config,
    "registry-counter-mutation": _rule_registry_counter_mutation,
    "int32-overflow": _rule_int32_overflow,
    "unseeded-rng": _rule_unseeded_rng,
    "wall-clock-leak": _rule_wall_clock_leak,
    "unbounded-signature": _rule_unbounded_signature,
    "interproc-unordered-iteration": _rule_interproc_unordered,
}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def lint_file(path: Path, root: Path,
              rules: Optional[Sequence[str]] = None) -> List[Finding]:
    src = Path(path).read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as e:
        return [Finding(
            rule="syntax", path=_rel(path, root), line=e.lineno or 1,
            col=e.offset or 0, severity="error",
            message=f"cannot parse: {e.msg}", hint="fix the syntax error")]
    mod = ModuleInfo(Path(path), _rel(path, root), tree)
    out: List[Finding] = []
    for rule in rules or RULES:
        out.extend(_RULE_FNS[rule](mod))
    return out


def _rel(path: Path, root: Path) -> str:
    try:
        return Path(path).resolve().relative_to(
            Path(root).resolve()).as_posix()
    except ValueError:
        return Path(path).as_posix()


_DEFAULT_EXCLUDES = ("analysis_fixtures",)


def iter_python_files(paths: Sequence[Path],
                      excludes: Sequence[str] = _DEFAULT_EXCLUDES
                      ) -> List[Path]:
    files: List[Path] = []
    for p in paths:
        p = Path(p)
        if p.is_file() and p.suffix == ".py":
            files.append(p)
        elif p.is_dir():
            files.extend(sorted(p.rglob("*.py")))
    return [f for f in files
            if not any(part in excludes for part in f.parts)]


def lint_paths(paths: Sequence[Path], root: Path,
               rules: Optional[Sequence[str]] = None,
               excludes: Sequence[str] = _DEFAULT_EXCLUDES
               ) -> List[Finding]:
    out: List[Finding] = []
    for f in iter_python_files(paths, excludes):
        out.extend(lint_file(f, root, rules))
    return out
