"""Abstract integer-width / overflow pass (ISSUE 10 tentpole, part 2).

One rule, ``int32-overflow``: an *accumulator* — a value that grows by
repeated addition — held in a narrow integer dtype (int32 or smaller)
whose magnitude scales with stream length wraps silently once the running
total passes 2³¹−1.  At the declared :data:`SCALE_TARGET` (the ROADMAP's
10⁸-tuple runs) that happens as soon as the *mean per-step increment*
reaches ``(2³¹−1) // SCALE_TARGET`` ≈ 21, so "it worked in the tests"
(3·10⁴ tuples) says nothing about target scale.

The pass is a small dtype lattice evaluated flow-insensitively over each
module's AST:

* **dtype evidence** — every assignment records the dtypes its target has
  been observed to hold.  Array constructors with a dtype token
  (``np.zeros(n, np.int32)``, ``jnp.zeros(..., jnp.int32)``,
  ``x.astype(np.int32)``, ``np.int32(v)``, ``dtype="int32"``) seed the
  lattice; ``np.bincount`` seeds int64 (numpy's intp default); arithmetic
  joins to the widest operand.  Locals key on ``(scope, name)``;
  ``self.X`` attributes key on the enclosing class, joined across all its
  methods (a table allocated int32 in one method and accumulated in
  another is exactly the hazard).
* **accumulation sites** — ``x += v``, ``x = x + v``, ``x[i] += v``,
  ``np.add.at(x, i, v)``, and the jax functional form
  ``x = x.at[i].add(v)``.
* **scale filter** — the accumulator only scales with the stream when it
  aggregates per-tuple quantities; the pass requires a scale hint
  (:data:`SCALE_HINTS` substring) on the accumulator's name or on any
  name feeding the increment, so int32 *id* arrays and bounded local
  counters stay quiet.

Findings name the accumulator and the overflow point at
:data:`SCALE_TARGET`.  What the lattice cannot see (dtypes entering
through opaque calls, device kernels accumulating traced arguments) is
documented in DESIGN.md §15 — the differential sanitizer is the dynamic
backstop for exactly that residue.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .contracts import SCALE_TARGET
from .findings import Finding

__all__ = ["SCALE_TARGET", "SCALE_HINTS", "rule_int32_overflow"]

_INT32_MAX = 2 ** 31 - 1

#: Narrow integer dtypes the rule fires on (anything that wraps below the
#: int64 stream-count envelope).
_NARROW = {"int8", "int16", "int32", "uint8", "uint16", "uint32"}
_WIDE = {"int64", "uint64", "float64"}
_DTYPE_NAMES = _NARROW | _WIDE | {"float32"}

#: Constructors whose result dtype is the explicit dtype token if one is
#: given.  Without a token, ``zeros``-family default to float64 and the
#: carriers (``asarray``/``array``/``arange``) stay unknown.
_ZEROS_FAMILY = {"zeros", "ones", "empty", "full",
                 "zeros_like", "ones_like", "empty_like", "full_like"}
_CARRIERS = {"asarray", "array", "arange", "fromiter", "frombuffer"}

#: Substrings marking a name as stream-scale: tuple counts, byte billing,
#: engine clocks, running aggregates.  Matched case-insensitively against
#: the accumulator name and the names feeding the increment.
SCALE_HINTS: Tuple[str, ...] = (
    "count", "cnt", "total", "sum", "byte", "fed", "moved", "tuple",
    "busy", "offset", "acc", "bill", "replay",
)

_WIDTH = {"int8": 8, "uint8": 8, "int16": 16, "uint16": 16,
          "int32": 32, "uint32": 32, "float32": 32,
          "int64": 64, "uint64": 64, "float64": 64}


def _hinted(*names: str) -> bool:
    return any(h in n.lower() for n in names if n for h in SCALE_HINTS)


def _numeric_aliases(tree: ast.Module) -> Set[str]:
    """Local aliases of numpy and jax.numpy (``np``, ``jnp``, ...)."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name in ("numpy", "jax.numpy"):
                    out.add(a.asname or a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            if node.module == "jax" and node.level == 0:
                for a in node.names:
                    if a.name == "numpy":
                        out.add(a.asname or "numpy")
    return out


def _dtype_token(node: ast.AST, aliases: Set[str]) -> Optional[str]:
    """``np.int32`` / ``jnp.int32`` / ``"int32"`` → ``"int32"``."""
    if (isinstance(node, ast.Attribute) and node.attr in _DTYPE_NAMES
            and isinstance(node.value, ast.Name)
            and node.value.id in aliases):
        return node.attr
    if isinstance(node, ast.Constant) and node.value in _DTYPE_NAMES:
        return str(node.value)
    return None


class _DtypeEnv:
    """Flow-insensitive dtype evidence: every dtype each key was observed
    to hold anywhere in its scope (locals) or class (self attributes)."""

    def __init__(self) -> None:
        self.locals: Dict[Tuple[str, str], Set[str]] = {}
        self.attrs: Dict[Tuple[str, str], Set[str]] = {}

    @staticmethod
    def _class_key(node: ast.AST) -> Optional[str]:
        cur = getattr(node, "_parent", None)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return getattr(cur, "_scope", cur.name)
            cur = getattr(cur, "_parent", None)
        return None

    def key_for(self, target: ast.AST) -> Optional[Tuple[str, ...]]:
        """('local', scope, name) or ('attr', class, name) for a target."""
        if isinstance(target, ast.Name):
            return ("local", getattr(target, "_scope", "<module>"),
                    target.id)
        if (isinstance(target, ast.Attribute)
                and isinstance(target.value, ast.Name)
                and target.value.id == "self"):
            cls = self._class_key(target)
            if cls is not None:
                return ("attr", cls, target.attr)
        return None

    def record(self, target: ast.AST, dtype: Optional[str]) -> None:
        if dtype is None:
            return
        key = self.key_for(target)
        if key is None:
            return
        store = self.locals if key[0] == "local" else self.attrs
        store.setdefault((key[1], key[2]), set()).add(dtype)

    def observed(self, target: ast.AST) -> Set[str]:
        key = self.key_for(target)
        if key is None:
            return set()
        store = self.locals if key[0] == "local" else self.attrs
        return store.get((key[1], key[2]), set())


def _widest(a: Optional[str], b: Optional[str]) -> Optional[str]:
    if a is None or b is None:
        return a or b
    return a if _WIDTH[a] >= _WIDTH[b] else b


def _expr_dtype(node: ast.AST, env: _DtypeEnv, aliases: Set[str]
                ) -> Optional[str]:
    tok = _dtype_token(node, aliases)
    if tok is not None and isinstance(node, ast.Attribute):
        return None  # a dtype object, not a value of that dtype
    if isinstance(node, ast.Call):
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr == "astype":
                for sub in list(node.args) + [kw.value
                                              for kw in node.keywords]:
                    t = _dtype_token(sub, aliases)
                    if t:
                        return t
                return None
            if (isinstance(f.value, ast.Name) and f.value.id in aliases):
                if f.attr in _DTYPE_NAMES:
                    return f.attr          # np.int32(x)
                if f.attr == "bincount":
                    return "int64"
                if f.attr in _ZEROS_FAMILY | _CARRIERS:
                    for sub in list(node.args) + [kw.value
                                                  for kw in node.keywords]:
                        t = _dtype_token(sub, aliases)
                        if t:
                            return t
                    return ("float64" if f.attr in _ZEROS_FAMILY
                            else None)
        return None
    if isinstance(node, (ast.Name, ast.Attribute)):
        seen = env.observed(node)
        narrow = seen & _NARROW
        if narrow:
            # narrow evidence wins unless every write was wide: mixed
            # evidence means the accumulator *can* be narrow on some path
            return sorted(narrow, key=lambda d: -_WIDTH[d])[0]
        if seen:
            return sorted(seen, key=lambda d: -_WIDTH[d])[0]
        return None
    if isinstance(node, ast.BinOp):
        return _widest(_expr_dtype(node.left, env, aliases),
                       _expr_dtype(node.right, env, aliases))
    if isinstance(node, ast.Subscript):
        return _expr_dtype(node.value, env, aliases)
    return None


def _same_ref(a: ast.AST, b: ast.AST) -> bool:
    """`x` is `x`; `self.v` is `self.v` (one attribute level)."""
    if isinstance(a, ast.Name) and isinstance(b, ast.Name):
        return a.id == b.id
    if (isinstance(a, ast.Attribute) and isinstance(b, ast.Attribute)
            and a.attr == b.attr):
        return _same_ref(a.value, b.value)
    return False


def _display(node: ast.AST) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return f"{_display(node.value)}.{node.attr}"
    if isinstance(node, ast.Subscript):
        return f"{_display(node.value)}[...]"
    return "<expr>"


def _names_in(node: ast.AST) -> List[str]:
    out = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            out.append(sub.id)
        elif isinstance(sub, ast.Attribute):
            out.append(sub.attr)
    return out


def _accumulation_sites(tree: ast.Module):
    """Yield (anchor node, target expr, increment exprs) per site."""
    for node in ast.walk(tree):
        if isinstance(node, ast.AugAssign) and isinstance(node.op, ast.Add):
            t = node.target
            if isinstance(t, ast.Subscript):
                t = t.value
            if isinstance(t, (ast.Name, ast.Attribute)):
                yield node, t, [node.value]
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if not isinstance(t, (ast.Name, ast.Attribute)):
                continue
            v = node.value
            # x = x + inc  (either operand order)
            if isinstance(v, ast.BinOp) and isinstance(v.op, ast.Add):
                if _same_ref(v.left, t):
                    yield node, t, [v.right]
                elif _same_ref(v.right, t):
                    yield node, t, [v.left]
            # x = x.at[i].add(inc)  (jax functional scatter-add)
            elif (isinstance(v, ast.Call) and isinstance(v.func,
                                                         ast.Attribute)
                  and v.func.attr == "add"
                  and isinstance(v.func.value, ast.Subscript)
                  and isinstance(v.func.value.value, ast.Attribute)
                  and v.func.value.value.attr == "at"
                  and _same_ref(v.func.value.value.value, t)):
                yield node, t, list(v.args)
        elif (isinstance(node, ast.Call)
              and isinstance(node.func, ast.Attribute)
              and node.func.attr == "at"
              and isinstance(node.func.value, ast.Attribute)
              and node.func.value.attr == "add"
              and len(node.args) >= 3):
            # np.add.at(target, idx, inc)
            t = node.args[0]
            if isinstance(t, (ast.Name, ast.Attribute)):
                yield node, t, [node.args[2]]


def rule_int32_overflow(mod) -> List[Finding]:
    """``int32-overflow``: narrow-int accumulators that scale with stream
    length (see module docstring for the lattice)."""
    aliases = _numeric_aliases(mod.tree)
    if not aliases:
        return []
    env = _DtypeEnv()
    # two sweeps: evidence flows through one level of name indirection
    # (`nv = jnp.zeros(..., jnp.int32)` before `self._v = nv`) regardless
    # of the walk order
    for _ in range(2):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign):
                dt = _expr_dtype(node.value, env, aliases)
                for t in node.targets:
                    env.record(t, dt)
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                env.record(node.target,
                           _expr_dtype(node.value, env, aliases))

    out: List[Finding] = []
    seen: Set[Tuple[int, int]] = set()
    min_inc = _INT32_MAX // SCALE_TARGET
    for anchor, target, incs in _accumulation_sites(mod.tree):
        dt = _expr_dtype(target, env, aliases)
        if dt not in _NARROW:
            continue
        names = [_display(target).split(".")[-1]]
        for inc in incs:
            names.extend(_names_in(inc))
        if not _hinted(*names):
            continue
        key = (anchor.lineno, anchor.col_offset)
        if key in seen:
            continue
        seen.add(key)
        out.append(mod.finding(
            "int32-overflow", anchor, "error",
            f"`{_display(target)}` accumulates in {dt} and scales with "
            f"stream length — at SCALE_TARGET={SCALE_TARGET:.0e} tuples "
            f"it wraps 2³¹−1 once the mean per-step increment reaches "
            f"{min_inc}",
            "hold the running total in int64 (a device kernel can keep "
            "its int32 chunk domain and widen into an int64 host total "
            "at the fold)"))
    return out
