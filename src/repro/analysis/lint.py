"""seclint — repo-specific static invariants of the SeCluD engine.

The device hot path (PR 5/6) is fast for reasons the type system cannot
see: traced code never syncs to host, jit cache keys are quantized
shapes, PAD discipline makes masked execution exact, and every kernel
package ships its jnp oracle.  These are one careless edit away from
silently rotting, so they are linted as ASTs:

* **SEC001** — host-device sync points inside traced code of the
  device-path modules (``core/device_engine.py``, ``kernels/*``):
  ``.item()``, ``np.asarray``/``np.array``, ``int()``/``float()``/
  ``bool()`` on traced values, and implicit truthiness (``if x:`` on a
  traced value).  Any of these blocks dispatch and drags the value over
  PCIe — exactly the host⇄device ping-pong the fused fold removed.

* **SEC002** — recompilation hazards anywhere in ``src/``: ``jax.jit``
  constructed inside a function body (a fresh jit per call retraces
  every batch; exempt under ``functools.lru_cache``/``cache``, the
  sharded fold's pattern), unhashable ``static_arg*`` defaults, and raw
  ``len(...)``/``.shape`` expressions passed as static arguments of a
  jitted callable without going through ``_quantize`` — dynamic shapes
  leaking into the jit cache key defeat the ~1/8 quantization grid.

* **SEC003** — literal ``-1`` sentinel use on doc/query cell data in the
  data-plane modules: comparisons against ``-1`` and ``cells[...] = -1``
  style fills must use the exported ``PAD``/``QUERY_PAD`` constants
  (``repro.kernels.intersect.ref`` / ``repro.core.queries``) so the
  sentinel stays one value everywhere the fold masks on it.

* **SEC004** — kernel-contract completeness: every ``kernels/<name>/``
  package must ship ``kernel.py`` (the pallas kernel), ``ref.py`` (the
  jnp oracle), ``ops.py`` importing the oracle as its fallback, and a
  ``tests/test_kernels_<name>.py`` kernel≡ref test.

* **SEC005** — jit construction in the serving request path
  (``serve/*``): the serving loop's whole latency story rests on the
  shape-grid prewarm — every executable compiled at startup, zero
  traces under traffic.  ``jax.jit`` (or ``partial(jax.jit, ...)``)
  constructed inside any function body of a serve module builds a
  fresh empty cache per request and retraces every batch; bind jitted
  callables at module level or behind ``functools.lru_cache`` (the
  engine's pattern) and let the loop prewarm them.

* **SEC006** — resilience-defeating error handling in the fault-path
  modules (``serve/*``, ``dist/*``): a bare ``except:``, an
  ``except Exception:`` whose body only passes/continues, or a
  ``while True:`` loop with no ``break``/``return``/``raise`` in its
  own body.  The resilience ladder only degrades gracefully if every
  failure is *observed* (fed to the circuit breaker / straggler
  monitor) and every retry is *bounded*; swallowed exceptions and
  unbounded retry loops turn a dead shard into a silent hang.

``lint_paths`` is the engine; ``tools/seclint.py`` is the CLI.  Rules
are deliberately narrow: a finding is an invariant violation, not a
style nit, and ``src/`` must stay finding-free (CI enforces it).
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

__all__ = ["Finding", "lint_paths", "lint_file", "lint_source", "RULES"]

RULES = {
    "SEC001": "host-device sync point in traced device-path code",
    "SEC002": "jit recompilation hazard",
    "SEC003": "literal -1 sentinel instead of PAD/QUERY_PAD",
    "SEC004": "incomplete kernel contract (kernel + ref + ops + test)",
    "SEC005": "jit construction in the serving request path",
    "SEC006": "resilience-defeating error handling (swallowed exception "
    "or unbounded retry loop)",
}

# Modules whose traced code must never sync to host (SEC001).  Matched
# against the posix path suffix.
DEVICE_PATH_PATTERNS = (
    "*/core/device_engine.py",
    "*/kernels/*/kernel.py",
    "*/kernels/*/ref.py",
    "*/kernels/*/ops.py",
)

# Serving modules whose function bodies must never construct jit
# (SEC005): request-path code compiles at startup, not under traffic.
SERVE_PATH_PATTERNS = ("*/serve/*.py",)

# Fault-path modules where error handling must stay observable and
# bounded (SEC006): the serving tier's resilience ladder and the
# distributed fault-tolerance layer.
RESILIENCE_PATH_PATTERNS = ("*/serve/*.py", "*/dist/*.py")

# Data-plane modules where -1 must be spelled PAD/QUERY_PAD (SEC003).
# analysis/ is excluded: the linter itself necessarily names -1.
SENTINEL_PATTERNS = (
    "*/core/*.py",
    "*/kernels/*.py",
    "*/kernels/*/*.py",
    "*/serve/*.py",
    "*/index/*.py",
    "*/dist/*.py",
)

# numpy module aliases recognized for np.asarray / np.array (SEC001).
_NP_ALIASES = {"np", "numpy", "onp"}

# Parameter annotations that mark a host scalar/static, exempt from
# taint in transitively-traced helpers (e.g. ``group_width: int`` and
# ``stage_levels: Tuple[int, ...]`` of the fold).
_SCALAR_ANNOTATIONS = {"int", "bool", "float", "str"}
_SCALAR_ANNOTATION_PREFIXES = ("Tuple", "tuple", "Sequence", "List", "list")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    rule: str
    path: str
    line: int
    col: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


def _matches(path: str, patterns: Sequence[str]) -> bool:
    p = Path(path).as_posix()
    return any(fnmatch.fnmatch(p, pat) for pat in patterns)


# ----------------------------------------------------------------------
# jit-construction recognition (shared by SEC001 root finding and SEC002)
# ----------------------------------------------------------------------


def _is_jit_name(node: ast.AST) -> bool:
    """``jax.jit`` / ``jit`` as an expression."""
    if isinstance(node, ast.Attribute):
        return node.attr == "jit"
    return isinstance(node, ast.Name) and node.id == "jit"


def _is_partial_name(node: ast.AST) -> bool:
    if isinstance(node, ast.Attribute):
        return node.attr == "partial"
    return isinstance(node, ast.Name) and node.id == "partial"


def _static_names_of(call: ast.Call) -> Set[str]:
    """The ``static_argnames`` strings of a jit(-partial) call."""
    out: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    out.add(e.value)
    return out


def _jit_call_info(node: ast.AST) -> Optional[ast.Call]:
    """The jit-constructing Call if ``node`` is ``jax.jit(...)`` or
    ``functools.partial(jax.jit, ...)``; else None."""
    if not isinstance(node, ast.Call):
        return None
    if _is_jit_name(node.func):
        return node
    if _is_partial_name(node.func) and node.args and _is_jit_name(node.args[0]):
        return node
    return None


def _is_cache_decorated(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", ()):
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = target.attr if isinstance(target, ast.Attribute) else getattr(
            target, "id", ""
        )
        if name in ("lru_cache", "cache"):
            return True
    return False


# ----------------------------------------------------------------------
# SEC001 — taint analysis over traced function bodies
# ----------------------------------------------------------------------

# Attribute accesses that yield static (host) metadata under trace:
# shapes are Python ints inside jit, so ``b, l = x.shape`` launders the
# taint legitimately.
_STATIC_ATTRS = {"shape", "ndim", "dtype"}


def _scalar_annotated(arg: ast.arg) -> bool:
    ann = arg.annotation
    if ann is None:
        return False
    if isinstance(ann, ast.Name):
        return ann.id in _SCALAR_ANNOTATIONS
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        text = ann.value
    else:
        try:
            text = ast.unparse(ann)
        except Exception:  # pragma: no cover - malformed annotation
            return False
    text = text.strip()
    if text.startswith("Optional[") and text.endswith("]"):
        text = text[len("Optional[") : -1]
    return all(
        part == "None"
        or part in _SCALAR_ANNOTATIONS
        or part.startswith(_SCALAR_ANNOTATION_PREFIXES)
        for part in (p.strip() for p in text.split("|"))
    )


def _walk_skipping_static_attrs(node: ast.AST):
    """Yield nodes like ast.walk, but do not descend into ``x.shape`` /
    ``x.ndim`` / ``x.dtype`` subtrees (static under trace)."""
    if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
        return
    yield node
    for child in ast.iter_child_nodes(node):
        yield from _walk_skipping_static_attrs(child)


def _names_in(node: ast.AST) -> Set[str]:
    return {
        n.id
        for n in _walk_skipping_static_attrs(node)
        if isinstance(n, ast.Name)
    }


class _ModuleScan:
    """One parsed module: its functions, jit roots, and jitted bindings."""

    def __init__(self, tree: ast.Module):
        self.tree = tree
        # name -> FunctionDef, module level and nested (last def wins —
        # good enough for lint purposes).
        self.functions: Dict[str, ast.AST] = {}
        # function node -> static param names (from a jit decorator or a
        # module-level ``x = partial(jax.jit, ...)(f)`` binding).
        self.static_of: Dict[ast.AST, Set[str]] = {}
        # binding name -> static names of the jitted callable it holds.
        self.jitted_bindings: Dict[str, Set[str]] = {}
        self.roots: List[ast.AST] = []
        self._collect()

    def _collect(self):
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = node
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    call = _jit_call_info(dec)
                    if call is not None:
                        self._add_root(node, _static_names_of(call))
                    elif _is_jit_name(dec):
                        self._add_root(node, set())
            elif isinstance(node, ast.Assign):
                self._scan_binding(node)

    def _scan_binding(self, node: ast.Assign):
        """``X = functools.partial(jax.jit, ...)(f)`` and
        ``X = jax.jit(f, ...)`` bind a jitted callable to X and make f a
        traced root."""
        value = node.value
        statics: Optional[Set[str]] = None
        target_fn: Optional[ast.AST] = None
        if isinstance(value, ast.Call):
            inner = _jit_call_info(value.func)
            if inner is not None:  # partial(jax.jit, ...)(f)
                statics = _static_names_of(inner)
                if value.args and isinstance(value.args[0], ast.Name):
                    target_fn = self.functions.get(value.args[0].id)
            elif _is_jit_name(value.func):  # jax.jit(f, ...)
                statics = _static_names_of(value)
                if value.args and isinstance(value.args[0], ast.Name):
                    target_fn = self.functions.get(value.args[0].id)
        if statics is None:
            return
        for t in node.targets:
            if isinstance(t, ast.Name):
                self.jitted_bindings[t.id] = statics
        if target_fn is not None:
            self._add_root(target_fn, statics)

    def _add_root(self, fn: ast.AST, statics: Set[str]):
        if fn not in self.static_of:
            self.roots.append(fn)
        self.static_of.setdefault(fn, set()).update(statics)

    def traced_functions(self) -> List[ast.AST]:
        """Transitive closure of traced code: jit roots, their nested
        defs, and same-module functions they call or pass as arguments
        (fori_loop bodies, shard_map bodies, pallas kernels)."""
        seen: List[ast.AST] = []
        queue = list(self.roots)
        while queue:
            fn = queue.pop()
            if fn in seen:
                continue
            seen.append(fn)
            for node in ast.walk(fn):
                if node is not fn and isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    if node not in seen:
                        queue.append(node)
                elif isinstance(node, ast.Call):
                    for ref in [node.func, *node.args]:
                        if isinstance(ref, ast.Name):
                            callee = self.functions.get(ref.id)
                            if callee is not None and callee not in seen:
                                queue.append(callee)
        return seen


def _initial_taint(fn: ast.AST, statics: Set[str]) -> Set[str]:
    tainted: Set[str] = set()
    a = fn.args
    for arg in [*a.posonlyargs, *a.args, *a.kwonlyargs]:
        if arg.arg in statics or _scalar_annotated(arg):
            continue
        tainted.add(arg.arg)
    for extra in (a.vararg, a.kwarg):
        if extra is not None and extra.arg not in statics:
            tainted.add(extra.arg)
    return tainted


def _propagate_taint(fn: ast.AST, tainted: Set[str]) -> Set[str]:
    """Forward-propagate taint through assignments in ``fn``'s own body
    (nested defs analyzed separately), to a fixpoint."""
    own_nodes = _own_body_nodes(fn)
    for _ in range(10):
        before = len(tainted)
        for node in own_nodes:
            value = None
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                value, targets = node.value, node.targets
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                value, targets = node.value, [node.target]
            elif isinstance(node, ast.AugAssign):
                value, targets = node.value, [node.target]
            elif isinstance(node, ast.For):
                value, targets = node.iter, [node.target]
            elif isinstance(node, ast.withitem) and node.optional_vars:
                value, targets = node.context_expr, [node.optional_vars]
            elif isinstance(node, (ast.NamedExpr,)):
                value, targets = node.value, [node.target]
            if value is None:
                continue
            if _names_in(value) & tainted:
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            tainted.add(n.id)
        if len(tainted) == before:
            break
    return tainted


def _own_body_nodes(fn: ast.AST) -> List[ast.AST]:
    """All AST nodes of ``fn`` excluding nested function subtrees."""
    out: List[ast.AST] = []

    def visit(node: ast.AST, top: bool):
        if not top and isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            return
        out.append(node)
        for child in ast.iter_child_nodes(node):
            visit(child, False)

    visit(fn, True)
    return out


def _check_sec001(scan: _ModuleScan, path: str) -> List[Finding]:
    findings: List[Finding] = []
    for fn in scan.traced_functions():
        statics = scan.static_of.get(fn, set())
        tainted = _propagate_taint(fn, _initial_taint(fn, statics))
        if not tainted:
            continue

        def is_tainted(expr: ast.AST) -> bool:
            return bool(_names_in(expr) & tainted)

        for node in _own_body_nodes(fn):
            if isinstance(node, ast.Call):
                f = node.func
                # x.item() — a forced device->host scalar pull.
                if (
                    isinstance(f, ast.Attribute)
                    and f.attr == "item"
                    and not node.args
                    and is_tainted(f.value)
                ):
                    findings.append(
                        Finding(
                            "SEC001",
                            path,
                            node.lineno,
                            node.col_offset,
                            ".item() on a traced value blocks dispatch "
                            f"(in `{fn.name}`)",
                        )
                    )
                # np.asarray / np.array on a traced value — implicit D2H.
                elif (
                    isinstance(f, ast.Attribute)
                    and f.attr in ("asarray", "array")
                    and isinstance(f.value, ast.Name)
                    and f.value.id in _NP_ALIASES
                    and any(is_tainted(a) for a in node.args)
                ):
                    findings.append(
                        Finding(
                            "SEC001",
                            path,
                            node.lineno,
                            node.col_offset,
                            f"np.{f.attr}() on a traced value is an "
                            f"implicit device->host transfer (in `{fn.name}`)",
                        )
                    )
                # int(x) / float(x) / bool(x) — concretization error or sync.
                elif (
                    isinstance(f, ast.Name)
                    and f.id in ("int", "float", "bool")
                    and node.args
                    and is_tainted(node.args[0])
                ):
                    findings.append(
                        Finding(
                            "SEC001",
                            path,
                            node.lineno,
                            node.col_offset,
                            f"{f.id}() on a traced value syncs to host "
                            f"(in `{fn.name}`)",
                        )
                    )
            elif isinstance(node, (ast.If, ast.While)) and is_tainted(
                node.test
            ):
                findings.append(
                    Finding(
                        "SEC001",
                        path,
                        node.lineno,
                        node.col_offset,
                        "branching on a traced value is an implicit bool() "
                        f"host sync — use jnp.where/lax.cond (in `{fn.name}`)",
                    )
                )
            elif isinstance(node, ast.Assert) and is_tainted(node.test):
                findings.append(
                    Finding(
                        "SEC001",
                        path,
                        node.lineno,
                        node.col_offset,
                        "assert on a traced value is an implicit bool() "
                        f"host sync (in `{fn.name}`)",
                    )
                )
    return findings


# ----------------------------------------------------------------------
# SEC002 — recompilation hazards
# ----------------------------------------------------------------------

_UNHASHABLE_DEFAULTS = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp)


def _check_sec002(scan: _ModuleScan, path: str) -> List[Finding]:
    findings: List[Finding] = []

    # (a) per-call jit construction: a fresh jit has an empty cache, so
    # construct-and-invoke (``jax.jit(f)(x)``) or construction inside a
    # loop body retraces every time it runs.  One-time factory/__init__
    # construction is fine; lru_cache'd builders (one jit per
    # quantized-shape key) are the sanctioned parametric form.
    # ``partial(jax.jit, ...)(f)`` is construction (binding the jitted
    # callable), so only a direct ``jax.jit(f)(x)`` counts as invocation.
    for node in ast.walk(scan.tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Call)
            and _is_jit_name(node.func.func)
        ):
            findings.append(
                Finding(
                    "SEC002",
                    path,
                    node.lineno,
                    node.col_offset,
                    "immediately-invoked jax.jit builds a fresh cache "
                    "and retraces on every call — bind the jitted "
                    "callable once (module level or lru_cache)",
                )
            )
    for fn in ast.walk(scan.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if _is_cache_decorated(fn):
            continue
        for node in _own_body_nodes(fn):
            if not isinstance(node, (ast.For, ast.While)):
                continue
            for inner in ast.walk(node):
                if _jit_call_info(inner) is not None:
                    findings.append(
                        Finding(
                            "SEC002",
                            path,
                            inner.lineno,
                            inner.col_offset,
                            "jax.jit constructed inside a loop retraces "
                            "per iteration — hoist the construction or "
                            "cache with functools.lru_cache "
                            f"(in `{fn.name}`)",
                        )
                    )

    # (b) unhashable static arg defaults: jit hashes static args into
    # the cache key; a list/dict default raises at call time.
    def check_statics(fn: ast.AST, statics: Set[str]):
        a = fn.args
        pos = [*a.posonlyargs, *a.args]
        defaults = [None] * (len(pos) - len(a.defaults)) + list(a.defaults)
        pairs = list(zip(pos, defaults, strict=True)) + list(
            zip(a.kwonlyargs, a.kw_defaults, strict=True)
        )
        for arg, default in pairs:
            if (
                arg.arg in statics
                and default is not None
                and isinstance(default, _UNHASHABLE_DEFAULTS)
            ):
                findings.append(
                    Finding(
                        "SEC002",
                        path,
                        default.lineno,
                        default.col_offset,
                        f"static arg `{arg.arg}` of `{fn.name}` has an "
                        "unhashable default — jit cannot key the cache "
                        "on it",
                    )
                )

    for fn, statics in scan.static_of.items():
        if statics:
            check_statics(fn, statics)

    # (c) dynamic shapes leaking into the jit cache key: static kwargs
    # of a known-jitted binding built from raw len()/.shape instead of
    # the _quantize grid retrace per batch size.
    def leaks_shape(expr: ast.AST) -> bool:
        for n in ast.walk(expr):
            if isinstance(n, ast.Call):
                f = n.func
                if isinstance(f, ast.Name) and f.id in (
                    "_quantize",
                    "quantize",
                ):
                    return False  # quantized — the sanctioned route
                if isinstance(f, ast.Name) and f.id == "len":
                    return True
            elif isinstance(n, ast.Attribute) and n.attr == "shape":
                return True
        return False

    for node in ast.walk(scan.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (
            isinstance(f, ast.Name) and f.id in scan.jitted_bindings
        ):
            continue
        statics = scan.jitted_bindings[f.id]
        for kw in node.keywords:
            if kw.arg in statics and leaks_shape(kw.value):
                findings.append(
                    Finding(
                        "SEC002",
                        path,
                        kw.value.lineno,
                        kw.value.col_offset,
                        f"static arg `{kw.arg}` of jitted `{f.id}` is a "
                        "raw dynamic shape — every batch size becomes a "
                        "new jit cache entry; round through _quantize",
                    )
                )
    return findings


# ----------------------------------------------------------------------
# SEC003 — literal -1 sentinels
# ----------------------------------------------------------------------


def _is_neg_one(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.UnaryOp)
        and isinstance(node.op, ast.USub)
        and isinstance(node.operand, ast.Constant)
        and node.operand.value == 1
    )


_CELL_NAME_HINTS = ("cell", "post", "doc", "member")


def _check_sec003(tree: ast.Module, path: str) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Compare):
            operands = [node.left, *node.comparators]
            if any(_is_neg_one(o) for o in operands):
                findings.append(
                    Finding(
                        "SEC003",
                        path,
                        node.lineno,
                        node.col_offset,
                        "comparison against literal -1 — use the exported "
                        "PAD/QUERY_PAD sentinel constants",
                    )
                )
        elif isinstance(node, ast.Assign) and _is_neg_one(node.value):
            for t in node.targets:
                if isinstance(t, ast.Subscript):
                    base = t.value
                    name = (
                        base.id
                        if isinstance(base, ast.Name)
                        else getattr(base, "attr", "")
                    )
                    if any(h in name.lower() for h in _CELL_NAME_HINTS):
                        findings.append(
                            Finding(
                                "SEC003",
                                path,
                                node.lineno,
                                node.col_offset,
                                f"filling `{name}[...]` with literal -1 — "
                                "use the exported PAD/QUERY_PAD sentinels",
                            )
                        )
                        break
    return findings


# ----------------------------------------------------------------------
# SEC005 — jit construction in the serving request path
# ----------------------------------------------------------------------


def _check_sec005(scan: _ModuleScan, path: str) -> List[Finding]:
    """Flag ``jax.jit(...)`` / ``partial(jax.jit, ...)`` constructed inside
    any function body of a serve module.

    Request-path functions run per batch under traffic; a jit built there
    starts with an empty compile cache every call, so the shape-grid
    prewarm can never cover it.  Module-level bindings and
    ``functools.lru_cache``-decorated builders (the engine's pattern:
    construct once, reuse the cached executable) are the sanctioned
    homes and are exempt.
    """
    findings: List[Finding] = []
    for node in ast.walk(scan.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if _is_cache_decorated(node):
            continue
        # Decorators evaluate once at def time, not per call — a
        # ``@jax.jit`` on a nested def is someone else's problem
        # (SEC002 territory), not a per-request construction.
        deco_nodes = {
            id(n) for d in node.decorator_list for n in ast.walk(d)
        }
        for sub in _own_body_nodes(node):
            if id(sub) in deco_nodes:
                continue
            call = _jit_call_info(sub)
            if call is None:
                continue
            findings.append(
                Finding(
                    "SEC005",
                    path,
                    call.lineno,
                    call.col_offset,
                    "jax.jit constructed in the serving request path — "
                    "bind the jitted callable at startup (module level "
                    "or a functools.lru_cache builder) and prewarm its "
                    f"shape grid (in `{node.name}`)",
                )
            )
    return findings


# ----------------------------------------------------------------------
# SEC006 — resilience-defeating error handling in fault-path modules
# ----------------------------------------------------------------------

_BROAD_EXC_NAMES = {"Exception", "BaseException"}


def _is_broad_handler(handler: ast.ExceptHandler) -> bool:
    """Bare ``except:`` or ``except Exception/BaseException`` (possibly
    in a tuple)."""
    t = handler.type
    if t is None:
        return True
    types = t.elts if isinstance(t, ast.Tuple) else [t]
    for typ in types:
        name = typ.attr if isinstance(typ, ast.Attribute) else getattr(
            typ, "id", ""
        )
        if name in _BROAD_EXC_NAMES:
            return True
    return False


def _swallows(handler: ast.ExceptHandler) -> bool:
    """True when the handler body does nothing but pass/continue — the
    exception is silently discarded."""
    return all(
        isinstance(stmt, (ast.Pass, ast.Continue)) for stmt in handler.body
    )


def _loop_own_nodes(loop: ast.While) -> List[ast.AST]:
    """Nodes of the loop body, excluding nested function/lambda subtrees
    and nested loops' own break targets — a ``break`` inside an inner
    ``for`` does not exit the outer ``while True``.  ``return``/``raise``
    anywhere (outside nested defs) does exit, so those are collected from
    the full non-def subtree."""
    exits: List[ast.AST] = []

    def collect(node: ast.AST, loop_depth: int):
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            return
        if isinstance(node, ast.Break):
            if loop_depth == 0:
                exits.append(node)
            return
        if isinstance(node, (ast.Return, ast.Raise)):
            exits.append(node)
            return
        child_depth = (
            loop_depth + 1
            if isinstance(node, (ast.For, ast.While, ast.AsyncFor))
            else loop_depth
        )
        for child in ast.iter_child_nodes(node):
            collect(child, child_depth)

    for stmt in loop.body:
        collect(stmt, 0)
    return exits


def _check_sec006(tree: ast.Module, path: str) -> List[Finding]:
    """Flag error handling that defeats the resilience ladder:

    * bare ``except:`` — catches ``KeyboardInterrupt``/``SystemExit`` and
      hides *which* failure fired, so nothing upstream can count strikes;
    * ``except Exception:`` (or broader) whose body only passes/continues
      — the failure is observed by no one: no breaker strike, no
      straggler record, no fallback level in the stats;
    * ``while True:`` with no ``break``/``return``/``raise`` reachable in
      its own body — an unbounded retry spin that turns a dead shard into
      a hang instead of a degraded-but-answering service.  (A ``break``
      belonging to a nested loop does not count; exits inside nested
      ``def``/``lambda`` bodies do not count.)
    """
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler):
            if node.type is None:
                findings.append(
                    Finding(
                        "SEC006",
                        path,
                        node.lineno,
                        node.col_offset,
                        "bare `except:` hides the failure from the "
                        "resilience ladder — catch the typed error and "
                        "feed the breaker/monitor",
                    )
                )
            elif _is_broad_handler(node) and _swallows(node):
                findings.append(
                    Finding(
                        "SEC006",
                        path,
                        node.lineno,
                        node.col_offset,
                        "`except Exception: pass/continue` swallows the "
                        "failure — record it (breaker strike, shard "
                        "times, fallback level) or re-raise",
                    )
                )
        elif (
            isinstance(node, ast.While)
            and isinstance(node.test, ast.Constant)
            and node.test.value is True
            and not _loop_own_nodes(node)
        ):
            findings.append(
                Finding(
                    "SEC006",
                    path,
                    node.lineno,
                    node.col_offset,
                    "unbounded `while True:` retry loop with no "
                    "break/return/raise — bound the attempts "
                    "(for attempt in range(budget)) so a dead shard "
                    "degrades instead of hanging",
                )
            )
    return findings


# ----------------------------------------------------------------------
# SEC004 — kernel-contract completeness (directory-level rule)
# ----------------------------------------------------------------------

_KERNEL_REQUIRED = ("kernel.py", "ref.py", "ops.py")


def _ops_imports_ref(ops_path: Path) -> bool:
    try:
        tree = ast.parse(ops_path.read_text())
    except SyntaxError:
        return False
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == "ref" or mod.endswith(".ref"):
                return True
            if any(a.name == "ref" for a in node.names):
                return True
    return False


def check_kernel_contracts(
    kernels_dir: Path, tests_dir: Optional[Path]
) -> List[Finding]:
    """SEC004 over one ``kernels/`` package directory."""
    findings: List[Finding] = []
    for pkg in sorted(kernels_dir.iterdir()):
        if not pkg.is_dir() or not (pkg / "__init__.py").exists():
            continue
        name = pkg.name
        for required in _KERNEL_REQUIRED:
            if not (pkg / required).exists():
                findings.append(
                    Finding(
                        "SEC004",
                        str(pkg),
                        1,
                        0,
                        f"kernel package `{name}` is missing {required} "
                        "(contract: pallas kernel + jnp ref oracle + ops "
                        "wrapper)",
                    )
                )
        ops = pkg / "ops.py"
        if ops.exists() and not _ops_imports_ref(ops):
            findings.append(
                Finding(
                    "SEC004",
                    str(ops),
                    1,
                    0,
                    f"`{name}/ops.py` does not import its ref oracle — "
                    "the ops wrapper must expose the jnp fallback",
                )
            )
        if tests_dir is not None:
            test_file = tests_dir / f"test_kernels_{name}.py"
            if not test_file.exists():
                findings.append(
                    Finding(
                        "SEC004",
                        str(pkg),
                        1,
                        0,
                        f"kernel package `{name}` has no kernel≡ref test "
                        f"(expected {test_file.name})",
                    )
                )
    return findings


# ----------------------------------------------------------------------
# Drivers
# ----------------------------------------------------------------------


def lint_source(source: str, path: str) -> List[Finding]:
    """Per-file rules (SEC001–SEC003, SEC005, SEC006) over one module's
    source text."""
    try:
        tree = ast.parse(source)
    except SyntaxError as exc:
        return [
            Finding(
                "SEC000", path, exc.lineno or 1, 0, f"syntax error: {exc.msg}"
            )
        ]
    findings: List[Finding] = []
    scan = _ModuleScan(tree)
    if _matches(path, DEVICE_PATH_PATTERNS):
        findings += _check_sec001(scan, path)
    findings += _check_sec002(scan, path)
    if _matches(path, SENTINEL_PATTERNS):
        findings += _check_sec003(tree, path)
    if _matches(path, SERVE_PATH_PATTERNS):
        findings += _check_sec005(scan, path)
    if _matches(path, RESILIENCE_PATH_PATTERNS):
        findings += _check_sec006(tree, path)
    return findings


def lint_file(path: Path) -> List[Finding]:
    return lint_source(path.read_text(), str(path))


def _iter_py_files(root: Path):
    if root.is_file():
        yield root
        return
    for p in sorted(root.rglob("*.py")):
        if "__pycache__" not in p.parts:
            yield p


def lint_paths(
    paths: Sequence[Path], tests_dir: Optional[Path] = None
) -> List[Finding]:
    """Lint files/trees; SEC004 runs once per discovered ``kernels/``
    directory.  ``tests_dir`` enables the kernel≡ref test-existence
    check (pass None to skip it, e.g. for fixture trees)."""
    findings: List[Finding] = []
    kernels_dirs: List[Path] = []
    for root in paths:
        root = Path(root)
        for f in _iter_py_files(root):
            findings += lint_file(f)
            for parent in f.parents:
                if parent.name == "kernels" and parent not in kernels_dirs:
                    kernels_dirs.append(parent)
    for kd in kernels_dirs:
        findings += check_kernel_contracts(kd, tests_dir)
    return sorted(
        findings, key=lambda f: (f.path, f.line, f.col, f.rule)
    )
