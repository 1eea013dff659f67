"""graftlint shared core: repo model, suppressions, findings, call graph.

The checkers (tools/graftlint/checks/) enforce the invariants the serving
hot path depends on (docs/LINTING.md); this module gives them one parsed
view of the repo so every checker agrees on what a "function", a "jitted
callable", or a "hot-path function" is.

Design stance: checkers are PRECISION-FIRST. A finding should be worth a
human's time, so the matchers under-approximate (a dynamic dispatch or a
function value stored in a local is invisible to them) and the documented
conventions (``# graftlint: hot``, ``# graftlint: ok(<rule>)``) close the
gap explicitly instead of heuristics guessing.

Analysis units come at two granularities:

- ``FunctionInfo`` — outermost functions and methods. Nested defs and
  lambdas belong to their outermost enclosing function: the hot-path walk
  and the host-sync scan treat the whole lexical body as one unit.
- ``Unit`` — every def/lambda separately, with parent links. The
  pallas-guard taint analysis needs this resolution: a nested ``scan``
  helper that reaches a kernel must not taint its enclosing ``search``
  when every reference to it is wrapped in ``pallas_guarded``.
"""

from __future__ import annotations

import ast
import dataclasses
import functools
import io
import os
import re
import tokenize
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Set, Tuple

SUPPRESS_RE = re.compile(r"#\s*graftlint:\s*ok\(([^)]*)\)")
HOT_RE = re.compile(r"#\s*graftlint:\s*hot\b")
# ``graftlint: atomic(attr[, attr2])`` comment markers — a reviewed
# declaration that the named attribute(s) of the lexically enclosing class
# are benign to access without a lock across threads (monotonic counters,
# publish-once flags, single-machine-word reads whose staleness is
# acceptable). Consumed by the shared-state-race checker; a marker that
# waives no live cross-root access is itself a finding (the atomic-rot
# half of the suppression audit).
ATOMIC_RE = re.compile(r"#\s*graftlint:\s*atomic\(([^)]*)\)")

# call-graph roots for the hot-path walk (module path suffix, qualname);
# any function annotated `# graftlint: hot` is an additional root.
# Index.search_batched is the scheduler's launch target (the merged-window
# serving path reaches the engine through it, not through Index.search),
# and the mesh search entry points are the one-launch serving programs —
# rooting them keeps the host-sync checker policing the multi-chip path
# even where dynamic dispatch (scheduler callbacks, tpu_index attribute
# calls) hides the edges from the name-based walk.
#
# The roots themselves live in utils/jitreg.py (the jit-entry registry):
# the registry, this AST tier and the IR tier all describe the same
# compiled-program surface, so there is exactly ONE declaration of it.
# The registry file keeps its declarations as pure literals so this
# stdlib-only tier can AST-parse it without importing jax.

_JITREG_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    "distributed_faiss_tpu", "utils", "jitreg.py")

# IR-tier rule names (tools/graftlint/ir). Declared here so the AST tier
# can recognize ok(ir-*) suppressions as known — and hold them dormant
# (not stale) on runs where the IR tier didn't execute.
IR_RULES = frozenset({
    "ir-device-residency", "ir-dtype", "ir-const-capture",
    "ir-bucket-budget", "ir-trace-failure",
})


@functools.lru_cache(maxsize=1)
def _registry_literals() -> Dict[str, object]:
    """AST-parse utils/jitreg.py for its declarative literals."""
    with open(_JITREG_PATH, "r", encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=_JITREG_PATH)
    out: Dict[str, object] = {}
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id in ("HOT_ROOTS", "REGISTRY",
                                           "PURE_CALLBACK_ALLOWLIST")):
            out[node.targets[0].id] = ast.literal_eval(node.value)
    missing = {"HOT_ROOTS", "REGISTRY"} - set(out)
    if missing:
        raise RuntimeError(
            f"utils/jitreg.py is missing literal declarations {sorted(missing)}"
            " — the AST tier derives its hot-root/launch views from them")
    return out


def registry_rows() -> Tuple[dict, ...]:
    """The jit-entry registry rows, as literals (no jax import)."""
    return tuple(_registry_literals()["REGISTRY"])


def registry_launch_names() -> frozenset:
    """Qualnames of every registered jitted launch target — unioned into
    the blocking checker's launch-name set so a registered kernel carries
    launch semantics even where dynamic dispatch hides the jit decoration
    from the per-module AST scan."""
    return frozenset(r["qualname"] for r in registry_rows() if r.get("trace"))


HOT_ROOTS: Tuple[Tuple[str, str], ...] = tuple(
    (str(p), str(q)) for p, q in _registry_literals()["HOT_ROOTS"])

# module aliases that resolve to code outside this repo: attribute calls
# rooted here are never treated as calls to repo functions
EXTERNAL_ROOTS = frozenset({
    "jax", "jnp", "lax", "pl", "pltpu", "np", "numpy", "os", "np_mod",
    "threading", "functools", "itertools", "logging", "pickle", "json",
    "socket", "struct", "time", "re", "math", "selectors", "pathlib",
    "ctypes", "subprocess", "sys", "random",
})

NUMPY_ALIASES = frozenset({"np", "numpy"})

# names of the utils.lockdep factory functions: `self.x = lockdep.lock(...)`
# creates a (possibly instrumented) lock exactly like `threading.Lock()`.
# Lock detection must recognize both spellings or wiring the runtime
# witness would silently blind every lock checker (the frame-protocol
# stale-pin audit exists to catch exactly that class of drift).
LOCKDEP_FACTORIES = frozenset({"lock", "rlock", "condition"})
_LOCK_CTORS = frozenset({"Lock", "RLock", "Condition"})


def is_lock_ctor(node: ast.AST) -> bool:
    """True when ``node`` is a lock-creating call: ``threading.Lock()`` /
    ``RLock()`` / ``Condition()``, or a ``lockdep.lock/rlock/condition(...)``
    factory call (utils/lockdep.py — plain primitive when DFT_LOCKDEP is
    off, instrumented witness when on)."""
    if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
        return False
    if node.func.attr in _LOCK_CTORS:
        return True
    return (node.func.attr in LOCKDEP_FACTORIES
            and attr_root(node.func) == "lockdep")


def lock_attrs(class_node) -> set:
    """Attributes of ``self`` assigned a lock anywhere in the class body
    (see ``is_lock_ctor`` for what counts as a lock)."""
    locks = set()
    for node in ast.walk(class_node):
        if not isinstance(node, ast.Assign):
            continue
        if not is_lock_ctor(node.value):
            continue
        for t in node.targets:
            if (isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                locks.add(t.attr)
    return locks


def lock_context_events(method_node, lock_names):
    """Walk one method body under the lock-discipline lexical model,
    yielding two event kinds:

    - ``("acquire", lock_attr, held_before, node)`` — a ``with
      self.<lock>:`` item, with the ordered tuple of locks already held
      lexically at that point (multi-item withs acquire left to right);
    - ``("node", ast_node, held)`` — every other AST node, with the
      ordered tuple of locks held around it.

    Lambdas inherit the surrounding lock context (they run inline);
    nested ``def``s reset it (they usually run later on another thread).
    """

    def self_lock(expr):
        if (isinstance(expr, ast.Attribute) and isinstance(expr.value, ast.Name)
                and expr.value.id == "self" and expr.attr in lock_names):
            return expr.attr
        return None

    def visit(node, held):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            # items evaluate left to right, each AFTER the previous items'
            # locks are acquired — so a later item's context expression
            # (e.g. `with self.lock, sock.accept() as c:`) runs with the
            # earlier locks held
            new_held = list(held)
            for item in node.items:
                attr = self_lock(item.context_expr)
                if attr is not None:
                    yield ("acquire", attr, tuple(new_held), item.context_expr)
                    if attr not in new_held:
                        new_held.append(attr)
                else:
                    yield from visit(item.context_expr, tuple(new_held))
            for sub in node.body:
                yield from visit(sub, tuple(new_held))
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for sub in node.body:
                yield from visit(sub, ())  # runs later: no inherited locks
            return
        if isinstance(node, ast.Lambda):
            yield from visit(node.body, held)  # runs inline: inherits locks
            return
        yield ("node", node, held)
        for child in ast.iter_child_nodes(node):
            yield from visit(child, held)

    for stmt in method_node.body:
        yield from visit(stmt, ())

# method names excluded as hot-path call-graph edges: ubiquitous container/
# builtin method names that would otherwise alias repo functions (a
# `seen.add(x)` inside a hot function must not mark every `Index.add` hot —
# ingest paths are reached from `add_batch`, not `search`)
HOT_EDGE_STOPLIST = frozenset({
    "add", "append", "extend", "update", "pop", "get", "set", "clear",
    "remove", "close", "record", "join", "split", "copy", "items", "keys",
    "values", "wait", "acquire", "release", "put",
})


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.rule}] {self.message}"


@dataclasses.dataclass(frozen=True)
class JitInfo:
    static_names: frozenset
    static_nums: Tuple[int, ...]


def _is_jit_ref(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "jit") or (
        isinstance(node, ast.Name) and node.id == "jit"
    )


def _const_items(node: ast.AST) -> list:
    if isinstance(node, ast.Constant):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts if isinstance(e, ast.Constant)]
    return []


def jit_info_from_call(call: ast.Call) -> Optional[JitInfo]:
    """JitInfo for ``jax.jit(...)`` / ``functools.partial(jax.jit, ...)``
    call expressions; None when the call is neither."""
    f = call.func
    is_partial = (isinstance(f, ast.Attribute) and f.attr == "partial") or (
        isinstance(f, ast.Name) and f.id == "partial"
    )
    inner_jit = is_partial and call.args and _is_jit_ref(call.args[0])
    if not (_is_jit_ref(f) or inner_jit):
        return None
    names: frozenset = frozenset()
    nums: Tuple[int, ...] = ()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            names = frozenset(v for v in _const_items(kw.value) if isinstance(v, str))
        elif kw.arg == "static_argnums":
            nums = tuple(v for v in _const_items(kw.value) if isinstance(v, int))
    return JitInfo(names, nums)


def decorator_jit_info(node) -> Optional[JitInfo]:
    for dec in node.decorator_list:
        if _is_jit_ref(dec):
            return JitInfo(frozenset(), ())
        if isinstance(dec, ast.Call):
            info = jit_info_from_call(dec)
            if info is not None:
                return info
    return None


def call_name(call: ast.Call) -> Optional[str]:
    """Bare name of a call target: ``f(...)`` -> "f", ``a.b.c(...)`` -> "c"."""
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def attr_root(node: ast.AST) -> Optional[str]:
    """Leftmost Name of an attribute chain: ``a.b.c`` -> "a"."""
    while isinstance(node, ast.Attribute):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


def dotted(node: ast.AST) -> Optional[str]:
    """Full dotted name of Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


class Unit:
    """One def/lambda, at full nesting resolution (pallas-guard taint)."""

    __slots__ = (
        "module", "name", "qualname", "node", "parent", "lineno",
        "has_pallas_call", "calls_pallas_guarded",
    )

    def __init__(self, module, name, qualname, node, parent, lineno):
        self.module = module
        self.name = name  # None for lambdas
        self.qualname = qualname
        self.node = node
        self.parent = parent
        self.lineno = lineno
        self.has_pallas_call = False
        self.calls_pallas_guarded = False


class FunctionInfo:
    """One outermost function/method (nested defs included in its body)."""

    __slots__ = (
        "module", "name", "qualname", "cls", "node", "lineno", "jit",
        "called_names", "hot", "hot_annotated",
    )

    def __init__(self, module, name, qualname, cls, node):
        self.module = module
        self.name = name
        self.qualname = qualname
        self.cls = cls  # enclosing class name or None
        self.node = node
        self.lineno = node.lineno
        self.jit = decorator_jit_info(node)
        self.called_names: Set[str] = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                n = call_name(sub)
                if n:
                    self.called_names.add(n)
        first = min([d.lineno for d in node.decorator_list] + [node.lineno])
        self.hot_annotated = any(
            ln in module.hot_lines for ln in range(first - 1, node.lineno + 1)
        )
        self.hot = False


def module_level_stmts(stmts):
    """Yield defs/classes at module (or class) level, descending into
    statement blocks (if/try/with/for/while — version gates, availability
    guards) but never into function bodies."""
    for s in stmts:
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            yield s
        elif isinstance(s, (ast.If, ast.Try, ast.With, ast.For, ast.While,
                            ast.AsyncWith, ast.AsyncFor)):
            blocks = [getattr(s, "body", []), getattr(s, "orelse", []),
                      getattr(s, "finalbody", [])]
            blocks += [h.body for h in getattr(s, "handlers", [])]
            for blk in blocks:
                yield from module_level_stmts(blk)


class ModuleInfo:
    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.suppressions: Dict[int, Set[str]] = {}
        self.hot_lines: Set[int] = set()
        self.atomic_marks: Dict[int, Set[str]] = {}
        for i, text in self._comment_lines():
            m = SUPPRESS_RE.search(text)
            if m:
                self.suppressions[i] = {
                    r.strip() for r in m.group(1).split(",") if r.strip()
                }
            if HOT_RE.search(text):
                self.hot_lines.add(i)
            m = ATOMIC_RE.search(text)
            if m:
                self.atomic_marks[i] = {
                    a.strip() for a in m.group(1).split(",") if a.strip()
                }
        # alias -> imported module dotted path (for internal/external calls)
        self.import_aliases: Dict[str, str] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.import_aliases[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.import_aliases[a.asname or a.name] = (
                        f"{node.module}.{a.name}"
                    )
        self.functions: List[FunctionInfo] = []
        self.classes: List[ast.ClassDef] = []
        self.units: List[Unit] = []
        self._collect()

    def _collect(self) -> None:
        for node in module_level_stmts(self.tree.body):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions.append(
                    FunctionInfo(self, node.name, node.name, None, node))
            elif isinstance(node, ast.ClassDef):
                self.classes.append(node)
                for sub in module_level_stmts(node.body):
                    if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        self.functions.append(FunctionInfo(
                            self, sub.name, f"{node.name}.{sub.name}",
                            node.name, sub))
        for fi in self.functions:
            self._collect_units(fi.node, fi.qualname, None)

    def _collect_units(self, node, qualprefix: str, parent: Optional[Unit]):
        name = getattr(node, "name", None)
        qual = qualprefix if parent is None else f"{qualprefix}.{name or '<lambda>'}"
        unit = Unit(self, name, qual, node, parent, node.lineno)
        self.units.append(unit)
        body = node.body if not isinstance(node, ast.Lambda) else [node.body]

        def scan(n):
            if isinstance(n, ast.Call):
                cn = call_name(n)
                if cn == "pallas_call":
                    unit.has_pallas_call = True
                if cn in ("pallas_guarded", "GuardedScan"):
                    unit.calls_pallas_guarded = True
            for child in ast.iter_child_nodes(n):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda)):
                    self._collect_units(child, qual, unit)
                else:
                    scan(child)

        for stmt in body:
            scan(stmt)

    # -- suppression / classification helpers ----------------------------

    def _comment_lines(self):
        """(line, text) for every line carrying a real ``#`` COMMENT token.
        Annotations live in comments; scanning raw source lines would also
        match docstring/string-literal mentions of the syntax (e.g. the
        examples in this package's own docstrings), which must neither
        create suppressions nor trip the suppression-rot audit. Falls
        back to the raw line scan only when the module fails to tokenize
        (it already parsed, so this is near-unreachable)."""
        try:
            out = []
            for tok in tokenize.generate_tokens(
                    io.StringIO(self.source).readline):
                if tok.type == tokenize.COMMENT:
                    out.append((tok.start[0], tok.string))
            return out
        except (tokenize.TokenError, IndentationError):  # pragma: no cover
            return [(i, t) for i, t in enumerate(self.lines, 1) if "#" in t]

    def match_suppression(self, rule: str, line: int) -> Optional[int]:
        """Comment line of the ``# graftlint: ok(<rule>)`` that covers a
        finding at ``line`` — its own line, the line above, or on/above
        the ``def`` line of an enclosing function (which scopes the
        suppression to the whole function). None when unsuppressed. The
        returned line is how ``lint`` records which suppressions earned
        their keep (the suppression-rot audit flags the rest)."""
        for ln in (line, line - 1):
            rules = self.suppressions.get(ln)
            if rules and (rule in rules or "all" in rules):
                return ln
        for u in self.units:
            end = getattr(u.node, "end_lineno", u.lineno)
            if not (u.lineno <= line <= end):
                continue
            for ln in (u.lineno, u.lineno - 1):
                rules = self.suppressions.get(ln)
                if rules and (rule in rules or "all" in rules):
                    return ln
        return None

    def suppressed(self, rule: str, line: int) -> bool:
        return self.match_suppression(rule, line) is not None

    def internal_alias(self, name: str) -> bool:
        """True when ``name`` is an import alias of a module in this repo
        (anything under the repo's own top-level packages)."""
        target = self.import_aliases.get(name)
        if target is None:
            return False
        root = target.split(".")[0]
        return root in ("distributed_faiss_tpu", "tools") or target.startswith(".")

    def is_ops(self) -> bool:
        return "/ops/" in self.relpath or self.relpath.startswith("ops/")


class RepoModel:
    def __init__(self, modules: List[ModuleInfo], subset: bool = False):
        # subset=True: a partial lint (`--changed`) — cross-artifact rules
        # that are only decidable against the full package (knob/doc
        # drift, the suppression-rot audit) must gate themselves off
        self.subset = subset
        self.modules = modules
        self.functions: List[FunctionInfo] = [
            f for m in modules for f in m.functions
        ]
        self.units: List[Unit] = [u for m in modules for u in m.units]
        self.by_name: Dict[str, List[FunctionInfo]] = defaultdict(list)
        for f in self.functions:
            self.by_name[f.name].append(f)
        self.jitted_names: Set[str] = {f.name for f in self.functions if f.jit}
        self._mark_hot()

    def _mark_hot(self) -> None:
        roots = [f for f in self.functions if f.hot_annotated]
        for suffix, qualname in HOT_ROOTS:
            roots += [
                f for f in self.functions
                if f.qualname == qualname and f.module.relpath.endswith(suffix)
            ]
        seen: Set[int] = set()
        stack = list(roots)
        while stack:
            f = stack.pop()
            if id(f) in seen:
                continue
            seen.add(id(f))
            f.hot = True
            for name in f.called_names:
                if name in HOT_EDGE_STOPLIST:
                    continue
                for g in self.by_name.get(name, ()):
                    if id(g) not in seen:
                        stack.append(g)


def collect_files(paths: Iterable[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                out.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [
                d for d in sorted(dirnames)
                if not d.startswith(".") and d != "__pycache__"
            ]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return out


def build_model(paths: Iterable[str], subset: bool = False) -> RepoModel:
    modules = []
    for path in collect_files(paths):
        with open(path, "r", encoding="utf-8") as f:
            source = f.read()
        modules.append(ModuleInfo(path, os.path.relpath(path), source))
    return RepoModel(modules, subset=subset)


# ---------------------------------------------------------- thread-root model
#
# The shared-state-race checker (checks/races.py) needs a whole-program
# answer to "which THREAD touches this attribute, holding what?". The
# thread-root model is that answer: it enumerates every thread ENTRY POINT
# the package creates — named ``threading.Thread`` targets (including
# nested-def targets like the engine's save watcher), ``ThreadPoolExecutor``
# ``submit``/``map`` callables, and the public-API caller root (the user's
# own thread entering any public method of a lock-owning class) — then
# walks the call graph from each root with an interprocedural LOCKSET:
# the lexical ``with self.<lock>`` model (lock_context_events) extended by
# held-at-entry propagation, so a helper only ever called under a lock
# carries that lock into its accesses. Where a function is reachable under
# several locksets within one root, the entry lockset is the INTERSECTION
# (a lock held on every path), which is the conservative direction for
# race detection.
#
# Resolution is the package's precision-first shape — bare names prefer
# same-module definitions (else a globally unique one), ``self.m()``
# dispatches exactly — plus one deliberate loosening shared with the
# blocking checker: an attribute call whose method name is globally unique
# (and not stoplisted / rooted in an external module) resolves, because
# watcher threads reach the engine through parameters
# (``engine.compact()``), which exact resolution cannot see. Spawn sites
# themselves (``Thread(target=...)``, ``pool.submit(fn)``) never create a
# same-root call edge — the callee runs on the OTHER root.

API_ROOT = "api"

_SPAWN_METHODS = frozenset({"submit", "map"})

MUTATOR_METHODS = frozenset({
    "append", "extend", "insert", "pop", "popitem", "remove", "clear",
    "update", "setdefault", "add", "discard", "sort", "reverse",
})

_SKIP_WALK_METHODS = frozenset({"__init__", "__new__", "__del__"})


@dataclasses.dataclass(frozen=True)
class SharedAccess:
    """One ``self.<attr>`` touch attributed to a thread root."""

    cls: str
    attr: str
    write: bool
    path: str
    line: int
    col: int
    locks: frozenset  # qualified "Cls.lock" keys held (lexical + entry)
    root: str         # thread-root label ("api", "thread:...", "pool:...")
    func: str         # qualname of the accessing function (provenance)


# expressions that build a plain container: a ``.append``/``.update``-class
# call on an attribute holding one of these is a container MUTATION (a
# write for race purposes); the same method name on a domain object
# (``self.membership.remove(pos)`` — MembershipTable's internally-locked
# method) is an ordinary call and must not be misread as a torn write
_CONTAINER_CTORS = frozenset({
    "list", "dict", "set", "deque", "defaultdict", "OrderedDict", "Counter",
})


def _container_assigned_attrs(class_node) -> set:
    """Attributes of ``self`` assigned a container literal/constructor
    anywhere in the class body (including ``__init__``)."""
    out = set()
    for node in ast.walk(class_node):
        if not isinstance(node, ast.Assign):
            continue
        v = node.value
        is_container = isinstance(v, (
            ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
            ast.SetComp,
        )) or (isinstance(v, ast.Call) and call_name(v) in _CONTAINER_CTORS)
        if not is_container:
            continue
        for t in node.targets:
            if (isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                out.add(t.attr)
    return out


def _is_thread_ctor_call(call: ast.Call, mod: ModuleInfo) -> bool:
    if dotted(call.func) == "threading.Thread":
        return True
    if isinstance(call.func, ast.Name):
        return mod.import_aliases.get(call.func.id) == "threading.Thread"
    return False


class ThreadRootModel:
    """Thread roots + per-root shared-state accesses over one RepoModel."""

    def __init__(self, model: RepoModel):
        self.model = model
        # label -> (kind, relpath, line): spawn-site provenance per root
        self.roots: Dict[str, Tuple[str, str, int]] = {}
        self.accesses: List[SharedAccess] = []
        self._class_locks: Dict[Tuple[int, str], set] = {}
        self._container_attrs: Dict[Tuple[int, str], set] = {}
        for mod in model.modules:
            for cnode in mod.classes:
                attrs = lock_attrs(cnode)
                if attrs:
                    self._class_locks[(id(mod), cnode.name)] = attrs
                self._container_attrs[(id(mod), cnode.name)] = (
                    _container_assigned_attrs(cnode))
        self._analyzed: Dict[int, Tuple[list, list]] = {}
        self._fns: Dict[int, FunctionInfo] = {}
        for label, seeds in self._enumerate_roots().items():
            self._walk(label, seeds)
        self.accesses.sort(key=lambda a: (a.path, a.line, a.col, a.root))

    # ------------------------------------------------------------ resolution

    def _ref_targets(self, expr, fi: FunctionInfo) -> List[FunctionInfo]:
        """Functions a callable REFERENCE (a Thread target, a submit arg)
        may denote — includes nested defs of the enclosing function (the
        save watcher's ``_watch``), which close over the method's scope."""
        model = self.model
        if isinstance(expr, ast.Name):
            for sub in ast.walk(fi.node):
                if (isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and sub.name == expr.id and sub is not fi.node):
                    return [FunctionInfo(
                        fi.module, sub.name, f"{fi.qualname}.{sub.name}",
                        fi.cls, sub)]
            cands = model.by_name.get(expr.id, [])
            same = [g for g in cands if g.module is fi.module]
            if same:
                return same
            return list(cands) if len(cands) == 1 else []
        if isinstance(expr, ast.Attribute):
            if (isinstance(expr.value, ast.Name) and expr.value.id == "self"
                    and fi.cls is not None):
                exact = [g for g in model.by_name.get(expr.attr, ())
                         if g.module is fi.module and g.cls == fi.cls]
                if exact:
                    return exact
            if attr_root(expr) in EXTERNAL_ROOTS:
                return []
            if expr.attr in HOT_EDGE_STOPLIST:
                return []
            cands = model.by_name.get(expr.attr, [])
            return list(cands) if len(cands) == 1 else []
        return []

    def _call_targets(self, call: ast.Call, fi: FunctionInfo):
        """Same-root callees of one call site (spawn sites excluded: their
        callable runs on the root the spawn created, not this one). Bare
        names resolve same-module-first (never into nested defs — those
        are already walked inline by the lexical model)."""
        f = call.func
        if (isinstance(f, ast.Attribute) and f.attr in _SPAWN_METHODS
                and call.args and self._ref_targets(call.args[0], fi)):
            return []
        if isinstance(f, ast.Name):
            if f.id in HOT_EDGE_STOPLIST:
                return []
            cands = self.model.by_name.get(f.id, [])
            same = [g for g in cands if g.module is fi.module]
            if same:
                return same
            return list(cands) if len(cands) == 1 else []
        if isinstance(f, ast.Attribute):
            return self._ref_targets(f, fi)
        return []

    # ------------------------------------------------------------ enumeration

    def _enumerate_roots(self) -> Dict[str, List[FunctionInfo]]:
        seeds: Dict[str, List[FunctionInfo]] = defaultdict(list)
        seen_nodes: Dict[str, Set[int]] = defaultdict(set)

        def add(label, kind, fn, relpath, line):
            if id(fn.node) in seen_nodes[label]:
                return
            seen_nodes[label].add(id(fn.node))
            self.roots.setdefault(label, (kind, relpath, line))
            seeds[label].append(fn)

        for fi in self.model.functions:
            # the public-API caller root: a user thread may enter any
            # public method of a lock-owning class (and any public
            # module-level function) directly
            public = not fi.name.startswith("_")
            if public and (fi.cls is None or (id(fi.module), fi.cls)
                           in self._class_locks):
                add(API_ROOT, "api", fi, fi.module.relpath, fi.lineno)
            for sub in ast.walk(fi.node):
                if not isinstance(sub, ast.Call):
                    continue
                if _is_thread_ctor_call(sub, fi.module):
                    target = next((kw.value for kw in sub.keywords
                                   if kw.arg == "target"), None)
                    if target is None:
                        continue
                    for g in self._ref_targets(target, fi):
                        add(f"thread:{g.qualname}", "thread", g,
                            fi.module.relpath, sub.lineno)
                elif (isinstance(sub.func, ast.Attribute)
                        and sub.func.attr in _SPAWN_METHODS and sub.args
                        and attr_root(sub.func) not in EXTERNAL_ROOTS):
                    for g in self._ref_targets(sub.args[0], fi):
                        add(f"pool:{g.qualname}", "pool", g,
                            fi.module.relpath, sub.lineno)
        return seeds

    # ------------------------------------------------------------ the walk

    def _analyze(self, fn: FunctionInfo):
        """Cached per-function scan: (raw accesses, raw call edges), each
        carrying the LEXICALLY held own-class locks at the site."""
        cached = self._analyzed.get(id(fn.node))
        if cached is not None:
            return cached
        lock_names = self._class_locks.get(
            (id(fn.module), fn.cls), frozenset()) if fn.cls else frozenset()
        containers = self._container_attrs.get(
            (id(fn.module), fn.cls), frozenset()) if fn.cls else frozenset()
        accesses: list = []   # (attr, write, line, col, held-tuple)
        calls: list = []      # (callee FunctionInfo, held-tuple)
        skip_reads: Set[int] = set()  # inner attr nodes of write wrappers

        for ev in lock_context_events(fn.node, lock_names):
            if ev[0] != "node":
                continue
            _, node, held = ev
            if isinstance(node, ast.Attribute):
                if not (isinstance(node.value, ast.Name)
                        and node.value.id == "self"):
                    continue
                attr = node.attr
                if (attr in lock_names or attr.startswith("__")):
                    continue
                if isinstance(node.ctx, (ast.Store, ast.Del)):
                    accesses.append((attr, True, node.lineno,
                                     node.col_offset, held))
                elif id(node) not in skip_reads:
                    accesses.append((attr, False, node.lineno,
                                     node.col_offset, held))
            elif isinstance(node, ast.Subscript) and isinstance(
                    node.ctx, (ast.Store, ast.Del)):
                base = node.value
                if (isinstance(base, ast.Attribute)
                        and isinstance(base.value, ast.Name)
                        and base.value.id == "self"
                        and base.attr not in lock_names):
                    accesses.append((base.attr, True, node.lineno,
                                     node.col_offset, held))
                    skip_reads.add(id(base))
            elif isinstance(node, ast.Call):
                f = node.func
                if (isinstance(f, ast.Attribute)
                        and f.attr in MUTATOR_METHODS
                        and isinstance(f.value, ast.Attribute)
                        and isinstance(f.value.value, ast.Name)
                        and f.value.value.id == "self"
                        and f.value.attr in containers
                        and f.value.attr not in lock_names):
                    accesses.append((f.value.attr, True, node.lineno,
                                     node.col_offset, held))
                    skip_reads.add(id(f.value))
                for g in self._call_targets(node, fn):
                    if g.name not in _SKIP_WALK_METHODS:
                        calls.append((g, held))
        result = (accesses, calls)
        self._analyzed[id(fn.node)] = result
        self._fns[id(fn.node)] = fn
        return result

    def _walk(self, label: str, seeds: List[FunctionInfo]) -> None:
        def qualify(fn, held):
            return frozenset(f"{fn.cls}.{h}" for h in held)

        entry: Dict[int, frozenset] = {}
        fns: Dict[int, FunctionInfo] = {}
        work: List[FunctionInfo] = []
        for fn in seeds:
            entry[id(fn.node)] = frozenset()
            fns[id(fn.node)] = fn
            work.append(fn)
        # phase 1: propagate held-at-entry locksets to a fixpoint
        # (intersection merge — only a lock held on EVERY path counts)
        while work:
            fn = work.pop()
            eff_base = entry[id(fn.node)]
            _, calls = self._analyze(fn)
            for g, held in calls:
                eff = eff_base | qualify(fn, held)
                cur = entry.get(id(g.node))
                if cur is None:
                    entry[id(g.node)] = eff
                    fns[id(g.node)] = g
                    work.append(g)
                else:
                    merged = cur & eff
                    if merged != cur:
                        entry[id(g.node)] = merged
                        work.append(fns[id(g.node)])
        # phase 2: record every self.<attr> access with its final lockset.
        # Scope: methods of LOCK-OWNING classes only (the lock-discipline
        # scope) — lock-less helper classes (frame decode cursors, the
        # tombstone set) are either method-local or reached exclusively
        # through a lock-owning owner whose pinned attribute already
        # carries the guarantee
        for nid, base in entry.items():
            fn = fns[nid]
            if fn.cls is None or (
                    id(fn.module), fn.cls) not in self._class_locks:
                continue
            accesses, _ = self._analyze(fn)
            for attr, write, line, col, held in accesses:
                self.accesses.append(SharedAccess(
                    fn.cls, attr, write, fn.module.relpath, line, col,
                    frozenset(base | qualify(fn, held)), label, fn.qualname))


def thread_root_model(model: RepoModel) -> ThreadRootModel:
    """The (memoized) thread-root model for one RepoModel."""
    cached = getattr(model, "_thread_root_model", None)
    if cached is None:
        cached = ThreadRootModel(model)
        model._thread_root_model = cached
    return cached


SUPPRESSION_AUDIT_RULE = "unused-suppression"


def _audit_suppressions(model: RepoModel, used: Dict[int, Set[int]],
                        known_rules: Set[str],
                        dormant_rules: Set[str] = frozenset()) -> List[Finding]:
    """The suppression-rot audit: every ``# graftlint: ok(<rule>)`` comment
    must either suppress a live finding THIS run or name a rule that no
    longer exists — a suppression that does neither is itself a finding,
    so the reviewed-waiver inventory can't rot into a pile of comments
    nobody can tell apart from load-bearing ones. Deliberately-dormant
    waivers (e.g. version-gated code paths) opt out explicitly with
    ``ok(unused-suppression)`` beside them — which that very audit then
    tracks like any other suppression."""
    out: List[Finding] = []
    for mod in model.modules:
        used_lines = used.get(id(mod), set())
        markers = []  # pure ok(unused-suppression) lines, audited last
        for line in sorted(mod.suppressions):
            if line in used_lines:
                continue
            rules = mod.suppressions[line]
            if rules & dormant_rules:
                # names a rule belonging to a tier that did not run this
                # invocation (the IR tier on an AST-only lint): whether the
                # suppression is live is undecidable here, exactly like a
                # subset lint — the tier's own full run audits it
                continue
            if SUPPRESSION_AUDIT_RULE in rules:
                # an opt-out marker is "used" exactly when it waives a
                # dormant neighbor (recorded below). A PURE marker that
                # ends up waiving nothing is itself rot and is audited
                # after all neighbors have been processed; a combined
                # line (ok(<rule>, unused-suppression)) self-waives.
                if rules == {SUPPRESSION_AUDIT_RULE}:
                    markers.append(line)
                continue
            unknown = sorted(
                r for r in rules
                if r not in known_rules and r != "all")
            waiver = mod.match_suppression(SUPPRESSION_AUDIT_RULE, line)
            if waiver is not None:
                used_lines.add(waiver)
                continue
            if unknown:
                msg = (f"suppression names unknown rule(s) "
                       f"{', '.join(unknown)} — a typo'd ok() suppresses "
                       "nothing; fix the rule name or delete the comment")
            else:
                msg = (f"stale suppression: ok({', '.join(sorted(rules))}) "
                       "no longer suppresses any finding — delete it, or "
                       "waive deliberately-dormant waivers with "
                       "ok(unused-suppression)")
            out.append(Finding(SUPPRESSION_AUDIT_RULE, mod.relpath,
                               line, 0, msg))
        for line in markers:
            if line in used_lines:
                continue
            out.append(Finding(
                SUPPRESSION_AUDIT_RULE, mod.relpath, line, 0,
                "orphaned ok(unused-suppression): it waives no dormant "
                "suppression beside it — the waiver it covered was "
                "deleted; delete this marker too"))
    return out


def lint(model: RepoModel,
         ir_findings: Optional[List[Finding]] = None,
         ast_checks: bool = True) -> List[Finding]:
    """Run the AST checkers (plus, when ``ir_findings`` is given, merge the
    IR tier's pre-suppression findings) through the one suppression and
    rot-audit pipeline. ``ir_findings=None`` means the IR tier did not run:
    its rules stay *known* (a typo'd ok(ir-dtype) is still flagged) but
    *dormant* for staleness — only a run that actually traced the registry
    can decide whether an IR suppression is live. ``ast_checks=False``
    (the ``--ir-only`` path) skips the AST checkers; pair it with a
    subset model so the rot audit — undecidable without them — stays off."""
    from tools.graftlint import checks

    findings: List[Finding] = []
    by_path = {m.relpath: m for m in model.modules}
    used: Dict[int, Set[int]] = defaultdict(set)  # id(mod) -> comment lines

    def _consume(stream):
        for f in stream:
            mod = by_path.get(f.path)
            if mod is not None:
                sline = mod.match_suppression(f.rule, f.line)
                if sline is not None:
                    used[id(mod)].add(sline)
                    continue
            findings.append(f)

    if ast_checks:
        for checker in checks.ALL:
            _consume(checker.check(model))
    if ir_findings is not None:
        _consume(ir_findings)
    if not model.subset:
        # the rot audit is only decidable against the full package: a
        # suppression whose finding resolves through modules OUTSIDE the
        # linted subset (a locked device launch into an unlinted jitted
        # callee, say) would look stale on every partial lint
        known = set(checks.RULES) | {SUPPRESSION_AUDIT_RULE} | set(IR_RULES)
        dormant = IR_RULES if ir_findings is None else frozenset()
        findings += _audit_suppressions(model, used, known, dormant)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def lint_paths(paths: Iterable[str], subset: bool = False,
               ir_findings: Optional[List[Finding]] = None) -> List[Finding]:
    """Lint ``paths``. ``subset=True`` marks a partial lint (the
    ``--changed`` precommit fast path): cross-artifact rules that are
    only decidable against the full package — the suppression-rot audit
    and env-knob-drift's doc cross-check — gate themselves off; CI's
    full lint keeps them on. ``ir_findings`` merges the IR tier's
    pre-suppression findings (``tools.graftlint.ir.lint_ir()``) into the
    same suppression/audit pipeline."""
    return lint(build_model(paths, subset=subset), ir_findings=ir_findings)
