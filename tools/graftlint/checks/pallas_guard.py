"""pallas-guard: every route into ``pl.pallas_call`` passes pallas_guarded.

The runtime contract (models/ivf.py:pallas_guarded): a Pallas kernel fault
must be attributed (bad kernel vs bad request: the XLA path runs as the
oracle), demote that index's kernel (kernel -> XLA oracle -> demote), and
never crash a serving request that the XLA oracle could have answered. That only holds if NO public code path reaches
a kernel without the guard.

Static approximation (unit = every def/lambda, nested separately):

- A1: ``pl.pallas_call`` may only appear in kernel modules
  (``ops/*_pallas.py``) — kernels live with their VMEM budgets and
  interpret-mode fallbacks, not inline in model code.
- A2: taint = reaches-a-kernel. Seed: units containing ``pallas_call``.
  Propagate: a unit referencing a tainted unit (call or bare reference —
  passing a tainted function onward counts) becomes tainted, UNLESS the
  reference sits lexically inside the arguments of a guard-equivalent
  call, or the unit itself was defined inside such arguments (the lambdas
  handed to ``pallas_guarded`` run under the guard). Guard-equivalent:
  ``pallas_guarded`` and ``GuardedScan`` (the same ladder with its wait
  put off to the collect), any unit whose body calls either
  (wrapper helpers like mesh.py's ``guarded``), and the reviewed ALLOW
  list (first-use oracle checks). Findings: tainted units with a public
  (non-underscore) name outside ``ops/``.

Name resolution follows Python scoping for bare names (a ``body`` helper
in one module never matches a ``body`` in another): own/ancestor nested
defs, then same-module top-level functions. ``self.x`` and
internal-module-alias attributes match repo units by name; calls through
external roots (``jax.*`` etc.) never do.
"""

import ast
from collections import defaultdict

from tools.graftlint.core import Finding, attr_root, call_name

RULE = "pallas-guard"

# reviewed guard-equivalent functions: these intentionally run kernels
# outside pallas_guarded (first-use oracle validation against the XLA path)
ALLOW = frozenset({"_validate_flat_pallas"})

# an index's ``search``: guard-equivalent by derivation for as long as the
# ladder's call sat in the local indexes' ``search`` bodies, which is what
# kept the sharded indexes' ``search`` (kernels behind ``_guarded_scan``,
# through nested helpers this check books to the enclosing def) off the
# findings. The call sits in ``launch_search`` since ``search`` is
# ``launch_search(...).collect()``; the name stays guard-equivalent.
SEARCH_ENTRIES = frozenset({"search"})


def _kernel_module(mod) -> bool:
    return mod.relpath.endswith("_pallas.py") and (
        "/ops/" in mod.relpath or mod.relpath.startswith("ops/"))


def check(model):
    for u in model.units:
        if u.has_pallas_call and not _kernel_module(u.module):
            yield Finding(
                RULE, u.module.relpath, u.lineno, u.node.col_offset,
                f"pl.pallas_call in {u.qualname}: kernels belong in "
                "ops/*_pallas.py modules (VMEM budgets, interpret fallback, "
                "guard wiring live there)",
            )

    guard_names = {"pallas_guarded", "GuardedScan"} | ALLOW | SEARCH_ENTRIES
    for u in model.units:
        if u.calls_pallas_guarded and u.name:
            guard_names.add(u.name)

    children = defaultdict(list)
    toplevel = defaultdict(list)  # module -> units with no parent
    for u in model.units:
        if u.parent is not None:
            children[id(u.parent)].append(u)
        else:
            toplevel[id(u.module)].append(u)
    by_name_global = defaultdict(list)
    for u in model.units:
        if u.name:
            by_name_global[u.name].append(u)

    def bare_candidates(unit, name):
        cur = unit
        while cur is not None:
            local = [c for c in children[id(cur)] if c.name == name]
            if local:
                return local
            cur = cur.parent
        return [u for u in toplevel[id(unit.module)] if u.name == name]

    # pass 1: which def/lambda nodes sit inside guard-call arguments
    guarded_defsites = set()

    def mark_defsites(node, depth):
        extra = 0
        if isinstance(node, ast.Call) and call_name(node) in guard_names:
            extra = 1
        for child in ast.iter_child_nodes(node):
            d = depth + extra
            if isinstance(node, ast.Call) and extra and child is node.func:
                d = depth
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                if d > 0:
                    guarded_defsites.add(id(child))
                continue
            mark_defsites(child, d)

    for mod in model.modules:
        mark_defsites(mod.tree, 0)

    # pass 2: per-unit references (candidate units, guarded flag)
    refs = {}
    for u in model.units:
        out = []
        base_depth = 1 if id(u.node) in guarded_defsites else 0
        body = u.node.body if not isinstance(u.node, ast.Lambda) else [u.node.body]

        def visit(node, depth, u=u, out=out):
            extra = 0
            if isinstance(node, ast.Call) and call_name(node) in guard_names:
                extra = 1
            if isinstance(node, ast.Name) and node.id not in guard_names:
                cands = bare_candidates(u, node.id)
                if cands:
                    out.append((cands, depth > 0))
            elif (isinstance(node, ast.Attribute)
                    and node.attr not in guard_names):
                root = attr_root(node)
                if root in ("self", "cls") or (
                        root is not None and u.module.internal_alias(root)):
                    cands = by_name_global.get(node.attr)
                    if cands:
                        out.append((cands, depth > 0))
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda)):
                    continue  # separate unit
                d = depth + extra
                if isinstance(node, ast.Call) and extra and child is node.func:
                    d = depth
                visit(child, d)

        for stmt in body:
            visit(stmt, base_depth)
        refs[u] = out

    # pass 3: fixpoint taint propagation
    tainted = {u for u in model.units if u.has_pallas_call}
    changed = True
    while changed:
        changed = False
        for u in model.units:
            if u in tainted or (u.name and u.name in guard_names):
                continue
            for cands, in_guard in refs[u]:
                if not in_guard and any(c in tainted for c in cands):
                    tainted.add(u)
                    changed = True
                    break

    for u in sorted(tainted, key=lambda u: (u.module.relpath, u.lineno)):
        if u.name is None:
            continue
        # public = importable surface: no underscore-prefixed component in
        # the qualified name (a helper nested in a private function is not
        # an entry point)
        if any(part.startswith("_") for part in u.qualname.split(".")):
            continue
        if _kernel_module(u.module) or u.module.is_ops():
            continue
        yield Finding(
            RULE, u.module.relpath, u.lineno, u.node.col_offset,
            f"public callable {u.qualname} reaches pl.pallas_call without "
            "going through pallas_guarded (no fault attribution / XLA "
            "demotion on kernel failure)",
        )
