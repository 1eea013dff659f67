"""blocking-under-lock: no unbounded blocking while a lock is held.

A thread that blocks indefinitely while holding a lock turns every other
user of that lock into a hostage: a peer that stops draining TCP, a
child that never exits, or a device launch that wedges the runtime
freezes the whole serving surface behind one stuck thread. This checker
flags, inside any ``with self.<lock>:`` body (lexical model shared with
lock-discipline — lambdas inherit, nested defs reset):

- socket operations that can block without bound (``sendall``, ``recv``,
  ``recv_into``, ``recvfrom``, ``sendto``, ``accept``);
- unbounded joins/waits: zero-argument ``.join()`` (``str.join`` always
  takes an argument, so bare ``join()`` is Thread/Process/greenlet
  style), zero-argument ``.wait()`` (Event/Condition/Popen without a
  timeout), zero-argument ``.get()`` (blocking queue pop — ``dict.get``
  always takes a key);
- ``time.sleep`` (bounded, but a lock is exactly the wrong place to
  spend the bound);
- jitted device launches: a call that (transitively, over the name-based
  call graph) reaches a ``jax.jit``-decorated function or a Pallas
  kernel. A launch can recompile or wedge the runtime for unbounded
  time; the engine's designed locked launch (one in-flight device search
  per index) carries a reasoned suppression instead.

Indirect blocking propagates through PRECISELY resolvable calls only
(bare names preferring same-module definitions, exact ``self.method()``
dispatch — the lock-order checker's resolution), so hiding ``sendall``
one helper down (``rpc._send_parts``) still flags the locked caller,
while a ``search`` on some other object never inherits an unrelated
class's ``search``. Launch detection is deliberately looser (attribute
names minus the stoplist): model entry points are reached through
``self.tpu_index.<method>`` dynamic dispatch, which exact resolution
cannot see. Audited, deliberate sites — the serial RPC client that
holds its stub lock across a round trip by definition, the
SO_SNDTIMEO-bounded mux frame write — carry
``# graftlint: ok(blocking-under-lock): <reason>``.
"""

import ast
from collections import defaultdict

from tools.graftlint.core import (
    EXTERNAL_ROOTS,
    Finding,
    HOT_EDGE_STOPLIST,
    attr_root,
    call_name,
    dotted,
    lock_attrs,
    lock_context_events,
    registry_launch_names,
)

RULE = "blocking-under-lock"

_SKIP_METHODS = frozenset({"__init__", "__new__", "__del__"})

_SOCKET_BLOCKING = frozenset({
    "sendall", "recv", "recv_into", "recvfrom", "sendto", "accept",
})

# zero-argument spellings of these attribute calls block without bound;
# any argument (timeout positional/keyword, str.join's iterable, a dict
# key) makes them bounded or a different method entirely
_ZERO_ARG_BLOCKING = {
    "join": "unbounded .join()",
    "wait": "untimed .wait()",
    "get": "blocking .get()",
}


def _direct_reason(call: ast.Call):
    """Reason string when this call blocks by itself, else None."""
    f = call.func
    if isinstance(f, ast.Attribute):
        if f.attr in _SOCKET_BLOCKING:
            return f"socket .{f.attr}()"
        if (f.attr in _ZERO_ARG_BLOCKING and not call.args
                and not call.keywords):
            return _ZERO_ARG_BLOCKING[f.attr]
    dn = dotted(f)
    if dn == "time.sleep":
        return "time.sleep()"
    return None


def _callee_names(call: ast.Call):
    """Names a call site may resolve through, for blocking/launch
    propagation: bare names, and attribute calls NOT rooted in an
    external module alias. Stoplisted ubiquitous names never carry."""
    f = call.func
    if isinstance(f, ast.Name):
        if f.id not in HOT_EDGE_STOPLIST:
            yield f.id
    elif isinstance(f, ast.Attribute):
        root = attr_root(f)
        if root in EXTERNAL_ROOTS:
            return
        if f.attr not in HOT_EDGE_STOPLIST:
            yield f.attr


def _may_block(model):
    """function id -> reason for every repo function that may block,
    directly or through PRECISELY resolved calls (lock_order._resolve:
    bare names preferring same-module definitions, else a globally unique
    one; exact ``self.method()`` dispatch)."""
    from tools.graftlint.checks.lock_order import _resolve

    reasons = {}   # function id -> reason
    callers = defaultdict(set)  # callee id -> set of caller fids
    for fi in model.functions:
        for sub in ast.walk(fi.node):
            if not isinstance(sub, ast.Call):
                continue
            r = _direct_reason(sub)
            if r is not None and id(fi) not in reasons:
                reasons[id(fi)] = r
            for g in _resolve(sub, fi, model):
                callers[id(g)].add(id(fi))
    # propagate callee->caller to a fixpoint
    fns = {id(fi): fi for fi in model.functions}
    work = list(reasons)
    while work:
        fid = work.pop()
        for cid in callers.get(fid, ()):
            if cid not in reasons:
                reasons[cid] = (f"calls {fns[fid].qualname}: "
                                f"{reasons[fid]}")
                work.append(cid)
    return reasons


def _may_launch(model):
    """Names of repo functions that may launch a jitted device program
    (directly jitted, calling a jitted name or a Pallas entry, or
    reaching one transitively)."""
    launching = set()  # function ids
    callers = defaultdict(set)
    fns = {}
    # the jit-entry registry's launch targets (utils/jitreg.py, parsed by
    # core.registry_launch_names) are launch-semantic by declaration —
    # unioned with the per-module jit scan so the registry, HOT_ROOTS and
    # this checker can't drift apart on what "a launch" is
    registry_names = registry_launch_names()
    for fi in model.functions:
        fns[id(fi)] = fi
        if fi.jit is not None or fi.name in registry_names:
            launching.add(id(fi))
        for sub in ast.walk(fi.node):
            if not isinstance(sub, ast.Call):
                continue
            cn = call_name(sub)
            if cn in ("pallas_call", "pallas_guarded", "GuardedScan") or (
                    cn in registry_names) or (
                    cn in model.jitted_names and cn not in HOT_EDGE_STOPLIST):
                launching.add(id(fi))
            for name in _callee_names(sub):
                callers[name].add(id(fi))
    work = list(launching)
    while work:
        fid = work.pop()
        for cid in callers.get(fns[fid].name, ()):
            if cid not in launching:
                launching.add(cid)
                work.append(cid)
    return {fns[fid].name for fid in launching} - HOT_EDGE_STOPLIST


def check(model):
    from tools.graftlint.checks.lock_order import _resolve

    blocking = _may_block(model)
    launch_names = _may_launch(model)

    lock_names_by_cls = {}
    for mod in model.modules:
        for cnode in mod.classes:
            names = lock_attrs(cnode)
            if names:
                lock_names_by_cls[(id(mod), cnode.name)] = names

    for fi in model.functions:
        if fi.cls is None or fi.name in _SKIP_METHODS:
            continue
        lock_names = lock_names_by_cls.get((id(fi.module), fi.cls))
        if not lock_names:
            continue
        for ev in lock_context_events(fi.node, lock_names):
            if ev[0] != "node":
                continue
            _, node, held = ev
            if not held or not isinstance(node, ast.Call):
                continue
            reason = _direct_reason(node)
            if reason is None:
                for g in _resolve(node, fi, model):
                    if id(g) in blocking:
                        reason = (f"`{g.qualname}` may block "
                                  f"({blocking[id(g)]})")
                        break
            if reason is None:
                for name in _callee_names(node):
                    if name in launch_names:
                        reason = (f"`{name}` may launch a jitted "
                                  "device program")
                        break
            if reason is None:
                continue
            locks = ", ".join(f"self.{h}" for h in held)
            yield Finding(
                RULE, fi.module.relpath, node.lineno, node.col_offset,
                f"{fi.cls}.{fi.name} holds {locks} across a "
                f"potentially unbounded blocking call: {reason}",
            )
