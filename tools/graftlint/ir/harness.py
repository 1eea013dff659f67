"""IR-tier harness: trace registered jit entries, check the equation graph.

For each ``trace=True`` registry row the harness resolves the jitted
callable, abstract-evals every representative signature to a ClosedJaxpr
(``fn.trace(*args, **kwargs)`` with ``jax.ShapeDtypeStruct`` args — no
compile, no execute, no device data) and walks the equation graph,
recursing into sub-jaxprs carried in equation params (scan/while/cond
bodies, pallas kernels, nested pjit).  Failures are findings, never
skips: a row that cannot be resolved or traced is an unverified entry.

Findings anchor to real source lines: equation-level findings use jax's
source-info user frame (the repo line that built the op), entry-level
findings use the def line of the registered callable, registry-drift
findings use the offending def/row site.
"""

import ast
import os
from collections import defaultdict

from tools.graftlint.core import (
    Finding,
    decorator_jit_info,
    jit_info_from_call,
)

RULE_RESIDENCY = "ir-device-residency"
RULE_DTYPE = "ir-dtype"
RULE_CONST = "ir-const-capture"
RULE_BUDGET = "ir-bucket-budget"
RULE_TRACE = "ir-trace-failure"

# a const above this many bytes baked into a program is weight-sized: it
# bloats every executable that captures it and silently re-ships on every
# recompile (the operand belongs in the argument list, donated or sharded)
CONST_BYTE_LIMIT = 1 << 20  # 1 MiB

# operand/accumulator dtypes that lose mantissa in a contraction; a
# dot/conv whose operands include one of these must accumulate wider
# (fp32, or int32 for integer codes)
_LOW_PRECISION = frozenset({
    "bfloat16", "float16", "float8_e4m3fn", "float8_e5m2",
    "int8", "uint8", "int4", "uint4",
})

_CONTRACTION_PRIMS = frozenset({"dot_general", "conv_general_dilated"})


# --------------------------------------------------------------- row access
#
# Registry rows come from utils/jitreg.py; fixture/unit-test rows may carry
# the callables directly ("fn" / "spec_fn" / "buckets_fn") instead of the
# import-and-name indirection.


def _jitreg():
    from distributed_faiss_tpu.utils import jitreg

    return jitreg


def _resolve(row):
    if row.get("fn") is not None:
        return row["fn"]
    return _jitreg().resolve(row)


def _signatures(row):
    if row.get("spec_fn") is not None:
        return row["spec_fn"]()
    return _jitreg().signatures(row)


def _buckets(row):
    if row.get("buckets_fn") is not None:
        return row["buckets_fn"]()
    return _jitreg().enumerate_buckets(row)


# ------------------------------------------------------------- jaxpr access


def _closed_jaxprs_in(value):
    """ClosedJaxprs nested in an eqn param value (lists/tuples included)."""
    import jax.extend.core as jcore

    if isinstance(value, jcore.ClosedJaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _closed_jaxprs_in(v)


def _jaxprs_in(value):
    import jax.extend.core as jcore

    if isinstance(value, jcore.Jaxpr):
        yield value
    for cj in _closed_jaxprs_in(value):
        yield cj.jaxpr


def _walk_eqns(jaxpr):
    """Every eqn in the program, recursing into param-carried sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in _jaxprs_in(value):
                yield from _walk_eqns(sub)


def _def_site(fn):
    """(file, line) of the function a jit wrapper wraps, via __wrapped__."""
    inner, hops = fn, 0
    while hasattr(inner, "__wrapped__") and hops < 8:
        inner = inner.__wrapped__
        hops += 1
    code = getattr(inner, "__code__", None)
    if code is None:
        return None, 1
    return code.co_filename, code.co_firstlineno


def _eqn_site(eqn, default_path, default_line):
    """Repo-relative (path, line) of the user frame that built this eqn,
    falling back to the entry's def site for jax-internal frames."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is not None:
        fname = getattr(frame, "file_name", None)
        line = getattr(frame, "start_line", None)
        if fname:
            rel = os.path.relpath(fname, os.getcwd())
            if not rel.startswith(".."):
                return rel, int(line or default_line)
    return default_path, default_line


def _nbytes(const):
    nb = getattr(const, "nbytes", None)
    if nb is not None:
        return int(nb)
    try:
        import numpy as np

        return int(np.asarray(const).nbytes)
    except Exception:
        return 0


def _callback_name(eqn):
    """Best-effort name of a pure_callback's python target (allowlist key)."""
    cb = eqn.params.get("callback")
    name = getattr(cb, "__name__", None)
    if name in (None, "<lambda>"):
        for attr in ("callback_func", "func", "f", "fun"):
            inner = getattr(cb, attr, None)
            if inner is not None and getattr(inner, "__name__", None):
                name = inner.__name__
                break
    return name or repr(cb)


# ---------------------------------------------------------------- checkers


def _check_program(row, closed, def_line, allow):
    """Run the per-eqn checkers over one traced ClosedJaxpr."""
    path = row["path"]

    for var, const in zip(closed.jaxpr.constvars, closed.consts):
        nb = _nbytes(const)
        if nb > CONST_BYTE_LIMIT:
            aval = getattr(var, "aval", None)
            yield Finding(
                RULE_CONST, path, def_line, 0,
                f"`{row['qualname']}` bakes a {nb}-byte array "
                f"({aval}) into the program as a const "
                f"(limit {CONST_BYTE_LIMIT}); pass it as an argument",
            )

    for eqn in _walk_eqns(closed.jaxpr):
        name = eqn.primitive.name

        if "callback" in name or name in ("infeed", "outfeed"):
            if name == "pure_callback" and _callback_name(eqn) in allow:
                continue
            p, ln = _eqn_site(eqn, path, def_line)
            detail = (f" (target `{_callback_name(eqn)}` not in "
                      "PURE_CALLBACK_ALLOWLIST)"
                      if name == "pure_callback" else "")
            yield Finding(
                RULE_RESIDENCY, p, ln, 0,
                f"`{row['qualname']}` contains host primitive "
                f"`{name}`{detail}: registered programs must stay "
                "on-device",
            )
            continue

        if name in _CONTRACTION_PRIMS:
            in_dts = sorted({str(v.aval.dtype) for v in eqn.invars
                             if hasattr(getattr(v, "aval", None), "dtype")})
            low = [d for d in in_dts if d in _LOW_PRECISION]
            if not low:
                continue
            outvar = eqn.outvars[0]
            out_dt = str(outvar.aval.dtype)
            if out_dt in _LOW_PRECISION:
                p, ln = _eqn_site(eqn, path, def_line)
                yield Finding(
                    RULE_DTYPE, p, ln, 0,
                    f"`{row['qualname']}`: {name} over "
                    f"{'/'.join(low)} operands accumulates in {out_dt}; "
                    "policy is fp32 (int32 for codes) accumulation — "
                    "set preferred_element_type",
                )
            continue

        for value in eqn.params.values():
            for sub in _closed_jaxprs_in(value):
                for const in sub.consts:
                    nb = _nbytes(const)
                    if nb > CONST_BYTE_LIMIT:
                        p, ln = _eqn_site(eqn, path, def_line)
                        yield Finding(
                            RULE_CONST, p, ln, 0,
                            f"`{row['qualname']}`: nested `{name}` "
                            f"program captures a {nb}-byte const "
                            f"(limit {CONST_BYTE_LIMIT})",
                        )


def _check_row(row, allow):
    path = row["path"]

    if row.get("buckets") or row.get("buckets_fn") is not None:
        try:
            buckets = _buckets(row)
        except Exception as exc:  # enumerator itself broke
            buckets = None
            yield Finding(
                RULE_BUDGET, path, 1, 0,
                f"`{row['qualname']}` bucket enumerator failed: "
                f"{type(exc).__name__}: {exc}",
            )
        if buckets is not None and len(buckets) != row["budget"]:
            yield Finding(
                RULE_BUDGET, path, 1, 0,
                f"`{row['qualname']}` reaches {len(buckets)} shape "
                f"buckets but the registry declares {row['budget']} — "
                "the pow2 bucketing and utils/jitreg.py drifted apart "
                f"(enumerated: {buckets})",
            )

    if not row.get("trace"):
        return

    try:
        fn = _resolve(row)
    except Exception as exc:
        yield Finding(
            RULE_TRACE, path, 1, 0,
            f"stale registry row: `{row['import']}.{row['qualname']}` "
            f"failed to resolve ({type(exc).__name__}: {exc})",
        )
        return

    _, def_line = _def_site(fn)

    if not hasattr(fn, "trace"):
        yield Finding(
            RULE_TRACE, path, def_line, 0,
            f"`{row['qualname']}` is registered as a jit entry but is "
            "not a jitted callable (no .trace)",
        )
        return

    try:
        sigs = _signatures(row)
    except Exception as exc:
        yield Finding(
            RULE_TRACE, path, def_line, 0,
            f"`{row['qualname']}` spec builder failed: "
            f"{type(exc).__name__}: {exc}",
        )
        return
    if not sigs:
        yield Finding(
            RULE_TRACE, path, def_line, 0,
            f"`{row['qualname']}` declares no representative abstract "
            "signatures",
        )
        return

    for i, (args, kwargs) in enumerate(sigs):
        try:
            closed = fn.trace(*args, **kwargs).jaxpr
        except Exception as exc:
            yield Finding(
                RULE_TRACE, path, def_line, 0,
                f"`{row['qualname']}` signature #{i} failed to trace: "
                f"{type(exc).__name__}: {str(exc)[:300]}",
            )
            continue
        yield from _check_program(row, closed, def_line, allow)


# ----------------------------------------------------------- registry drift


def _module_jit_defs(tree):
    """(name, lineno, col) of module-level jitted launch targets: decorated
    defs and ``name = jax.jit(...)`` assignments.  Inline ``jax.jit(...)``
    calls inside functions are exempt — they are per-instance programs
    already policed by the AST recompile-hazard rule."""
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if decorator_jit_info(node) is not None:
                yield node.name, node.lineno, node.col_offset
        elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            if jit_info_from_call(node.value) is not None:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        yield tgt.id, node.lineno, node.col_offset


def _drift_findings(rows):
    """Registry-vs-code drift over the covered files: every module-level
    jit def in a covered file must have a row."""
    by_path = defaultdict(set)
    for row in rows:
        by_path[row["path"]].add(row["qualname"])
    for path in sorted(by_path):
        if not os.path.isfile(path):
            yield Finding(
                RULE_BUDGET, path, 1, 0,
                "registry row points at a missing file",
            )
            continue
        with open(path, "r", encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), filename=path)
        for name, line, col in _module_jit_defs(tree):
            if name not in by_path[path]:
                yield Finding(
                    RULE_BUDGET, path, line, col,
                    f"unregistered jit entry `{name}`: every module-level "
                    "jitted launch target in a covered file needs a "
                    "utils/jitreg.py row (spec + budget)",
                )


# ------------------------------------------------------------------- driver


def lint_ir(entries=None, callback_allowlist=None):
    """Run the IR tier. ``entries`` overrides the registry rows (fixtures);
    ``callback_allowlist`` overrides PURE_CALLBACK_ALLOWLIST. Returns
    pre-suppression findings sorted by (path, line, rule)."""
    if entries is None:
        rows = _jitreg().rows()
    else:
        rows = tuple(entries)
    if callback_allowlist is None:
        allow = frozenset(_jitreg().PURE_CALLBACK_ALLOWLIST)
    else:
        allow = frozenset(callback_allowlist)

    findings = list(_drift_findings(rows))
    for row in rows:
        findings.extend(_check_row(row, allow))

    seen = set()
    out = []
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule)):
        key = (f.rule, f.path, f.line, f.message)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out
