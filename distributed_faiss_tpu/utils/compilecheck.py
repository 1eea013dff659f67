"""Runtime compile-count witness (DFT_COMPILECHECK=1): XLA compilations
tallied per jit entry so steady-state serving windows can assert they
compile NOTHING new after warmup.

The IR tier's ``ir-bucket-budget`` rule proves the pow2 bucketing yields
a bounded program *set*; this witness proves the running system actually
stays inside it: every retrace is a multi-hundred-millisecond XLA stall
on the serving path, so a steady-state window that compiles is a latency
bug even when the programs themselves are clean. Fifth sibling of the
lockdep/threadcheck/racecheck/xfercheck family:

- ``install()`` attaches a ``logging.Handler`` to jax's lowering logger
  and drops that logger to DEBUG, parsing the ``Compiling <name> with
  global shapes`` records into a per-name tally (the same records
  ``jax_log_compiles`` would print, captured at their quiet DEBUG level
  so the console stays clean). ``uninstall()`` restores the level.
- ``snapshot()`` / ``new_since(snap)`` bound a serving window: warm the
  entries, snapshot, run the storm, then assert ``new_since`` is empty
  (tests/test_scheduler_identity.py pins the scheduler's budget this
  way).
- counting is passive — nothing raises mid-serve; the *assertion* lives
  in the test that owns the window, so the witness adds no control flow
  to production code.

Counts key on jax's logged computation name (``jit(<fn>)`` style
fragments normalized to the bare function name), which is how retraces
of the same entry at a new abstract signature show up: same key, higher
count.
"""

import logging
import re
import threading

from distributed_faiss_tpu.utils import envutil

__all__ = [
    "enabled", "install", "uninstall", "snapshot", "new_since",
    "counts", "reset",
]


def enabled() -> bool:
    """DFT_COMPILECHECK master switch, read per call."""
    return envutil.env_flag("DFT_COMPILECHECK", False)


# _MU is a strict leaf guarding _COUNTS (nothing else acquired inside).
_MU = threading.Lock()
_COUNTS = {}  # computation name -> number of XLA compilations observed

# jax logs lowering via the pxla interpreter logger (DEBUG
# normally, WARNING under jax_log_compiles — both match):
#   "Compiling <name> with global shapes and types [...]."
_LOGGER_NAME = "jax._src.interpreters.pxla"
_COMPILE_RE = re.compile(r"^Compiling (\S+) with global shapes")


def _normalize(name: str) -> str:
    """Strip jit(...) wrappers/suffixes down to the launch name jax
    derived it from, so counts line up with registry qualnames."""
    m = re.match(r"^jit\((.+)\)$", name)
    if m:
        name = m.group(1)
    return name


class _CompileTally(logging.Handler):
    def emit(self, record):
        try:
            m = _COMPILE_RE.match(record.getMessage())
        except Exception:  # a hostile record must never kill serving
            return
        if not m:
            return
        name = _normalize(m.group(1))
        with _MU:
            _COUNTS[name] = _COUNTS.get(name, 0) + 1


_installed = []  # [(logger, handler, prev_level)]


def install() -> None:
    """Idempotently start tallying compilations (hooks jax's lowering
    logger at DEBUG, where the compile records flow without the console
    spam ``jax_log_compiles`` would add)."""
    if _installed:
        return
    logger = logging.getLogger(_LOGGER_NAME)
    handler = _CompileTally(level=logging.DEBUG)
    prev_level = logger.level
    logger.setLevel(logging.DEBUG)
    logger.addHandler(handler)
    _installed.append((logger, handler, prev_level))


def uninstall() -> None:
    """Undo install() (restores the logger level)."""
    while _installed:
        logger, handler, prev_level = _installed.pop()
        logger.removeHandler(handler)
        logger.setLevel(prev_level)


def counts() -> dict:
    """Snapshot of the per-name compilation tally."""
    with _MU:
        return dict(_COUNTS)


def snapshot() -> dict:
    """Alias of counts(), named for the warmup/storm protocol."""
    return counts()


def new_since(snap: dict) -> dict:
    """Names compiled (or re-compiled) since ``snap``: the steady-state
    assertion is ``new_since(snap) == {}`` after warmup."""
    now = counts()
    return {
        name: n - snap.get(name, 0)
        for name, n in now.items()
        if n > snap.get(name, 0)
    }


def reset() -> None:
    """Clear the tally (test isolation)."""
    with _MU:
        _COUNTS.clear()
