"""Arithmetic on the chip's timeline as the scheduler books it (PR 42), for
the per-layer readers that read it (beside ``ledger.py``).

Once a collected window a rank's completer books, under
``scheduler.queues``: ``sched.chip_busy`` (the window's own span on the chip,
as the host sees it), ``sched.chip_queue`` (its programs' wait behind the
window ahead) and, where the chip stood idle before the window,
``sched.chip_idle.empty`` / ``.window_wait`` / ``.host`` by what the batcher
thread was in meanwhile (``docs/OPERATIONS.md``, Stage ledger). Busy plus
idle is the timeline; a rank serves all five rows from its first window on,
a cause that took no gap at zero. A program without the timeline (before PR
42) has none of them: every function here then returns None.
"""

from perfbench import ledger, stats

CAUSES = ("empty", "window_wait", "host")


def busy_ms(obs):
    """Window mean of a window's own span on the chip, slowest rank."""
    busy = stats.per_rank(obs, ledger.sched("sched.chip_busy"))
    return None if busy is None else 1e3 * max(busy)


def idle_pct(obs, causes=CAUSES):
    """Seconds the chip stood idle for ``causes`` in the window over the
    window, in %, mean over the ranks; None where a rank collected no
    window in it (it booked no gap: there is no share to give)."""
    windows = stats.per_rank(obs, ledger.sched("sched.chip_busy"),
                             stats.window_count)
    if windows is None or not all(windows):
        return None
    return ledger.share_of_window_pct(
        obs, [ledger.sched(f"sched.chip_idle.{cause}") for cause in causes])
