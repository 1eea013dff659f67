"""Engine and scheduler, host work in series with the device: share of the
window the batcher thread spent in ``sched.assemble`` + ``engine.lock_wait`` +
``engine.feed`` + ``engine.join`` + ``sched.split``, mean over ranks. The chip
idles meanwhile."""

from perfbench import ledger


def read(obs):
    return ledger.share_of_window_pct(obs, [
        ledger.sched("sched.assemble"), ledger.engine(obs, "engine.lock_wait"),
        ledger.engine(obs, "engine.feed"), ledger.engine(obs, "engine.join"),
        ledger.sched("sched.split")])
