"""Engine launch-to-fetch, join: the host's share of ``engine.scan``, on the
slowest rank: seconds of ``engine.dispatch`` in the window (the scan's
dispatch call, the rerank's, the start of the copies to the host; batcher
thread) over its launches in the window."""

from perfbench import ledger


def read(obs):
    return ledger.per_launch_ms(obs, "engine.dispatch")
