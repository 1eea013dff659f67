"""Client fan-out: mean time from ``IndexClient.search`` submitting the per-rank
calls to a fan-out worker taking one (``client.client.fanout_wait``). The
client's pool is as wide as its ranks are many, so callers beyond that wait
here for a worker, not in the rank."""

from perfbench import ledger


def read(obs):
    return ledger.client_wide_mean_ms(obs, "client.fanout_wait")
