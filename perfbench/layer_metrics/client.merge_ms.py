"""Client merge: mean time to merge the per-rank result blocks into one top-k
(``client.client.merge``); exists across ranks only."""

from perfbench import ledger


def read(obs):
    return ledger.client_wide_mean_ms(obs, "client.merge")
