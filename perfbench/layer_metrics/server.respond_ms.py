"""Server, after the launch: the slowest rank's mean ``server.finish_wait``
(batcher's callback to an RPC worker taking it) + ``server.pack`` (response
encode) + ``server.write`` (write-lock wait plus send)."""

from perfbench import ledger

STAGES = ("server.finish_wait", "server.pack", "server.write")


def read(obs):
    per_rank = ledger.summed_means(obs, [ledger.server(n) for n in STAGES])
    return None if per_rank is None else 1e3 * max(per_rank)
