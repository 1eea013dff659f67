"""Server, around the launch: the slowest rank's mean ``server.decode`` (whole
frame in hand to dispatch) + ``server.finish_wait`` + ``server.pack`` +
``server.write``: a rank's share of a request outside its ``search`` span."""

from perfbench import ledger

STAGES = ("server.decode", "server.finish_wait", "server.pack", "server.write")


def read(obs):
    per_rank = ledger.summed_means(obs, [ledger.server(n) for n in STAGES])
    return None if per_rank is None else 1e3 * max(per_rank)
