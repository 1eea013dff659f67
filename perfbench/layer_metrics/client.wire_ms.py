"""The wire both ways: the slowest rank's stub's mean round trip (end of the send
to demux completion, ``rpc.client.client.round_trip.search``) less that rank's
own mean ``server.request`` (whole frame in hand to last byte written)."""

from perfbench import ledger


def read(obs):
    return ledger.wire_ms(obs)
