"""List scan: the slowest rank's ``engine.scan`` seconds in the window (dispatch
of the scan program to the end of ``pallas_guarded``'s wait for it) over its
launches in the window."""

from perfbench import ledger


def read(obs):
    return ledger.per_launch_ms(obs, "engine.scan")
