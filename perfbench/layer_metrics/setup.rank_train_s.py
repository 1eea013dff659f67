"""Set-up, training, timed where it happens: the rank's own ``engine.train``
seconds when the window starts, slowest rank."""

from perfbench import ledger


def read(obs):
    train = ledger.at_window_start(obs, "engine.train")
    return None if train is None else max(train)
