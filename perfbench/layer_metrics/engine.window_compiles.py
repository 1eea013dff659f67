"""XLA compiles inside the timed window (``xla.compile`` count after less count
before), summed over ranks. Warm-up is meant to leave none: 0 is a value."""

from perfbench import ledger


def read(obs):
    return ledger.compiles(obs)
