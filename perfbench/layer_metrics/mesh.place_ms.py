"""Engine launch-to-fetch, join, a mesh rank: the host's time to place a
launch's replicated operands on the mesh (the query block, and the centroid
table and codebooks, which stay placed after the first launch): seconds of
``engine.mesh_place`` in the window over its launches, on the slowest rank.
Work a local index does not have. A program without the stage reads
nothing."""

from perfbench import ledger


def read(obs):
    return ledger.per_launch_ms(obs, "engine.mesh_place")
