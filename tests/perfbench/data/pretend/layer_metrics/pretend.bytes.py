"""The tests' pretend per-layer metric: what a later PR's reader looks like.
It reads one number the observation may hold and nothing where it does not."""


def read(obs):
    return obs.get("wire_bytes")
