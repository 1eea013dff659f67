"""Set-up, ingest path: rows indexed per second of the build, from the first
add call to every row indexed, training seconds taken out (a rank buffers
while it trains). Host clock, in this process."""


def read(obs):
    return obs["setup"]["add_rows_s"]
