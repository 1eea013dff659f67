"""Models and kernels, the exact scan: share of the rows the window's
launches scanned that are capacity padding, in %: 1 - stored rows x
launches / ``engine.scan_rows`` (the rows of its store each scan read,
capacity included), all ranks together. A store of 2^21 rows holding
1,751,277 reads 16.5%. A program without the counter has no such row and
reads nothing."""

from perfbench import ledger, stats


def read(obs):
    scanned = stats.per_rank(obs, ledger.engine(obs, "engine.scan_rows"),
                             ledger.window_total)
    launches = stats.per_rank(obs, ledger.engine(obs, "device_search_s"),
                              stats.window_count)
    if scanned is None or launches is None or not sum(scanned):
        return None
    stored = obs["config"]["rows"] / obs["config"]["ranks"]
    return 100.0 * (1.0 - stored * sum(launches) / sum(scanned))
