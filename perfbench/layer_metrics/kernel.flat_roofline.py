"""Models and kernels, the exact scan: the least time one launch could take
on this device (``flat_bytes.roofline_seconds``, from shapes: the stored
rows read once, or the product's operations, whichever is longer) over the
device's busy time a launch from the trace. Launches are the engine's
``device_search_s`` count over the window, rows a launch the scheduler's
``batch_rows``, as ``kernel.search_roofline`` takes them. Averaged over the
ranks."""

from perfbench import flat_bytes, ledger, stats


def read(obs):
    launches = stats.per_rank(obs, ledger.engine(obs, "device_search_s"),
                              stats.window_count)
    rows = stats.per_rank(obs, ledger.sched("batch_rows"))
    traces = obs.get("traces")
    if not launches or rows is None or not traces or min(launches) < 1:
        return None
    config = obs["config"]
    shares = []
    for n, nq, trace in zip(launches, rows, traces):
        least_s, bound = flat_bytes.roofline_seconds(
            config["index"]["dim"], config["rows"] / config["ranks"], config["k"],
            nq, obs["devices"][0]["device_kind"])
        busy_per_launch = trace["busy_s"] / n
        print(f"kernel.flat_roofline: {n} launches of {nq:.1f} rows, "
              f"{busy_per_launch * 1e3:.3f} ms busy a launch, least "
              f"{least_s * 1e3:.3f} ms ({bound}-bound)", flush=True)
        shares.append(100.0 * least_s / busy_per_launch)
    return sum(shares) / len(shares)
