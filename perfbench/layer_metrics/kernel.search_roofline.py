"""Models and kernels: the least time one search launch could take on this
device (``search_bytes.roofline_seconds``, from shapes) over the device's
busy time per launch from the trace. Launches are the engine's
``device_search_s`` count over the window; the trace covers the whole
window, so every launch lies inside it. Averaged over the ranks."""

from perfbench import search_bytes, stats


def read(obs):
    launches = stats.per_rank(obs, ("engine", obs["index_id"], "device_search_s"),
                              stats.window_count)
    rows = stats.per_rank(obs, ("scheduler", "queues", "batch_rows"))
    traces = obs.get("traces")
    if not launches or rows is None or not traces or min(launches) < 1:
        return None
    config = obs["config"]
    shares = []
    for n, nq, trace in zip(launches, rows, traces):
        least_s, bound = search_bytes.roofline_seconds(
            config["index"], config["rows"] / config["ranks"], config["k"], nq,
            obs["devices"][0]["device_kind"])
        busy_per_launch = trace["busy_s"] / n
        print(f"kernel.search_roofline: {n} launches of {nq:.1f} rows, "
              f"{busy_per_launch * 1e3:.3f} ms busy a launch, least "
              f"{least_s * 1e6:.1f} us ({bound}-bound)", flush=True)
        shares.append(100.0 * least_s / busy_per_launch)
    return sum(shares) / len(shares)
