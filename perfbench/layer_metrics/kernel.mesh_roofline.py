"""Models and kernels, a mesh rank: the least time one search launch could
take on ONE chip of the rank's mesh (``mesh_bytes.roofline_seconds``, from
shapes: the chip's share of the probed lists and refine rows, the replicated
table and queries once) over the device's busy time a launch from the trace,
which ``trace_reduce.reduce`` has already averaged over the rank's chips.
The chips are the devices the rank reported; launches are the engine's
``device_search_s`` count over the window, rows a launch the scheduler's
``batch_rows``, as ``kernel.search_roofline`` takes them (which divides the
whole index's work by one chip's peak, and so is not read in such a cell).

``mesh_devices: 0`` gives the rank every chip it sees, and ``perfbench.run``
refuses only a machine with fewer than the cell's ``chips``: a rank that
reports another number of TPU chips than the cell asks for (a larger host)
is a different deployment, and an error here, where the count is used. A
CPU rehearsal, whose device count is the test's, is let through."""

from perfbench import ledger, loader, mesh_bytes, stats


def read(obs):
    launches = stats.per_rank(obs, ledger.engine(obs, "device_search_s"),
                              stats.window_count)
    rows = stats.per_rank(obs, ledger.sched("batch_rows"))
    traces = obs.get("traces")
    if not launches or rows is None or not traces or min(launches) < 1:
        return None
    config = obs["config"]
    chips = loader.Cell(obs["cell"]).chips
    shares = []
    for n, nq, trace, device in zip(launches, rows, traces, obs["devices"]):
        if device["platform"] == "tpu" and device["count"] != chips:
            raise ValueError(f"{obs['cell']} asks for {chips} chips and its rank "
                             f"made a mesh of {device['count']}")
        least_s, bound = mesh_bytes.roofline_seconds(
            config["index"], config["rows"] / config["ranks"], config["k"], nq,
            device["count"], device["device_kind"])
        busy_per_launch = trace["busy_s"] / n
        print(f"kernel.mesh_roofline: {n} launches of {nq:.1f} rows on "
              f"{device['count']} chips, {busy_per_launch * 1e3:.3f} ms busy a "
              f"launch a chip, least {least_s * 1e6:.1f} us ({bound}-bound)",
              flush=True)
        shares.append(100.0 * least_s / busy_per_launch)
    return sum(shares) / len(shares)
