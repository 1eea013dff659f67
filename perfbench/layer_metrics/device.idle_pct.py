"""Device: share of the window in which no operation ran on the chip — one
minus the union of the trace's operation intervals over the window — averaged
over the chips used."""


def read(obs):
    traces = obs.get("traces")
    if not traces:
        return None
    busy = sum(t["busy_s"] for t in traces) / len(traces)
    return 100.0 * (1.0 - busy / obs["window_s"])
