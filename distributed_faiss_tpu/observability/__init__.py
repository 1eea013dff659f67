"""Distributed observability: request tracing, metrics export, ops CLI.

Three surfaces over the per-rank counters the serving stack already
keeps (utils/tracing.py ``LatencyStats``, every subsystem's
``get_perf_stats`` block):

- ``spans``  — cross-process request tracing: sampled requests
  (``DFT_TRACE_SAMPLE``) mint a ``trace_id`` that rides the CALL frame's
  optional meta element beside ``req_id``/``deadline_s``; every serving
  stage records a span into its process's bounded ``SpanBuffer``, pulled
  over the ordinary ``get_trace_spans`` RPC op and merged client-side
  into one causal timeline.
- ``export`` — Prometheus text-exposition rendering of the perf-stats
  tree (histograms as cumulative ``_bucket`` series over the real
  log-spaced bounds) behind an optional per-rank HTTP listener
  (``DFT_METRICS_PORT``).
- ``dfstat`` — the live cluster ops CLI:
  ``python -m distributed_faiss_tpu.observability.dfstat``.
"""

from distributed_faiss_tpu.observability.export import (  # noqa: F401
    MetricsExporter,
    render_prometheus,
)
from distributed_faiss_tpu.observability.spans import (  # noqa: F401
    SpanBuffer,
    local_buffer,
    maybe_sample,
    mint_trace_id,
    sample_rate,
)
