"""Brute-force exact index (FAISS IndexFlatIP/IndexFlatL2 parity).

Reference consumes flat indexes as both a standalone index type (`flat`
builder, distributed_faiss/index.py:94) and the coarse quantizer for IVF
variants (get_quantizer, index.py:25-33).

The reference's `flat` builder lambda always builds IndexFlatIP, silently
ignoring cfg.metric (index.py:94 vs the unused metric-respecting
init_flat_index at index.py:89-90). We consciously fix that: FlatIndex honors
the configured metric (golden tests pin ordering for both).

Storage codecs: fp32 / fp16 / bf16 (cast fused into the scan matmul) and
sq8 (int8 affine, dequantize-on-the-fly) — the sq8 variant also serves as
the exact-search fallback substrate for `hnswsq` until the graph index lands.
"""

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from distributed_faiss_tpu.models import base
from distributed_faiss_tpu.ops import distance, sq
from distributed_faiss_tpu.utils import sanitize, tracing

_CODEC_DTYPES = {
    "f32": jnp.float32,
    "f16": jnp.float16,
    "bf16": jnp.bfloat16,
    "sq8": jnp.uint8,
}


@functools.partial(jax.jit, static_argnames=("k", "metric", "codec"))
def _flat_search_fused(q3, data, ntotal, k: int, metric: str, codec: str,
                       vmin=None, span=None, live=None):
    """Whole multi-block exact scan in ONE device launch (lax.map over
    (nblocks, block, d) stacked queries — launch-bound serving, see
    base.pick_query_block). ``live`` is the optional (cap,) tombstone mask
    (mutation subsystem), AND-ed with the ntotal padding mask in the scan."""

    def body(qb):
        kwargs = {} if codec != "sq8" else {"codec": "sq8", "vmin": vmin, "span": span}
        return distance.knn(qb, data, k, metric=metric, ntotal=ntotal,
                            live=live, **kwargs)

    return jax.lax.map(body, q3)


class FlatIndex(base.TpuIndex):
    def __init__(self, dim: int, metric: str = "l2", codec: str = "f32"):
        super().__init__(dim, metric)
        if codec not in _CODEC_DTYPES:
            raise ValueError(f"unknown flat codec {codec!r}")
        self.codec = codec
        self.store = base.DeviceVectorStore((dim,), _CODEC_DTYPES[codec])
        self.sq_params = None  # sq8 only: {"vmin", "span"} device arrays
        self._trained = codec != "sq8"

    @property
    def is_trained(self) -> bool:
        return self._trained

    @property
    def ntotal(self) -> int:
        return self.store.ntotal

    def train(self, x: np.ndarray) -> None:
        if self.codec == "sq8":
            self.sq_params = sq.sq8_train(np.asarray(x, np.float32))
        self._trained = True

    def add(self, x: np.ndarray) -> None:
        if not self.is_trained:
            raise RuntimeError("sq8 flat index must be trained before add")
        x = np.asarray(x, np.float32)
        if self.codec == "sq8":
            rows = np.asarray(sq.sq8_encode(x, self.sq_params["vmin"], self.sq_params["span"]))
        else:
            rows = x
        self.store.add(rows)

    def remove_rows(self, rows: np.ndarray) -> None:
        self.store.mask_rows(rows)

    def search(self, q: np.ndarray, k: int):
        return self.launch_search(q, k).collect()

    def launch_search(self, q: np.ndarray, k: int) -> base.SearchHandle:
        nq = q.shape[0]
        if self.ntotal == 0:
            empty_d = np.full((nq, k), np.inf if self.metric == "l2" else -np.inf, np.float32)
            return base.finished((empty_d, np.full((nq, k), -1, np.int64)))
        kwargs = {}
        if self.codec == "sq8":
            kwargs = {"codec": "sq8", "vmin": self.sq_params["vmin"], "span": self.sq_params["span"]}
        # the store as this launch finds it: the programs hold these
        # operands, whatever an add does to the store before the collect
        store = self.store
        data, live, cap = store.data, store.live, store.cap
        # explicit device_put: the serving path runs under DFT_XFERCHECK's
        # transfer guard, which forbids the implicit upload at jit dispatch
        ntotal = jax.device_put(np.int32(store.ntotal))

        def scanned(out, blocks):
            """The scan as dispatched, its wait put off to the collect
            (``base.Dispatched``): ``engine.scan`` then runs from the
            dispatch to the end of that wait, and the fetch after it times
            the fetch alone. ``engine.scan_rows`` counts the rows of
            the store the scan read, capacity padding included;
            ``engine.scan_prefilter`` the scans whose per-chunk top-k chose
            its segments by their maxima (the rule the traced code
            branches on, asked of the same k and chunk)."""

            def settled(out):
                tracing.count("engine.scan_rows", float(blocks * cap))
                if distance.topk_prefilters(k, min(distance.SCAN_CHUNK, cap)):
                    tracing.count("engine.scan_prefilter")
                return out

            return base.Dispatched(out, settled)

        def scan_block(block):
            return scanned(distance.knn(
                block, data, k, metric=self.metric, ntotal=ntotal,
                live=live, **kwargs), 1)

        def scan_fused(q3):
            # multi-block batch: one launch for all blocks (lax.map)
            return scanned(sanitize.maybe_checked(
                _flat_search_fused, q3, data, ntotal, k=k,
                metric=self.metric, codec=self.codec, vmin=kwargs.get("vmin"),
                span=kwargs.get("span"), live=live), q3.shape[0])

        # per-query transient is the (nq, chunk) score block of the running
        # scan — launch-bound serving wants the largest block that keeps it
        # within budget (see base.pick_query_block)
        return base.launch_blocked_search(
            q, k, self.metric, scan_block,
            block=base.pick_query_block(distance.SCAN_CHUNK * 4),
            fused_fn=scan_fused)

    def reconstruct_batch(self, ids: np.ndarray) -> np.ndarray:
        rows = self.store.rows(np.asarray(ids))
        if self.codec == "sq8":
            # graftlint: ok(host-sync): reconstruct returns host rows by contract
            return np.asarray(sq.sq8_decode(jnp.asarray(rows), self.sq_params["vmin"], self.sq_params["span"]))
        return np.asarray(rows, np.float32)

    def state_dict(self) -> Dict[str, np.ndarray]:
        state = {
            "kind": "flat",
            "dim": self.dim,
            "metric": self.metric,
            "codec": self.codec,
            "trained": self._trained,
            "ntotal": self.store.ntotal,
            "data": self.store.all_rows(),
        }
        if self.sq_params is not None:
            state["sq_vmin"] = np.asarray(self.sq_params["vmin"])
            state["sq_span"] = np.asarray(self.sq_params["span"])
        return state

    @classmethod
    def from_state_dict(cls, state) -> "FlatIndex":
        idx = cls(int(state["dim"]), str(state["metric"]), str(state["codec"]))
        if "sq_vmin" in state:
            idx.sq_params = {
                "vmin": jnp.asarray(state["sq_vmin"]),
                "span": jnp.asarray(state["sq_span"]),
            }
        idx._trained = bool(state["trained"])
        data = state["data"]
        if data.shape[0]:
            idx.store.add(data)
        return idx
