"""Linear pre-transform wrapper (FAISS ``IndexPreTransform`` analog).

The reference reaches these through ``faiss.index_factory`` specs like
``"OPQ16,IVF4096,PQ16"`` or ``"PCA256,IVF1024,Flat"``
(distributed_faiss/index.py:396 accepts the whole FAISS grammar). The
wrapper applies ``(x - mean) @ matrix`` before delegating every index
operation to the inner index, and un-rotates on reconstruction.

Transforms:
- OPQ (``opq_m`` set): orthogonal rotation trained by ops/opq.py to
  minimize the inner PQ's reconstruction error; fit lazily on the first
  ``train`` call.
- PCA (``pca`` set): mean-centered projection onto the top d_out principal
  components; fit on the first ``train`` call.
- fixed: a caller-supplied matrix (already fit).
"""

from typing import Dict, Optional

import numpy as np

from distributed_faiss_tpu.models import base


class PreTransformIndex(base.TpuIndex):
    def __init__(self, inner: base.TpuIndex, d_in: int,
                 opq_m: Optional[int] = None, pca: bool = False,
                 matrix: Optional[np.ndarray] = None,
                 mean: Optional[np.ndarray] = None,
                 opq_iters: int = 8, pq_iters: int = 6):
        super().__init__(d_in, inner.metric)
        if (opq_m is not None) + bool(pca) + (matrix is not None) != 1:
            raise ValueError("exactly one of opq_m / pca / matrix must be given")
        self.inner = inner
        self.d_out = inner.dim
        self.opq_m = opq_m
        self.pca = bool(pca)
        self.opq_iters = opq_iters
        self.pq_iters = pq_iters
        self.matrix = None if matrix is None else np.asarray(matrix, np.float32)
        self.mean = None if mean is None else np.asarray(mean, np.float32)
        if self.matrix is not None and self.matrix.shape != (d_in, self.d_out):
            raise ValueError(
                f"transform matrix shape {self.matrix.shape} != ({d_in}, {self.d_out})"
            )

    # --- transform --------------------------------------------------------

    def _fit(self, x: np.ndarray) -> None:
        if self.opq_m is not None:
            from distributed_faiss_tpu.ops import opq

            r, _ = opq.opq_train(x, self.opq_m, d_out=self.d_out,
                                 opq_iters=self.opq_iters, pq_iters=self.pq_iters)
            self.matrix = np.asarray(r)
        else:  # pca
            if x.shape[0] < self.d_out:
                # vt has min(n, d_in) rows; fewer would silently truncate
                # the basis and desync dims with the inner index
                raise RuntimeError(
                    f"PCA to {self.d_out} dims needs >= {self.d_out} training "
                    f"rows, got {x.shape[0]}"
                )
            self.mean = x.mean(0)
            xc = x - self.mean
            # right singular vectors of the centered data = principal axes
            _, _, vt = np.linalg.svd(xc, full_matrices=False)
            self.matrix = np.ascontiguousarray(vt[: self.d_out].T)

    def apply(self, x: np.ndarray) -> np.ndarray:
        # plain numpy: the (nq, d)x(d, d_out) matmul is microseconds on host,
        # while routing through jax would cost two host<->device transfers
        # per call before the inner index re-uploads the result anyway
        if self.matrix is None:
            raise RuntimeError("transform is not fit; call train() first")
        x = np.asarray(x, np.float32)
        if self.mean is not None:
            x = x - self.mean
        return x @ self.matrix

    def apply_inverse(self, y: np.ndarray) -> np.ndarray:
        """Orthonormal-column pseudo-inverse: y @ matrix.T (+ mean)."""
        x = np.asarray(y, np.float32) @ self.matrix.T
        if self.mean is not None:
            x = x + self.mean
        return x

    # --- lifecycle (delegate) --------------------------------------------

    @property
    def is_trained(self) -> bool:
        return self.matrix is not None and self.inner.is_trained

    @property
    def ntotal(self) -> int:
        return self.inner.ntotal

    def train(self, x: np.ndarray) -> None:
        x = np.asarray(x, np.float32)
        if self.matrix is None:
            self._fit(x)
        self.inner.train(self.apply(x))

    def add(self, x: np.ndarray) -> None:
        self.inner.add(self.apply(x))

    def search(self, q: np.ndarray, k: int):
        return self.inner.search(self.apply(q), k)

    def launch_search(self, q: np.ndarray, k: int) -> base.SearchHandle:
        # the transform is the host's; the launch is whatever the inner
        # index offers
        return self.inner.launch_search(self.apply(q), k)

    def supports_remove_rows(self) -> bool:
        return self.inner.supports_remove_rows()

    def remove_rows(self, rows: np.ndarray) -> None:
        # the transform maps vectors, not row slots: positional ids pass
        # through unchanged, so the tombstone mask delegates untouched
        self.inner.remove_rows(rows)

    def reconstruct_batch(self, ids: np.ndarray) -> np.ndarray:
        return self.apply_inverse(self.inner.reconstruct_batch(ids))

    def set_nprobe(self, nprobe: int) -> None:
        self.inner.set_nprobe(nprobe)

    def get_centroids(self):
        return self.inner.get_centroids()

    # --- persistence ------------------------------------------------------

    def state_dict(self) -> Dict[str, np.ndarray]:
        state = {
            "kind": "pretransform",
            "dim": self.dim,
            "metric": self.metric,
            "opq_m": -1 if self.opq_m is None else int(self.opq_m),
            "pca": self.pca,
            "fit": self.matrix is not None,
        }
        if self.matrix is not None:
            state["matrix"] = np.asarray(self.matrix)
        if self.mean is not None:
            state["mean"] = np.asarray(self.mean)
        for k, v in self.inner.state_dict().items():
            state[f"inner.{k}"] = v
        return state

    @classmethod
    def from_state_dict(cls, state) -> "PreTransformIndex":
        from distributed_faiss_tpu.models.factory import index_from_state_dict

        inner_state = {
            k[len("inner."):]: v for k, v in state.items() if k.startswith("inner.")
        }
        inner = index_from_state_dict(inner_state)
        opq_m = int(state["opq_m"])
        fit = bool(state["fit"])
        if fit:
            # a fit matrix enters the ctor as 'fixed' (satisfying its
            # one-of check); the original fit-mode flags are restored below
            idx = cls(inner, int(state["dim"]),
                      matrix=np.asarray(state["matrix"]),
                      mean=np.asarray(state["mean"]) if "mean" in state else None)
        else:
            idx = cls(inner, int(state["dim"]),
                      opq_m=None if opq_m < 0 else opq_m,
                      pca=bool(state["pca"]))
        idx.opq_m = None if opq_m < 0 else opq_m
        idx.pca = bool(state["pca"])
        return idx
