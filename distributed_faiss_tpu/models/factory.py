"""Builder registry + factory-string parser (the plugin boundary).

Parity with the reference's ``faiss_special_index_factories`` dict
(distributed_faiss/index.py:93-100) and its ``faiss.index_factory`` path with
``{centroids}`` templating (index.py:380-401). BASELINE.json names this
boundary as the north star: ``ivf_tpu`` is the mesh-sharded builder slot.

Builders (same names as the reference):
- flat      — exact search. The reference's lambda always builds IndexFlatIP,
              ignoring cfg.metric (index.py:94); we consciously fix that and
              honor the metric.
- ivf_simple— IVF + raw fp32 lists (IndexIVFFlat, index.py:36-40)
- knnlm     — IVF-PQ, m=cfg.extra['code_size'] (default 64), 8-bit
              (IndexIVFPQ, index.py:43-48)
- ivfsq     — IVF + fp16 lists (IndexIVFScalarQuantizer QT_fp16,
              index.py:63-68)
- hnswsq    — reference: IndexHNSWSQ over SQ8 codes, L2 only
              (index.py:51-60). Graph traversal is TPU-hostile; until the
              native HNSW lands this builds the exact sq8 flat index (same
              storage codec, exact instead of approximate — recall >= HNSW,
              throughput lower on huge corpora). Documented substitute.
- ivf_tpu   — the TPU analog of the reference's ivf_gpu (index.py:71-86):
              IVF with clustering and scan on the accelerator; gains
              multi-chip mesh sharding via parallel/mesh.py.
"""

import logging
import re
from typing import Optional

from distributed_faiss_tpu.models.flat import FlatIndex
from distributed_faiss_tpu.models.ivf import IVFFlatIndex, IVFPQIndex
from distributed_faiss_tpu.utils.config import IndexCfg


def _centroids(cfg: IndexCfg) -> int:
    c = int(cfg.centroids)
    if c <= 0:
        raise RuntimeError(
            "cfg.centroids must be set (or inferred by the engine) before building an IVF index"
        )
    return c


def _kmeans_iters(cfg: IndexCfg) -> int:
    return int(cfg.extra.get("kmeans_iters", 10))


def _mesh(cfg: IndexCfg):
    """Resolve the optional device mesh: cfg.extra['mesh_devices'] wins
    (an explicit 0 pins ALL local devices, overriding the host env), else
    None — the index constructors then call make_mesh(None), which applies
    the per-host DFT_MESH_DEVICES default (lazy import: only mesh-backed
    builders pay for jax.sharding)."""
    from distributed_faiss_tpu.parallel.mesh import make_mesh

    n_dev = cfg.extra.get("mesh_devices")
    if n_dev is None:
        return None  # make_mesh(None) downstream applies the env default
    return make_mesh(int(n_dev))


def _probe_routing(cfg: IndexCfg) -> bool:
    """Sharded-IVF serving mode: cfg.extra['probe_routing'] wins, else the
    per-host DFT_MESH_MODE default ('routed' -> True)."""
    from distributed_faiss_tpu.utils.config import MeshCfg

    pr = cfg.extra.get("probe_routing")
    if pr is None:
        return MeshCfg.from_env().mode == "routed"
    return bool(pr)


def _build_flat(cfg: IndexCfg):
    if cfg.extra.get("mesh_shards"):
        # exact search with the corpus sharded across the chip mesh
        from distributed_faiss_tpu.parallel.mesh import ShardedFlatIndex

        return ShardedFlatIndex(cfg.dim, cfg.get_metric(), mesh=_mesh(cfg))
    if cfg.extra.get("mesh_devices") is not None:  # 0 is an explicit pin too
        logging.getLogger().warning(
            "mesh_devices is set but mesh_shards is not: building a "
            "single-device flat index (set mesh_shards=True to shard)"
        )
    return FlatIndex(cfg.dim, cfg.get_metric())


def _flat_scan_knobs(cfg: IndexCfg) -> dict:
    """IVF-Flat/SQ8 scan knobs riding in cfg.extra (engine config plumbing):
    - pallas_flat: fused VMEM list-scan kernel (ops/flat_pallas.py),
      oracle-checked on first use with clean XLA fallback;
    - scan_bf16: bf16 MXU scan, legal only with refine_k_factor > 0 (the
      constructor enforces it) so the shortlist is rescored exactly;
    - refine_k_factor: exact fp16 rerank of the top k*factor.
    """
    return dict(
        use_pallas=bool(cfg.extra.get("pallas_flat", False)),
        scan_bf16=bool(cfg.extra.get("scan_bf16", False)),
        refine_k_factor=int(cfg.extra.get("refine_k_factor", 0)),
    )


def _build_ivf_simple(cfg: IndexCfg) -> IVFFlatIndex:
    return IVFFlatIndex(cfg.dim, _centroids(cfg), cfg.get_metric(), "f32",
                        kmeans_iters=_kmeans_iters(cfg), **_flat_scan_knobs(cfg))


def _build_knnlm(cfg: IndexCfg):
    m = int(cfg.extra.get("code_size", 64))
    nbits = int(cfg.extra.get("nbits", 8))
    if cfg.extra.get("opq"):
        # OPQ rotation in front of the IVF-PQ (FAISS "OPQ<m>,IVF,PQ<m>"):
        # train fits the rotation on the train sample, then the inner index
        # trains on rotated data. Works for sharded and unsharded inners
        # (the wrapper delegates everything, incl. state_dict round-trip).
        from distributed_faiss_tpu.models.pretransform import PreTransformIndex

        # build the inner from the same cfg minus the opq flag (the flag
        # would otherwise recurse); restore the caller's extra afterwards
        orig_extra = cfg.extra
        cfg.extra = dict(orig_extra, opq=False)
        try:
            inner = _build_knnlm(cfg)
        finally:
            cfg.extra = orig_extra
        return PreTransformIndex(inner, cfg.dim, opq_m=m,
                                 opq_iters=int(cfg.extra.get("opq_iters", 8)))
    if cfg.extra.get("shard_lists"):
        from distributed_faiss_tpu.parallel.mesh import ShardedIVFPQIndex

        return ShardedIVFPQIndex(
            cfg.dim, _centroids(cfg), m=m, nbits=nbits, metric=cfg.get_metric(),
            mesh=_mesh(cfg), kmeans_iters=_kmeans_iters(cfg),
            probe_routing=_probe_routing(cfg),
            # absent: the index chooses its ADC kernel; set: forced
            use_pallas=cfg.extra.get("pallas_adc"),
            refine_k_factor=int(cfg.extra.get("refine_k_factor", 0)),
        )
    if _probe_routing(cfg):
        logging.getLogger().warning(
            "probe_routing (cfg.extra or DFT_MESH_MODE=routed) requires "
            "shard_lists=True on the knnlm builder; ignored — building "
            "the single-device scan"
        )
    return IVFPQIndex(cfg.dim, _centroids(cfg), m=m, nbits=nbits, metric=cfg.get_metric(),
                      kmeans_iters=_kmeans_iters(cfg),
                      # absent: the index chooses its ADC kernel; set: forced
                      use_pallas=cfg.extra.get("pallas_adc"),
                      refine_k_factor=int(cfg.extra.get("refine_k_factor", 0)))


def _build_ivfsq(cfg: IndexCfg) -> IVFFlatIndex:
    return IVFFlatIndex(cfg.dim, _centroids(cfg), cfg.get_metric(), "f16",
                        kmeans_iters=_kmeans_iters(cfg), **_flat_scan_knobs(cfg))


def _build_hnswsq(cfg: IndexCfg):
    # reference asserts L2 (index.py:52)
    assert cfg.metric == "l2", "hnswsq only supports l2 metric"
    from distributed_faiss_tpu.models import hnsw

    if hnsw.native_available():
        # defaults mirror the reference's hnswsq builder (index.py:55-58):
        # store_n=128 graph degree, efConstruction=100. refine_k_factor=8
        # (fp16 exact rescore of the SQ8 shortlist) is ON by default: the
        # bare SQ8 codec plateaus ~0.90 recall (shared with the reference's
        # IndexHNSWSQ) and the rerank is what clears the 0.95 bar — set
        # extra={'refine_k_factor': 0} for reference-exact behavior
        return hnsw.HNSWSQIndex(
            cfg.dim, "l2",
            M=int(cfg.extra.get("store_n", 128)),
            ef_construction=int(cfg.extra.get("ef_construction", 100)),
            refine_k_factor=int(cfg.extra.get("refine_k_factor", 8)),
        )
    # no C++ toolchain: exact sq8 scan keeps the builder slot working
    return FlatIndex(cfg.dim, "l2", codec="sq8")


def _build_ivf_tpu(cfg: IndexCfg):
    from distributed_faiss_tpu.parallel.mesh import IvfTpuIndex, ShardedIVFFlatIndex

    mesh = _mesh(cfg)
    if cfg.extra.get("shard_lists"):
        # full multi-chip path: inverted lists partitioned across the mesh.
        # scan_bf16 + refine_k_factor are wired (sharded raw-row refine,
        # pre-merge exact rescore — parallel/mesh.py). The fused pallas
        # flat-scan kernel remains single-chip-only: its scalar-prefetched
        # gather indexes the global (nlist, cap) layout, which shard_map's
        # per-chip list blocks cannot express — a documented limitation
        # (docs/OPERATIONS.md#multi-chip-serving), logged only when the
        # knob is explicitly set; the default config builds silently.
        if cfg.extra.get("pallas_flat"):
            logging.getLogger().warning(
                "pallas_flat is a documented single-chip limitation for the "
                "sharded (shard_lists=True) flat scan; serving the masked/"
                "routed XLA scan (docs/OPERATIONS.md#multi-chip-serving)")
        return ShardedIVFFlatIndex(cfg.dim, _centroids(cfg), cfg.get_metric(),
                                   mesh=mesh, kmeans_iters=_kmeans_iters(cfg),
                                   probe_routing=_probe_routing(cfg),
                                   refine_k_factor=int(
                                       cfg.extra.get("refine_k_factor", 0)),
                                   scan_bf16=bool(
                                       cfg.extra.get("scan_bf16", False)))
    if _probe_routing(cfg):
        logging.getLogger().warning(
            "probe_routing (cfg.extra or DFT_MESH_MODE=routed) requires "
            "shard_lists=True on the ivf_tpu builder; ignored — building "
            "the single-device scan"
        )
    return IvfTpuIndex(cfg.dim, _centroids(cfg), cfg.get_metric(), "f32",
                       mesh=mesh, kmeans_iters=_kmeans_iters(cfg),
                       **_flat_scan_knobs(cfg))


INDEX_BUILDERS = {
    "flat": _build_flat,
    "ivf_simple": _build_ivf_simple,
    "knnlm": _build_knnlm,
    "ivfsq": _build_ivfsq,
    "hnswsq": _build_hnswsq,
    "ivf_tpu": _build_ivf_tpu,
}


_OPQ_RE = re.compile(r"^OPQ(\d+)(?:_(\d+))?$")
_PCA_RE = re.compile(r"^PCAR?(\d+)$")
_HNSW_RE = re.compile(r"^HNSW(\d+)$")


def parse_factory(cfg: IndexCfg):
    """Build from a FAISS-style factory spec.

    Grammar (the subset of faiss.index_factory the reference can reach via
    its cfg files — distributed_faiss/index.py:396 plus
    scripts/idx_cfg.json's "IVF{centroids},SQ8"):

      [OPQ<m>[_<dout>],|PCA<dout>,|PCAR<dout>,] <core> [,RFlat|,Refine(Flat)]
      core := Flat | SQ8 | SQfp16 | PQ<m>[x8]
            | IVF<n>,(Flat|SQ8|SQfp16|PQ<m>[x8])
            | HNSW<M>[,Flat|,SQ8]

    Notes vs FAISS: PCAR's trailing random rotation is folded into the PCA
    basis (principal axes are already a rotation; the extra random rotation
    only matters for balancing PQ subspaces, which OPQ does better); HNSW
    always stores SQ8 codes (the native graph's storage codec — "HNSW32"
    and "HNSW32,Flat" get SQ8 storage, documented divergence); RFlat keeps
    fp16 rows and reranks k*refine_k_factor (cfg.extra, default 8 — FAISS's
    k_factor default of 1 barely moves recall). RFlat under a DIM-REDUCING
    pre-transform ("OPQ8_32,...,RFlat" / "PCA32,...,RFlat") reranks in the
    reduced space — it cannot recover projection error the way FAISS's
    IndexRefineFlat (full-dim f32 rows) can; a warning is logged. Under a
    full-dim rotation the rerank is equivalent (rotations preserve l2/ip).
    """
    spec = cfg.faiss_factory
    if "{centroids}" in spec:
        spec = spec.format(centroids=int(cfg.centroids))
    parts = [p.strip() for p in spec.split(",")]
    metric = cfg.get_metric()
    iters = _kmeans_iters(cfg)

    def parse_pq_m(token: str) -> int:
        body = token[2:]
        if "x" in body:
            body, bits = body.split("x")
            if int(bits) != 8:
                raise RuntimeError(f"only 8-bit PQ supported, got {token}")
        return int(body)

    # ---- optional refine suffix ----------------------------------------
    refine_k = 0
    if parts and parts[-1] in ("RFlat", "Refine(Flat)"):
        refine_k = int(cfg.extra.get("refine_k_factor", 8))
        parts = parts[:-1]

    # ---- optional pre-transform prefix ---------------------------------
    pre = None  # (kind, arg, d_out)
    if parts:
        m_opq = _OPQ_RE.match(parts[0])
        m_pca = _PCA_RE.match(parts[0])
        if m_opq:
            d_out = int(m_opq.group(2)) if m_opq.group(2) else cfg.dim
            pre = ("opq", int(m_opq.group(1)), d_out)
            parts = parts[1:]
        elif m_pca:
            pre = ("pca", None, int(m_pca.group(1)))
            parts = parts[1:]
        if pre is not None and pre[2] > cfg.dim:
            raise RuntimeError(
                f"pre-transform output dim {pre[2]} > input dim {cfg.dim} in {spec!r}"
            )
    dim = pre[2] if pre else cfg.dim

    def build_core() -> "FlatIndex":
        if len(parts) == 1:
            p = parts[0]
            if p == "Flat":
                return FlatIndex(dim, metric)
            if p == "SQ8":
                return FlatIndex(dim, metric, codec="sq8")
            if p == "SQfp16":
                return FlatIndex(dim, metric, codec="f16")
            if p.startswith("PQ"):
                # flat PQ == IVF-PQ with a single list, always probed
                idx = IVFPQIndex(dim, 1, m=parse_pq_m(p), metric=metric,
                                 refine_k_factor=refine_k)
                idx.set_nprobe(1)
                return idx
            if _HNSW_RE.match(p):
                return _build_hnsw_spec(int(_HNSW_RE.match(p).group(1)), dim, cfg)
        if len(parts) == 2 and _HNSW_RE.match(parts[0]):
            if parts[1] not in ("Flat", "SQ8"):
                raise RuntimeError(f"unsupported HNSW storage {parts[1]!r} in {spec!r}")
            return _build_hnsw_spec(int(_HNSW_RE.match(parts[0]).group(1)), dim, cfg)
        if len(parts) == 2 and parts[0].startswith("IVF"):
            nlist = int(parts[0][3:])
            tail = parts[1]
            # pallas_flat / scan_bf16 ride cfg.extra (the one extraction in
            # _flat_scan_knobs); refine comes from the RFlat suffix so the
            # grammar stays FAISS-shaped
            knobs = _flat_scan_knobs(cfg)
            knobs.pop("refine_k_factor")
            if tail == "Flat":
                return IVFFlatIndex(dim, nlist, metric, "f32", kmeans_iters=iters,
                                    refine_k_factor=refine_k, **knobs)
            if tail == "SQ8":
                # RFlat composes: exact fp16 rerank of the sq8 shortlist
                return IVFFlatIndex(dim, nlist, metric, "sq8", kmeans_iters=iters,
                                    refine_k_factor=refine_k, **knobs)
            if tail in ("SQfp16", "SQ16"):
                # RFlat composes under scan_bf16 (the exact rerank is what
                # makes the bf16 scan legal); without it the constructor
                # logs and disables refine exactly as before
                return IVFFlatIndex(dim, nlist, metric, "f16", kmeans_iters=iters,
                                    refine_k_factor=refine_k, **knobs)
            if tail.startswith("PQ"):
                return IVFPQIndex(dim, nlist, m=parse_pq_m(tail), metric=metric,
                                  kmeans_iters=iters, refine_k_factor=refine_k)
        raise RuntimeError(f"unsupported factory spec {spec!r}")

    core = build_core()
    if refine_k and not getattr(core, "refine_k_factor", 0):
        # accurate rationale per inner: f32 inners already score exactly;
        # fp16 inners match the refine store's own precision; anything else
        # (e.g. HNSW's sq8 graph) simply doesn't wire refine yet
        exact = isinstance(core, (FlatIndex, IVFFlatIndex)) and \
            getattr(core, "codec", "f32") == "f32"
        logging.getLogger().warning(
            "RFlat suffix on %r: %s; refine ignored", spec,
            "inner index scores are already exact fp32" if exact
            else "refine is not wired for this inner index (recall may "
                 "trail FAISS's Refine(Flat) here)",
        )
    if pre is None:
        return core

    if refine_k and pre[2] < cfg.dim and getattr(core, "refine_k_factor", 0):
        logging.getLogger().warning(
            "RFlat under a dim-reducing pre-transform (%r): rerank happens in "
            "the reduced %d-dim space and cannot recover projection error "
            "(FAISS IndexRefineFlat reranks full-dim rows)", spec, pre[2]
        )

    from distributed_faiss_tpu.models.pretransform import PreTransformIndex

    kind, arg, d_out = pre
    if core.dim != d_out:
        raise RuntimeError(f"pre-transform output dim {d_out} mismatch in {spec!r}")
    if kind == "opq":
        return PreTransformIndex(core, cfg.dim, opq_m=arg,
                                 opq_iters=int(cfg.extra.get("opq_iters", 8)))
    return PreTransformIndex(core, cfg.dim, pca=True)


def _build_hnsw_spec(M: int, dim: int, cfg: IndexCfg):
    """HNSW<M> factory spec -> native graph (SQ8 storage), mirroring the
    hnswsq builder's fallback discipline."""
    if cfg.metric != "l2":
        raise RuntimeError("HNSW factory specs support l2 only (reference index.py:52)")
    from distributed_faiss_tpu.models import hnsw

    if hnsw.native_available():
        return hnsw.HNSWSQIndex(
            dim, "l2", M=M,
            ef_construction=int(cfg.extra.get("ef_construction", 100)),
            refine_k_factor=int(cfg.extra.get("refine_k_factor", 8)),
        )
    return FlatIndex(dim, "l2", codec="sq8")


def remove_rows_unsupported(cfg: IndexCfg) -> bool:
    """True when ``cfg`` resolves to a model WITHOUT a tombstone mask (the
    native HNSW graph — traversal cannot skip masked nodes without recall
    loss). Checkable BEFORE the model instance exists, so
    ``engine.Index.remove_ids`` can reject a delete up front while every
    row still sits in the add buffer (``tpu_index`` is None at that
    point); must mirror the build dispatch: without the C++ graph both
    the ``hnswsq`` builder and ``HNSW<M>`` factory cores fall back to the
    exact sq8 FlatIndex, which masks fine."""
    from distributed_faiss_tpu.models import hnsw

    if cfg.index_builder_type == "hnswsq":
        return hnsw.native_available()
    spec = cfg.faiss_factory or ""
    if "{centroids}" in spec:
        spec = spec.format(centroids=int(cfg.centroids or 0))
    if any(_HNSW_RE.match(p.strip()) for p in spec.split(",")):
        return hnsw.native_available()
    return False


def build_index(cfg: IndexCfg):
    """Resolve cfg -> index model (reference _init_faiss_index, index.py:380-401).

    Engine is responsible for resolving cfg.centroids (inference tiers) before
    calling when an IVF type is requested.
    """
    if cfg.index_builder_type:
        try:
            builder = INDEX_BUILDERS[cfg.index_builder_type]
        except KeyError:
            raise RuntimeError(f"unknown index_builder_type {cfg.index_builder_type!r}")
        return builder(cfg)
    if cfg.faiss_factory:
        return parse_factory(cfg)
    raise RuntimeError(
        "Either faiss_factory or valid index_builder_type should be specified to initialize index"
    )


def _sharded_flat_cls():
    # lazy: only deserializing a sharded index pays the mesh import
    from distributed_faiss_tpu.parallel.mesh import ShardedFlatIndex

    return ShardedFlatIndex


def _hnswsq_cls():
    from distributed_faiss_tpu.models import hnsw

    if hnsw.native_available():
        return hnsw.HNSWSQIndex

    class _HnswSqFallback:
        """Restore an hnswsq shard on a host without a C++ toolchain: the
        codes + codec in the state dict are exactly the sq8 flat layout, so
        serve them with the exact scan (recall >= the graph's)."""

        @staticmethod
        def from_state_dict(state):
            import jax.numpy as jnp
            import numpy as np

            idx = FlatIndex(int(state["dim"]), "l2", codec="sq8")
            idx.sq_params = {
                "vmin": jnp.asarray(state["sq_vmin"]),
                "span": jnp.asarray(np.asarray(state["sq_step"]) * 255.0),
            }
            idx._trained = bool(state["trained"])
            codes = np.asarray(state.get("codes", np.zeros((0, int(state["dim"])), np.uint8)))
            if codes.shape[0]:
                idx.store.add(codes)
            return idx

    return _HnswSqFallback


def _sharded_ivf_cls():
    from distributed_faiss_tpu.parallel.mesh import ShardedIVFFlatIndex

    return ShardedIVFFlatIndex


def _sharded_ivf_pq_cls():
    from distributed_faiss_tpu.parallel.mesh import ShardedIVFPQIndex

    return ShardedIVFPQIndex


def _pretransform_cls():
    from distributed_faiss_tpu.models.pretransform import PreTransformIndex

    return PreTransformIndex


_STATE_KINDS = {
    "flat": lambda: FlatIndex,
    "ivf_flat": lambda: IVFFlatIndex,
    "ivf_pq": lambda: IVFPQIndex,
    "sharded_flat": _sharded_flat_cls,
    "sharded_ivf_flat": _sharded_ivf_cls,
    "sharded_ivf_pq": _sharded_ivf_pq_cls,
    "hnswsq": _hnswsq_cls,
    "pretransform": _pretransform_cls,
}


def index_from_state_dict(state):
    """Rebuild any registered index model from its state_dict."""
    kind = str(state["kind"])
    try:
        cls = _STATE_KINDS[kind]()
    except KeyError:
        raise RuntimeError(f"unknown serialized index kind {kind!r}")
    return cls.from_state_dict(state)
