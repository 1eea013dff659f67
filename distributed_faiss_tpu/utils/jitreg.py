"""Jit-entry registry: the ONE declaration of the compiled-program surface.

Every module-level jitted launch target in the covered files (``REGISTRY``
rows with ``"trace": True``) is registered here with representative
abstract shapes/dtypes drawn from the serving pow2 bucketing, plus the
declared abstract-signature budget its steady-state serving traffic may
compile.  Three consumers read it:

- ``tools/graftlint/ir`` (the IR tier): resolves each row to its jitted
  callable, abstract-evals it to a ClosedJaxpr (``jit(...).trace`` with
  ``jax.ShapeDtypeStruct`` args — no compile, no execute) and runs the
  equation-graph checkers over it.  A row that fails to resolve or trace
  is a finding, not a skip; a module-level jit def in a covered file with
  no row here is a registry-drift finding.
- ``tools/graftlint/core`` (the AST tier): **AST-parses this file** — the
  AST tier is stdlib-only and must not import jax, so everything the AST
  tier consumes (``HOT_ROOTS``, ``REGISTRY``, ``PURE_CALLBACK_ALLOWLIST``)
  is a pure literal at module top.  HOT_ROOTS (the hot-path call-graph
  roots) and the blocking checker's jitted-launch names are derived from
  here, so a new kernel cannot be added half-covered.
- ``utils/compilecheck`` (DFT_COMPILECHECK witness): registered qualnames
  are the per-entry buckets the compile counter reports against.

Structure rules (enforced by tools/graftlint/ir and its tests):
- module top level: stdlib imports only; ``HOT_ROOTS`` / ``REGISTRY`` /
  ``PURE_CALLBACK_ALLOWLIST`` / ``MAX_SERVING_WINDOW_ROWS`` are literals
  (``ast.literal_eval``-able).
- all jax work lives inside the ``spec_*`` / ``buckets_*`` builder
  functions named (as strings) by the rows, resolved lazily by the
  harness.
"""

import functools

# Serving hot-path roots for the AST tier's call-graph walk
# (tools/graftlint/core.py derives its HOT_ROOTS view from this literal).
# Matched by (path-suffix, qualname).
HOT_ROOTS = (
    ("engine.py", "Index.search"),
    ("engine.py", "Index.search_batched"),
    ("parallel/mesh.py", "ShardedFlatIndex.search"),
    ("parallel/mesh.py", "ShardedIVFFlatIndex.search"),
    ("parallel/mesh.py", "ShardedIVFPQIndex.search"),
)

# pure_callback targets allowed inside registered programs (device-residency
# rule).  Empty on purpose: the serving programs are callback-free today and
# any new callback must be named here with a review.
PURE_CALLBACK_ALLOWLIST = ()

# Upper bound on merged serving-window rows used by the bucket enumerators
# (the scheduler's max_batch_rows is far below this; the bound only caps
# the fused nblocks enumeration).
MAX_SERVING_WINDOW_ROWS = 8192

# One row per registered entry.  Keys:
#   path     repo-relative file (graftlint finding/suppression anchor)
#   import   dotted module for the lazy resolve
#   qualname module attribute holding the jitted callable
#   trace    True -> the harness must resolve + abstract-eval this row;
#            False -> budget-only pseudo-entry (host-side driver)
#   spec     name of the spec_* builder returning [(args, kwargs), ...]
#            representative abstract signatures (None when trace=False)
#   buckets  name of the buckets_* enumerator for the entry's reachable
#            abstract-signature family (None -> no budget check)
#   budget   declared max reachable bucket count (checked against the
#            enumerator; drift in either direction past it is a finding)
#   hot      entry is reachable from the serving hot path
REGISTRY = (
    # --- ops/distance.py -------------------------------------------------
    {"path": "distributed_faiss_tpu/ops/distance.py",
     "import": "distributed_faiss_tpu.ops.distance", "qualname": "_knn_scan",
     "trace": True, "spec": "spec_knn_scan",
     "buckets": "buckets_query_blocks", "budget": 8, "hot": True},
    # --- ops/flat_pallas.py ----------------------------------------------
    {"path": "distributed_faiss_tpu/ops/flat_pallas.py",
     "import": "distributed_faiss_tpu.ops.flat_pallas",
     "qualname": "flat_list_scan_pallas",
     "trace": True, "spec": "spec_flat_list_scan_pallas",
     "buckets": "buckets_query_blocks", "budget": 8, "hot": True},
    # --- ops/adc_pallas.py -----------------------------------------------
    {"path": "distributed_faiss_tpu/ops/adc_pallas.py",
     "import": "distributed_faiss_tpu.ops.adc_pallas",
     "qualname": "adc_scan_pallas_planes",
     "trace": True, "spec": "spec_adc_scan_pallas",
     "buckets": None, "budget": 0, "hot": True},
    # --- ops/pq.py -------------------------------------------------------
    {"path": "distributed_faiss_tpu/ops/pq.py",
     "import": "distributed_faiss_tpu.ops.pq", "qualname": "_pq_encode_block",
     "trace": True, "spec": "spec_pq_encode_block",
     "buckets": None, "budget": 0, "hot": False},
    {"path": "distributed_faiss_tpu/ops/pq.py",
     "import": "distributed_faiss_tpu.ops.pq", "qualname": "pq_decode",
     "trace": True, "spec": "spec_pq_decode",
     "buckets": None, "budget": 0, "hot": False},
    {"path": "distributed_faiss_tpu/ops/pq.py",
     "import": "distributed_faiss_tpu.ops.pq", "qualname": "adc_lut",
     "trace": True, "spec": "spec_adc_lut",
     "buckets": "buckets_query_blocks", "budget": 8, "hot": True},
    {"path": "distributed_faiss_tpu/ops/pq.py",
     "import": "distributed_faiss_tpu.ops.pq", "qualname": "adc_scan",
     "trace": True, "spec": "spec_adc_scan",
     "buckets": None, "budget": 0, "hot": True},
    {"path": "distributed_faiss_tpu/ops/pq.py",
     "import": "distributed_faiss_tpu.ops.pq", "qualname": "adc_scan_shared",
     "trace": True, "spec": "spec_adc_scan_shared",
     "buckets": None, "budget": 0, "hot": True},
    # --- models/flat.py --------------------------------------------------
    {"path": "distributed_faiss_tpu/models/flat.py",
     "import": "distributed_faiss_tpu.models.flat",
     "qualname": "_flat_search_fused",
     "trace": True, "spec": "spec_flat_search_fused",
     "buckets": "buckets_fused_nblocks", "budget": 3, "hot": True},
    # --- models/base.py --------------------------------------------------
    {"path": "distributed_faiss_tpu/models/base.py",
     "import": "distributed_faiss_tpu.models.base", "qualname": "_write_rows",
     "trace": True, "spec": "spec_write_rows",
     "buckets": None, "budget": 0, "hot": False},
    {"path": "distributed_faiss_tpu/models/base.py",
     "import": "distributed_faiss_tpu.models.base",
     "qualname": "_mask_rows_false",
     "trace": True, "spec": "spec_mask_rows_false",
     "buckets": None, "budget": 0, "hot": False},
    {"path": "distributed_faiss_tpu/models/base.py",
     "import": "distributed_faiss_tpu.models.base", "qualname": "row_norms_f32",
     "trace": True, "spec": "spec_row_norms_f32",
     "buckets": None, "budget": 0, "hot": True},
    {"path": "distributed_faiss_tpu/models/base.py",
     "import": "distributed_faiss_tpu.models.base",
     "qualname": "_mask_cells_neg1",
     "trace": True, "spec": "spec_mask_cells_neg1",
     "buckets": None, "budget": 0, "hot": False},
    {"path": "distributed_faiss_tpu/models/base.py",
     "import": "distributed_faiss_tpu.models.base", "qualname": "_scatter_lists",
     "trace": True, "spec": "spec_scatter_lists",
     "buckets": None, "budget": 0, "hot": False},
    {"path": "distributed_faiss_tpu/models/base.py",
     "import": "distributed_faiss_tpu.models.base",
     "qualname": "_gather_flat_rows",
     "trace": True, "spec": "spec_gather_flat_rows",
     "buckets": None, "budget": 0, "hot": False},
    # blocked_search is the host-side block driver (not itself jitted): its
    # row pins the pow2 shape-bucket cardinality every launch target behind
    # it inherits (block buckets + fused nblocks buckets).
    {"path": "distributed_faiss_tpu/models/base.py",
     "import": "distributed_faiss_tpu.models.base", "qualname": "blocked_search",
     "trace": False, "spec": None,
     "buckets": "buckets_blocked_search", "budget": 11, "hot": True},
    # --- models/ivf.py ---------------------------------------------------
    {"path": "distributed_faiss_tpu/models/ivf.py",
     "import": "distributed_faiss_tpu.models.ivf", "qualname": "_coarse_assign",
     "trace": True, "spec": "spec_coarse_assign",
     "buckets": None, "budget": 0, "hot": True},
    {"path": "distributed_faiss_tpu/models/ivf.py",
     "import": "distributed_faiss_tpu.models.ivf", "qualname": "_rerank_exact",
     "trace": True, "spec": "spec_rerank_exact",
     "buckets": None, "budget": 0, "hot": True},
    {"path": "distributed_faiss_tpu/models/ivf.py",
     "import": "distributed_faiss_tpu.models.ivf",
     "qualname": "_ivf_flat_search",
     "trace": True, "spec": "spec_ivf_flat_search",
     "buckets": "buckets_query_blocks", "budget": 8, "hot": True},
    {"path": "distributed_faiss_tpu/models/ivf.py",
     "import": "distributed_faiss_tpu.models.ivf", "qualname": "_ivf_pq_search",
     "trace": True, "spec": "spec_ivf_pq_search",
     "buckets": "buckets_query_blocks", "budget": 8, "hot": True},
    {"path": "distributed_faiss_tpu/models/ivf.py",
     "import": "distributed_faiss_tpu.models.ivf",
     "qualname": "_ivf_flat_search_fused",
     "trace": True, "spec": "spec_ivf_flat_search_fused",
     "buckets": "buckets_fused_nblocks", "budget": 3, "hot": True},
    {"path": "distributed_faiss_tpu/models/ivf.py",
     "import": "distributed_faiss_tpu.models.ivf",
     "qualname": "_ivf_pq_search_fused",
     "trace": True, "spec": "spec_ivf_pq_search_fused",
     "buckets": "buckets_fused_nblocks", "budget": 3, "hot": True},
    # --- parallel/mesh.py ------------------------------------------------
    {"path": "distributed_faiss_tpu/parallel/mesh.py",
     "import": "distributed_faiss_tpu.parallel.mesh",
     "qualname": "_sharded_knn_jit",
     "trace": True, "spec": "spec_sharded_knn_jit",
     "buckets": "buckets_query_blocks", "budget": 8, "hot": True},
    {"path": "distributed_faiss_tpu/parallel/mesh.py",
     "import": "distributed_faiss_tpu.parallel.mesh",
     "qualname": "_sharded_knn_fused",
     "trace": True, "spec": "spec_sharded_knn_fused",
     "buckets": "buckets_fused_nblocks", "budget": 3, "hot": True},
    {"path": "distributed_faiss_tpu/parallel/mesh.py",
     "import": "distributed_faiss_tpu.parallel.mesh",
     "qualname": "_kmeans_step_jit",
     "trace": True, "spec": "spec_kmeans_step_jit",
     "buckets": None, "budget": 0, "hot": False},
    {"path": "distributed_faiss_tpu/parallel/mesh.py",
     "import": "distributed_faiss_tpu.parallel.mesh", "qualname": "_take_rows",
     "trace": True, "spec": "spec_take_rows",
     "buckets": None, "budget": 0, "hot": False},
    {"path": "distributed_faiss_tpu/parallel/mesh.py",
     "import": "distributed_faiss_tpu.parallel.mesh",
     "qualname": "_sharded_ivf_flat_search",
     "trace": True, "spec": "spec_sharded_ivf_flat_search",
     "buckets": "buckets_query_blocks", "budget": 8, "hot": True},
    {"path": "distributed_faiss_tpu/parallel/mesh.py",
     "import": "distributed_faiss_tpu.parallel.mesh",
     "qualname": "_sharded_ivf_flat_search_fused",
     "trace": True, "spec": "spec_sharded_ivf_flat_search_fused",
     "buckets": "buckets_fused_nblocks", "budget": 3, "hot": True},
    {"path": "distributed_faiss_tpu/parallel/mesh.py",
     "import": "distributed_faiss_tpu.parallel.mesh",
     "qualname": "_sharded_ivf_pq_search",
     "trace": True, "spec": "spec_sharded_ivf_pq_search",
     "buckets": "buckets_query_blocks", "budget": 8, "hot": True},
    {"path": "distributed_faiss_tpu/parallel/mesh.py",
     "import": "distributed_faiss_tpu.parallel.mesh",
     "qualname": "_sharded_ivf_pq_search_fused",
     "trace": True, "spec": "spec_sharded_ivf_pq_search_fused",
     "buckets": "buckets_fused_nblocks", "budget": 3, "hot": True},
    {"path": "distributed_faiss_tpu/parallel/mesh.py",
     "import": "distributed_faiss_tpu.parallel.mesh",
     "qualname": "_sharded_ivf_flat_search_routed",
     "trace": True, "spec": "spec_sharded_ivf_flat_search_routed",
     "buckets": "buckets_query_blocks", "budget": 8, "hot": True},
    {"path": "distributed_faiss_tpu/parallel/mesh.py",
     "import": "distributed_faiss_tpu.parallel.mesh",
     "qualname": "_sharded_ivf_pq_search_routed",
     "trace": True, "spec": "spec_sharded_ivf_pq_search_routed",
     "buckets": "buckets_query_blocks", "budget": 8, "hot": True},
)


# ------------------------------------------------------------ lazy helpers
#
# Everything below may import jax (lazily) — the AST tier never executes
# this module, and the IR harness only calls builders after jax is up.

# representative dims, all drawn from the pow2 bucket families the serving
# paths actually produce (see buckets_* below): a 256-row query bucket, a
# pow2 list capacity, pow2 corpus, m*dsub == d
_D = 16          # vector dim
_K = 8           # top-k
_NQ = 256        # query-block bucket (distance.bucket_size family)
_NBLOCKS = 4     # fused stacked-block bucket (_next_pow2 family)
_CORPUS = 4096   # flat corpus rows (pow2 — WRITE_BUCKET grown)
_NLIST = 64      # IVF lists (pow2 padded)
_CAP = 64        # per-list capacity (pow2 grown)
_NPROBE = 8
_M = 8           # PQ subspaces
_KSUB = 256
_L = 512         # ADC candidate-list length


def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


@functools.lru_cache(maxsize=1)
def _mesh():
    """All visible devices (bypasses DFT_MESH_DEVICES so the lint result
    does not depend on operator env)."""
    from distributed_faiss_tpu.parallel import mesh as mesh_mod

    return mesh_mod.make_mesh(0)


def _nshards():
    from distributed_faiss_tpu.parallel import mesh as mesh_mod

    return _mesh().shape[mesh_mod.AXIS]


# ------------------------------------------------------------ spec builders
#
# Each returns [(args, kwargs), ...]: one trace per representative abstract
# signature.  Two signatures per entry where a codec/mask/refine flag flips
# the traced program class; the bucket enumerators (not extra traces) cover
# the shape families.


def spec_knn_scan():
    q = _sds((_NQ, _D), "float32")
    x = _sds((_CORPUS, _D), "float32")
    x8 = _sds((_CORPUS, _D), "uint8")
    nt = _sds((), "int32")
    prm = _sds((_D,), "float32")
    live = _sds((_CORPUS,), "bool")
    return [
        ((q, x, nt), dict(k=_K, metric="l2", chunk=_CORPUS)),
        ((q, x8, nt), dict(k=_K, metric="l2", chunk=_CORPUS, codec="sq8",
                           vmin=prm, span=prm, live=live)),
    ]


def spec_flat_list_scan_pallas():
    q = _sds((_K, _D), "float32")
    data = _sds((_NLIST, _CAP, _D), "float16")
    ids = _sds((_NLIST, _CAP), "int32")
    li = _sds((_K, _NPROBE), "int32")
    sz = _sds((_K, _NPROBE), "int32")
    norms = _sds((_NLIST, _CAP), "float32")
    data8 = _sds((_NLIST, _CAP, _D), "uint8")
    prm = _sds((_D,), "float32")
    return [
        ((q, data, ids, li, sz, norms), dict(metric="l2", codec="f16",
                                             interpret=True)),
        ((q, data, ids, li, sz, norms), dict(metric="l2", codec="f16",
                                             scan_bf16=True, interpret=True)),
        ((q, data8, ids, li, sz, norms, prm, prm),
         dict(metric="l2", codec="sq8", interpret=True)),
    ]


def spec_adc_scan_pallas():
    lut = _sds((_K, _M, _KSUB), "float32")
    codes = _sds((_K, _L, _M), "uint8")
    sizes = _sds((_K,), "int32")
    return [((lut, codes, sizes), dict(interpret=True))]


def _codebooks():
    return _sds((_M, _KSUB, _D // _M), "float32")


def spec_pq_encode_block():
    return [((_sds((1024, _D), "float32"), _codebooks()), {})]


def spec_pq_decode():
    return [((_sds((_NQ, _M), "uint8"), _codebooks()), {})]


def spec_adc_lut():
    return [((_sds((_NQ, _D), "float32"), _codebooks()), dict(metric="l2"))]


def spec_adc_scan():
    lut = _sds((_NQ, _M, _KSUB), "float32")
    codes = _sds((_NQ, _L, _M), "uint8")
    return [((lut, codes), {})]


def spec_adc_scan_shared():
    lut = _sds((_NQ, _M, _KSUB), "float32")
    codes = _sds((_L, _M), "uint8")
    return [((lut, codes), {})]


def spec_flat_search_fused():
    q3 = _sds((_NBLOCKS, _NQ, _D), "float32")
    data = _sds((_CORPUS, _D), "float32")
    nt = _sds((), "int32")
    live = _sds((_CORPUS,), "bool")
    return [
        ((q3, data, nt), dict(k=_K, metric="l2", codec="f32", live=live)),
    ]


def spec_write_rows():
    return [((_sds((_CORPUS, _D), "float32"), _sds((_NQ, _D), "float32"),
              _sds((), "int32")), {})]


def spec_mask_rows_false():
    return [((_sds((_CORPUS,), "bool"), _sds((1024,), "int64")), {})]


def spec_row_norms_f32():
    return [((_sds((_NQ, _NPROBE, _CAP, _D), "float16"),), {})]


def spec_mask_cells_neg1():
    return [((_sds((_NLIST * _CAP,), "int64"), _sds((1024,), "int64")), {})]


def spec_scatter_lists():
    flat_data = _sds((_NLIST * _CAP, _D), "float16")
    flat_ids = _sds((_NLIST * _CAP,), "int64")
    upd = 256
    return [((flat_data, flat_ids, _sds((upd,), "int32"),
              _sds((upd, _D), "float16"), _sds((upd,), "int64")), {})]


def spec_gather_flat_rows():
    return [((_sds((_NLIST, _CAP, _D), "float16"),
              _sds((1024,), "int64")), {})]


def spec_coarse_assign():
    return [((_sds((_NLIST, _D), "float32"), _sds((_NQ, _D), "float32")),
             dict(metric="l2"))]


def spec_rerank_exact():
    store = _sds((_CORPUS, _D), "float16")
    cand = _sds((_NQ, 4 * _K), "int32")
    return [((store, _sds((_NQ, _D), "float32"), cand),
             dict(k=_K, metric="l2"))]


def _ivf_flat_operands(codec="f16"):
    dt = {"f16": "float16", "sq8": "uint8"}[codec]
    return (_sds((_NLIST, _D), "float32"),      # centroids
            _sds((_NLIST, _CAP, _D), dt),       # list_data
            _sds((_NLIST, _CAP), "int64"),      # list_ids
            _sds((_NLIST,), "int32"))           # list_sizes


def _listmajor_tiling():
    from distributed_faiss_tpu.models import ivf

    tile, group, sub = ivf.listmajor_tiling(_NQ, _NPROBE, _NLIST, _CAP, _D, 2)
    return dict(tile=tile, group=group, sub=sub)


def spec_ivf_flat_search():
    cents, data, ids, sizes = _ivf_flat_operands()
    q = _sds((_NQ, _D), "float32")
    norms = _sds((_NLIST, _CAP), "float32")
    # the XLA arm as IVFFlatIndex.search launches it: list-major under the
    # index's own (tile, group) rule, the block's real rows a traced scalar
    stat = dict(k=_K, nprobe=_NPROBE, g=_NPROBE, metric="l2", codec="f16",
                **_listmajor_tiling())
    dyn = dict(list_norms=norms, nvalid=_sds((), "int32"))
    return [
        ((cents, data, ids, sizes, q), dict(stat, **dyn)),
        ((cents, data, ids, sizes, q), dict(stat, **dyn, scan_bf16=True)),
        # a one-row request's bucket: a tile is a pair
        ((cents, data, ids, sizes, _sds((8, _D), "float32")),
         dict(stat, **dyn, tile=1)),
    ]


def spec_ivf_pq_search():
    cents = _sds((_NLIST, _D), "float32")
    codes = _sds((_NLIST, _CAP, _M), "uint8")
    ids = _sds((_NLIST, _CAP), "int64")
    sizes = _sds((_NLIST,), "int32")
    q = _sds((_NQ, _D), "float32")
    stat = dict(k=_K, nprobe=_NPROBE, g=_NPROBE, metric="l2")
    return [((cents, _codebooks(), codes, ids, sizes, q), stat)]


def spec_ivf_flat_search_fused():
    cents, data, ids, sizes = _ivf_flat_operands()
    refine = _sds((_CORPUS, _D), "float16")
    q3 = _sds((_NBLOCKS, _NQ, _D), "float32")
    norms = _sds((_NLIST, _CAP), "float32")
    return [((cents, data, ids, sizes, refine, q3),
             dict(k=_K, scan_k=4 * _K, nprobe=_NPROBE, g=_NPROBE,
                  metric="l2", codec="f16", refine=True, list_norms=norms,
                  counts=_sds((_NBLOCKS,), "int32"), **_listmajor_tiling()))]


def spec_ivf_pq_search_fused():
    cents = _sds((_NLIST, _D), "float32")
    codes = _sds((_NLIST, _CAP, _M), "uint8")
    ids = _sds((_NLIST, _CAP), "int64")
    sizes = _sds((_NLIST,), "int32")
    refine = _sds((_CORPUS, _D), "float16")
    q3 = _sds((_NBLOCKS, _NQ, _D), "float32")
    return [((cents, _codebooks(), codes, ids, sizes, refine, q3),
             dict(k=_K, adc_k=4 * _K, nprobe=_NPROBE, g=_NPROBE, metric="l2",
                  use_pallas=False, refine=True))]


def _sharded_flat_operands():
    S = _nshards()
    cap_local = _CORPUS // S if _CORPUS % S == 0 else _CORPUS
    return (S, _sds((S * cap_local, _D), "float32"), _sds((S,), "int32"),
            cap_local)


def spec_sharded_knn_jit():
    S, x, ntotals, cap_local = _sharded_flat_operands()
    q = _sds((_NQ, _D), "float32")
    live = _sds((S * cap_local,), "bool")
    stat = dict(mesh=_mesh(), k=_K, metric="l2", chunk=cap_local)
    return [
        ((q, x, ntotals), stat),
        ((q, x, ntotals), dict(stat, live=live)),
    ]


def spec_sharded_knn_fused():
    S, x, ntotals, cap_local = _sharded_flat_operands()
    q3 = _sds((_NBLOCKS, _NQ, _D), "float32")
    return [((q3, x, ntotals),
             dict(mesh=_mesh(), k=_K, metric="l2", chunk=cap_local))]


def spec_kmeans_step_jit():
    S = _nshards()
    per = 256
    return [((_sds((S * per, _D), "float32"), _sds((S * per,), "float32"),
              _sds((_NLIST, _D), "float32")),
             dict(mesh=_mesh(), k=_NLIST, chunk=per))]


def spec_take_rows():
    return [((_sds((_CORPUS, _D), "float32"), _sds((1024,), "int64")), {})]


def _sharded_lists_operands(payload):
    """Mesh-sharded padded lists: nlist_pad divisible by S."""
    S = _nshards()
    nlist = max(_NLIST, S)
    if nlist % S:
        nlist = S * (-(-nlist // S))
    if payload == "pq":
        data = _sds((nlist, _CAP, _M), "uint8")
    else:
        data = _sds((nlist, _CAP, _D), "float16")
    return (_sds((nlist, _D), "float32"), data,
            _sds((nlist, _CAP), "int64"), _sds((nlist,), "int32"), nlist)


def spec_sharded_ivf_flat_search():
    cents, data, ids, sizes, nlist = _sharded_lists_operands("flat")
    q = _sds((_NQ, _D), "float32")
    norms = _sds((nlist, _CAP), "float32")
    raw = _sds((nlist, _CAP, _D), "float16")
    stat = dict(mesh=_mesh(), k=_K, nprobe=_NPROBE, g=_NPROBE, metric="l2")
    return [
        ((cents, data, ids, sizes, q), dict(stat, list_norms=norms)),
        ((cents, data, ids, sizes, q),
         dict(stat, list_norms=norms, scan_bf16=True, adc_k=4 * _K,
              raw_data=raw)),
    ]


def spec_sharded_ivf_flat_search_fused():
    cents, data, ids, sizes, nlist = _sharded_lists_operands("flat")
    q3 = _sds((_NBLOCKS, _NQ, _D), "float32")
    norms = _sds((nlist, _CAP), "float32")
    return [((cents, data, ids, sizes, q3),
             dict(mesh=_mesh(), k=_K, nprobe=_NPROBE, g=_NPROBE, metric="l2",
                  list_norms=norms))]


def spec_sharded_ivf_pq_search():
    cents, codes, ids, sizes, nlist = _sharded_lists_operands("pq")
    q = _sds((_NQ, _D), "float32")
    raw = _sds((nlist, _CAP, _D), "float16")
    stat = dict(mesh=_mesh(), k=_K, nprobe=_NPROBE, g=_NPROBE, metric="l2")
    return [
        ((cents, _codebooks(), codes, ids, sizes, q), stat),
        ((cents, _codebooks(), codes, ids, sizes, q),
         dict(stat, adc_k=4 * _K, raw_data=raw)),
    ]


def spec_sharded_ivf_pq_search_fused():
    cents, codes, ids, sizes, nlist = _sharded_lists_operands("pq")
    q3 = _sds((_NBLOCKS, _NQ, _D), "float32")
    return [((cents, _codebooks(), codes, ids, sizes, q3),
             dict(mesh=_mesh(), k=_K, nprobe=_NPROBE, g=_NPROBE,
                  metric="l2"))]


def _routed_statics():
    from distributed_faiss_tpu.parallel import mesh as mesh_mod

    S = _nshards()
    group = _NPROBE
    bucket = mesh_mod.routed_pair_bucket(_NQ, _NPROBE, S, group)
    return dict(mesh=_mesh(), k=_K, nprobe=_NPROBE, pair_bucket=bucket,
                group=group, metric="l2")


def spec_sharded_ivf_flat_search_routed():
    cents, data, ids, sizes, nlist = _sharded_lists_operands("flat")
    q = _sds((_NQ, _D), "float32")
    nq_real = _sds((), "int32")
    norms = _sds((nlist, _CAP), "float32")
    return [((cents, data, ids, sizes, q, nq_real),
             dict(_routed_statics(), list_norms=norms))]


def spec_sharded_ivf_pq_search_routed():
    cents, codes, ids, sizes, nlist = _sharded_lists_operands("pq")
    q = _sds((_NQ, _D), "float32")
    nq_real = _sds((), "int32")
    return [((cents, _codebooks(), codes, ids, sizes, q, nq_real),
             _routed_statics())]


# -------------------------------------------------------- bucket enumerators
#
# Each returns the entry's reachable abstract-signature bucket family,
# computed by RUNNING the code's own pow2 helpers — so a change to
# bucket_size / pick_query_block / MAX_QUERY_BLOCK moves the enumeration
# and trips the declared budget (registry-drift-from-code).


def _serving_block():
    from distributed_faiss_tpu.models import base

    # the flat serving block (the largest any model path uses — IVF blocks
    # shrink with cap, never grow past this)
    return base.pick_query_block(65536 * 4)


def buckets_query_blocks():
    """nq buckets a single-block launch can see: query_blocks buckets every
    chunk through distance.bucket_size."""
    from distributed_faiss_tpu.ops import distance

    block = _serving_block()
    return sorted({distance.bucket_size(n) for n in range(1, block + 1)})


def buckets_fused_nblocks():
    """nblocks buckets the fused multi-block entries can see for windows up
    to MAX_SERVING_WINDOW_ROWS (blocked_search pads nblocks to pow2)."""
    from distributed_faiss_tpu.models import base

    block = _serving_block()
    return sorted({base._next_pow2(-(-n // block), 1)
                   for n in range(block + 1, MAX_SERVING_WINDOW_ROWS + 1)})


def buckets_blocked_search():
    """The driver's full family: single-block nq buckets plus fused nblocks
    buckets (what steady-state serving can compile through it)."""
    return ([("block", b) for b in buckets_query_blocks()]
            + [("nblocks", b) for b in buckets_fused_nblocks()])


# ------------------------------------------------------------------- lookup


def rows():
    """REGISTRY as a tuple of dicts (stable order)."""
    return REGISTRY


def registered_qualnames():
    return tuple(r["qualname"] for r in REGISTRY)


def resolve(row):
    """Import and return the callable a registry row points at.

    Raises (ImportError/AttributeError) on a stale row — the IR harness
    converts that into a finding."""
    import importlib

    mod = importlib.import_module(row["import"])
    return getattr(mod, row["qualname"])


def signatures(row):
    """The row's representative abstract signatures: [(args, kwargs), ...]."""
    if not row["trace"]:
        return []
    return globals()[row["spec"]]()


def enumerate_buckets(row):
    """The row's reachable bucket family (empty when no enumerator)."""
    if not row["buckets"]:
        return []
    return globals()[row["buckets"]]()
