"""IVF index family: coarse k-means quantizer + padded inverted lists.

Replaces FAISS ``IndexIVFFlat`` / ``IndexIVFScalarQuantizer`` /
``IndexIVFPQ`` (reference builders ivf_simple/ivfsq/knnlm at
distributed_faiss/index.py:36-68).

TPU-first search path (one jitted program per variant):
  coarse einsum (nq, nlist) -> top-nprobe -> the probe scan. PQ: lax.scan
  over probes, each step gathering one (nq, g, cap, m) code block from HBM,
  scoring it by ADC LUT, masking the padded tail and merging into a running
  top-k carry. Flat/fp16/sq8: list-major (_listmajor_scan) — the (query,
  probe) pairs are sorted by list on the device and every list's run of
  pairs is cut into runs of T queries; a tile is one such run x ONE LIVE
  SUB-BLOCK of its list (listmajor_sub_rows rows: 256 at d 512 in fp16),
  made only for the sub-blocks that hold a row, so the scan gathers and
  multiplies what a list holds and not its padded capacity. A tile's
  sub-block is gathered once and multiplied against all its queries on
  the MXU (dequant fused into the einsum); the top-k is taken once a
  query over its probed lists' columns. The flat/sq8 l2 scan
  gathers STORED fp32 row norms (a (nlist, cap) sidecar filled at
  add/encode time, bit-identical to an in-scan recompute) instead of
  running a second elementwise pass over the block; with use_pallas the
  whole gather+decode+dot+mask step runs query-major in a fused VMEM
  kernel (ops/flat_pallas.py) and no gathered block exists in HBM.

Coarse assignment follows the reference's quantizer choice (get_quantizer,
index.py:25-33): argmax inner product for metric=dot, argmin L2 otherwise.
PQ encoding is residual for l2 (FAISS IVFPQ by_residual) and raw for dot
(FAISS disables residual PQ for IP).

Host state is the id -> (list, within-list position) map only (8 bytes/row):
the payload lives solely in the device lists, and reconstruct_batch /
persistence gather it back through that map (base.gather_list_rows). The
previous design also mirrored the full encoded corpus in host RAM; at the
reference knnlm scale (1e9 x 768) that second copy was terabytes (VERDICT
r4). Lists are rebuilt by one bulk append on load.
"""

import functools
import logging
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_faiss_tpu.models import base
from distributed_faiss_tpu.ops import adc_pallas, distance, kmeans, pq, sq
from distributed_faiss_tpu.utils import sanitize, tracing, xfercheck

logger = logging.getLogger()

_HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("metric",))
def _coarse_assign(centroids, x, metric: str):
    s = distance.pairwise_scores(x, centroids, metric)
    return jnp.argmax(s, axis=1).astype(jnp.int32)


def exact_candidate_scores(q, rows, metric: str):
    """Exact (nq, R) scores of gathered candidate rows, higher-is-better.

    The one scoring formula shared by every exact-refine site (single-device
    _rerank_exact and both sharded pre-merge reranks in parallel/mesh.py):
    fp32 HIGHEST einsum; dot = ip, l2 = -(qn - 2 ip + rn).
    """
    q = q.astype(jnp.float32)
    rows = rows.astype(jnp.float32)
    ip = jnp.einsum("qd,qrd->qr", q, rows, precision=_HIGHEST,
                    preferred_element_type=jnp.float32)
    if metric == "dot":
        return ip
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    rn = jnp.sum(rows * rows, axis=2)
    return -(qn - 2.0 * ip + rn)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _rerank_exact(store, q, cand_ids, k: int, metric: str):
    """Exact refine of an ADC shortlist (FAISS IndexRefine-style).

    store: (cap, d) fp16 raw rows (id-ordered); cand_ids: (nq, R) from the
    ADC pass (-1 padding). Gathers the R candidate rows per query (row
    gathers are DMA-friendly, unlike the element gathers ADC avoids),
    rescans exactly in fp32, returns the top-k re-ordered subset.
    """
    with jax.named_scope("refine"):
        safe = jnp.where(cand_ids >= 0, cand_ids, 0)
        rows = store[safe]  # (nq, R, d)
        s = exact_candidate_scores(q, rows, metric)
        s = jnp.where(cand_ids >= 0, s, distance.NEG_INF)
        best, pos = jax.lax.top_k(s, k)
        return best, jnp.take_along_axis(cand_ids, pos, axis=1)


# paired bound: base._QUERY_PAYLOAD_BUDGET = 2x this, so even when one
# probe's block-payload exceeds this budget (g floors at 1) the gather
# transient stays within 2x, not unbounded
_GROUP_BYTE_BUDGET = 128 * 1024 * 1024
# list-major scan (listmajor_tiling). A step's gathered block stays under
# _LIST_BLOCK_BYTES in the storage dtype: at 64 MiB XLA keeps it in the
# v5e's 128 MiB of VMEM, past it a step costs a third more. The score
# buffer of a block stays under _SCORE_BYTES. The gather takes its rows in
# slices of at most _GATHER_SLICE_BYTES: a slice of a whole (cap, d) list is
# past what XLA:TPU gathers in place, and it then copies the WHOLE store
# into slabs in every loop step (PERF.md section 6, PR 31). Lists fuller
# than _WHOLE_LIST_FILL are scanned a whole list a tile: a sub-block tile's
# bookkeeping costs a scan 7% (capacity 4096) to 12% (512) where nothing is
# skipped, which is what a tenth of the capacity skipped pays back, and lists
# filled past 0.9 leave less than that to skip (PERF.md section 6, PR 43).
_MAX_TILE = 64
_MAX_GROUP = 64
_WHOLE_LIST_FILL = 0.9
_LIST_BLOCK_BYTES = 64 * 1024 * 1024
_SCORE_BYTES = 1024 * 1024 * 1024
_GATHER_SLICE_BYTES = 256 * 1024


def probe_group_size(nprobe: int, per_probe_bytes: int) -> int:
    """Largest divisor of nprobe whose group payload fits the byte budget.

    Grouping probes amortizes the per-step overhead that dominated a
    probe-at-a-time scan on TPU (one top_k + small gathers per probe measured
    ~0.7 ms/probe on v5e); within a group everything is one batched einsum
    and one top_k.
    """
    g = max(1, min(nprobe, _GROUP_BYTE_BUDGET // max(1, per_probe_bytes)))
    while nprobe % g:
        g -= 1
    return g


def pq_probe_payload_bytes(cap: int, m: int, ksub: int = 256,
                           nq_block: int = 256) -> int:
    """Per-probed-list payload for the ADC group sizing: gathered codes +
    ids for an ``nq_block``-query block plus the per-probe LUT block. The
    ONE formula shared by IVFPQIndex.search and the sharded masked path
    (parallel/mesh.py) so the memory model can't drift between them."""
    return nq_block * cap * (m + 8) + nq_block * m * ksub * 4


def _merge_group(carry, s, ids, k):
    """Merge a (nq, width) score block + ids into the running (nq, k) top-k
    (two-stage segmented top-k: width can reach g*cap ~ tens of thousands,
    where single-pass lax.top_k dominates the probe scan)."""
    best_v, best_i = carry
    with jax.named_scope("merge_topk"):
        cv, cids = distance.segmented_topk_rows(s, k, ids)
        return distance.merge_topk(best_v, best_i, cv, cids, k)


def listmajor_sub_rows(cap: int, dim: int, itemsize: int) -> int:
    """Rows of a list's sub-block: the grain at which the list-major scan
    gathers a list and stops at its end. The largest power-of-two share of
    ``cap`` (down to 32 rows) whose (sub, dim) slice in the storage dtype
    stays inside ``_GATHER_SLICE_BYTES``: 256 rows at d 512 in float16."""
    sub = cap
    while sub % 64 == 0 and sub * dim * itemsize > _GATHER_SLICE_BYTES:
        sub //= 2
    return sub


def listmajor_tiling(rows: int, nprobe: int, nlist: int, cap: int, dim: int,
                     itemsize: int, fill: float = 0.0):
    """(T, G, sub) of the list-major probe scan for a block of ``rows``
    query rows over lists of ``cap`` rows of ``dim`` x ``itemsize`` bytes
    filled to the share ``fill``, from static shapes and what the index
    knows without a device read. A tile is a run of up to T queries that
    probe one list x ONE LIVE SUB-BLOCK of ``sub`` rows of that list; G
    tiles make a loop step. The ONE rule — ``IVFFlatIndex._scan_tiling``
    asks it before the trace.

    T follows the expected reuse of a probed list, ``rows * nprobe /
    nlist`` pairs. Up to 1 a tile's run is a pair (T = 1): the scan is then
    the pair-major one, a matrix-vector product a pair, bound by the read
    of the list. Past it T is a power of two from 8 (one sublane tile of
    queries; fewer cost the same) up to 4 x the reuse: a tile costs the
    gather of its sub-block and a product whose 128 MXU columns are paid
    for whether 8 or 64 are filled, so it is wide enough that a list's
    pairs nearly always fit one run — as long as the block's score buffer,
    ``(pairs + nlist * T) * cap * 4`` bytes, stays inside ``_SCORE_BYTES``.
    ``sub`` is ``listmajor_sub_rows`` (a tile then costs what its list
    holds, to that grain), unless the lists are fuller than
    ``_WHOLE_LIST_FILL``: full lists leave nothing to skip and a tile of
    the whole capacity spares them a sub-block tile's bookkeeping (its
    queries gathered and its slab written once a sub-block). G keeps a
    step's gathered block inside ``_LIST_BLOCK_BYTES``: the sub-blocks of
    up to ``_MAX_GROUP`` whole lists, so a step is as fat as when a tile
    was a whole padded list and a launch takes fewer of them (a loop step
    costs its drain whether it is full or not). Every number is a v5e's at
    d 512, float16, capacities 512 to 4096: PERF.md section 6, PR 31 and
    PR 43."""
    pairs = rows * nprobe
    tile = 1
    if pairs > nlist:
        tile = 8
        while tile < min(_MAX_TILE, 4 * pairs / nlist):
            tile *= 2
        while tile > 8 and (pairs + nlist * tile) * cap * 4 > _SCORE_BYTES:
            tile //= 2
    lists = 1
    while (lists < _MAX_GROUP
           and 2 * lists * cap * dim * itemsize <= _LIST_BLOCK_BYTES):
        lists *= 2
    sub = cap if fill > _WHOLE_LIST_FILL else listmajor_sub_rows(cap, dim, itemsize)
    return tile, lists * (cap // sub), sub


def listmajor_tile_bound(npairs: int, nlist: int, tile: int) -> int:
    """Runs of up to ``tile`` queries that ``npairs`` (query, probe) pairs
    can make, whatever the probes: every full run holds ``tile`` pairs and
    each probed list adds at most one partial run, and no run is empty. A
    run makes a tile per live sub-block of its list, ``cap / sub`` at
    most: the scan's tile arrays hold that many times this."""
    return min(npairs, -(-npairs // tile) + min(nlist, npairs))


def _listmajor_plan(probes, list_sizes, tile: int, sub: int, nruns: int,
                    ntiles: int, nvalid):
    """Invert ``probes`` (nq, nprobe) into tiles, on the device: sort the
    pairs by list id, cut every list's run of pairs into runs of ``tile``
    query slots, and give each run one tile per LIVE sub-block of its list
    (``ceil(size / sub)`` of them: none for an empty list), a run's tiles
    side by side in sub-block order. Pairs of rows at or past ``nvalid`` (a
    block's zero padding) sort behind every list and make no run.

    What costs a gather an element is made a run (``nruns`` of them, the
    whole-list scan's tiles), not a tile (``ntiles``: ``cap / sub`` times
    as many): a tile knows its run and its sub-block, from two scatters of
    the runs' first tiles and a running count and maximum over the tiles.

    Returns ``run_list`` (nruns,) the list a run scans and ``run_q``
    (nruns, tile) the query row of each of its slots (an arbitrary live
    row in a slot past the list's run: its scores are never read);
    ``tile_run``, ``tile_sub`` (ntiles,) a tile's run and sub-block (past
    the tiles in use: the last run, and a count from their end);
    ``where`` (nq, nprobe) the ``first tile * T + slot`` of each pair's run
    (its sub-block j is ``T * j`` rows further); ``pair_live`` (nq,
    nprobe); and the traced numbers of tiles and of runs in use."""
    nq, nprobe = probes.shape
    nlist = list_sizes.shape[0]
    npairs = nq * nprobe
    pair = jnp.arange(npairs, dtype=jnp.int32)
    key = probes.reshape(npairs).astype(jnp.int32)
    if nvalid is not None:
        key = jnp.where(pair // nprobe < nvalid, key, nlist)
    order = jnp.argsort(key).astype(jnp.int32)
    skey = key[order]
    start = jnp.searchsorted(
        skey, jnp.arange(nlist + 1, dtype=jnp.int32)).astype(jnp.int32)
    nrun = -(-(start[1:] - start[:-1]) // tile)  # runs of each list
    rend = jnp.cumsum(nrun)
    rfirst = rend - nrun
    r = jnp.arange(nruns, dtype=jnp.int32)
    run_list = jnp.minimum(
        jnp.searchsorted(rend, r, side="right"), nlist - 1).astype(jnp.int32)
    pos = ((start[run_list] + (r - rfirst[run_list]) * tile)[:, None]
           + jnp.arange(tile, dtype=jnp.int32)[None, :])
    run_q = order[jnp.minimum(pos, npairs - 1)] // nprobe
    # tiles of each run: the live sub-blocks of its list (a run past those
    # in use makes none and starts where the tiles in use end)
    nsub = -(-list_sizes.astype(jnp.int32) // sub)
    width = jnp.where(r < rend[-1], nsub[run_list], 0)
    tend = jnp.cumsum(width)
    tfirst = tend - width
    # a tile's run is the last that starts at or before it; its sub-block
    # is its distance from that start
    marks = jnp.zeros((ntiles + 1,), jnp.int32)
    tile_run = jnp.cumsum(marks.at[tfirst].add(1))[:ntiles] - 1
    seg = jax.lax.cummax(marks.at[tfirst].max(tfirst))[:ntiles]
    tile_sub = jnp.arange(ntiles, dtype=jnp.int32) - seg
    slist = jnp.minimum(skey, nlist - 1)
    rank = pair - start[slist]  # a sorted pair's place in its list's run
    where = jnp.zeros((npairs,), jnp.int32).at[order].set(
        jnp.where(skey < nlist,
                  tfirst[jnp.minimum(rfirst[slist] + rank // tile, nruns - 1)] * tile
                  + rank % tile, 0),
        unique_indices=True)
    return (run_list, run_q, tile_run, tile_sub, where.reshape(nq, nprobe),
            (key < nlist).reshape(nq, nprobe), tend[-1], rend[-1])


def _decode_block(block, codec: str, vmin, span):
    """Stored list rows (..., d) -> fp32."""
    if codec == "sq8":
        return vmin + block.astype(jnp.float32) * (span / 255.0)
    return block.astype(jnp.float32)


def _gather_blocks(lists, blocks, sub: int, slice_rows: int):
    """Rows ``[b * sub, (b + 1) * sub)`` of a padded-list array (nlist, cap,
    ...) taken as one run of ``nlist * cap`` rows, for each ``b`` of
    ``blocks`` (G,): (G, sub, ...) in the storage dtype — list ``l``'s
    sub-block ``j`` is ``b = l * (cap // sub) + j``. Gathered through a view
    of the array in slices of ``slice_rows`` rows (``listmajor_sub_rows``:
    at most ``_GATHER_SLICE_BYTES``; the reshape is a bitcast for the
    payload, whole sublane tiles of rows stay together): a slice of a
    whole (cap, d) list is past what XLA:TPU gathers in place."""
    view = lists.reshape((-1, slice_rows) + lists.shape[2:])
    each = sub // slice_rows
    idx = blocks[:, None] * each + jnp.arange(each, dtype=blocks.dtype)[None, :]
    return view[idx.reshape(-1)].reshape((blocks.shape[0], sub) + lists.shape[2:])


def _listmajor_scan(list_data, list_ids, list_sizes, q, qn, probes, k: int,
                    metric: str, codec: str, vmin, span, list_norms,
                    scan_bf16: bool, tile: int, group: int, sub: int, nvalid):
    """The probe scan in list-major order: each live sub-block (``sub``
    rows; 0: the whole capacity) of each probed list is gathered once per
    ``tile`` queries that probe the list and multiplied against all of
    them, ``einsum("gtd,gsd->gts")``, where the query-major scan gathers
    one (cap, d) block per (query, probe) pair for a matrix-vector product.
    A sub-block past the end of its list (``j >= ceil(size / sub)``) is
    never gathered, never multiplied and never written: the scan costs the
    rows the lists hold, to the grain of ``sub``, and not their padded
    capacity.

    Every row of every probed list is scored for every query that probes
    it, at the query-major scan's precision. NO PAIR IS EVER DROPPED: the
    tile arrays are sized by ``listmajor_tile_bound``, which no set of
    probes can pass, so there is no overflow and no retry; the loop's trip
    count is the traced number of tile groups in use, so the bound costs
    nothing when the probes are spread.

    The loop only scores: a tile's masked scores land in a ``(tiles, T,
    sub)`` buffer at its own place. The top-k is taken once a query, over
    its ``nprobe`` lists' columns side by side in probe order — the
    query-major scan's candidates in the query-major scan's order, so ties
    fall as there (earlier probe, then lower position); a column past the
    end of its list reads -inf there whatever the buffer holds (it is
    ``jax.lax.empty``: a dead sub-block's rows were never written). A
    ``top_k`` a pair inside the loop is a sort of every ``cap``-wide slot
    row on a v5e, 60% of a launch (PERF.md section 6, PR 31); over a
    query's whole row ``_seg_reduce`` picks the few segments that can hold
    a neighbour first. The buffer is ``(pairs + nlist * T) * cap * 4``
    bytes at most.

    -> (vals, ids, counts): ``counts`` int32 (2,), the sub-blocks of the
    tiles in use had every list been full (runs x ``cap / sub``: what a
    scan of whole padded lists gathers) and those the scan gathered."""
    nq, nprobe = probes.shape
    nlist, cap, d = list_data.shape
    sub = sub or cap
    parts = cap // sub
    slice_rows = listmajor_sub_rows(sub, d, list_data.dtype.itemsize)
    nruns = listmajor_tile_bound(nq * nprobe, nlist, tile)
    ntiles = -(-nruns * parts // group) * group
    with jax.named_scope("coarse"):
        (run_list, run_q, tile_run, tile_sub, where, pair_live, used,
         runs) = _listmajor_plan(probes, list_sizes, tile, sub, nruns, ntiles, nvalid)
        tile_sub = jnp.minimum(tile_sub, parts - 1)  # past the tiles in use
        run_qn = qn[run_q]  # (nruns, T, 1)

    def body(i, scores):
        with jax.named_scope("list_scan"):
            t0 = i * group
            tr = jax.lax.dynamic_slice_in_dim(tile_run, t0, group)  # (G,)
            ts = jax.lax.dynamic_slice_in_dim(tile_sub, t0, group)
            tl = run_list[tr]
            tq = run_q[tr]  # (G, T)
            tb = tl * parts + ts
            block_ids = _gather_blocks(list_ids, tb, sub, slice_rows)  # (G, sub)
            stored = (None if list_norms is None
                      else _gather_blocks(list_norms, tb, sub, slice_rows))
            block = _decode_block(_gather_blocks(list_data, tb, sub, slice_rows),
                                  codec, vmin, span)  # (G, sub, d)
            qs = q[tq]  # (G, T, d)
            if scan_bf16:
                ip = jnp.einsum("gtd,gsd->gts", qs.astype(jnp.bfloat16),
                                block.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32)
            else:
                ip = jnp.einsum("gtd,gsd->gts", qs, block, precision=_HIGHEST,
                                preferred_element_type=jnp.float32)
            if metric == "dot":
                s = ip
            else:
                bn = stored if stored is not None else base.row_norms_f32(block)
                s = -(run_qn[tr] - 2.0 * ip + bn[:, None, :])
            # rows of its list a tile's sub-block holds, past 0: at most sub
            live = list_sizes[tl].astype(jnp.int32) - ts * sub
            valid = (jnp.arange(sub)[None, :] < live[:, None]) & (block_ids >= 0)
            s = jnp.where(valid[:, None, :], s, distance.NEG_INF)
            return jax.lax.dynamic_update_slice_in_dim(scores, s, t0, axis=0)

    # every slot a live pair reads is written by a tile in use: no fill; the
    # buffer ends in ``parts`` tiles no loop step writes, so that a pair's
    # ``parts`` sub-blocks, dead ones too, are read inside it
    scores = jax.lax.fori_loop(
        0, -(-used // group), body,
        jax.lax.empty((ntiles + parts, tile, sub), jnp.float32))
    with jax.named_scope("merge_topk"):
        # a pair's row: its slot of the ``parts`` tiles from its run's first
        # (one gather slice a pair, as when a tile was a whole list)
        dn = jax.lax.GatherDimensionNumbers(
            offset_dims=(1, 2), collapsed_slice_dims=(1,), start_index_map=(0, 1))
        row = jax.lax.gather(
            scores, jnp.stack([(where // tile).reshape(-1),
                               (where % tile).reshape(-1)], -1), dn,
            slice_sizes=(parts, 1, sub), mode="promise_in_bounds")
        row = row.reshape(nq, nprobe, cap)
        held = pair_live[:, :, None] & (
            jnp.arange(cap)[None, None, :] < list_sizes[probes][:, :, None])
        row = jnp.where(held, row, distance.NEG_INF)
        vals, pos = distance.segmented_argtopk(row.reshape(nq, nprobe * cap), k)
        if vals.shape[1] < k:  # fewer columns than k: the tail stays empty
            pad = ((0, 0), (0, k - vals.shape[1]))
            vals = jnp.pad(vals, pad, constant_values=distance.NEG_INF)
            pos = jnp.pad(pos, pad, constant_values=-1)
        found = (pos >= 0) & (vals > distance.NEG_INF)
        pos = jnp.where(found, pos, 0)
        lists = jnp.take_along_axis(probes, pos // cap, axis=1)
        out_ids = list_ids[lists, pos % cap].astype(jnp.int32)
        counts = jnp.stack([runs * parts, used]).astype(jnp.int32)
        return vals, jnp.where(found, out_ids, -1), counts


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "g", "metric", "codec",
                                             "use_pallas", "scan_bf16", "tile",
                                             "group", "sub"))
def _ivf_flat_search(centroids, list_data, list_ids, list_sizes, q,
                     k: int, nprobe: int, g: int, metric: str, codec: str,
                     vmin=None, span=None, list_norms=None,
                     use_pallas: bool = False, scan_bf16: bool = False,
                     tile: int = 1, group: int = 1, sub: int = 0, nvalid=None):
    """IVF-Flat/SQ8 probe scan.

    The XLA arm scans list-major (_listmajor_scan): tiles of ``tile`` query
    slots over one live sub-block of ``sub`` rows of a probed list,
    ``group`` tiles a loop step, all three chosen by the index
    (``listmajor_tiling``); the defaults are the pair-major scan over whole
    lists, one pair a step. The Pallas arm scans query-major, ``g`` probes
    of every query a step.
    -> (vals, ids, counts): ``counts`` int32 (2,) is ``_listmajor_scan``'s
    (sub-blocks of the tiles at whole capacity, sub-blocks gathered); the
    Pallas arm, which gathers no block, returns zeros.
    nvalid: traced int32, the block's real rows; the rest is zero padding
    whose pairs the list-major scan leaves out (their result rows are
    empty). None: every row is real.

    list_norms: (nlist, cap) fp32 stored ``||x||^2`` of the decoded rows
    (computed once at add/encode time — see base.row_norms_f32); None falls
    back to recomputing them from the gathered block every query (the
    pre-stored-norms behavior, kept as the A/B/golden reference).
    use_pallas: fused VMEM kernel (ops/flat_pallas.py) — the probed tiles
    stream HBM->VMEM via a scalar-prefetched gather and no gathered block
    exists in HBM.
    scan_bf16: bf16 MXU scan (halved compute-operand traffic); models gate
    it behind refine_k_factor > 0 so final scores stay exact.
    """
    q = q.astype(jnp.float32)
    nq = q.shape[0]
    cap = list_data.shape[1]
    # the four scopes (coarse, list_scan, merge_topk, refine) name the
    # device's op events in a profiler trace; metadata only
    with jax.named_scope("coarse"):
        coarse = distance.pairwise_scores(q, centroids, metric)
        _, probes = distance.segmented_argtopk(coarse, nprobe)  # (nq, nprobe)
        qn = jnp.sum(q * q, axis=1, keepdims=True)
    if not use_pallas:
        return _listmajor_scan(list_data, list_ids, list_sizes, q, qn, probes,
                               k, metric, codec, vmin, span, list_norms,
                               scan_bf16, tile, group, sub, nvalid)
    from distributed_faiss_tpu.ops import flat_pallas

    groups = probes.reshape(nq, nprobe // g, g).transpose(1, 0, 2)  # (ng, nq, g)
    init = (
        jnp.full((nq, k), distance.NEG_INF, jnp.float32),
        jnp.full((nq, k), -1, jnp.int32),
    )

    def body(carry, li):  # li: (nq, g)
        with jax.named_scope("list_scan"):
            ids = list_ids[li]  # (nq, g, cap)
            s = flat_pallas.flat_list_scan_auto(
                q, list_data, list_ids, li, list_sizes[li], list_norms, vmin, span,
                metric=metric, codec=codec, scan_bf16=scan_bf16,
            )  # (nq, g, cap), size/ids mask already applied in-kernel
        return _merge_group(carry, s.reshape(nq, g * cap), ids.reshape(nq, g * cap), k), None

    (vals, ids), _ = jax.lax.scan(body, init, groups)
    return vals, ids, jnp.zeros((2,), jnp.int32)


def _adc_pair_scores(lut, codes, sizes, use_pallas: bool):
    """ADC scores of P (query, probe) pairs: ``lut`` (P, m, ksub) f32 tables,
    ``codes`` (P, L, m) uint8, ``sizes`` (P,) int32 the rows each pair's list
    holds (0 for a pair the caller will mask whole) -> ((P, L) f32, the
    candidate columns scored as an int32 scalar). The one place the PQ
    programs (here and in parallel/mesh.py) pick between the fused
    three-plane kernel, which scores a list's whole sub-tiles and leaves the
    rest at -inf, and the XLA one-hot einsum, which scores every column;
    the caller masks past ``sizes`` either way. ``use_pallas`` is the
    index's answer (IVFPQIndex._kernel_applies), taken before the trace."""
    P, L, m = codes.shape
    if use_pallas:
        return (adc_pallas.adc_scan_pallas_planes(
                    lut, codes, sizes, interpret=not adc_pallas.on_tpu()),
                adc_pallas.scanned_columns(sizes, L))
    return pq.adc_scan(lut, codes), jnp.int32(P * L)


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "g", "metric", "use_pallas"))
def _ivf_pq_search(centroids, codebooks, list_codes, list_ids, list_sizes, q,
                   k: int, nprobe: int, g: int, metric: str,
                   use_pallas: bool = False):
    """-> (vals, ids, the candidate columns the scan computed ADC sums for:
    an int32 scalar, ``nq * nprobe * cap`` on the XLA arm)."""
    q = q.astype(jnp.float32)
    nq = q.shape[0]
    cap = list_codes.shape[1]
    m, ksub, dsub = codebooks.shape
    with jax.named_scope("coarse"):
        coarse = distance.pairwise_scores(q, centroids, metric)
        _, probes = distance.segmented_argtopk(coarse, nprobe)
        groups = probes.reshape(nq, nprobe // g, g).transpose(1, 0, 2)  # (ng, nq, g)

    if metric != "l2":
        with jax.named_scope("list_scan"):
            shared_lut = pq.adc_lut(q, codebooks, metric=metric)  # (nq, m, ksub)

    init = (
        jnp.full((nq, k), distance.NEG_INF, jnp.float32),
        jnp.full((nq, k), -1, jnp.int32),
    )

    def body(carry, li):  # (nq, g)
        with jax.named_scope("list_scan"):
            codes = list_codes[li]  # (nq, g, cap, m)
            ids = list_ids[li]
            sizes = list_sizes[li]
            if metric == "l2":
                r = q[:, None, :] - centroids[li]  # (nq, g, d) residuals
                lut = pq.adc_lut(r.reshape(nq * g, -1), codebooks, metric="l2")
                lut = lut.reshape(nq, g, m, ksub)
            else:
                lut = jnp.broadcast_to(shared_lut[:, None], (nq, g, m, ksub))
            s, cols = _adc_pair_scores(lut.reshape(nq * g, m, ksub),
                                       codes.reshape(nq * g, cap, m),
                                       sizes.reshape(nq * g), use_pallas)
            s = s.reshape(nq, g, cap)
            valid = (jnp.arange(cap)[None, None, :] < sizes[:, :, None]) & (ids >= 0)
            s = jnp.where(valid, s, distance.NEG_INF)
        return _merge_group(carry, s.reshape(nq, g * cap), ids.reshape(nq, g * cap), k), cols

    (vals, ids), cols = jax.lax.scan(body, init, groups)
    return vals, ids, jnp.sum(cols)


@functools.partial(jax.jit, static_argnames=("k", "scan_k", "nprobe", "g", "metric",
                                             "codec", "refine", "use_pallas",
                                             "scan_bf16", "tile", "group", "sub"))
def _ivf_flat_search_fused(centroids, list_data, list_ids, list_sizes, refine_data,
                           q3, k: int, scan_k: int, nprobe: int, g: int,
                           metric: str, codec: str, refine: bool,
                           vmin=None, span=None, list_norms=None,
                           use_pallas: bool = False, scan_bf16: bool = False,
                           tile: int = 1, group: int = 1, sub: int = 0,
                           counts=None):
    """Whole multi-block search in ONE device launch.

    q3: (nblocks, block, d); counts: (nblocks,) int32 real rows of each
    block (``_ivf_flat_search``'s ``nvalid``), or None. ``lax.map`` runs
    the per-block program sequentially on device, so the transient-memory
    budgets sized for one block still hold — but the host pays a single
    dispatch for the entire batch instead of one per block
    (benchmarks/profile_ivf.py). The third output is ``_ivf_flat_search``'s,
    one pair of counts a block."""

    def body(block):
        qb, nvalid = block
        vals, ids, scanned = _ivf_flat_search(
            centroids, list_data, list_ids, list_sizes, qb, scan_k, nprobe, g,
            metric, codec, vmin, span, list_norms, use_pallas=use_pallas,
            scan_bf16=scan_bf16, tile=tile, group=group, sub=sub, nvalid=nvalid)
        if refine:
            vals, ids = _rerank_exact(refine_data, qb, ids, k, metric)
        return vals, ids, scanned

    return jax.lax.map(body, (q3, counts))


@functools.partial(jax.jit, static_argnames=("k", "adc_k", "nprobe", "g", "metric",
                                             "use_pallas", "refine"))
def _ivf_pq_search_fused(centroids, codebooks, list_codes, list_ids, list_sizes,
                         refine_data, q3, k: int, adc_k: int, nprobe: int, g: int,
                         metric: str, use_pallas: bool, refine: bool):
    """Multi-block IVF-PQ search in one launch (see _ivf_flat_search_fused);
    the third output is ``_ivf_pq_search``'s, one count a block."""

    def body(qb):
        vals, ids, cols = _ivf_pq_search(centroids, codebooks, list_codes, list_ids,
                                         list_sizes, qb, adc_k, nprobe, g, metric,
                                         use_pallas=use_pallas)
        if refine:
            vals, ids = _rerank_exact(refine_data, qb, ids, k, metric)
        return vals, ids, cols

    return jax.lax.map(body, q3)


class _IVFBase(base.TpuIndex):
    """Shared coarse-quantizer + list bookkeeping for IVF variants."""

    def __init__(self, dim: int, nlist: int, metric: str, kmeans_iters: int = 10):
        super().__init__(dim, metric)
        if nlist < 1:
            raise ValueError("nlist must be >= 1")
        self.nlist = nlist
        self.kmeans_iters = kmeans_iters
        self.centroids = None  # jnp (nlist, d)
        self.lists: Optional[base.PaddedLists] = None
        # id -> (list, within-list position) map, the ONLY per-row host
        # state (8 bytes/row). Payload lives solely in the device lists;
        # reconstruct and persistence gather it back through this map
        # (VERDICT r4: the previous insertion-order payload mirror put the
        # whole corpus in host RAM a second time — ~1.5 TB at the reference
        # knnlm scale of 1e9 x 768 fp16).
        self._host_assign = []  # list of np int32 chunks, list idx in id order
        self._host_pos = []  # list of np int32 chunks, within-list slot in id order
        self._n = 0

    @property
    def is_trained(self) -> bool:
        return self.centroids is not None

    @property
    def ntotal(self) -> int:
        return self._n

    def get_centroids(self) -> Optional[np.ndarray]:
        if self.centroids is None:
            return None
        return np.asarray(self.centroids)

    def get_assignments(self) -> np.ndarray:
        """Coarse-list assignment of every added row, in insertion order.

        Public counterpart of get_centroids for tooling that needs the
        host-side inverted-list structure (e.g. the CPU-IVF baseline in
        benchmarks/baseline_configs.py)."""
        return self._host_assign_array()

    def _assign_host(self, x: np.ndarray, chunk: int = None) -> np.ndarray:
        # bound the (chunk, nlist) fp32 score block — a fixed chunk would
        # blow up at the 65k/262k centroid tiers
        chunk = kmeans.auto_chunk(self.nlist, chunk)
        out = np.empty(x.shape[0], np.int64)
        for s in range(0, x.shape[0], chunk):
            # graftlint: ok(host-sync): designed chunked host fetch — assignments land in a preallocated host buffer; chunking exists to bound the (chunk, nlist) device transient (ingest path, reached from search only via name-collision propagation)
            out[s : s + chunk] = np.asarray(
                _coarse_assign(self.centroids, jnp.asarray(x[s : s + chunk]), self.metric)
            )
        return out

    def _train_centroids(self, x: np.ndarray):
        self.centroids = kmeans.kmeans(x, self.nlist, iters=self.kmeans_iters)

    def add(self, x: np.ndarray) -> None:
        if not self.is_trained:
            raise RuntimeError("IVF index must be trained before add")
        x = np.asarray(x, np.float32)
        if x.shape[0] == 0:
            return
        assign = self._assign_host(x)
        rows = self._encode(x, assign)
        gids = np.arange(self._n, self._n + x.shape[0], dtype=np.int64)
        pos = self.lists.append(assign, rows, gids)
        self._append_extra(x, assign, gids, rows)
        self._host_assign.append(assign.astype(np.int32))
        self._host_pos.append(pos)
        self._n += x.shape[0]

    def _host_assign_array(self) -> np.ndarray:
        if len(self._host_assign) > 1:
            self._host_assign = [np.concatenate(self._host_assign)]
        return self._host_assign[0] if self._host_assign else np.zeros((0,), np.int32)

    def _host_pos_array(self) -> np.ndarray:
        if len(self._host_pos) > 1:
            self._host_pos = [np.concatenate(self._host_pos)]
        return self._host_pos[0] if self._host_pos else np.zeros((0,), np.int32)

    def remove_rows(self, rows: np.ndarray) -> None:
        """Tombstone rows out of the inverted lists: scatter -1 into the
        device ids plane at the rows' (slot, pos) cells. Every scan entry —
        the XLA probe scan, the fused pallas flat/ADC kernels, and the
        mesh-sharded masked/routed programs — already ANDs ``ids >= 0``
        with the size mask, so a tombstoned cell is indistinguishable from
        padding to all of them, and the delete-nothing case (no scatter)
        stays byte-identical to the pre-mutation program."""
        rows = np.asarray(rows, np.int64)
        if rows.size == 0 or self.lists is None:
            return
        assign = self._host_assign_array()[rows].astype(np.int64)
        pos = self._host_pos_array()[rows].astype(np.int64)
        cells = np.asarray(self.lists.slot_of(assign)) * self.lists.cap + pos
        self.lists.mask_cells(cells)

    def _device_rows(self, ids: np.ndarray) -> np.ndarray:
        """Stored payload rows (encoded) for global ids, gathered from the
        device lists — one bucketed launch, no host corpus mirror."""
        ids = np.asarray(ids, np.int64)
        return base.gather_list_rows(
            self.lists, self._host_assign_array()[ids], self._host_pos_array()[ids]
        )

    def _rows_in_insertion_order(self, chunk: int = 1 << 20, lists=None) -> np.ndarray:
        """Stream the full encoded payload back from device in id order
        (persistence). Host cost is the output array itself — the same bytes
        the save file needs — plus one chunk of gather transients. ``lists``
        selects a sidecar sharing the payload lists' (assign, pos) layout
        (e.g. the stored-norms lists); default is the payload lists."""
        lists = lists if lists is not None else self.lists
        out = np.zeros((self._n,) + tuple(lists.payload_shape), lists.dtype)
        assign, pos = self._host_assign_array(), self._host_pos_array()
        for s in range(0, self._n, chunk):
            e = min(self._n, s + chunk)
            ids = np.arange(s, e, dtype=np.int64)
            out[s:e] = base.gather_list_rows(lists, assign[ids], pos[ids])
        return out

    def _launch_blocks(self, q: np.ndarray, k: int, fn, block: int = 256,
                       fused_fn=None, refine_fn=None,
                       with_counts: bool = False) -> base.SearchHandle:
        """Blocked search driver — see ``models.base.launch_blocked_search``
        (the single shared implementation: one launch per block by default;
        with ``fused_fn`` a multi-block batch runs in ONE lax.map launch,
        with the pow2-bucketing and memory-cliff rationale documented
        there; ``refine_fn`` is the per-block exact rerank, dispatched
        behind ``fn``'s scan)."""
        return base.launch_blocked_search(q, k, self.metric, fn, block,
                                          fused_fn, refine_fn, with_counts)

    def _search_blocks(self, q: np.ndarray, k: int, fn, block: int = 256,
                       fused_fn=None, refine_fn=None, with_counts: bool = False):
        """``_launch_blocks`` and its collect in one call (the mesh
        indexes, whose scan callables wait themselves)."""
        return self._launch_blocks(q, k, fn, block, fused_fn, refine_fn,
                                   with_counts).collect()

    def _empty_results(self, nq: int, k: int):
        d = np.full((nq, k), np.inf if self.metric == "l2" else -np.inf, np.float32)
        return d, np.full((nq, k), -1, np.int64)

    # subclass hooks
    def _encode(self, x: np.ndarray, assign: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def _append_extra(self, x: np.ndarray, assign: np.ndarray, gids: np.ndarray,
                      rows: np.ndarray) -> None:
        """Hook: store side-car payloads (raw rows for exact refine, stored
        row norms for the flat scan). ``rows`` is the encoded payload the
        lists just stored — norms must be computed from the DECODED stored
        value, not the fp32 input, to stay bit-identical to an in-scan
        recompute."""


def clip_f16(x: np.ndarray) -> np.ndarray:
    """fp32 -> fp16 with clipping: an out-of-range component would store inf
    and poison that row's refined score to -inf forever."""
    f16max = np.float16(np.finfo(np.float16).max)
    return np.clip(np.asarray(x, np.float32), -f16max, f16max).astype(np.float16)


def _first_use_check(index, scan, probe, kernel: str, tol: float) -> None:
    """First-use oracle check of a pallas scan kernel: run ``scan(probe,
    True)`` (the kernel) and ``scan(probe, False)`` (the XLA path) on one
    tiny padded block and demote the kernel for this process
    (``index._pallas_runtime_ok = False``, logged, shown in
    ``ping()["kernels"]["pallas_degraded"]``) if the scores disagree past
    ``tol`` (relative and absolute) — ``pallas_guarded`` catches a kernel
    that raises, this one that runs and returns wrong numbers. A probe
    where BOTH paths fail is a bad request — leave the kernel alone and let
    the real search surface the error through pallas_guarded."""
    try:
        pv = scan(probe, True)[0]
        jax.block_until_ready(pv)
    except Exception:
        try:
            jax.block_until_ready(scan(probe, False))
        except Exception:
            return  # both failed: request/state problem, not the kernel
        index._pallas_runtime_ok = False
        logger.exception(
            "pallas %s kernel failed its first-use oracle check; using the "
            "XLA scan for the rest of this process", kernel)
        return
    xv = scan(probe, False)[0]
    with xfercheck.explicit("first-use oracle check fetch"):
        pv, xv = np.asarray(pv), np.asarray(xv)
    finite = np.isfinite(xv)
    if not (np.array_equal(finite, np.isfinite(pv))
            and np.allclose(pv[finite], xv[finite], rtol=tol, atol=tol)):
        index._pallas_runtime_ok = False
        logger.error(
            "pallas %s kernel disagrees with the XLA oracle on first use "
            "(max delta %.3g); using the XLA scan", kernel,
            float(np.max(np.abs(pv[finite] - xv[finite]))) if finite.any() else 0.0,
        )


class IVFFlatIndex(_IVFBase):
    """IVF with raw/fp16/sq8 vector payloads.

    codec 'f32' == reference ivf_simple (IndexIVFFlat, index.py:36-40);
    codec 'f16' == reference ivfsq QT_fp16 (index.py:63-68);
    codec 'sq8' == factory spec "IVF{centroids},SQ8" (scripts/idx_cfg.json).
    """

    _DTYPES = {"f32": np.float32, "f16": np.float16, "sq8": np.uint8}

    def __init__(self, dim: int, nlist: int, metric: str = "l2", codec: str = "f32",
                 kmeans_iters: int = 10, refine_k_factor: int = 0,
                 use_pallas: bool = False, scan_bf16: bool = False):
        super().__init__(dim, nlist, metric, kmeans_iters)
        if codec not in self._DTYPES:
            raise ValueError(f"unknown ivf_flat codec {codec!r}")
        self.codec = codec
        self.sq_params = None
        # exact fp16 rerank of the top k*refine_k_factor (factory "RFlat"
        # suffix). Meaningful for the sq8 codec (codec noise) and for any
        # codec under scan_bf16 (bf16 matmul noise); otherwise the f16 list
        # codec already matches the refine store's precision and f32 is exact
        if refine_k_factor and codec != "sq8" and not scan_bf16:
            logging.getLogger().warning(
                "refine_k_factor on the %s codec adds no precision over the "
                "stored lists; disabled", codec
            )
            refine_k_factor = 0
        if scan_bf16 and not refine_k_factor:
            raise ValueError(
                "scan_bf16 perturbs scan scores (bf16 MXU pass) and is only "
                "legal with refine_k_factor > 0 so the shortlist is rescored exactly"
            )
        self.refine_k_factor = int(refine_k_factor)
        self.refine_store = (
            base.DeviceVectorStore((dim,), jnp.float16) if self.refine_k_factor else None
        )
        # fused VMEM list-scan kernel (ops/flat_pallas.py); guarded like the
        # ADC kernel — oracle-checked on first use, runtime demotion to the
        # XLA path on kernel fault (never persisted)
        self.use_pallas = bool(use_pallas)
        self.scan_bf16 = bool(scan_bf16)
        self._pallas_runtime_ok = True
        self._pallas_flat_validated = False
        # stored-norms scan is the default; the recompute path stays as the
        # bit-exact golden reference and the profile_ivf A/B arm
        self.use_stored_norms = True
        self.norm_lists = None  # (nlist, cap) fp32 sidecar, layout == lists

    def _make_lists(self):
        # exact fp32 ||x||^2 per stored row, appended in lockstep with the
        # payload (same assign/gids stream -> same (slot, pos) layout and
        # capacity growth), so the scan gathers (nq, g, cap) norms instead
        # of re-deriving them from the block every query. Only l2 ever
        # reads norms — a dot index skips the sidecar entirely (no extra
        # HBM, no per-add launch, no snapshot payload).
        if self.metric == "l2":
            self.norm_lists = base.PaddedLists(self.nlist, (), np.float32)
        return base.PaddedLists(self.nlist, (self.dim,), self._DTYPES[self.codec])

    def train(self, x: np.ndarray) -> None:
        x = np.asarray(x, np.float32)
        self._train_centroids(x)
        if self.codec == "sq8":
            self.sq_params = sq.sq8_train(x)
        self.lists = self._make_lists()

    def _encode(self, x: np.ndarray, assign: np.ndarray) -> np.ndarray:
        if self.codec == "sq8":
            return np.asarray(sq.sq8_encode(x, self.sq_params["vmin"], self.sq_params["span"]))
        return x.astype(self._DTYPES[self.codec])

    def _row_norms(self, rows: np.ndarray, chunk: int = 1 << 20) -> np.ndarray:
        """Exact fp32 ||x||^2 of ENCODED rows after decode — the same decode
        + minor-axis fp32 sum the scan's recompute path runs, so stored and
        recomputed norms are bit-identical (golden-equality tests). Chunked:
        the snapshot-backfill caller hands the whole corpus at once, and an
        unchunked decode would materialize an (n, d) fp32 transient (~300 GB
        at the 1e8 x 768 rehearsal scale)."""
        out = np.empty(rows.shape[0], np.float32)
        for s in range(0, rows.shape[0], chunk):
            r = jnp.asarray(rows[s:s + chunk])
            if self.codec == "sq8":
                r = sq.sq8_decode(r, self.sq_params["vmin"], self.sq_params["span"])
            # graftlint: ok(host-sync): designed chunked host fetch — norms land in a preallocated host buffer; the chunking bounds the decode transient (~300 GB unchunked at rehearsal scale; save/backfill path, not serving)
            out[s:s + chunk] = np.asarray(base.row_norms_f32(r))
        return out

    def _append_extra(self, x: np.ndarray, assign: np.ndarray, gids: np.ndarray,
                      rows: np.ndarray) -> None:
        if self.refine_store is not None:
            self.refine_store.add(clip_f16(x))
        if self.norm_lists is not None:
            self.norm_lists.append(assign, self._row_norms(rows), gids)

    def _scan_norms(self):
        if not (self.use_stored_norms and self.norm_lists is not None):
            return None
        if self.norm_lists.cap != self.lists.cap:
            # loud failure (survives python -O, unlike an assert): stale
            # (slot, pos) norm gathers would silently corrupt l2 scores
            raise RuntimeError(
                f"norm/payload list capacities diverged "
                f"({self.norm_lists.cap} != {self.lists.cap})")
        return self.norm_lists.data

    _PALLAS_KERNEL = "flat scan"

    def _kernel_applies(self) -> bool:
        """pallas_guarded's question: the flat-scan kernel runs where asked."""
        return self.use_pallas

    def _validate_flat_pallas(self, scan) -> None:
        self._pallas_flat_validated = True
        _first_use_check(self, scan, self._pallas_probe, self._PALLAS_KERNEL, 1e-3)

    def _scan_tiling(self, rows: int, nprobe: int):
        """(tile, group, sub) of the XLA probe scan for a block of ``rows``
        query rows: the one place they are decided, asked at every search
        since the capacity and the fill grow with the lists
        (``listmajor_tiling`` is the rule)."""
        return listmajor_tiling(rows, nprobe, self.nlist, self.lists.cap,
                                self.dim, np.dtype(self.lists.dtype).itemsize,
                                fill=self._n / (self.nlist * self.lists.cap))

    def search(self, q: np.ndarray, k: int):
        return self.launch_search(q, k).collect()

    def launch_search(self, q: np.ndarray, k: int) -> base.SearchHandle:
        if self._n == 0:
            return base.finished(self._empty_results(q.shape[0], k))
        nprobe = min(self.nprobe, self.nlist)
        # nb: rows a block, launch-bound-aware (see base.pick_query_block);
        # g: probes a step of the Pallas arm, sized as for a gathered fp32
        # (nb, g, cap, d) block it never materializes. The XLA arm scans
        # list-major under (tile, group).
        nb = base.pick_query_block(self.lists.cap * self.dim * 4)
        g = probe_group_size(nprobe, nb * self.lists.cap * self.dim * 4)
        # the block this call launches: a batch under one block pads to its
        # own pow2 bucket, not to nb
        rows = nb if q.shape[0] > nb else distance.bucket_size(q.shape[0])
        tile, group, sub = self._scan_tiling(rows, nprobe)
        extra = dict(tile=tile, group=group, sub=sub)
        if self.codec == "sq8":
            extra.update(vmin=self.sq_params["vmin"], span=self.sq_params["span"])
        norms = self._scan_norms()
        scan_k = k * self.refine_k_factor if self.refine_k_factor else k
        # the index as this launch finds it: every program of the search,
        # the collect's oracle included, reads these operands, whatever an
        # add replaces on the index before the collect
        lists = (self.centroids, self.lists.data, self.lists.ids, self.lists.sizes)
        refine_rows = self.refine_store.data if self.refine_k_factor else None

        counts = []  # the count output of every scan the XLA arm served

        def launched(out):
            return _count_on_its_way(out, "list rows count")

        def scan(b, with_pallas, nvalid=None):
            # maybe_checked = GRAFT_SANITIZE=1 checkify wrapper (identity
            # when off); scalar knobs ride as kwargs so the sanitizer can
            # partial-bind them before checkify abstracts the operands
            return launched(sanitize.maybe_checked(
                _ivf_flat_search, *lists,
                b, k=scan_k, nprobe=nprobe, g=g, metric=self.metric,
                codec=self.codec, list_norms=norms, use_pallas=with_pallas,
                scan_bf16=self.scan_bf16, nvalid=nvalid, **extra,
            ))

        if self.use_pallas and self._pallas_runtime_ok and not self._pallas_flat_validated:
            self._pallas_probe = jnp.asarray(
                distance.pad_rows(np.asarray(q[:8], np.float32), 8))
            self._validate_flat_pallas(scan)

        def listmajor(out, with_pallas):
            """The count row that says the scan's program took the
            list-major order (``engine.scan_listmajor``, beside the
            ``engine.scan`` stage whose wait books it): the XLA arm does,
            the Pallas kernel scans query-major; the last path tried is the
            one served, and its counts are the ones booked."""
            vals, ids, scanned = out
            if not with_pallas:
                tracing.count("engine.scan_listmajor")
                counts.append(scanned)
            return vals, ids

        def run(b, n):
            return GuardedScan(self, lambda p: scan(b, p, n), listmajor)

        def refine(b, ids):
            return _rerank_exact(refine_rows, b, ids, k, self.metric)

        def run_fused(q3, nvalid):
            return GuardedScan(
                self, lambda p: launched(sanitize.maybe_checked(
                    _ivf_flat_search_fused, *lists, refine_rows,
                    q3, k=k, scan_k=scan_k, nprobe=nprobe, g=g,
                    metric=self.metric, codec=self.codec,
                    refine=bool(self.refine_k_factor), list_norms=norms,
                    use_pallas=p, scan_bf16=self.scan_bf16, counts=nvalid,
                    **extra,
                )), listmajor)

        pending = self._launch_blocks(
            q, k, run, block=nb, fused_fn=run_fused,
            refine_fn=refine if self.refine_k_factor else None,
            with_counts=True)

        def collect():
            out = pending.collect()
            self._book_list_rows(counts, sub)
            return out

        return base.SearchHandle(collect)

    @staticmethod
    def _book_list_rows(counts, sub: int) -> None:
        """Two count rows a list-major scan, beside ``engine.scan_listmajor``,
        from the program's third output (sub-blocks of ``sub`` rows: those
        of the scan's tiles had every list been full, and those it
        gathered): ``engine.scan_list_rows`` (rows of the tiles at whole
        capacity: what a scan of whole padded lists gathers and multiplies)
        and ``engine.scan_list_rows_skipped`` (those of them in sub-blocks
        past the end of their list, which the scan never gathers). Called
        once the search's results are on the host, as
        ``IVFPQIndex._book_adc_cols`` is and for its reason: by then the
        counts, whose copies started with their launches, have landed."""
        for scanned in counts:
            with xfercheck.explicit("list rows count fetch"):
                whole, live = np.asarray(scanned).reshape(-1, 2).sum(0, dtype=np.int64)
            tracing.count("engine.scan_list_rows", float(whole * sub))
            tracing.count("engine.scan_list_rows_skipped", float((whole - live) * sub))

    def reconstruct_batch(self, ids: np.ndarray) -> np.ndarray:
        rows = self._device_rows(ids)
        if self.codec == "sq8":
            # graftlint: ok(host-sync): reconstruct returns host rows by contract
            return np.asarray(sq.sq8_decode(jnp.asarray(rows), self.sq_params["vmin"], self.sq_params["span"]))
        return rows.astype(np.float32)

    def state_dict(self) -> Dict[str, np.ndarray]:
        state = {
            "kind": "ivf_flat",
            "dim": self.dim,
            "metric": self.metric,
            "codec": self.codec,
            "nlist": self.nlist,
            "nprobe": self.nprobe,
            "trained": self.is_trained,
            "refine_k_factor": self.refine_k_factor,
            "use_pallas": self.use_pallas,
            "scan_bf16": self.scan_bf16,
        }
        if self.is_trained:
            state["centroids"] = np.asarray(self.centroids)
            state["rows"] = self._rows_in_insertion_order()
            state["assign"] = self._host_assign_array()
            if self._n and self.norm_lists is not None:
                state["list_norms"] = self._rows_in_insertion_order(
                    lists=self.norm_lists)
            if self.sq_params is not None:
                state["sq_vmin"] = np.asarray(self.sq_params["vmin"])
                state["sq_span"] = np.asarray(self.sq_params["span"])
            if self.refine_store is not None:
                state["refine_rows"] = self.refine_store.all_rows()
        return state

    def _restore_norms(self, state, rows, assign, gids) -> None:
        """Append the norms sidecar on load: from the snapshot when present,
        else backfilled from the decoded rows (pre-norms snapshots) — the
        two are bit-identical by construction (_row_norms)."""
        if self.norm_lists is None:  # dot metric: no sidecar to restore
            return
        if "list_norms" in state:
            norms = np.asarray(state["list_norms"], np.float32)
        else:
            logger.info(
                "snapshot predates stored norms: backfilling %d row norms "
                "from the decoded payload", rows.shape[0])
            norms = self._row_norms(rows)
        self.norm_lists.append(assign, norms, gids)

    @classmethod
    def from_state_dict(cls, state) -> "IVFFlatIndex":
        idx = cls(int(state["dim"]), int(state["nlist"]), str(state["metric"]), str(state["codec"]),
                  refine_k_factor=int(state.get("refine_k_factor", 0)),
                  use_pallas=bool(state.get("use_pallas", False)),
                  scan_bf16=bool(state.get("scan_bf16", False)))
        idx.nprobe = int(state["nprobe"])
        if not bool(state["trained"]):
            return idx
        idx.centroids = jnp.asarray(state["centroids"])
        if "sq_vmin" in state:
            idx.sq_params = {"vmin": jnp.asarray(state["sq_vmin"]), "span": jnp.asarray(state["sq_span"])}
        idx.lists = idx._make_lists()
        rows, assign = state["rows"], state["assign"]
        if rows.shape[0]:
            gids = np.arange(rows.shape[0], dtype=np.int64)
            pos = idx.lists.append(assign, rows, gids)
            idx._host_assign = [assign.astype(np.int32)]
            idx._host_pos = [pos]
            idx._n = rows.shape[0]
            idx._restore_norms(state, rows, assign, gids)
            if idx.refine_store is not None:
                idx.refine_store.add(np.asarray(state["refine_rows"], np.float16))
        return idx


def _count_on_its_way(out, what: str):
    """A scan program's outputs, the copy of its count (the third) to the
    host started with the launch: by the time the search's results are
    fetched it has landed, and booking it costs no device-to-host latency
    (0.4 ms on a v5e even for four ready bytes: PERF.md, PR 35)."""
    with xfercheck.explicit(what + ", started with the launch"):
        out[2].copy_to_host_async()
    return out


class GuardedScan(base.Dispatched):
    """``call(use_pallas)`` on the ladder kernel -> XLA oracle -> demote, in
    two halves: the dispatch (here) and ``wait()`` (``blocked_search``'s
    collect), with ``pallas_guarded`` the two in one call.

    The kernel runs where the index wants it (``index._kernel_applies()``)
    and this process has not demoted it. If that attempt raises — at the
    dispatch (a trace or compile fault) or at the wait, where an
    asynchronous kernel abort surfaces — the XLA path runs as a
    side-effect-free oracle, on the block the handle still holds. It raises
    too: the request itself is bad (a dim mismatch fails in the shared
    coarse scoring) — re-raise, no flag flipped, no cache cleared, so one
    misbehaving client cannot cost the others their kernel. It returns: the
    kernel is at fault — log, demote it for the rest of this process
    (``_pallas_runtime_ok``; never persisted, listed in
    ``ping()["kernels"]["pallas_degraded"]``) and serve the oracle's
    result. ``settled(out, with_pallas)`` is handed the path that was
    served, for the count row it books."""

    def __init__(self, index, call, settled=None):
        self.index, self.call, self._settled = index, call, settled
        self.with_pallas = index._kernel_applies() and index._pallas_runtime_ok
        try:
            self.out = call(self.with_pallas)
        except Exception:
            self.out = self._oracle()

    def _oracle(self):
        """Called where the attempt raised (inside the handler): the XLA
        path's outputs, waited for, or the attempt's error again."""
        if not self.with_pallas:
            raise
        out = jax.block_until_ready(self.call(False))  # raises: a bad request
        logger.exception(
            "pallas kernel (%s) failed on this backend; using the XLA path "
            "for the rest of this process (persisted use_pallas intent is "
            "unchanged)", self.index._PALLAS_KERNEL)
        self.index._pallas_runtime_ok = False
        self.with_pallas = False
        return out

    def wait(self):
        try:
            out = self._ready()
        except Exception:
            out = self.out = self._oracle()
        return out if self._settled is None else self._settled(out, self.with_pallas)


def pallas_guarded(index, call):
    """``GuardedScan``'s ladder with no time between dispatch and wait: what
    a caller with nothing to do meanwhile uses (the mesh indexes)."""
    return GuardedScan(index, call).wait()


class IVFPQIndex(_IVFBase):
    """IVF-PQ: inverted lists of m uint8 codes per vector, ADC search.

    Parity target: reference `knnlm` builder (IndexIVFPQ with
    code_size=m, nbits=8, distributed_faiss/index.py:43-48).
    """

    def __init__(self, dim: int, nlist: int, m: int = 64, nbits: int = 8,
                 metric: str = "l2", kmeans_iters: int = 10, pq_iters: int = 15,
                 use_pallas: Optional[bool] = None, refine_k_factor: int = 0):
        super().__init__(dim, nlist, metric, kmeans_iters)
        if dim % m != 0:
            raise ValueError(f"dim {dim} not divisible by PQ m={m}")
        if nbits != 8:
            raise ValueError("only 8-bit PQ codes supported (uint8 storage)")
        self.m = m
        self.nbits = nbits
        self.pq_iters = pq_iters
        # fused ADC kernel instead of the XLA one-hot: None = the index
        # chooses from what it can see; True / False force (tests, A/B runs,
        # the knnlm builder's ``pallas_adc`` extra) — _kernel_applies
        self.use_pallas = None if use_pallas is None else bool(use_pallas)
        self._pallas_runtime_ok = True  # runtime disable, not persisted
        self._adc_validated = False  # first fused scan checked against XLA
        # refine_k_factor > 0: keep fp16 raw rows in HBM and exactly rescore
        # the top k*refine_k_factor ADC candidates (FAISS IndexRefine-style;
        # what lifts PQ configs past recall 0.95)
        if int(refine_k_factor) != refine_k_factor or int(refine_k_factor) < 0:
            raise ValueError(f"refine_k_factor must be a non-negative int, got {refine_k_factor!r}")
        self.refine_k_factor = int(refine_k_factor)
        self.refine_store = (
            base.DeviceVectorStore((dim,), jnp.float16) if self.refine_k_factor else None
        )
        self.codebooks = None  # (m, 256, dsub)

    @property
    def is_trained(self) -> bool:
        return self.centroids is not None and self.codebooks is not None

    def _make_lists(self):
        return base.PaddedLists(self.nlist, (self.m,), np.uint8)

    _PALLAS_KERNEL = "ADC three-plane"

    def _kernel_applies(self) -> bool:
        """Does the fused ADC kernel run for this search? The one place that
        is decided, asked at every search since the capacity grows with the
        lists. ``use_pallas`` False: never. Else only at a geometry the
        three-plane kernel compiles for (ksub 256, capacity in whole 128-row
        tiles, a table of m inside the VMEM model); anything else runs the
        XLA one-hot. There None takes the kernel where the code can see a
        TPU (elsewhere it would run in the interpreter) and True wherever
        the geometry holds (tests, the sharded index, chip_smoke.py's A/B)."""
        if self.use_pallas is False or self.lists is None:
            return False
        return (adc_pallas.planes_supported(self.m, 1 << self.nbits, self.lists.cap)
                and (self.use_pallas is True or adc_pallas.on_tpu()))

    @staticmethod
    def _fused_counted(out, with_pallas):
        """The count row that says the scan ran the fused kernel
        (``engine.scan_fused``, beside the ``engine.scan`` stage whose wait
        books it): the last path tried is the one whose result is served."""
        if with_pallas:
            tracing.count("engine.scan_fused")
        return out

    def _guarded_scan(self, call):
        """pallas_guarded plus ``engine.scan_fused``, dispatch and wait in
        one call (the sharded index's scans)."""
        return GuardedScan(self, call, self._fused_counted).wait()

    @staticmethod
    def _book_adc_cols(counts) -> None:
        """Two count rows a scan, beside ``engine.scan_fused``, from
        ``counts``' (columns of the scan's pairs at their whole capacity,
        the program's third output): ``engine.scan_adc_cols`` and
        ``engine.scan_adc_cols_skipped`` (those the ADC scan did not compute:
        the kernel stops at the end of each list, the XLA one-hot skips
        none). Called once the search's results are on the host: a
        device-to-host read is 0.4 ms of latency on a v5e even for four
        ready bytes (PERF.md, PR 35), and by then the counts, whose copies
        started with their launches, have landed."""
        for adc_cols, cols in counts:
            with xfercheck.explicit("ADC column count fetch"):
                scored = int(np.sum(np.asarray(cols), dtype=np.int64))
            tracing.count("engine.scan_adc_cols", float(adc_cols))
            tracing.count("engine.scan_adc_cols_skipped", float(adc_cols - scored))

    def train(self, x: np.ndarray) -> None:
        x = np.asarray(x, np.float32)
        self._train_centroids(x)
        if self.metric == "l2":
            assign = self._assign_host(x)
            train_vecs = x - np.asarray(self.centroids)[assign]
        else:
            train_vecs = x
        self.codebooks = pq.pq_train(train_vecs, self.m, iters=self.pq_iters)
        self.lists = self._make_lists()

    def _encode(self, x: np.ndarray, assign: np.ndarray) -> np.ndarray:
        if self.metric == "l2":
            x = x - np.asarray(self.centroids)[assign]
        return np.asarray(pq.pq_encode(jnp.asarray(x), self.codebooks))

    def _append_extra(self, x: np.ndarray, assign: np.ndarray, gids: np.ndarray,
                      rows: np.ndarray) -> None:
        if self.refine_store is not None:
            self.refine_store.add(clip_f16(x))

    def search(self, q: np.ndarray, k: int):
        return self.launch_search(q, k).collect()

    def launch_search(self, q: np.ndarray, k: int) -> base.SearchHandle:
        if self._n == 0:
            return base.finished(self._empty_results(q.shape[0], k))
        nprobe = min(self.nprobe, self.nlist)
        # group payload: codes + ids + lut + score blocks (the one-hot feeds
        # the MXU contraction without full materialization)
        nb = base.pick_query_block(self.lists.cap * (self.m + 8) + self.m * 256 * 4)
        # the group is sized for the block this call launches: a batch under
        # one block pads to its own pow2 bucket, not to nb, and a probe of
        # it gathers that much less — a short window then scans its probes
        # in a few loop steps (one, online) and not in nprobe of them, each
        # with its own gather, top-k merge and tens of device ops
        rows = nb if q.shape[0] > nb else distance.bucket_size(q.shape[0])
        cap = self.lists.cap
        g = probe_group_size(
            nprobe, pq_probe_payload_bytes(cap, self.m, nq_block=rows))
        adc_k = k * self.refine_k_factor if self.refine_k_factor else k
        # the index as this launch finds it: every program of the search,
        # the collect's oracle included, reads these operands, whatever an
        # add replaces on the index before the collect
        lists = (self.centroids, self.codebooks, self.lists.data, self.lists.ids,
                 self.lists.sizes)
        refine_rows = self.refine_store.data if self.refine_k_factor else None

        counts = []  # (capacity columns, columns scored) of every scan

        def launched(out):
            return _count_on_its_way(out, "ADC column count")

        def counted(rows):
            """At a scan's wait: its count taken off its outputs."""

            def settled(out, with_pallas):
                vals, ids, cols = self._fused_counted(out, with_pallas)
                counts.append((rows * nprobe * cap, cols))
                return vals, ids

            return settled

        def adc(b, with_pallas):
            return launched(sanitize.maybe_checked(
                _ivf_pq_search, *lists, b, k=adc_k, nprobe=nprobe, g=g,
                metric=self.metric, use_pallas=with_pallas,
            ))

        if (self._kernel_applies() and self._pallas_runtime_ok
                and not self._adc_validated):
            # first fused scan of this index (warm-up, in a served rank):
            # its ADC scores against the XLA path's on one small block
            self._adc_validated = True
            _first_use_check(
                self, adc,
                jax.device_put(distance.pad_rows(np.asarray(q[:8], np.float32), 8)),
                self._PALLAS_KERNEL, 1e-4)

        def run(b):
            return GuardedScan(self, lambda p: adc(b, p), counted(b.shape[0]))

        def refine(b, ids):
            return _rerank_exact(refine_rows, b, ids, k, self.metric)

        def adc_fused(q3, with_pallas):
            return launched(sanitize.maybe_checked(
                _ivf_pq_search_fused, *lists, refine_rows,
                q3, k=k, adc_k=adc_k, nprobe=nprobe, g=g, metric=self.metric,
                use_pallas=with_pallas,
                refine=bool(self.refine_k_factor),
            ))

        def run_fused(q3):
            return GuardedScan(self, lambda p: adc_fused(q3, p),
                               counted(q3.shape[0] * q3.shape[1]))

        pending = self._launch_blocks(
            q, k, run, block=nb, fused_fn=run_fused,
            refine_fn=refine if self.refine_k_factor else None)

        def collect():
            out = pending.collect()
            self._book_adc_cols(counts)
            return out

        return base.SearchHandle(collect)

    def reconstruct_batch(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        codes = self._device_rows(ids)
        # graftlint: ok(host-sync): reconstruct returns host rows by contract
        rec = np.asarray(pq.pq_decode(jnp.asarray(codes), self.codebooks))
        if self.metric == "l2":
            assign = self._host_assign_array()[ids]
            rec = rec + np.asarray(self.centroids)[assign]
        return rec

    def state_dict(self) -> Dict[str, np.ndarray]:
        state = {
            "kind": "ivf_pq",
            "dim": self.dim,
            "metric": self.metric,
            "nlist": self.nlist,
            "m": self.m,
            "nbits": self.nbits,
            "nprobe": self.nprobe,
            "trained": self.is_trained,
            "refine_k_factor": self.refine_k_factor,
            # the kernel intent as held: None = choose, True / False = forced
            "pallas_adc": self.use_pallas,
        }
        if self.is_trained:
            state["centroids"] = np.asarray(self.centroids)
            state["codebooks"] = np.asarray(self.codebooks)
            state["rows"] = self._rows_in_insertion_order()
            state["assign"] = self._host_assign_array()
            if self.refine_store is not None:
                state["refine_rows"] = self.refine_store.all_rows()
        return state

    @staticmethod
    def _saved_kernel_intent(state) -> Optional[bool]:
        """``use_pallas`` as a snapshot holds it. One from before
        ``pallas_adc`` holds a bool ``use_pallas`` only, and its False was
        the default of the time, not a choice: it loads as "choose" and must
        not pin the XLA path; its True was asked for. An old snapshot's
        ``adc_lut_bf16`` (a rounded table) is not read: exact tables serve."""
        if "pallas_adc" in state:
            return state["pallas_adc"]
        return True if state.get("use_pallas") else None

    @classmethod
    def from_state_dict(cls, state) -> "IVFPQIndex":
        idx = cls(int(state["dim"]), int(state["nlist"]), int(state["m"]),
                  int(state["nbits"]), str(state["metric"]),
                  use_pallas=cls._saved_kernel_intent(state),
                  refine_k_factor=int(state.get("refine_k_factor", 0)))
        idx.nprobe = int(state["nprobe"])
        if not bool(state["trained"]):
            return idx
        idx.centroids = jnp.asarray(state["centroids"])
        idx.codebooks = jnp.asarray(state["codebooks"])
        idx.lists = idx._make_lists()
        rows, assign = state["rows"], state["assign"]
        if rows.shape[0]:
            pos = idx.lists.append(assign, rows, np.arange(rows.shape[0], dtype=np.int64))
            idx._host_assign = [assign.astype(np.int32)]
            idx._host_pos = [pos]
            idx._n = rows.shape[0]
        if idx.refine_store is not None and "refine_rows" in state:
            idx.refine_store.add(np.asarray(state["refine_rows"], np.float16))
        return idx
