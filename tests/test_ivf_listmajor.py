"""The IVF-flat probe scan in list-major order (PR 31): each probed list is
gathered once per tile of queries that probe it and multiplied against all
of them — since PR 43 a live sub-block of it at a time, the sub-blocks past
the end of a list never gathered. It must give the neighbours a plain numpy
scan of the probed lists gives — over codec, metric, stored or recomputed
norms, every block size with its zero padding, lists of every size from
empty to full, removed ids and the most skewed probes there can be — and
drop no (query, probe) pair whatever the probes and the sizes."""

import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_faiss_tpu.models import base
from distributed_faiss_tpu.models import ivf as ivfmod
from distributed_faiss_tpu.models.ivf import IVFFlatIndex
from distributed_faiss_tpu.ops import distance, sq


def build(rng, codec="f16", metric="l2", d=24, n=2500, nlist=16, nprobe=5, **kw):
    x = rng.standard_normal((n, d)).astype(np.float32) * 2.0
    idx = IVFFlatIndex(d, nlist, metric, codec=codec, kmeans_iters=3, **kw)
    idx.train(x[: n // 2])
    for c in np.array_split(x, 3):
        idx.add(c)
    idx.set_nprobe(nprobe)
    return idx, x


def decoded_lists(idx):
    data = np.asarray(idx.lists.data)
    if idx.codec == "sq8":
        return np.asarray(sq.sq8_decode(jnp.asarray(data), idx.sq_params["vmin"],
                                        idx.sq_params["span"]))
    return data.astype(np.float32)


def numpy_probes(idx, q, nprobe):
    """The ``nprobe`` best lists of each query, best first, in float64."""
    cents = np.asarray(idx.centroids, np.float64)
    q = q.astype(np.float64)
    coarse = q @ cents.T
    if idx.metric == "l2":
        coarse = -((q ** 2).sum(1)[:, None] - 2 * coarse + (cents ** 2).sum(1)[None, :])
    return np.argsort(-coarse, axis=1, kind="stable")[:, :nprobe]


def numpy_probe_scan(idx, q, k, nprobe):
    """Neighbours of ``q`` among the rows of its ``nprobe`` best lists, in
    float64: (scores (nq, k) higher-better, ids (nq, k)); -inf / -1 where a
    query has fewer than k candidates."""
    rows = decoded_lists(idx).astype(np.float64)
    ids = np.asarray(idx.lists.ids)
    sizes = np.asarray(idx.lists.sizes)
    probes = numpy_probes(idx, q, nprobe)
    q = q.astype(np.float64)
    out_s = np.full((q.shape[0], k), -np.inf)
    out_i = np.full((q.shape[0], k), -1, np.int64)
    for r in range(q.shape[0]):
        cs, ci = [], []
        for l in probes[r]:
            live = (np.arange(ids.shape[1]) < sizes[l]) & (ids[l] >= 0)
            s = rows[l][live] @ q[r]
            if idx.metric == "l2":
                s = -((q[r] ** 2).sum() - 2 * s + (rows[l][live] ** 2).sum(1))
            cs.append(s)
            ci.append(ids[l][live])
        cs, ci = np.concatenate(cs), np.concatenate(ci)
        top = np.argsort(-cs, kind="stable")[:k]
        out_s[r, :len(top)], out_i[r, :len(top)] = cs[top], ci[top]
    return out_s, out_i


def scan(idx, q, k, nprobe, tile, group, nvalid=None, norms="stored",
         with_counts=False, sub=None):
    """``_ivf_flat_search``'s XLA arm on a padded block, as the index calls
    it (``sub``: the tile's rows, the rule's own for sparse lists where
    None): (vals, ids), and with ``with_counts`` its third output."""
    if sub is None:
        sub = ivfmod.listmajor_sub_rows(idx.lists.cap, idx.dim,
                                        np.dtype(idx.lists.dtype).itemsize)
    extra = {}
    if idx.codec == "sq8":
        extra = dict(vmin=idx.sq_params["vmin"], span=idx.sq_params["span"])
    list_norms = idx.norm_lists.data if (norms == "stored" and idx.metric == "l2") else None
    vals, ids, counts = ivfmod._ivf_flat_search(
        idx.centroids, idx.lists.data, idx.lists.ids, idx.lists.sizes, jnp.asarray(q),
        k=k, nprobe=nprobe, g=1, metric=idx.metric, codec=idx.codec,
        list_norms=list_norms, tile=tile, group=group, sub=sub,
        nvalid=None if nvalid is None else jnp.int32(nvalid), **extra)
    out = (np.asarray(vals), np.asarray(ids))
    return out + (np.asarray(counts),) if with_counts else out


def assert_same_neighbours(vals, ids, want_s, want_i, tol=2e-4):
    np.testing.assert_array_equal(ids, want_i)
    finite = np.isfinite(want_s)
    np.testing.assert_array_equal(np.isfinite(vals), finite)
    np.testing.assert_allclose(vals[finite], want_s[finite], rtol=tol, atol=tol)


def assert_same_neighbours_up_to_ties(vals, ids, want_s, want_i, tol=2e-4):
    """``assert_same_neighbours`` where rows of the index are identical: two
    such rows score the same in float64 and to rounding in float32, so
    either may come first. An id may differ from the numpy scan's only
    where the next or the last score is the same to ``tol`` (the row's last
    column ties with what fell off it)."""
    finite = np.isfinite(want_s)
    np.testing.assert_array_equal(np.isfinite(vals), finite)
    np.testing.assert_allclose(vals[finite], want_s[finite], rtol=tol, atol=tol)
    np.testing.assert_array_equal(ids < 0, want_i < 0)
    gap = np.abs(np.diff(want_s, axis=1))
    tied = np.ones_like(finite)
    tied[:, :-1] = gap <= tol
    tied[:, 1:-1] |= gap[:, :-1] <= tol
    tied[:, -1] = True
    assert tied[ids != want_i].all(), "an untied neighbour differs"


# ------------------------------------------------------------ the scan itself


@pytest.mark.parametrize("norms", ["stored", "recomputed"])
@pytest.mark.parametrize("metric", ["l2", "dot"])
@pytest.mark.parametrize("codec", ["f32", "f16", "sq8"])
def test_list_major_scan_gives_the_numpy_scans_neighbours(rng, codec, metric, norms):
    """ids equal and scores allclose to a float64 scan of the probed lists,
    at a tile narrower and a tile wider than a list's run of pairs, and
    equal bit for bit between the two norms (dot never reads them)."""
    idx, x = build(rng, codec, metric)
    q = rng.standard_normal((24, x.shape[1])).astype(np.float32)
    want_s, want_i = numpy_probe_scan(idx, q, 10, 5)
    outs = []
    for tile, group in ((1, 4), (4, 2), (8, 16), (64, 1)):
        vals, ids = scan(idx, q, 10, 5, tile, group, norms=norms)
        assert_same_neighbours(vals, ids, want_s, want_i)
        outs.append(vals)
    if norms == "recomputed":
        stored, _ = scan(idx, q, 10, 5, 8, 16, norms="stored")
        np.testing.assert_array_equal(outs[2], stored)


@pytest.mark.parametrize("rows", [1, 5, 8, 50, 64, 200, 256])
def test_every_block_size_with_its_zero_padding(rng, rows):
    """A request of ``rows`` rows runs in its pow2 bucket, the rest zeros:
    the real rows' answers are the numpy scan's whether the padding's pairs
    are left out (the index passes ``nvalid``) or scanned like any row, and
    a padded row left out comes back empty."""
    idx, x = build(rng, nlist=32, nprobe=8)
    q = rng.standard_normal((rows, x.shape[1])).astype(np.float32)
    want_s, want_i = numpy_probe_scan(idx, q, 10, 8)
    D, I = idx.search(q, 10)
    assert_same_neighbours(-D, I, want_s, want_i)
    bucket = distance.bucket_size(rows)
    tile, group, sub = idx._scan_tiling(bucket, 8)
    padded = distance.pad_rows(q, bucket)
    for nvalid in (rows, None):
        vals, ids = scan(idx, padded, 10, 8, tile, group, nvalid=nvalid, sub=sub)
        assert_same_neighbours(vals[:rows], ids[:rows], want_s, want_i)
    vals, ids = scan(idx, padded, 10, 8, tile, group, nvalid=rows, sub=sub)
    assert (ids[rows:] == -1).all() and np.isneginf(vals[rows:]).all()


def test_a_real_all_zero_query_is_a_query(rng):
    """Padding is told from rows by the count alone: a caller's zero vector
    gets its neighbours."""
    idx, x = build(rng)
    q = np.zeros((3, x.shape[1]), np.float32)
    q[1] = x[7]
    want_s, want_i = numpy_probe_scan(idx, q, 10, 5)
    D, I = idx.search(q, 10)
    assert_same_neighbours(-D, I, want_s, want_i)


@pytest.mark.parametrize("metric", ["l2", "dot"])
def test_probing_every_list_is_the_exact_scan(rng, metric):
    idx, x = build(rng, "f32", metric, nlist=8, nprobe=8)
    q = rng.standard_normal((19, x.shape[1])).astype(np.float32)
    s = q @ x.T if metric == "dot" else -((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    D, I = idx.search(q, 7)
    np.testing.assert_array_equal(I, np.argsort(-s, axis=1)[:, :7])


def test_empty_lists_and_removed_ids(rng):
    """More lists than clusters leaves some empty; removed rows carry id -1
    in the lists. Neither is ever returned, and what is left is the numpy
    scan's answer; a query whose lists hold fewer than k rows ends in -1."""
    d, nlist = 16, 32
    x = np.repeat(rng.standard_normal((6, d)).astype(np.float32) * 8, 40, axis=0)
    x += rng.standard_normal(x.shape).astype(np.float32)
    idx = IVFFlatIndex(d, nlist, "l2", codec="f16", kmeans_iters=4)
    idx.centroids = jnp.asarray(np.concatenate(
        [x[::40][:6], rng.standard_normal((nlist - 6, d)).astype(np.float32) * 50]))
    idx.lists = idx._make_lists()
    idx.add(x)
    assert int((np.asarray(idx.lists.sizes) == 0).sum()) >= nlist - 6
    gone = np.arange(0, 240, 3)
    idx.remove_rows(gone)
    idx.set_nprobe(4)
    q = x[rng.choice(240, 21)] + 0.01
    want_s, want_i = numpy_probe_scan(idx, q, 10, 4)
    D, I = idx.search(q, 10)
    # norms near 1e3: the fp32 cancellation in -(qn - 2 ip + bn) is 1e-4 wide
    assert_same_neighbours(-D, I, want_s, want_i, tol=2e-3)
    assert not np.isin(I, gone).any()
    idx.set_nprobe(1)
    idx.remove_rows(np.arange(240)[np.arange(240) % 40 >= 4])  # 4 rows a list left
    D, I = idx.search(q[:5], 10)
    assert (I[:, 4:] == -1).all() and np.isinf(D[:, 4:]).all()


# ------------------------------------------ the scan stops at a list's end


def lists_of_sizes(rng, monkeypatch, codec, metric, d=16, sub=32):
    """An index over 12 far-apart centroids whose list ``l`` holds exactly
    ``sizes[l]`` rows, under a capacity of 256 in sub-blocks of ``sub`` rows
    (the slice budget is shrunk to one sub-block of this width and codec):
    empty, one row, either side of a sub-block's edge, either side of the
    capacity, and sizes between. Rows come in identical pairs (ties), and
    some are removed: a whole last sub-block, a row inside a list, every
    row of a list."""
    itemsize = np.dtype(IVFFlatIndex._DTYPES[codec]).itemsize
    monkeypatch.setattr(ivfmod, "_GATHER_SLICE_BYTES", sub * d * itemsize)
    cap = 256
    sizes = np.array([0, 1, sub - 1, sub, sub + 1, cap - 1, cap,
                      2 * sub + 5, 3 * sub, 77, 0, 130])
    cents = np.zeros((len(sizes), d), np.float32)
    cents[np.arange(len(sizes)), np.arange(len(sizes))] = 12.0
    idx = IVFFlatIndex(d, len(sizes), metric, codec=codec, kmeans_iters=2)
    idx.centroids = jnp.asarray(cents)
    x = np.repeat(cents, sizes, axis=0)
    noise = rng.standard_normal(x.shape).astype(np.float32)
    noise[1::2] = noise[0::2]  # rows in identical pairs (an even count of rows)
    x += noise
    if codec == "sq8":
        idx.sq_params = sq.sq8_train(x)
    idx.lists = idx._make_lists()
    order = rng.permutation(len(x))
    for part in np.array_split(order, 3):
        idx.add(x[part])
    assert idx.lists.cap == cap
    np.testing.assert_array_equal(np.asarray(idx.lists.sizes), sizes)
    assert ivfmod.listmajor_sub_rows(cap, d, itemsize) == sub
    assign = idx.get_assignments()
    pos = idx._host_pos_array()
    gone = np.concatenate([
        np.flatnonzero((assign == 4) & (pos >= sub)),       # list 4's last sub-block
        np.flatnonzero((assign == 6) & (pos == 100)),       # a row inside a full list
        np.flatnonzero(assign == 9)])                       # every row of a list
    idx.remove_rows(gone)
    idx.set_nprobe(5)
    q = np.concatenate([cents[[0, 1, 4, 6, 9, 10]] + 0.5,
                        x[order[:18]] + 0.01]).astype(np.float32)
    return idx, q, gone


def with_every_size_at_capacity(monkeypatch, cap):
    """The plan told that every list is full: every sub-block of every
    probed list is gathered and multiplied, the work of the whole-list scan
    (PR 31). The masks, in the loop and in the merge, keep the real sizes."""
    plan = ivfmod._listmajor_plan
    monkeypatch.setattr(
        ivfmod, "_listmajor_plan",
        lambda probes, sizes, tile, sub, nruns, ntiles, nvalid: plan(
            probes, jnp.full_like(sizes, cap), tile, sub, nruns, ntiles, nvalid))


@pytest.mark.parametrize("metric", ["l2", "dot"])
@pytest.mark.parametrize("codec", ["f32", "f16", "sq8"])
def test_lists_of_every_size_scan_as_whole_lists_do(rng, monkeypatch, codec, metric):
    """Sizes 0, 1, sub-1, sub, sub+1, cap-1, cap and between, with removed
    ids and tied rows: the scan that stops at each list's end gives the
    neighbours, in the order, of the same scan made to gather every
    sub-block of every probed list, and those of the float64 numpy scan;
    its third output counts what it left out; and the norms recomputed
    from the gathered sub-blocks give the stored norms' scores bit for bit."""
    idx, q, gone = lists_of_sizes(rng, monkeypatch, codec, metric)
    cap, sub = idx.lists.cap, 32
    sizes = np.asarray(idx.lists.sizes)
    want_s, want_i = numpy_probe_scan(idx, q, 10, 5)
    assert not np.isin(want_i, gone).any()
    bucket = distance.bucket_size(len(q))
    padded = distance.pad_rows(q, bucket)
    ivfmod._ivf_flat_search.clear_cache()
    try:
        for tile, group in ((1, 8), (8, 16), (16, 3 * (cap // sub))):
            vals, ids, counts = scan(idx, padded, 10, 5, tile, group, nvalid=len(q),
                                     with_counts=True)
            assert_same_neighbours_up_to_ties(vals[: len(q)], ids[: len(q)],
                                              want_s, want_i)
            probes = numpy_probes(idx, q, 5)
            runs = -(-np.bincount(probes.reshape(-1), minlength=len(sizes)) // tile)
            assert counts.tolist() == [runs.sum() * (cap // sub),
                                       (runs * -(-sizes // sub)).sum()]
            with monkeypatch.context() as whole:
                with_every_size_at_capacity(whole, cap)
                ivfmod._ivf_flat_search.clear_cache()
                wv, wi, wc = scan(idx, padded, 10, 5, tile, group, nvalid=len(q),
                                  with_counts=True)
            ivfmod._ivf_flat_search.clear_cache()
            assert wc.tolist() == [counts[0], counts[0]]
            np.testing.assert_array_equal(ids, wi)
            np.testing.assert_allclose(vals, wv, rtol=2e-4, atol=2e-4)
        # a tile of the whole capacity (the rule's choice for full lists): the
        # same neighbours, nothing skipped but the empty lists' runs
        fv, fi, fc = scan(idx, padded, 10, 5, 8, 2, nvalid=len(q), with_counts=True,
                          sub=cap)
        np.testing.assert_array_equal(fi, ids)
        np.testing.assert_allclose(fv, vals, rtol=2e-4, atol=2e-4)
        runs = -(-np.bincount(probes.reshape(-1), minlength=len(sizes)) // 8)
        assert fc.tolist() == [runs.sum(), runs[sizes > 0].sum()]
        # the norms recomputed from each gathered sub-block: bit for bit the stored ones'
        rv, ri = scan(idx, padded, 10, 5, 16, 3 * (cap // sub), nvalid=len(q),
                      norms="recomputed")
        np.testing.assert_array_equal(rv, vals)
        np.testing.assert_array_equal(ri, ids)
    finally:
        ivfmod._ivf_flat_search.clear_cache()


def test_the_index_serves_lists_of_every_size(rng, monkeypatch):
    """The same lists through ``IVFFlatIndex.search``: the index's own
    tiling, blocks, padding and booking."""
    idx, q, gone = lists_of_sizes(rng, monkeypatch, "f16", "l2")
    ivfmod._ivf_flat_search.clear_cache()
    try:
        want_s, want_i = numpy_probe_scan(idx, q, 10, 5)
        D, I = idx.search(q, 10)
        assert_same_neighbours_up_to_ties(-D, I, want_s, want_i)
    finally:
        ivfmod._ivf_flat_search.clear_cache()


# ------------------------------------------------------ the plan drops no pair


SUB, PARTS = 32, 4  # the plan tests' lists: capacity 128 in sub-blocks of 32


def sizes_of(kind, nlist, rng=None):
    """List sizes for the plan tests: every list full (the parent's work,
    where the bound is reached), or every size there is between empty and
    full, the sub-block's edges among them."""
    if kind == "full":
        return np.full(nlist, SUB * PARTS, np.int32)
    edges = np.array([0, 1, SUB - 1, SUB, SUB + 1, 2 * SUB, SUB * PARTS - 1,
                      SUB * PARTS], np.int32)
    sizes = np.resize(edges, nlist)
    return sizes if rng is None else rng.permutation(sizes)


def plan_of(probes, sizes, tile, nvalid=None):
    probes = jnp.asarray(probes, jnp.int32)
    nruns = ivfmod.listmajor_tile_bound(probes.size, len(sizes), tile)
    out = ivfmod._listmajor_plan(probes, jnp.asarray(sizes, jnp.int32), tile, SUB, nruns,
                                 nruns * PARTS,
                                 None if nvalid is None else jnp.int32(nvalid))
    return [np.asarray(o) for o in out], nruns * PARTS


def assert_every_pair_has_its_slot(probes, sizes, tile, nvalid=None):
    """Every live (query, probe) pair has, for every live sub-block of its
    list, a slot of its own in a tile in use whose run scans that list for
    that query and whose sub-block is that one; and the tiles and runs in
    use are those, counted in numpy."""
    (run_list, run_q, tile_run, tile_sub, where, pair_live, used, runs), bound = plan_of(
        probes, sizes, tile, nvalid)
    nq, nprobe = probes.shape
    rows = nq if nvalid is None else nvalid
    assert used <= bound
    assert pair_live[:rows].all() and not pair_live[rows:].any()
    first, slot = where[:rows].reshape(-1) // tile, where[:rows].reshape(-1) % tile
    lists = probes[:rows].reshape(-1)
    live = -(-sizes // SUB)  # sub-blocks of each list that hold a row
    seen = set()
    for p in np.flatnonzero(live[lists]):
        for j in range(live[lists[p]]):
            t = first[p] + j
            assert t < used, "a pair fell past the tiles the loop scans"
            assert (run_list[tile_run[t]], tile_sub[t]) == (lists[p], j)
            assert run_q[tile_run[t], slot[p]] == p // nprobe
            seen.add((t, slot[p]))
    assert len(seen) == live[lists].sum(), "two pairs share a result row"
    # past the tiles in use a tile still names a run there is
    assert ((0 <= tile_run) & (tile_run < len(run_list))).all() and (tile_sub >= 0).all()
    pairs_of = np.bincount(lists, minlength=len(sizes))
    assert runs == (-(-pairs_of // tile)).sum()
    assert used == (-(-pairs_of // tile) * live).sum()
    return int(used), bound


@pytest.mark.parametrize("kind", ["full", "mixed"])
@pytest.mark.parametrize("tile", [1, 2, 8, 16, 64])
def test_adversarial_skew_overflows_every_first_tile_and_loses_no_pair(tile, kind):
    """Every query probes the same ``nprobe`` lists: each list's run is
    ``nq`` pairs long, so every list overflows its first tile. The tiles in
    use stay inside the static bound and every pair keeps its own slot in
    every live sub-block of its list, whether the lists are full or of
    every size."""
    nq, nprobe, nlist = 96, 6, 40
    sizes = sizes_of(kind, nlist)
    probes = np.tile(np.array([3, 39, 0, 17, 22, 8]), (nq, 1))
    used, bound = assert_every_pair_has_its_slot(probes, sizes, tile)
    assert used == -(-nq // tile) * (-(-sizes[probes[0]] // SUB)).sum()
    assert bound == PARTS * ivfmod.listmajor_tile_bound(nq * nprobe, nlist, tile) \
        == PARTS * min(nq * nprobe, -(-nq * nprobe // tile) + nlist)
    # and with part of the block padding: the zero rows all probe alike too
    assert_every_pair_has_its_slot(probes, sizes, tile, nvalid=37)


@pytest.mark.parametrize("kind", ["full", "mixed"])
@pytest.mark.parametrize("nq,nprobe,nlist,tile", [
    (8, 4, 64, 1), (64, 8, 16, 8), (128, 16, 32, 16), (33, 5, 7, 4), (1, 7, 7, 8)])
def test_random_probes_keep_every_pair_inside_the_bound(rng, nq, nprobe, nlist, tile,
                                                        kind):
    probes = np.stack([rng.permutation(nlist)[:nprobe] for _ in range(nq)])
    sizes = sizes_of(kind, nlist, rng)
    assert_every_pair_has_its_slot(probes, sizes, tile)
    assert_every_pair_has_its_slot(probes, sizes, tile, nvalid=max(1, nq // 3))


@pytest.mark.parametrize("kind", ["full", "mixed"])
def test_the_bound_is_reached_and_never_passed(kind):
    """One pair more than whole tiles on every list: a partial tile each,
    ``cap / sub`` of them where the list is full. And where a tile is a
    pair, full lists reach the bound exactly, ``cap / sub`` times the
    whole-list scan's; lists of every size stay under it by the sub-blocks
    they do not hold."""
    nlist, tile = 5, 4
    sizes = sizes_of(kind, nlist)
    probes = np.repeat(np.arange(nlist), tile + 1)[:, None]  # (25, 1)
    used, bound = assert_every_pair_has_its_slot(probes, sizes, tile)
    assert bound == PARTS * (-(-25 // tile) + nlist) == PARTS * 12
    assert used == 2 * (-(-sizes // SUB)).sum() <= PARTS * 2 * nlist
    used, bound = assert_every_pair_has_its_slot(probes, sizes, 1)
    assert bound == PARTS * 25
    assert (used == bound) if kind == "full" else (0 < used < bound)


def test_skewed_probes_through_the_whole_scan(rng):
    """The same skew end to end: identical queries, so every pair of the
    block lands on the same lists; the answers are the numpy scan's."""
    idx, x = build(rng, nlist=16, nprobe=4)
    q = np.tile(x[5:6] + 0.05, (40, 1)).astype(np.float32)
    want_s, want_i = numpy_probe_scan(idx, q, 10, 4)
    for tile, group in ((1, 16), (8, 4), (16, 16)):
        vals, ids = scan(idx, q, 10, 4, tile, group)
        assert_same_neighbours(vals, ids, want_s, want_i)


# --------------------------------------------------- blocks, rule and counter


def test_the_fused_multi_block_entry(rng, monkeypatch):
    """Requests over one block ride ``_ivf_flat_search_fused``: the trailing
    block is part padding and the pow2 of blocks adds whole blocks of it,
    each told its own count."""
    monkeypatch.setattr(base, "MAX_QUERY_BLOCK", 8)
    idx, x = build(rng, codec="sq8", nlist=16, nprobe=4)
    q = rng.standard_normal((37, x.shape[1])).astype(np.float32)  # 5 blocks -> 8
    want_s, want_i = numpy_probe_scan(idx, q, 6, 4)
    seen = []
    fused = ivfmod._ivf_flat_search_fused

    def spy(*args, **kwargs):
        seen.append(np.asarray(kwargs["counts"]).tolist())
        return fused(*args, **kwargs)

    monkeypatch.setattr(ivfmod, "_ivf_flat_search_fused", spy)
    D, I = idx.search(q, 6)
    assert seen == [[8, 8, 8, 8, 5, 0, 0, 0]]
    assert_same_neighbours(-D, I, want_s, want_i)


# the geometry of each line: (rows, nprobe, nlist, cap, dim, itemsize) -> (T, G, sub)
@pytest.mark.parametrize("shape,want", [
    # ivfsq-batch (float16, capacity 4096, d 512, nprobe 64 of 1024 lists): a
    # one-row request and every window the cell warms; at 256 rows a tile of
    # 64 would make a score buffer of 1.34 GB; a step is 16 lists' worth of
    # 256-row sub-blocks
    ((8, 64, 1024, 4096, 512, 2), (1, 256, 256)),
    ((16, 64, 1024, 4096, 512, 2), (1, 256, 256)),
    ((32, 64, 1024, 4096, 512, 2), (8, 256, 256)),
    ((64, 64, 1024, 4096, 512, 2), (16, 256, 256)),
    ((128, 64, 1024, 4096, 512, 2), (32, 256, 256)),
    ((256, 64, 1024, 4096, 512, 2), (32, 256, 256)),
    # chip_smoke.py's ivfsq lists (capacity 512) and the capacities between
    ((8, 64, 1024, 512, 512, 2), (1, 128, 256)),
    ((32, 64, 1024, 512, 512, 2), (8, 128, 256)),
    ((64, 64, 1024, 512, 512, 2), (16, 128, 256)),
    ((128, 64, 1024, 512, 512, 2), (32, 128, 256)),
    ((256, 64, 1024, 512, 512, 2), (64, 128, 256)),
    ((64, 64, 1024, 1024, 512, 2), (16, 256, 256)),
    ((64, 64, 1024, 2048, 512, 2), (16, 256, 256)),
    # the sq8 codec's bytes, and ivf_simple's float32 rows
    ((128, 64, 1024, 4096, 512, 1), (32, 256, 512)),
    ((128, 64, 1024, 4096, 512, 4), (32, 256, 128)),
    ((256, 8, 1024, 1024, 128, 4), (8, 128, 512)),
    ((64, 8, 1024, 1024, 128, 4), (1, 128, 512)),
    # lists so long that one fills the step's budget: the buffer holds the tile at 8
    ((128, 64, 1024, 65536, 512, 2), (8, 256, 256)),
    # a quarter of the lists: the reuse of 32 and 64 the chip timed
    ((128, 64, 256, 4096, 512, 2), (64, 256, 256)),
    ((256, 64, 256, 4096, 512, 2), (64, 256, 256)),
    # every list probed by every row: the widest tile; a list is one sub-block
    ((1024, 64, 64, 256, 64, 4), (64, 64, 256)),
])
def test_the_static_rule(shape, want):
    assert ivfmod.listmajor_tiling(*shape) == want
    # lists filled past _WHOLE_LIST_FILL: a tile is a whole list, as before
    # PR 43, a step the same bytes
    rows, nprobe, nlist, cap, dim, itemsize = shape
    tile, group, sub = ivfmod.listmajor_tiling(*shape, fill=0.95)
    assert (tile, group * cap, sub) == (want[0], want[1] * want[2], cap)
    assert ivfmod.listmajor_tiling(*shape, fill=ivfmod._WHOLE_LIST_FILL) == want


def test_the_index_asks_the_rule_with_what_it_holds(rng):
    idx, _ = build(rng, "f16", nlist=16, nprobe=5)
    fill = idx.ntotal / (16 * idx.lists.cap)
    assert 0 < fill < 1
    assert idx._scan_tiling(64, 5) == ivfmod.listmajor_tiling(
        64, 5, 16, idx.lists.cap, idx.dim, 2, fill=fill)


def test_the_gather_takes_slices_the_chip_gathers_in_place():
    """A (4096, 512) float16 list is 4 MB a slice: past what XLA:TPU gathers
    in place (it then copies the whole store into slabs every loop step).
    The scan gathers sub-blocks through a view of the store, 256 KB a
    slice, and list ``l``'s sub-block ``j`` is the view's ``l * parts + j``."""
    data = jnp.arange(6 * 4096 * 8, dtype=jnp.float32).reshape(6, 4096, 8)
    assert ivfmod.listmajor_sub_rows(4096, 8, 4) == 4096  # 128 KB a list: whole
    assert ivfmod.listmajor_sub_rows(4096, 512, 2) == 256
    assert ivfmod.listmajor_sub_rows(4096, 512, 4) == 128
    assert ivfmod.listmajor_sub_rows(4096, 512, 1) == 512
    assert ivfmod.listmajor_sub_rows(48, 4096, 4) == 48  # no whole sublane tiles to cut
    blocks = jnp.array([4 * 16 + 3, 5 * 16 + 15], jnp.int32)
    want = np.stack([np.asarray(data)[4, 3 * 256:4 * 256], np.asarray(data)[5, 15 * 256:]])
    for slice_rows in (256, 64):  # a sub-block in one slice, or in four
        np.testing.assert_array_equal(
            np.asarray(ivfmod._gather_blocks(data, blocks, 256, slice_rows)), want)
    np.testing.assert_array_equal(  # whole lists, in slices of a sub-block
        np.asarray(ivfmod._gather_blocks(data, jnp.array([4, 0, 4, 5], jnp.int32), 4096, 256)),
        np.asarray(data)[[4, 0, 4, 5]])
    S = jax.ShapeDtypeStruct
    d, cap, nlist, nprobe = 512, 4096, 1024, 64
    tile, group, sub = ivfmod.listmajor_tiling(128, nprobe, nlist, cap, d, 2)
    text = str(jax.make_jaxpr(
        lambda cents, data, ids, sizes, q, norms: ivfmod._ivf_flat_search(
            cents, data, ids, sizes, q, k=10, nprobe=nprobe, g=1, metric="l2",
            codec="f16", list_norms=norms, tile=tile, group=group, sub=sub))(
        S((nlist, d), np.float32), S((nlist, cap, d), np.float16),
        S((nlist, cap), np.int32), S((nlist,), np.int32), S((128, d), np.float32),
        S((nlist, cap), np.float32)))
    assert "slice_sizes=(1, 256, 512)" in text
    assert "slice_sizes=(1, 4096, 512)" not in text


@pytest.mark.parametrize("pallas,nq,scans,listmajor", [
    (False, 20, 1, 1),   # one block
    (False, 37, 1, 1),   # five blocks in one lax.map launch: one engine.scan
    (True, 20, 1, 0),    # the Pallas kernel (interpreted here) scans query-major
], ids=["xla", "xla-multiblock", "pallas"])
def test_scan_listmajor_counts_the_scans_of_the_xla_arm(rng, monkeypatch, pallas, nq,
                                                        scans, listmajor):
    """``engine.scan_listmajor`` beside ``engine.scan``: one record a scan
    whose program took the list-major order, by the arm that served it; and
    beside it, once a scan too, ``engine.scan_list_rows`` (the rows of the
    scan's tiles at whole capacity) and ``engine.scan_list_rows_skipped``
    (those in sub-blocks past their list's end), counted here in numpy."""
    from distributed_faiss_tpu.utils import tracing

    monkeypatch.setattr(base, "MAX_QUERY_BLOCK", 8)
    d, sub = 24, 32
    monkeypatch.setattr(ivfmod, "_GATHER_SLICE_BYTES", sub * d * 2)
    ivfmod._ivf_flat_search.clear_cache()
    ivfmod._ivf_flat_search_fused.clear_cache()
    try:
        idx, x = build(rng, nlist=8, nprobe=2, n=600, use_pallas=pallas)
        sink = tracing.LatencyStats()
        with tracing.stage("engine.launch", sink=sink):
            idx.search(x[:nq], 3)
    finally:
        ivfmod._ivf_flat_search.clear_cache()
        ivfmod._ivf_flat_search_fused.clear_cache()
    rows = sink.summary()
    assert rows["engine.scan"]["count"] == scans
    assert rows.get("engine.scan_listmajor", {"count": 0})["count"] == listmajor
    if not listmajor:
        assert "engine.scan_list_rows" not in rows
        assert "engine.scan_list_rows_skipped" not in rows
        return
    cap, sizes = idx.lists.cap, np.asarray(idx.lists.sizes)
    assert cap // sub >= 2 and (sizes <= cap - sub).any()
    want = [0, 0]
    nb = base.pick_query_block(cap * d * 4)  # the index's block, as it picks it
    rows_a_block = nb if nq > nb else distance.bucket_size(nq)
    for s in range(0, nq, rows_a_block):  # each block makes its own tiles
        block = x[:nq][s:s + rows_a_block]
        tile, _, got_sub = idx._scan_tiling(rows_a_block, 2)
        assert got_sub == sub
        pairs = np.bincount(numpy_probes(idx, block, 2).reshape(-1), minlength=8)
        runs = -(-pairs // tile)
        want[0] += runs.sum() * cap
        want[1] += (runs * (cap - -(-sizes // sub) * sub)).sum()
    assert rows["engine.scan_list_rows"]["count"] == scans
    assert rows["engine.scan_list_rows_skipped"]["count"] == scans
    assert rows["engine.scan_list_rows"]["total_s"] == want[0]
    assert rows["engine.scan_list_rows_skipped"]["total_s"] == want[1] > 0


def test_the_engine_serves_the_row_at_zero_until_a_scan_books_it(tmp_path):
    """``engine.scan_listmajor`` and the two ``engine.scan_list_rows`` rows
    are shown beside ``engine.scan`` (0 of n, not a missing row) for an
    index that never books them (``flat``, ``knnlm``), and count every scan
    of an IVF-flat index on the XLA arm."""
    from distributed_faiss_tpu import Index, IndexCfg, IndexState

    x = np.random.default_rng(3).standard_normal((3000, 16)).astype(np.float32)
    stats = {}
    for builder in ("ivf_simple", "flat", "knnlm"):
        cfg = IndexCfg(index_builder_type=builder, dim=16, metric="l2",
                       train_num=2000, centroids=16, nprobe=4,
                       **({"code_size": 4} if builder == "knnlm" else {}))
        cfg.index_storage_dir = str(tmp_path / builder)
        idx = Index(cfg)
        idx.add_batch(x, list(range(3000)), train_async_if_triggered=False)
        idx.train()
        deadline = time.time() + 120
        while idx.get_state() != IndexState.TRAINED or idx.get_idx_data_num()[0] > 0:
            assert time.time() < deadline, "train/drain timed out"
            time.sleep(0.02)
        idx.search(x[:8], 5)
        idx.search(x[:70], 5)
        stats[builder] = idx.perf_stats()
    ivf = stats["ivf_simple"]
    assert ivf["engine.scan_listmajor"]["count"] == ivf["engine.scan"]["count"] >= 2
    assert ivf["engine.scan_list_rows"]["count"] == ivf["engine.scan"]["count"]
    assert ivf["engine.scan_list_rows_skipped"]["count"] == ivf["engine.scan"]["count"]
    assert ivf["engine.scan_list_rows"]["total_s"] \
        >= ivf["engine.scan_list_rows_skipped"]["total_s"] >= 0
    for builder in ("flat", "knnlm"):
        assert stats[builder]["engine.scan"]["count"] >= 2
        for row in ("engine.scan_listmajor", "engine.scan_list_rows",
                    "engine.scan_list_rows_skipped"):
            assert stats[builder][row]["count"] == 0, (builder, row)


def _list_rows_snapshots(scans, skipped_share, ranks=1, rows=("rows", "skipped")):
    """A window of ``scans`` list-major scans a rank of 638 tiles of
    capacity 4096 each, ``skipped_share`` of it never gathered; ``rows``
    are the count rows the program has."""
    def snap(n):
        block = {"engine.scan": {"count": n, "total_s": 0.008 * n}}
        if "rows" in rows:
            block["engine.scan_list_rows"] = {"count": n, "total_s": 638 * 4096.0 * n}
        if "skipped" in rows:
            block["engine.scan_list_rows_skipped"] = {
                "count": n, "total_s": skipped_share * 638 * 4096.0 * n}
        return {"engine": {"bench": block}}

    return {"index_id": "bench", "window_s": 20.0,
            "stats_before": [snap(5)] * ranks, "stats_after": [snap(5 + scans)] * ranks}


@pytest.mark.parametrize("obs,want", [
    (_list_rows_snapshots(2500, 0.71875), 71.875),              # the whole window
    (_list_rows_snapshots(2500, 0.71875, ranks=4), 71.875),     # four ranks together
    (_list_rows_snapshots(2500, 0.0), 0.0),                     # every list full
    (_list_rows_snapshots(2500, 0.5, rows=("rows",)), None),    # a row missing
    (_list_rows_snapshots(2500, 0.5, rows=()), None),           # the parent of PR 43
    (_list_rows_snapshots(0, 0.5), None),                       # no scan: no share
    ({"index_id": "bench", "window_s": 20.0}, None),            # no snapshots
], ids=["whole-window", "four-ranks", "none-skipped", "no-skipped-row", "neither-row",
        "no-scan", "untraced"])
def test_the_builders_tool_reads_the_share_of_list_rows_skipped(obs, want):
    """``benchmarks/stage_ledger.list_skip_pct``: the lines a ``benchmark``
    PR is to make ``kernel.list_skip_pct``'s reader (PERF.md 7.1 p), on a
    pair of ``get_perf_stats`` snapshots as an ``ivfsq`` rank gives them."""
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from perfbench import loader

    tool = loader.load_module(os.path.join(repo, "benchmarks", "stage_ledger.py"))
    assert tool.list_skip_pct(obs) == want


# --------------------------------- the other cells' programs are the parent's


def _jaxpr_digest(fn, *args, **kwargs):
    text = str(jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args))
    return hashlib.sha256(text.encode()).hexdigest()[:16], len(text.splitlines())


# (sha256 of the jaxpr's text, its lines) by this same code under jax 0.9.0.
# The ``_knn_scan`` pair was taken at 6912cf4 (unchanged since): a jax upgrade
# re-takes it from a checkout of the commit before the change under test. The
# ``_ivf_flat_search`` pair is PR 43's own program, which changed it by design
@pytest.mark.parametrize("rows,want", [(64, ("d0058fe4b58f8e57", 1267)),
                                       (256, ("3d44affd9ae8dd58", 1267))])
def test_the_ivfsq_cells_program_is_the_parents_text(rows, want):
    """``_ivf_flat_search`` at ``ivfsq-batch``'s geometry (d 512, 1024
    float16 lists of capacity 4096, stored norms, k 10, nprobe 64, the
    list-major XLA arm under the index's own tiling). PR 35 changed the
    ``knnlm`` cells' program by design, so the digests that stood here for
    it at PR 31 went, case for case, to this cell's; PR 43 changed this
    cell's by design (a tile is a live sub-block of a probed list, and the
    program returns its counts), so they are now the text PR 43 left and
    the chip timed: a later PR that must not move this cell's program
    finds it pinned here, as ``flat768``'s is below."""
    d, cap, nlist, nprobe = 512, 4096, 1024, 64
    tile, group, sub = ivfmod.listmajor_tiling(rows, nprobe, nlist, cap, d, 2)
    S = jax.ShapeDtypeStruct
    got = _jaxpr_digest(
        lambda cents, data, ids, sizes, q, norms, nvalid: ivfmod._ivf_flat_search(
            cents, data, ids, sizes, q, k=10, nprobe=nprobe, g=1, metric="l2",
            codec="f16", list_norms=norms, tile=tile, group=group, sub=sub,
            nvalid=nvalid),
        S((nlist, d), np.float32), S((nlist, cap, d), np.float16),
        S((nlist, cap), np.int32), S((nlist,), np.int32), S((rows, d), np.float32),
        S((nlist, cap), np.float32), S((), np.int32))
    assert got == want


@pytest.mark.parametrize("rows,want", [(64, ("4b32cb91bad97fa4", 265)),
                                       (256, ("25fa9a952988b7d7", 265))])
def test_the_flat768_cells_program_is_the_parents_text(rows, want):
    """``_knn_scan`` at ``flat768-batch``'s geometry (2^21 x 768 float32
    rows, k 10, the scan's own chunk)."""
    S = jax.ShapeDtypeStruct
    got = _jaxpr_digest(
        distance._knn_scan,
        S((rows, 768), np.float32), S((2 ** 21, 768), np.float32), S((), np.int32),
        k=10, metric="l2", chunk=distance.SCAN_CHUNK)
    assert got == want
