"""The IVF-flat probe scan in list-major order (PR 31): each probed list is
gathered once per tile of queries that probe it and multiplied against all
of them. It must give the neighbours a plain numpy scan of the probed lists
gives — over codec, metric, stored or recomputed norms, every block size
with its zero padding, empty lists, removed ids and the most skewed probes
there can be — and drop no (query, probe) pair whatever the probes."""

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


def numpy_probe_scan(idx, q, k, nprobe):
    """Neighbours of ``q`` among the rows of its ``nprobe`` best lists, in
    float64: (scores (nq, k) higher-better, ids (nq, k)); -inf / -1 where a
    query has fewer than k candidates."""
    cents = np.asarray(idx.centroids, np.float64)
    rows = decoded_lists(idx).astype(np.float64)
    ids = np.asarray(idx.lists.ids)
    sizes = np.asarray(idx.lists.sizes)
    q = q.astype(np.float64)
    coarse = q @ cents.T
    if idx.metric == "l2":
        coarse = -((q ** 2).sum(1)[:, None] - 2 * coarse + (cents ** 2).sum(1)[None, :])
    probes = np.argsort(-coarse, axis=1, kind="stable")[:, :nprobe]
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


def scan(idx, q, k, nprobe, tile, group, nvalid=None, norms="stored"):
    """``_ivf_flat_search``'s XLA arm on a padded block, as the index calls it."""
    extra = {}
    if idx.codec == "sq8":
        extra = dict(vmin=idx.sq_params["vmin"], span=idx.sq_params["span"])
    list_norms = idx.norm_lists.data if (norms == "stored" and idx.metric == "l2") else None
    vals, ids = ivfmod._ivf_flat_search(
        idx.centroids, idx.lists.data, idx.lists.ids, idx.lists.sizes, jnp.asarray(q),
        k=k, nprobe=nprobe, g=1, metric=idx.metric, codec=idx.codec,
        list_norms=list_norms, tile=tile, group=group,
        nvalid=None if nvalid is None else jnp.int32(nvalid), **extra)
    return np.asarray(vals), np.asarray(ids)


def assert_same_neighbours(vals, ids, want_s, want_i, tol=2e-4):
    np.testing.assert_array_equal(ids, want_i)
    finite = np.isfinite(want_s)
    np.testing.assert_array_equal(np.isfinite(vals), finite)
    np.testing.assert_allclose(vals[finite], want_s[finite], rtol=tol, atol=tol)


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
    tile, group = idx._scan_tiling(bucket, 8)
    padded = distance.pad_rows(q, bucket)
    for nvalid in (rows, None):
        vals, ids = scan(idx, padded, 10, 8, tile, group, nvalid=nvalid)
        assert_same_neighbours(vals[:rows], ids[:rows], want_s, want_i)
    vals, ids = scan(idx, padded, 10, 8, tile, group, nvalid=rows)
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


# ------------------------------------------------------ the plan drops no pair


def plan_of(probes, nlist, tile, nvalid=None):
    probes = jnp.asarray(probes, jnp.int32)
    npairs = probes.size
    bound = ivfmod.listmajor_tile_bound(npairs, nlist, tile)
    out = ivfmod._listmajor_plan(probes, nlist, tile, bound,
                                 None if nvalid is None else jnp.int32(nvalid))
    return [np.asarray(o) for o in out], bound


def assert_every_pair_has_its_slot(probes, nlist, tile, nvalid=None):
    (tile_list, tile_q, where, pair_live, used), bound = plan_of(
        probes, nlist, tile, nvalid)
    nq, nprobe = probes.shape
    rows = nq if nvalid is None else nvalid
    assert used <= bound
    assert pair_live[:rows].all() and not pair_live[rows:].any()
    slots = where[:rows].reshape(-1)
    assert len(set(slots.tolist())) == slots.size, "two pairs share a result row"
    assert (slots // tile < used).all(), "a pair fell past the tiles the loop scans"
    np.testing.assert_array_equal(tile_list[slots // tile], probes[:rows].reshape(-1))
    np.testing.assert_array_equal(
        tile_q[slots // tile, slots % tile], np.repeat(np.arange(rows), nprobe))
    return int(used), bound


@pytest.mark.parametrize("tile", [1, 2, 8, 16, 64])
def test_adversarial_skew_overflows_every_first_tile_and_loses_no_pair(tile):
    """Every query probes the same ``nprobe`` lists: each list's run is
    ``nq`` pairs long, so every list overflows its first tile. The tiles in
    use stay inside the static bound and every pair keeps its own slot."""
    nq, nprobe, nlist = 96, 6, 40
    probes = np.tile(np.array([3, 39, 0, 17, 22, 8]), (nq, 1))
    used, bound = assert_every_pair_has_its_slot(probes, nlist, tile)
    assert used == nprobe * -(-nq // tile)
    assert bound == min(nq * nprobe, -(-nq * nprobe // tile) + nlist)
    # and with part of the block padding: the zero rows all probe alike too
    assert_every_pair_has_its_slot(probes, nlist, tile, nvalid=37)


@pytest.mark.parametrize("nq,nprobe,nlist,tile", [
    (8, 4, 64, 1), (64, 8, 16, 8), (128, 16, 32, 16), (33, 5, 7, 4), (1, 7, 7, 8)])
def test_random_probes_keep_every_pair_inside_the_bound(rng, nq, nprobe, nlist, tile):
    probes = np.stack([rng.permutation(nlist)[:nprobe] for _ in range(nq)])
    assert_every_pair_has_its_slot(probes, nlist, tile)
    assert_every_pair_has_its_slot(probes, nlist, tile, nvalid=max(1, nq // 3))


def test_the_bound_is_reached_and_never_passed():
    """One pair more than whole tiles on every list: a partial tile each."""
    nlist, tile = 5, 4
    probes = np.repeat(np.arange(nlist), tile + 1)[:, None]  # (25, 1)
    used, bound = assert_every_pair_has_its_slot(probes, nlist, tile)
    assert used == 2 * nlist and bound == -(-25 // tile) + nlist == 12


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


# the geometry of each line: (rows, nprobe, nlist, cap, dim, itemsize) -> (T, G)
@pytest.mark.parametrize("shape,want", [
    # ivfsq-batch (float16, capacity 4096, d 512, nprobe 64 of 1024 lists): a
    # one-row request and every window the cell warms; at 256 rows a tile of
    # 64 would make a score buffer of 1.34 GB
    ((8, 64, 1024, 4096, 512, 2), (1, 16)),
    ((16, 64, 1024, 4096, 512, 2), (1, 16)),
    ((32, 64, 1024, 4096, 512, 2), (8, 16)),
    ((64, 64, 1024, 4096, 512, 2), (16, 16)),
    ((128, 64, 1024, 4096, 512, 2), (32, 16)),
    ((256, 64, 1024, 4096, 512, 2), (32, 16)),
    # chip_smoke.py's ivfsq lists (capacity 512) and the capacities between
    ((8, 64, 1024, 512, 512, 2), (1, 64)),
    ((32, 64, 1024, 512, 512, 2), (8, 64)),
    ((64, 64, 1024, 512, 512, 2), (16, 64)),
    ((128, 64, 1024, 512, 512, 2), (32, 64)),
    ((256, 64, 1024, 512, 512, 2), (64, 64)),
    ((64, 64, 1024, 1024, 512, 2), (16, 64)),
    ((64, 64, 1024, 2048, 512, 2), (16, 32)),
    # the sq8 codec's bytes, and ivf_simple's float32 rows
    ((128, 64, 1024, 4096, 512, 1), (32, 32)),
    ((128, 64, 1024, 4096, 512, 4), (32, 8)),
    ((256, 8, 1024, 1024, 128, 4), (8, 64)),
    ((64, 8, 1024, 1024, 128, 4), (1, 64)),
    # lists so long that one fills the step's budget: the buffer holds the tile at 8
    ((128, 64, 1024, 65536, 512, 2), (8, 1)),
    # a quarter of the lists: the reuse of 32 and 64 the chip timed
    ((128, 64, 256, 4096, 512, 2), (64, 16)),
    ((256, 64, 256, 4096, 512, 2), (64, 16)),
    # every list probed by every row: the widest tile
    ((1024, 64, 64, 256, 64, 4), (64, 64)),
])
def test_the_static_rule(shape, want):
    assert ivfmod.listmajor_tiling(*shape) == want


def test_the_index_asks_the_rule_with_what_it_holds(rng):
    idx, _ = build(rng, "f16", nlist=16, nprobe=5)
    assert idx._scan_tiling(64, 5) == ivfmod.listmajor_tiling(
        64, 5, 16, idx.lists.cap, idx.dim, 2)


def test_the_gather_takes_slices_the_chip_gathers_in_place():
    """A (4096, 512) float16 list is 4 MB a slice: past what XLA:TPU gathers
    in place (it then copies the whole store into slabs every loop step).
    Through the view the slices are 256 KB, and the block is the same."""
    data = jnp.arange(6 * 4096 * 8, dtype=jnp.float32).reshape(6, 4096, 8)
    lists = jnp.array([4, 0, 4, 5], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ivfmod._gather_lists(data, lists)), np.asarray(data)[[4, 0, 4, 5]])
    S = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(ivfmod._gather_lists)(
        S((1024, 4096, 512), jnp.float16), S((16,), jnp.int32)))
    assert "slice_sizes=(1, 256, 512)" in text


@pytest.mark.parametrize("pallas,nq,scans,listmajor", [
    (False, 20, 1, 1),   # one block
    (False, 37, 1, 1),   # five blocks in one lax.map launch: one engine.scan
    (True, 20, 1, 0),    # the Pallas kernel (interpreted here) scans query-major
], ids=["xla", "xla-multiblock", "pallas"])
def test_scan_listmajor_counts_the_scans_of_the_xla_arm(rng, monkeypatch, pallas, nq,
                                                        scans, listmajor):
    """``engine.scan_listmajor`` beside ``engine.scan``: one record a scan
    whose program took the list-major order, by the arm that served it."""
    from distributed_faiss_tpu.utils import tracing

    monkeypatch.setattr(base, "MAX_QUERY_BLOCK", 8)
    idx, x = build(rng, nlist=8, nprobe=2, n=600, use_pallas=pallas)
    sink = tracing.LatencyStats()
    with tracing.stage("engine.launch", sink=sink):
        idx.search(x[:nq], 3)
    rows = sink.summary()
    assert rows["engine.scan"]["count"] == scans
    assert rows.get("engine.scan_listmajor", {"count": 0})["count"] == listmajor


def test_the_engine_serves_the_row_at_zero_until_a_scan_books_it(tmp_path):
    """``engine.scan_listmajor`` is shown beside ``engine.scan`` (0 of n, not
    a missing row) for an index that never books it, and counts every scan
    of an IVF-flat index on the XLA arm."""
    from distributed_faiss_tpu import Index, IndexCfg, IndexState

    x = np.random.default_rng(3).standard_normal((3000, 16)).astype(np.float32)
    stats = {}
    for builder in ("ivf_simple", "flat"):
        cfg = IndexCfg(index_builder_type=builder, dim=16, metric="l2",
                       train_num=2000, centroids=16, nprobe=4)
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
    assert stats["ivf_simple"]["engine.scan_listmajor"]["count"] \
        == stats["ivf_simple"]["engine.scan"]["count"] >= 2
    assert stats["flat"]["engine.scan"]["count"] >= 2
    assert stats["flat"]["engine.scan_listmajor"]["count"] == 0


# --------------------------------- the other cells' programs are the parent's


def _jaxpr_digest(fn, *args, **kwargs):
    text = str(jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args))
    return hashlib.sha256(text.encode()).hexdigest()[:16], len(text.splitlines())


# (sha256 of the jaxpr's text, its lines), taken at the parent commit (a741d52;
# the ``_knn_scan`` pair at 6912cf4, unchanged since) by this same code under
# jax 0.9.0; a jax upgrade re-takes them from a checkout of the commit before
# the change under test
@pytest.mark.parametrize("rows,want", [(64, ("a59c5fe916bcb6d1", 933)),
                                       (256, ("7d38668f4fd15659", 933))])
def test_the_ivfsq_cells_program_is_the_parents_text(rows, want):
    """``_ivf_flat_search`` at ``ivfsq-batch``'s geometry (d 512, 1024
    float16 lists of capacity 4096, stored norms, k 10, nprobe 64, the
    list-major XLA arm under the index's own tiling). PR 35 changed the
    ``knnlm`` cells' program by design (``_ivf_pq_search`` hands the kernel
    the lists' sizes and returns a count), so the digests that stood here
    for it at PR 31 went, case for case, to the cell whose program shares
    ``models/ivf.py`` with it and must not have moved."""
    d, cap, nlist, nprobe = 512, 4096, 1024, 64
    tile, group = ivfmod.listmajor_tiling(rows, nprobe, nlist, cap, d, 2)
    S = jax.ShapeDtypeStruct
    got = _jaxpr_digest(
        lambda cents, data, ids, sizes, q, norms, nvalid: ivfmod._ivf_flat_search(
            cents, data, ids, sizes, q, k=10, nprobe=nprobe, g=1, metric="l2",
            codec="f16", list_norms=norms, tile=tile, group=group, nvalid=nvalid),
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
