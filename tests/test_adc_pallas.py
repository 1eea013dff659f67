"""Pallas ADC kernel golden tests (interpreter mode on CPU — same kernel
code path the TPU runs compiled)."""

import collections

import numpy as np
import pytest

from distributed_faiss_tpu.ops import adc_pallas
from distributed_faiss_tpu.utils import tracing
from tools.graftlint.ir.harness import _walk_eqns


# ------------------------------------------------------------ the one guard


def small_pq(rng, kind="single", n=3000, **kw):
    """A trained IVF-PQ index of ``kind`` whose lists the three-plane kernel
    takes (n = 3000) or does not (n = 200: the starting capacity, 64)."""
    from distributed_faiss_tpu.models.ivf import IVFPQIndex
    from distributed_faiss_tpu.parallel.mesh import ShardedIVFPQIndex

    d = 32
    x = rng.standard_normal((n, d)).astype(np.float32)
    cls = {"single": IVFPQIndex, "sharded": ShardedIVFPQIndex}[kind]
    idx = cls(d, 8, m=8, metric="l2", kmeans_iters=3, pq_iters=3,
              refine_k_factor=4, **kw)
    idx.train(rng.standard_normal((2000, d)).astype(np.float32))
    idx.add(x)
    idx.set_nprobe(4)
    assert idx.lists.cap % 128 == 0 or n < 1000
    return idx, x


def xla_twin(idx):
    """The same trained index, forced onto the XLA one-hot."""
    ref = type(idx).from_state_dict({**idx.state_dict(), "pallas_adc": False})
    assert ref.use_pallas is False
    return ref


def _spy_on_the_program(kind, monkeypatch):
    """Record ``use_pallas`` of every launch of the index's scan program;
    returns (the record, the jitted program)."""
    from distributed_faiss_tpu.models import ivf as ivfmod
    from distributed_faiss_tpu.parallel import mesh as meshmod

    mod, name = {"single": (ivfmod, "_ivf_pq_search"),
                 "sharded": (meshmod, "_sharded_ivf_pq_search")}[kind]
    program, launched = getattr(mod, name), []

    def spy(*args, **kw):
        launched.append(kw["use_pallas"])
        return program(*args, **kw)

    monkeypatch.setattr(mod, name, spy)
    return launched, program


def _ping(idx):
    """``ping()["kernels"]`` of a rank that serves ``idx`` as index "i"."""
    import threading
    from types import SimpleNamespace

    from distributed_faiss_tpu.parallel.server import IndexServer
    from distributed_faiss_tpu.utils.state import IndexState

    rank = SimpleNamespace(
        rank=0, indexes_lock=threading.Lock(),
        indexes={"i": SimpleNamespace(tpu_index=idx,
                                      get_state=lambda: IndexState.TRAINED)})
    return IndexServer.ping(rank)["kernels"]


def _boom(*a, **k):
    raise RuntimeError("kernel abort (injected)")


@pytest.mark.parametrize("outcome", ["kernel-returns", "kernel-raises",
                                     "both-raise", "kernel-off-raises"])
@pytest.mark.parametrize("kind", ["single", "sharded"])
def test_the_guards_contract(rng, monkeypatch, kind, outcome):
    """pallas_guarded(index, call): kernel -> XLA oracle -> demote, and
    nothing else — no process-wide flag, no jit cache cleared."""
    idx, x = small_pq(rng, kind, use_pallas=outcome != "kernel-off-raises")
    idx._adc_validated = True  # the first-use check has tests of its own
    q, bad = x[:6], np.zeros((2, x.shape[1] + 1), np.float32)
    assert idx._kernel_applies() is (outcome != "kernel-off-raises")
    want_d, want_i = xla_twin(idx).search(q, 5)
    launched, program = _spy_on_the_program(kind, monkeypatch)

    if outcome == "kernel-returns":
        _, got_i = idx.search(q, 5)
        assert launched == [True], "the oracle ran beside a healthy kernel"
        np.testing.assert_array_equal(got_i, want_i)
        assert idx._pallas_runtime_ok and _ping(idx) == {"pallas_degraded": []}
    elif outcome == "kernel-raises":
        program.clear_cache()  # so that the injected failure is traced
        monkeypatch.setattr(adc_pallas, "adc_scan_pallas_planes", _boom)
        got_d, got_i = idx.search(q, 5)
        assert launched == [True, False]
        np.testing.assert_array_equal(got_i, want_i)  # served from the oracle
        np.testing.assert_array_equal(got_d, want_d)
        assert idx._pallas_runtime_ok is False
        assert _ping(idx) == {"pallas_degraded": ["i"]}
        idx.search(q, 5)
        assert launched == [True, False, False], "a demoted kernel was tried again"
        assert idx.use_pallas is True, "the demotion reached the persisted intent"
    elif outcome == "both-raise":
        idx.search(q, 5)
        cached = program._cache_size()
        with pytest.raises(Exception):
            idx.search(bad, 5)
        assert launched == [True, True, False]
        assert idx._pallas_runtime_ok, "a bad request demoted a healthy kernel"
        assert _ping(idx) == {"pallas_degraded": []}
        # no compiled program was dropped (a failed call leaves an entry of
        # its own behind): the next good search traces nothing
        assert program._cache_size() >= cached > 0
        from distributed_faiss_tpu.models import ivf as ivfmod

        monkeypatch.setattr(ivfmod, "_adc_pair_scores", _boom)
        idx.search(q, 5)
        assert launched[3:] == [True]
    else:
        with pytest.raises(Exception):
            idx.search(bad, 5)
        assert launched == [False], "a failing XLA call was retried"
        assert idx._pallas_runtime_ok


def test_pallas_degrade_ladder(rng, monkeypatch):
    """The ladder on a dot-metric index, one request after another: a bad
    request leaves the kernel alone, a kernel fault is served from the XLA
    path and demotes the kernel for this index only."""
    from distributed_faiss_tpu.models import ivf as ivfmod
    from distributed_faiss_tpu.models.ivf import IVFPQIndex

    n, d, m = 1500, 32, 8
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((6, d)).astype(np.float32)

    def build(use_pallas):
        idx = IVFPQIndex(d, 8, m=m, metric="dot", kmeans_iters=3, pq_iters=3,
                         use_pallas=use_pallas)
        idx.train(x)
        idx.add(x)
        idx.set_nprobe(4)
        return idx

    idx, other = build(True), build(True)
    assert idx._kernel_applies()
    want_d, want_i = xla_twin(idx).search(q, 5)
    other.search(q, 5)

    # drop compiled variants so the injected failure is actually reached
    ivfmod._ivf_pq_search.clear_cache()
    monkeypatch.setattr(adc_pallas, "adc_scan_pallas_planes", _boom)

    # a user error (bad dim) re-raises from the XLA oracle: no demotion
    with pytest.raises(Exception):
        idx.search(rng.standard_normal((2, d + 1)).astype(np.float32), 5)
    assert idx._pallas_runtime_ok

    got_d, got_i = idx.search(q, 5)
    assert not idx._pallas_runtime_ok
    assert other._pallas_runtime_ok, "one index's fault demoted another's kernel"
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-4, atol=1e-4)
    got_d, got_i = idx.search(q, 5)  # the XLA path from here on
    np.testing.assert_array_equal(got_i, want_i)
    ivfmod._ivf_pq_search.clear_cache()  # the injected kernel is in the traces


@pytest.mark.parametrize("kind", ["single", "sharded"])
def test_a_forced_kernel_at_a_geometry_it_does_not_take_serves_xla(
        rng, monkeypatch, kind):
    """``use_pallas=True`` on lists of capacity 64: the XLA arm serves, no
    fused scan is booked, and nothing is degraded — no kernel ever ran."""
    idx, x = small_pq(rng, kind, n=200, use_pallas=True)
    assert idx.lists.cap == 64 and not idx._kernel_applies()
    monkeypatch.setattr(adc_pallas, "adc_scan_pallas_planes", _boom)
    want_d, want_i = xla_twin(idx).search(x[:6], 5)
    launched, _ = _spy_on_the_program(kind, monkeypatch)
    sink = tracing.LatencyStats()
    with tracing.stage("engine.launch", sink=sink):
        got_d, got_i = idx.search(x[:6], 5)
    assert launched == [False]
    assert "engine.scan_fused" not in sink.summary()
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(got_d, want_d)
    assert idx._pallas_runtime_ok and _ping(idx) == {"pallas_degraded": []}


# ------------------------------------------------- three-plane kernel (PR 25)


def np_adc_f64(lut, codes):
    """float64 golden of the per-pair scan: lut (P, m, ksub), codes (P, L, m)."""
    out = np.zeros(codes.shape[:2])
    for mi in range(codes.shape[2]):
        out += np.take_along_axis(lut[:, mi, :].astype(np.float64),
                                  codes[:, :, mi].astype(np.int64), axis=1)
    return out


def wide_tables(rng, shape):
    """Entries of magnitude 1e-3 to 1e3, both signs: none is a bf16 value,
    so every one needs its mid and lo planes, and the sums cancel."""
    return (rng.choice([-1.0, 1.0], shape)
            * 10.0 ** rng.uniform(-3, 3, shape)).astype(np.float32)


def _whole_lists(codes):
    """``sizes`` of lists that fill their capacity: the whole scan."""
    return np.full(codes.shape[0], codes.shape[1], np.int32)


def steep_tables(rng, shape):
    """As an l2 table is: every entry negative, magnitudes up to 1e6."""
    return (-(10.0 ** rng.uniform(0, 6, shape))).astype(np.float32)


# (m, list length, table): whole tiles at the cells' m and the smallest; a
# list shorter than a tile and a ragged one (the kernel pads both, whatever
# planes_supported says of serving them); m off the cells'; an l2-like table
@pytest.mark.parametrize("m,L,tables", [
    (8, 128, wide_tables), (64, 128, wide_tables),
    (8, 1024, wide_tables), (64, 1024, wide_tables),
    (8, 8, wide_tables), (8, 200, wide_tables),
    (4, 256, wide_tables), (16, 256, wide_tables),
    (8, 256, steep_tables),
], ids=["m8-L128", "m64-L128", "m8-L1024", "m64-L1024", "short-L8",
        "ragged-L200", "m4", "m16", "negative-1e6"])
def test_planes_kernel_golden(rng, m, L, tables):
    lut = tables(rng, (3, m, 256))
    codes = rng.integers(0, 256, (3, L, m)).astype(np.uint8)
    got = np.asarray(adc_pallas.adc_scan_pallas_planes(
        lut, codes, _whole_lists(codes), interpret=True))
    assert got.shape == (3, L)
    np.testing.assert_allclose(got, np_adc_f64(lut, codes), rtol=1e-4, atol=1e-4)


def test_three_planes_hold_an_f32_exactly(rng):
    import jax.numpy as jnp

    x = wide_tables(rng, (1, 4096))
    planes = np.asarray(adc_pallas._bf16_planes(jnp.asarray(x)).astype(jnp.float32))
    assert planes.shape == (adc_pallas._PLANE_ROWS, 4096)
    np.testing.assert_array_equal((planes[0] + planes[1]) + planes[2], x[0])
    assert not planes[3:].any()
    assert planes[2].any(), "no entry needed its third plane: a weak table"
    # two planes are not enough: the bar the kernel test holds would not
    # tell, this does
    assert np.abs((planes[0] + planes[1]) - x[0]).max() > 0


def test_planes_kernel_is_exact_where_f32_sums_are(rng):
    """Entries on a 2**-13 grid under 2**7 (20 significant bits: all three
    planes) whose m=8 sums f32 holds exactly: the kernel must return the
    golden bit for bit, whatever order the MXU adds in."""
    lut = (rng.integers(-(1 << 20), 1 << 20, (2, 8, 256)) * 2.0 ** -13).astype(np.float32)
    codes = rng.integers(0, 256, (2, 256, 8)).astype(np.uint8)
    got = np.asarray(adc_pallas.adc_scan_pallas_planes(
        lut, codes, _whole_lists(codes), interpret=True))
    np.testing.assert_array_equal(got, np_adc_f64(lut, codes).astype(np.float32))


# ------------------------------------- the scan stops at a list's end (PR 35)

_SIZED = {(8, 256): 3, (64, 1024): 5, (16, 640): 7}  # (m, cap): a seed each


def _sized_case(m, cap):
    """Tables, codes and the kernel's scan of whole lists at (m, cap), made
    once a geometry."""
    if (m, cap) not in _sized_case.made:
        rng = np.random.default_rng(_SIZED[m, cap])
        lut = rng.standard_normal((7, m, 256)).astype(np.float32)
        codes = rng.integers(0, 256, (7, cap, m)).astype(np.uint8)
        whole = np.asarray(adc_pallas.adc_scan_pallas_planes(
            lut, codes, _whole_lists(codes), interpret=True))
        _sized_case.made[m, cap] = lut, codes, whole
    return _sized_case.made[m, cap]


_sized_case.made = {}


def _list_sizes(which, cap):
    if which == "mixed":
        return np.array([0, 1, 127, 128, 129, cap - 1, cap], np.int32)
    return np.full(7, {"cap-1": cap - 1, "cap": cap}.get(which, which), np.int32)


@pytest.mark.parametrize("which", [0, 1, 127, 128, 129, "cap-1", "cap", "mixed"])
@pytest.mark.parametrize("m,cap", sorted(_SIZED))
def test_the_scan_stops_at_the_end_of_each_list(m, cap, which):
    """A column under the pair's size holds what the scan of whole lists
    holds, bit for bit, and pq.adc_scan's sum; so does the rest of a
    sub-tile that holds a row; a sub-tile past the list is -inf, written by
    the kernel; and ``scanned_columns`` counts what was computed."""
    from distributed_faiss_tpu.ops import pq

    lut, codes, whole = _sized_case(m, cap)
    sizes = _list_sizes(which, cap)
    got = np.asarray(adc_pallas.adc_scan_pallas_planes(lut, codes, sizes, interpret=True))
    sub = adc_pallas._SUB_TILE
    computed = np.arange(cap)[None, :] < (-(-sizes // sub) * sub)[:, None]
    live = np.arange(cap)[None, :] < sizes[:, None]
    np.testing.assert_array_equal(got[computed], whole[computed])
    assert np.all(got[~computed] == -np.inf)
    xla = np.asarray(pq.adc_scan(lut, codes))
    np.testing.assert_allclose(got[live], xla[live], rtol=1e-4, atol=1e-4)
    assert int(adc_pallas.scanned_columns(sizes, cap)) == computed.sum()


def test_an_empty_list_splits_no_planes(monkeypatch):
    """``sizes[p]`` 0 skips the pair's whole scan, the plane split included:
    the split is traced inside the steps of the loop alone, and an empty
    list's loop has no step."""
    lut, codes, _ = _sized_case(8, 256)
    split = []
    orig = adc_pallas._bf16_planes
    monkeypatch.setattr(adc_pallas, "_bf16_planes",
                        lambda x: split.append(1) or orig(x))
    adc_pallas.adc_scan_pallas_planes.clear_cache()
    try:
        import jax

        S = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(lambda a, b, c: adc_pallas.adc_scan_pallas_planes(
            a, b, c, interpret=True))(S(lut.shape, np.float32), S(codes.shape, np.uint8),
                                      S((7,), np.int32))
    finally:
        adc_pallas.adc_scan_pallas_planes.clear_cache()
    # traced once a variant of a step that makes the planes, and nowhere else
    variants = adc_pallas._step_tiles(256)
    assert split == [1] * variants
    (call,) = [e for e in _walk_eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    top = [e.primitive.name for e in call.params["jaxpr"].eqns]
    assert top.count("while") == 1, "one loop of traced length over the live steps"
    assert "cond" not in top and "dot_general" not in top, "work outside the steps"
    assert "convert_element_type" not in top, "planes made outside the steps"
    (loop,) = [e for e in call.params["jaxpr"].eqns if e.primitive.name == "while"]
    steps = [e.primitive.name for e in _walk_eqns(loop.params["body_jaxpr"].jaxpr)]
    assert steps.count("dot_general") == 8 * (variants + 1)  # m a variant
    # the trip count is the live steps: 0 for an empty list
    got = np.asarray(adc_pallas.adc_scan_pallas_planes(
        lut, codes, np.zeros(7, np.int32), interpret=True))
    assert np.all(got == -np.inf)


def _lists_of_every_kind(rng, kind="single", **kw):
    """An IVF-PQ index (capacity 512) whose eight lists are empty, hold one
    row, end inside, at and just past a sub-tile, or are full, with rows
    removed from three of them; and queries that probe all of them."""
    from distributed_faiss_tpu.models.ivf import IVFPQIndex
    from distributed_faiss_tpu.parallel.mesh import ShardedIVFPQIndex

    d, nlist = 32, 8
    cls = {"single": IVFPQIndex, "sharded": ShardedIVFPQIndex}[kind]
    idx = cls(d, nlist, m=8, metric="l2", kmeans_iters=3, pq_iters=3, **kw)
    idx.train((4.0 * rng.standard_normal((nlist, 1, d))
               + rng.standard_normal((nlist, 250, d))).reshape(-1, d).astype(np.float32))
    cents = np.asarray(idx.centroids)
    want = [0, 1, 100, 128, 129, 300, 512, 40]
    x = np.concatenate([cents[i] + 0.05 * rng.standard_normal((n, d))
                        for i, n in enumerate(want)]).astype(np.float32)
    idx.add(x)
    idx.set_nprobe(nlist)
    assert idx.lists.cap == 512
    assert sorted(np.asarray(idx.lists.sizes).tolist()) == sorted(want)
    idx.remove_rows(np.array([1, 5, 101, 102, 300, 600, 601, 1100]))
    q = (cents[rng.integers(0, nlist, 12)]
         + 0.5 * rng.standard_normal((12, d))).astype(np.float32)
    return idx, q


def _with_every_size_at_capacity(monkeypatch):
    """The kernel told every list is full: the parent's scan (PR 31), which
    computed all of the capacity. The callers' masks are untouched."""
    import jax.numpy as jnp

    orig = adc_pallas.adc_scan_pallas_planes
    monkeypatch.setattr(
        adc_pallas, "adc_scan_pallas_planes",
        lambda lut, codes, sizes, **kw: orig(
            lut, codes, jnp.full_like(sizes, codes.shape[1]), **kw))


def test_the_search_is_the_whole_scans_bit_for_bit(rng, monkeypatch):
    """``_ivf_pq_search`` with the kernel stopping at each list's end gives
    the (vals, ids) it gives when the kernel scans every capacity, and its
    third output is the columns the kernel computed, counted here from the
    probes and the sizes."""
    import jax.numpy as jnp

    from distributed_faiss_tpu.models import ivf as ivfmod
    from distributed_faiss_tpu.ops import distance

    idx, q = _lists_of_every_kind(rng, use_pallas=True)
    nprobe, cap = 4, idx.lists.cap
    args = (idx.centroids, idx.codebooks, idx.lists.data, idx.lists.ids,
            idx.lists.sizes, jnp.asarray(distance.pad_rows(q, 16)))
    kw = dict(k=10, nprobe=nprobe, g=2, metric="l2")
    vals, ids, cols = ivfmod._ivf_pq_search(*args, use_pallas=True, **kw)
    _, probes = distance.segmented_argtopk(
        distance.pairwise_scores(args[5], idx.centroids, "l2"), nprobe)
    sizes = np.asarray(idx.lists.sizes)[np.asarray(probes)]
    sub = adc_pallas._SUB_TILE
    assert int(cols) == (-(-sizes // sub) * sub).sum() < sizes.size * cap
    xv, xi, xcols = ivfmod._ivf_pq_search(*args, use_pallas=False, **kw)
    assert int(xcols) == sizes.size * cap
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(xi))

    ivfmod._ivf_pq_search.clear_cache()
    _with_every_size_at_capacity(monkeypatch)
    try:
        wv, wi, _ = ivfmod._ivf_pq_search(*args, use_pallas=True, **kw)
    finally:
        ivfmod._ivf_pq_search.clear_cache()
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(wi))
    assert (np.asarray(ids) >= 0).all()


@pytest.mark.parametrize("forced,nq,skipped", [
    (True, 12, True),    # one block on the kernel
    (True, 37, True),    # five blocks in one lax.map launch: one count a block
    (False, 12, False),  # the XLA arm computes every column: 0 skipped
], ids=["fused", "fused-multiblock", "forced-off"])
def test_scan_adc_cols_books_the_capacity_and_what_was_skipped(
        rng, monkeypatch, forced, nq, skipped):
    from distributed_faiss_tpu.models import base

    monkeypatch.setattr(base, "MAX_QUERY_BLOCK", 8)
    idx, q = _lists_of_every_kind(rng, use_pallas=forced)
    q = np.concatenate([q] * 4)[:nq]
    sink = tracing.LatencyStats()
    with tracing.stage("engine.launch", sink=sink):
        idx.search(q, 5)
    rows = sink.summary()
    # rows of the launch: the pow2 bucket of a batch's blocks of 8
    padded = 8 * base._next_pow2(-(-nq // 8), 1)
    total = padded * idx.nprobe * idx.lists.cap
    assert rows["engine.scan"]["count"] == 1 == rows["engine.scan_adc_cols"]["count"]
    assert rows["engine.scan_adc_cols"]["total_s"] == total
    assert rows["engine.scan_adc_cols_skipped"]["count"] == 1
    got = rows["engine.scan_adc_cols_skipped"]["total_s"]
    if not skipped:
        assert got == 0
        return
    # every query probes all eight lists (padding rows too: a zero query
    # has probes of its own), so a row skips what the lists leave of 8 caps
    sizes = np.asarray(idx.lists.sizes)
    a_row = (idx.lists.cap - -(-sizes // 128) * 128).sum()
    assert got == padded * a_row > 0.5 * total


def _first_m_over_budget():
    m = 1
    while adc_pallas._planes_vmem_bytes(m, 256, 128) <= adc_pallas._ONEHOT_VMEM_BUDGET:
        m += 1
    return m


def _tile_chosen(m, L):
    """The candidate block adc_scan_pallas_planes picks, read off its grid
    (pairs, blocks of a list), and the sub-tile and the widest step its loop
    walks a block in, read off the variants' compares."""
    import jax

    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda a, b, c: adc_pallas.adc_scan_pallas_planes(
        a, b, c, interpret=True))(S((2, m, 256), np.float32), S((2, L, m), np.uint8),
                                  S((2,), np.int32))
    (call,) = [e for e in _walk_eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    pairs, tiles = call.params["grid_mapping"].grid
    assert pairs == 2 and L % tiles == 0
    assert call.params["grid_mapping"].num_index_operands == 1  # sizes, in SMEM
    widths = collections.Counter(
        e.outvars[0].aval.shape[1] for e in _walk_eqns(call.params["jaxpr"])
        if e.primitive.name == "eq" and e.outvars[0].aval.ndim == 2
        and e.outvars[0].aval.shape[0] == 256)
    # a variant a count of live sub-tiles of a step, m compares each; the
    # whole step twice, once on planes an earlier step made
    assert widths.pop(max(widths)) == 2 * m and set(widths.values()) <= {m}
    widths[adc_pallas._SUB_TILE * adc_pallas._step_tiles(L // tiles)] = m
    sub = min(widths)
    assert sorted(widths) == [n * sub for n in range(1, len(widths) + 1)]
    return L // tiles, sub, max(widths)


# planes_supported's truth table: what it admits the kernel has a tile for
# inside its VMEM budget (the max() over tiles is never over an empty set
# and 128 is never taken over the budget); at its boundaries it refuses
@pytest.mark.parametrize("m,ksub,L,admits", [
    (None, 256, None, True),                  # a sweep of what it admits
    (64, 256, 127, False), (64, 256, 129, False), (64, 256, 1000, False),
    (64, 16, 1024, False),                    # 4-bit codes
    ("first-over-budget", 256, 1024, False),
], ids=["admitted", "L127", "L129", "L1000", "ksub16", "m-over-budget"])
def test_what_planes_supported_admits_the_kernel_has_a_tile_for(m, ksub, L, admits):
    budget = adc_pallas._ONEHOT_VMEM_BUDGET
    if m == "first-over-budget":
        m = _first_m_over_budget()
        assert adc_pallas.planes_supported(m - 1, ksub, L), "not the boundary"
    if not admits:
        assert not adc_pallas.planes_supported(m, ksub, L)
        return
    top = _first_m_over_budget() - 1
    for m, L in [(1, 128), (1, 4096), (8, 640), (64, 128), (64, 1024),
                 (64, 4096), (64, 8192 + 128), (top, 128), (top, 4096)]:
        assert adc_pallas.planes_supported(m, 256, L), (m, L)
        tile, sub, step = _tile_chosen(m, L)
        assert tile % 128 == 0 and tile <= adc_pallas._PLANES_TILE
        assert adc_pallas._planes_vmem_bytes(m, 256, tile) <= budget, (m, L, tile)
        # the grain it stops at, and the widest it scans at once: whole
        # sub-tiles, a whole number of steps a block
        assert sub == adc_pallas._SUB_TILE == 128
        assert step == sub * adc_pallas._step_tiles(tile) and tile % step == 0, (m, L)


def _onehots_outside_the_kernel(eqns):
    """The XLA arm's mark: a compare whose result is (.., rows, m, ksub);
    the kernel's own compares are (ksub, tile)."""
    return [e for e in eqns if e.primitive.name == "eq"
            and e.outvars[0].aval.ndim >= 4 and e.outvars[0].aval.shape[-1] == 256]


# the knnlm cells' program (d 768, m 64, capacity 1024, k 10 x 8, nprobe 32)
# at a 64-row and a 256-row block; top_k counts are the parent's (bba2e24)
@pytest.mark.parametrize("rows,top_ks", [(64, 4), (256, 3)])
def test_the_served_program_holds_one_kernel_and_no_onehot(rows, top_ks):
    import jax

    from distributed_faiss_tpu.models import ivf as ivfmod

    d, m, cap, k, nprobe, nlist = 768, 64, 1024, 80, 32, 4096
    g = ivfmod.probe_group_size(
        nprobe, ivfmod.pq_probe_payload_bytes(cap, m, nq_block=rows))
    S = jax.ShapeDtypeStruct
    args = (S((nlist, d), np.float32), S((m, 256, d // m), np.float32),
            S((nlist, cap, m), np.uint8), S((nlist, cap), np.int32),
            S((nlist,), np.int32), S((rows, d), np.float32))

    def eqns(use_pallas):
        return list(_walk_eqns(jax.make_jaxpr(lambda *a: ivfmod._ivf_pq_search(
            *a, k=k, nprobe=nprobe, g=g, metric="l2",
            use_pallas=use_pallas))(*args).jaxpr))

    fused, xla = eqns(True), eqns(False)
    count = lambda es, name: sum(e.primitive.name == name for e in es)
    assert count(fused, "pallas_call") == 1 and count(xla, "pallas_call") == 0
    assert _onehots_outside_the_kernel(xla), "the detector is stale"
    assert not _onehots_outside_the_kernel(fused)
    assert count(fused, "top_k") == top_ks == count(xla, "top_k")


class _Lists:
    def __init__(self, cap):
        self.cap = cap


# (on a TPU, m, list capacity, use_pallas) -> the fused kernel runs
@pytest.mark.parametrize("tpu,m,cap,forced,fused", [
    (False, 8, 1024, None, False),   # the CPU backend keeps the XLA one-hot
    (True, 8, 1024, None, True),     # a TPU and a geometry the kernel takes
    (True, 64, 1024, None, True),    # the benchmark cells' own
    (True, 8, 96, None, False),      # capacity not in whole 128-row tiles
    (True, 8, 64, None, False),      # the smallest capacity lists start at
    (True, 512, 1024, None, False),  # a table the VMEM model refuses
    (False, 8, 1024, True, True),    # an explicit True forces (tests, A/B)
    (True, 8, 96, True, False),      # but only where the kernel takes the lists
    (True, 64, 1024, False, False),  # an explicit False forces
], ids=["cpu", "tpu", "tpu-m64", "cap96", "cap64", "m512", "force-on",
        "force-on-cap96", "force-off"])
def test_the_index_chooses_its_adc_kernel(monkeypatch, tpu, m, cap, forced, fused):
    from distributed_faiss_tpu.models import ivf as ivfmod

    monkeypatch.setattr(adc_pallas, "on_tpu", lambda: tpu)
    idx = ivfmod.IVFPQIndex(2 * m, 4, m=m, use_pallas=forced)
    assert idx._kernel_applies() is False  # no lists yet
    idx.lists = _Lists(cap)
    assert idx._kernel_applies() is fused


def test_a_chosen_index_runs_the_planes_kernel_and_matches_xla(rng, monkeypatch):
    """use_pallas=None on (what it takes for) a TPU: the probe loop calls
    the three-plane kernel — interpreted here, so the spy pins the mode —
    and serves what the XLA path serves."""
    from distributed_faiss_tpu.models import ivf as ivfmod

    idx, x = small_pq(rng)
    assert idx.use_pallas is None
    want_d, want_i = xla_twin(idx).search(x[:20], 5)
    calls = []
    orig = adc_pallas.adc_scan_pallas_planes

    def spy(lut, codes, sizes, **kw):
        calls.append(tuple(lut.shape))
        return orig(lut, codes, sizes, interpret=True)

    monkeypatch.setattr(adc_pallas, "on_tpu", lambda: True)
    monkeypatch.setattr(adc_pallas, "adc_scan_pallas_planes", spy)
    ivfmod._ivf_pq_search.clear_cache()
    got_d, got_i = idx.search(x[:20], 5)
    ivfmod._ivf_pq_search.clear_cache()  # the spy is baked into the traces
    assert calls and idx._adc_validated and idx._pallas_runtime_ok
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-4, atol=1e-4)


@pytest.fixture(scope="module")
def trained_state():
    idx, x = small_pq(np.random.default_rng(7))
    state = idx.state_dict()
    assert sorted(k for k in state if "pallas" in k or "lut" in k) == ["pallas_adc"]
    del state["pallas_adc"]
    return state, x[:8], xla_twin(idx).search(x[:8], 5)


@pytest.mark.parametrize("saved,loads_as", [
    ({"use_pallas": False}, None),                       # the old default: choose
    ({"use_pallas": True}, True),                        # the old pallas_adc=True
    # PR 25 to 29 wrote both keys, and the rounded-table mode beside them: it
    # loads at exact table values
    ({"use_pallas": True, "pallas_adc": True, "adc_lut_bf16": True}, True),
    ({"use_pallas": False, "pallas_adc": None}, None),
    ({"pallas_adc": None}, None),                        # today's default
    ({"pallas_adc": False}, False),                      # today's forced off
    ({"pallas_adc": True}, True),
], ids=["old-default", "old-forced", "old-bf16-table", "pr25-choose", "choose",
        "off", "on"])
def test_a_snapshots_kernel_intent(trained_state, saved, loads_as):
    from distributed_faiss_tpu.models.ivf import IVFPQIndex

    state, q, (want_d, want_i) = trained_state
    idx = IVFPQIndex.from_state_dict({**state, **saved})
    assert idx.use_pallas is loads_as
    again = IVFPQIndex.from_state_dict(idx.state_dict())
    assert again.use_pallas is loads_as
    got_d, got_i = idx.search(q, 5)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-5, atol=1e-5)


def test_the_factory_leaves_the_choice_to_the_index():
    from distributed_faiss_tpu.models import factory
    from distributed_faiss_tpu.utils.config import IndexCfg

    def build(**extra):
        return factory.build_index(IndexCfg(
            index_builder_type="knnlm", dim=32, metric="l2", centroids=4,
            code_size=8, **extra))

    assert build().use_pallas is None
    assert build(pallas_adc=True).use_pallas is True
    assert build(pallas_adc=False).use_pallas is False


def test_first_fused_scan_with_wrong_scores_demotes_and_serves_xla(rng, monkeypatch):
    """A kernel that runs and returns wrong numbers (one bf16 pass where
    three were meant: PR 21's finding) raises nothing for
    pallas_guarded to catch: the first-use check does, before a caller
    sees a score."""
    from distributed_faiss_tpu.models import ivf as ivfmod

    idx, x = small_pq(rng, use_pallas=True)
    want_d, want_i = xla_twin(idx).search(x[:20], 5)
    orig = adc_pallas.adc_scan_pallas_planes

    def one_plane(lut, codes, sizes, **kw):
        import jax.numpy as jnp

        return orig(lut.astype(jnp.bfloat16).astype(jnp.float32), codes, sizes, **kw)

    monkeypatch.setattr(adc_pallas, "adc_scan_pallas_planes", one_plane)
    ivfmod._ivf_pq_search.clear_cache()
    sink = tracing.LatencyStats()
    with tracing.stage("engine.launch", sink=sink):
        got_d, got_i = idx.search(x[:20], 5)
    ivfmod._ivf_pq_search.clear_cache()
    assert idx._adc_validated
    assert idx._pallas_runtime_ok is False, "wrong scores survived the first use"
    assert "engine.scan_fused" not in sink.summary()
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(got_d, want_d)


@pytest.mark.parametrize("forced,nq,scans,fused", [
    (True, 20, 1, 1),    # one block, fused
    (True, 37, 1, 1),    # five blocks in one lax.map launch: one engine.scan
    (False, 20, 1, 0),   # the XLA path books no fused scan
    (None, 20, 1, 0),    # nor does the CPU backend's own choice
], ids=["fused", "fused-multiblock", "forced-off", "cpu-choice"])
def test_scan_fused_counts_once_a_fused_scan(rng, monkeypatch, forced, nq, scans, fused):
    from distributed_faiss_tpu.models import base

    monkeypatch.setattr(base, "MAX_QUERY_BLOCK", 8)
    idx, x = small_pq(rng, use_pallas=forced)
    sink = tracing.LatencyStats()
    with tracing.stage("engine.launch", sink=sink):
        idx.search(x[:nq], 5)
    rows = sink.summary()
    assert rows["engine.scan"]["count"] == scans
    assert rows.get("engine.scan_fused", {"count": 0})["count"] == fused
    idx.search(x[:nq], 5)  # outside an engine there is no sink: nothing booked
    assert sink.summary()["engine.scan"]["count"] == scans


def test_per_block_scans_each_count(rng, monkeypatch):
    """Without a fused_fn every block is its own engine.scan; with the
    kernel on each books engine.scan_fused beside it."""
    from distributed_faiss_tpu.models import base

    monkeypatch.setattr(base, "MAX_QUERY_BLOCK", 8)
    idx, x = small_pq(rng, use_pallas=True)
    blocked = base.launch_blocked_search  # the driver's launch half
    monkeypatch.setattr(
        base, "launch_blocked_search",
        lambda q, k, metric, fn, block=256, fused_fn=None, refine_fn=None,
        with_counts=False:
        blocked(q, k, metric, fn, block, None, refine_fn, with_counts))
    sink = tracing.LatencyStats()
    with tracing.stage("engine.launch", sink=sink):
        idx.search(x[:20], 5)
    rows = sink.summary()
    assert rows["engine.scan"]["count"] == 3 == rows["engine.scan_fused"]["count"]


def _obs(scans, fused, ranks=1):
    def snap(n_scan, n_fused):
        block = {"engine.scan": {"count": n_scan, "total_s": 0.1 * n_scan}}
        if n_fused is not None:
            block["engine.scan_fused"] = {"count": n_fused, "total_s": float(n_fused)}
        return {"engine": {"bench": block}}

    return {"index_id": "bench", "window_s": 1.0,
            "stats_before": [snap(5, None if fused is None else 5)] * ranks,
            "stats_after": [snap(5 + scans, None if fused is None else 5 + fused)] * ranks}


@pytest.mark.parametrize("name", ["kernel.adc_fused_pct", "kernel.adc_fused_pct.online"])
def test_the_fused_share_readers(name):
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from perfbench import loader

    reader = loader.load_module(os.path.join(
        repo, "perfbench", "layer_metrics", f"{name}.py"))
    assert reader.read(_obs(40, 40)) == 100.0
    assert reader.read(_obs(40, 40, ranks=4)) == 100.0
    assert reader.read(_obs(40, 0)) == 0.0  # a silent demotion reads 0
    assert reader.read(_obs(40, 10)) == 25.0
    # a program without the counter (the parent commit): nothing to read
    assert reader.read(_obs(40, None)) is None
    assert reader.read({"index_id": "bench", "window_s": 1.0}) is None
    bench = loader.read_json(os.path.join(repo, "BENCHMARK.json"))
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["layer"] == "models and kernels"
