"""``blocked_search`` in two halves (models/base.py): ``collect(launch())`` is
the serial driver's result bit for bit, handles collect in any order, the
fault ladder does at the collect's wait what it did at the dispatch, and a
store that an add donates or grows between a launch and its collect leaves
that search its pre-add answer. CPU backend; the Pallas kernels run in the
interpreter."""

import logging

import jax
import numpy as np
import pytest

from distributed_faiss_tpu.models import base
from distributed_faiss_tpu.models import ivf as ivfmod
from distributed_faiss_tpu.models.flat import FlatIndex
from distributed_faiss_tpu.models.ivf import GuardedScan, IVFFlatIndex, IVFPQIndex
from distributed_faiss_tpu.models.pretransform import PreTransformIndex
from distributed_faiss_tpu.utils import tracing, xfercheck

D, K = 32, 5


def serial_blocked_search(q, k, metric, fn, block=256, fused_fn=None,
                          refine_fn=None, with_counts=False):
    """The driver as it was before the halves, kept as the reference: feed,
    scan, wait, refine, fetch, one block after the other."""
    def waited(out):
        return out.wait() if isinstance(out, base.Dispatched) else jax.block_until_ready(out)

    q = np.asarray(q, np.float32)
    nq = q.shape[0]
    if fused_fn is not None and nq > block:
        nblocks = base._next_pow2(-(-nq // block), 1)
        qp = np.pad(q, ((0, nblocks * block - nq), (0, 0)))
        q3 = jax.device_put(qp.reshape(nblocks, block, -1))
        rows = np.clip(nq - block * np.arange(nblocks), 0, block)
        counts = (jax.device_put(rows.astype(np.int32)),) if with_counts else ()
        vals, ids = waited(fused_fn(q3, *counts))
        with xfercheck.explicit("reference fetch"):
            out_s = np.asarray(vals).reshape(nblocks * block, -1)[:nq]
            out_i = np.asarray(ids).reshape(nblocks * block, -1)[:nq]
        return base.finalize_results(out_s, out_i, metric)
    out_s = np.empty((nq, k), np.float32)
    out_i = np.empty((nq, k), np.int64)
    for s in range(0, nq, block):
        n, chunk = base._padded_block(q, s, block)
        chunk = jax.device_put(chunk)
        counts = (jax.device_put(np.int32(n)),) if with_counts else ()
        vals, ids = waited(fn(chunk, *counts))
        if refine_fn is not None:
            vals, ids = refine_fn(chunk, ids)
        with xfercheck.explicit("reference fetch"):
            out_s[s:s + n], out_i[s:s + n] = base.finalize_results(
                np.asarray(vals)[:n], np.asarray(ids)[:n], metric)
    return out_s, out_i


def corpus(seed=0, n=3000):
    rng = np.random.default_rng(seed)
    centres = rng.standard_normal((24, D)).astype(np.float32) * 3
    return (centres[rng.integers(0, 24, n)]
            + rng.standard_normal((n, D)).astype(np.float32))


def build(kind, x, **kw):
    if kind == "flat":
        idx = FlatIndex(D, "l2")
    elif kind == "ivf_pq_refine":
        idx = IVFPQIndex(D, 8, m=8, metric="l2", kmeans_iters=3, pq_iters=3,
                         refine_k_factor=4, **kw)
    else:
        codec = {"ivf_flat_f16": "f16", "ivf_flat_sq8": "sq8"}[kind]
        idx = IVFFlatIndex(D, 8, "l2", codec=codec, kmeans_iters=3,
                           refine_k_factor=4 if codec == "sq8" else 0)
    idx.train(x[:2000])
    idx.add(x)
    idx.set_nprobe(4)
    return idx


KINDS = ["ivf_pq_refine", "ivf_flat_f16", "ivf_flat_sq8", "flat"]


@pytest.fixture(scope="module")
def indexes():
    x = corpus()
    return x, {kind: build(kind, x) for kind in KINDS}


@pytest.mark.parametrize("nq", [20, 37], ids=["single-block", "fused"])
@pytest.mark.parametrize("kind", KINDS)
def test_collect_of_launch_is_the_serial_drivers_result_bit_for_bit(
        indexes, monkeypatch, kind, nq):
    """37 rows over blocks of 8 ride the fused entry (five blocks in one
    launch), 20 rows over a block of 1024 a single dispatch."""
    x, built = indexes
    idx = built[kind]
    if nq == 37:
        monkeypatch.setattr(base, "MAX_QUERY_BLOCK", 8)
    q = x[100:100 + nq] + 0.01
    got_d, got_i = idx.launch_search(q, K).collect()
    again_d, again_i = idx.search(q, K)
    monkeypatch.setattr(
        base, "launch_blocked_search",
        lambda *a, **kw: base.finished(serial_blocked_search(*a, **kw)))
    want_d, want_i = idx.search(q, K)
    for d, i in ((got_d, got_i), (again_d, again_i)):
        assert d.dtype == want_d.dtype and i.dtype == want_i.dtype == np.int64
        np.testing.assert_array_equal(i, want_i)
        np.testing.assert_array_equal(d, want_d)
    assert (got_i[:, 0] >= 0).all()


@pytest.mark.parametrize("kind", KINDS)
def test_handles_launched_back_to_back_collect_in_either_order(indexes, kind):
    x, built = indexes
    idx = built[kind]
    qa, qb = x[:16] + 0.01, x[500:540] + 0.01
    want_a, want_b = idx.search(qa, K), idx.search(qb, K)
    for order in ((0, 1), (1, 0)):
        handles = [idx.launch_search(qa, K), idx.launch_search(qb, K)]
        got = {n: handles[n].collect() for n in order}
        for (d, i), (wd, wi) in ((got[0], want_a), (got[1], want_b)):
            np.testing.assert_array_equal(i, wi)
            np.testing.assert_array_equal(d, wd)


def test_an_empty_index_and_an_empty_batch_come_back_finished():
    idx = FlatIndex(D, "l2")
    d, i = idx.launch_search(np.zeros((3, D), np.float32), K).collect()
    assert d.shape == i.shape == (3, K) and (i == -1).all() and np.isinf(d).all()
    idx.add(corpus(n=100))
    d, i = idx.launch_search(np.zeros((0, D), np.float32), K).collect()
    assert d.shape == i.shape == (0, K) and i.dtype == np.int64


def test_the_stages_are_booked_once_a_launch_across_the_halves(indexes):
    """``engine.feed`` and ``engine.dispatch`` (the host's share of the scan)
    in the launch, ``engine.scan`` from the dispatch to the collect's wait,
    ``engine.refine_fetch`` in the collect: one record each, into the sink
    the launch found, though the collect runs where there is none."""
    x, built = indexes
    idx = built["ivf_pq_refine"]
    sink = tracing.LatencyStats()
    with tracing.stage("engine.launch", sink=sink):
        handle = idx.launch_search(x[:20], K)
    assert set(sink.summary()) == {"engine.feed", "engine.dispatch", "engine.launch"}
    handle.collect()
    rows = sink.summary()
    for name in ("engine.feed", "engine.dispatch", "engine.scan", "engine.refine_fetch"):
        assert rows[name]["count"] == 1, name
    assert rows["engine.scan"]["total_s"] > rows["engine.dispatch"]["total_s"] > 0
    # the count rows an index books after its collect go where the collect
    # runs: inside ``engine.launch``'s last leg in an engine
    with tracing.stage("engine.launch", sink=sink):
        idx.launch_search(x[:20], K).collect()
    rows = sink.summary()
    for name in ("engine.scan_adc_cols", "engine.scan_adc_cols_skipped"):
        assert rows[name]["count"] == 1, name
    assert rows["engine.scan"]["count"] == 2


# ------------------------------------------------- the ladder at the collect


class Leaf:
    """A program output whose wait raises, as an asynchronous kernel abort
    does: ``jax.block_until_ready`` calls ``block_until_ready`` of a leaf
    that has one."""

    def __init__(self, fails):
        self.fails = fails

    def block_until_ready(self):
        if self.fails:
            raise RuntimeError("kernel abort (injected at the wait)")
        return self


class Kernelled:
    _PALLAS_KERNEL = "injected"

    def __init__(self, applies=True):
        self._pallas_runtime_ok, self.applies = True, applies

    def _kernel_applies(self):
        return self.applies


@pytest.mark.parametrize("outcome", ["kernel-returns", "kernel-raises-at-the-wait",
                                     "both-raise-at-the-wait", "kernel-off-raises"])
def test_the_guards_contract_holds_at_the_collects_wait(outcome, caplog):
    """``GuardedScan``: nothing waits at the dispatch; at ``wait()`` the
    kernel's outputs raise, the XLA oracle runs on what the handle holds,
    and the ladder means what ``pallas_guarded``'s does."""
    index = Kernelled(applies=outcome != "kernel-off-raises")
    tried, served = [], []

    def call(with_pallas):
        tried.append(with_pallas)
        fails = {"kernel-returns": False,
                 "kernel-raises-at-the-wait": with_pallas,
                 "both-raise-at-the-wait": True,
                 "kernel-off-raises": True}[outcome]
        return (Leaf(fails), "ids-pallas" if with_pallas else "ids-xla")

    def settled(out, with_pallas):
        served.append(with_pallas)
        return out

    scan = GuardedScan(index, call, settled)
    assert tried == [outcome != "kernel-off-raises"], "the dispatch waited or retried"
    assert scan.out[1] == ("ids-xla" if outcome == "kernel-off-raises" else "ids-pallas")
    with caplog.at_level(logging.ERROR):
        if outcome == "kernel-returns":
            assert scan.wait()[1] == "ids-pallas"
            assert tried == [True] and served == [True] and index._pallas_runtime_ok
        elif outcome == "kernel-raises-at-the-wait":
            assert scan.wait()[1] == "ids-xla"  # served from the oracle
            assert tried == [True, False] and served == [False]
            assert index._pallas_runtime_ok is False
            assert "failed on this backend" in caplog.text
            # the next scan of the index does not try the kernel again
            assert GuardedScan(index, call).wait()[1] == "ids-xla"
            assert tried == [True, False, False]
        elif outcome == "both-raise-at-the-wait":
            with pytest.raises(RuntimeError, match="injected at the wait"):
                scan.wait()
            assert tried == [True, False] and served == []
            assert index._pallas_runtime_ok, "a bad request demoted a healthy kernel"
        else:
            with pytest.raises(RuntimeError, match="injected at the wait"):
                scan.wait()
            assert tried == [False], "a failing XLA call was retried"
            assert index._pallas_runtime_ok


def test_pallas_guarded_is_the_two_halves_in_one_call():
    index = Kernelled()
    out = ivfmod.pallas_guarded(
        index, lambda p: (Leaf(fails=p), "pallas" if p else "xla"))
    assert out[1] == "xla" and index._pallas_runtime_ok is False


def test_a_kernel_abort_at_the_wait_serves_the_oracle_and_reranks_on_its_ids(
        indexes, monkeypatch):
    """Through a real index: the fused ADC scan's outputs raise at the
    collect's wait (the rerank was dispatched on them already); the search
    is served from the XLA scan, reranked again on its candidates, equal to
    an index that never had the kernel, and the kernel is demoted."""
    x, _ = indexes
    idx = build("ivf_pq_refine", x, use_pallas=True)
    q = x[200:220] + 0.01
    idx.search(q, K)  # the first-use check, and a healthy kernel's answer
    assert idx._kernel_applies() and idx._pallas_runtime_ok
    ref = IVFPQIndex.from_state_dict({**idx.state_dict(), "pallas_adc": False})
    want_d, want_i = ref.search(q, K)
    ready = base.Dispatched._ready
    aborted = []

    def abort_once(self):
        if getattr(self, "with_pallas", False) and not aborted:
            aborted.append(self)
            raise RuntimeError("kernel abort (injected at the wait)")
        return ready(self)

    monkeypatch.setattr(base.Dispatched, "_ready", abort_once)
    sink = tracing.LatencyStats()
    with tracing.stage("engine.launch", sink=sink):
        handle = idx.launch_search(q, K)
        assert idx._pallas_runtime_ok and not aborted, "the launch waited"
        got_d, got_i = handle.collect()
    assert len(aborted) == 1 and idx._pallas_runtime_ok is False
    assert idx.use_pallas is True, "the demotion reached the persisted intent"
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(got_d, want_d)
    rows = sink.summary()
    assert rows["engine.scan"]["count"] == 1 and "engine.scan_fused" not in rows
    assert rows["engine.scan_adc_cols_skipped"]["total_s"] == 0  # the XLA arm skips none


def test_a_bad_request_raises_at_the_launch_with_no_flag_flipped(indexes):
    x, _ = indexes
    idx = build("ivf_pq_refine", x, use_pallas=True)
    idx.search(x[:8], K)
    with pytest.raises(Exception):
        idx.launch_search(np.zeros((4, D + 1), np.float32), K)
    assert idx._pallas_runtime_ok
    assert idx.search(x[:8], K)[1].shape == (8, K)


# --------------------------------------------- an add between the two halves


@pytest.mark.parametrize("kind", ["flat", "ivf_pq_refine", "ivf_flat_sq8"])
@pytest.mark.parametrize("new_rows", [100, 6000], ids=["donated", "grown"])
def test_an_add_between_launch_and_collect_leaves_the_pre_add_answer(kind, new_rows):
    """The launch holds its operands: an add that writes into the store
    through a donating program (100 rows), or reallocates it first (6000
    rows, past the capacity), before the collect changes nothing of that
    search; one launched after the add's return finds the new rows first."""
    x = corpus(1, 3000 + new_rows)
    idx = build(kind, x[:3000])
    store = idx.store if kind == "flat" else idx.refine_store
    cap = store.cap
    q = x[3000:3016]  # rows the add brings, searched before and after it
    want_d, want_i = idx.search(q, K)
    assert (want_i < 3000).all()
    handles = [idx.launch_search(q, K) for _ in range(2)]
    idx.add(x[3000:])
    assert (store.cap > cap) == (new_rows == 6000)
    after_d, after_i = idx.search(q, K)
    for handle in reversed(handles):
        got_d, got_i = handle.collect()
        np.testing.assert_array_equal(got_i, want_i)
        np.testing.assert_array_equal(got_d, want_d)
    np.testing.assert_array_equal(after_i[:, 0], np.arange(3000, 3016))  # self_lookup_top1
    assert idx.ntotal == 3000 + new_rows


# ------------------------------------------------------ who offers the halves


def test_a_pretransform_wrapper_launches_what_its_inner_index_offers(indexes):
    x, _ = indexes
    inner = IVFFlatIndex(16, 8, "l2", codec="f16", kmeans_iters=3)
    idx = PreTransformIndex(inner, D, pca=True)
    idx.train(x[:2000])
    idx.add(x)
    idx.set_nprobe(4)
    q = x[:12] + 0.01
    want_d, want_i = idx.search(q, K)
    sink = tracing.LatencyStats()
    with tracing.stage("engine.launch", sink=sink):
        handle = idx.launch_search(q, K)
    assert "engine.scan" not in sink.summary(), "the launch waited for the scan"
    got_d, got_i = handle.collect()
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(got_d, want_d)


def test_an_index_without_the_halves_hands_back_a_finished_handle():
    """The default: the whole search runs in the launch (HNSW, the mesh
    indexes, which reset what their local parents offer)."""
    from distributed_faiss_tpu.models.hnsw import HNSWSQIndex
    from distributed_faiss_tpu.parallel import mesh

    for cls in (HNSWSQIndex, mesh.ShardedFlatIndex, mesh.ShardedIVFFlatIndex,
                mesh.ShardedIVFPQIndex):
        assert cls.launch_search is base.TpuIndex.launch_search, cls

    class Counting(base.TpuIndex):
        searched = 0

        def search(self, q, k):
            self.searched += 1
            return np.zeros((q.shape[0], k), np.float32), np.zeros((q.shape[0], k), np.int64)

    idx = Counting(D, "l2")
    handle = idx.launch_search(np.zeros((2, D), np.float32), K)
    assert idx.searched == 1
    assert handle.collect()[1].shape == (2, K) and idx.searched == 1
