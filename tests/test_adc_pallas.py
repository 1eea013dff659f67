"""Pallas ADC kernel golden tests (interpreter mode on CPU — same kernel
code path the TPU runs compiled)."""

import numpy as np
import pytest

from distributed_faiss_tpu.ops import adc_pallas, pq
from distributed_faiss_tpu.utils import tracing


@pytest.fixture
def problem(rng):
    nq, m, ksub, L = 8, 4, 256, 700  # L deliberately not a tile multiple
    lut = rng.standard_normal((nq, m, ksub)).astype(np.float32)
    codes = rng.integers(0, 256, (L, m)).astype(np.uint8)
    return lut, codes


def np_adc(lut, codes):
    nq = lut.shape[0]
    L = codes.shape[0]
    out = np.zeros((nq, L), np.float32)
    for mi in range(codes.shape[1]):
        out += lut[:, mi, codes[:, mi].astype(np.int64)]
    return out


def test_shared_kernel_golden(problem):
    lut, codes = problem
    got = np.asarray(adc_pallas.adc_scan_shared_pallas(lut, codes, tile=128, interpret=True))
    np.testing.assert_allclose(got, np_adc(lut, codes), rtol=1e-5, atol=1e-5)


def test_shared_kernel_matches_xla_path(problem):
    lut, codes = problem
    got = np.asarray(adc_pallas.adc_scan_shared_auto(lut, codes, tile=256))
    want = np.asarray(pq.adc_scan_shared(lut, codes))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_per_query_kernel_golden(rng):
    nq, m, ksub, L = 5, 8, 256, 300
    lut = rng.standard_normal((nq, m, ksub)).astype(np.float32)
    codes = rng.integers(0, 256, (nq, L, m)).astype(np.uint8)
    got = np.asarray(adc_pallas.adc_scan_pallas(lut, codes, tile=128, interpret=True))
    want = np.asarray(pq.adc_scan(lut, codes))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_bf16_lut_close_to_f32(problem):
    """bf16 LUT (the fast serving mode, 1.5x on TPU v5e): one-hot side is
    exact, so error is bounded by bf16 rounding of the LUT entries."""
    import jax.numpy as jnp

    lut, codes = problem
    got = np.asarray(adc_pallas.adc_scan_shared_pallas(
        jnp.asarray(lut).astype(jnp.bfloat16), codes, tile=128, interpret=True))
    want = np_adc(lut, codes)
    # m=4 sums of bf16-rounded values (~0.4% rel each)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_bf16_lut_ivfpq_with_refine_recall(rng):
    """End-to-end: adc_lut_bf16 + refine matches the f32 pipeline's recall
    (the refine stage rescores the shortlist exactly either way)."""
    from distributed_faiss_tpu.models.ivf import IVFPQIndex

    n, d = 3000, 32
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((16, d)).astype(np.float32)

    def build(**kw):
        idx = IVFPQIndex(d, 16, m=8, metric="l2", kmeans_iters=4, pq_iters=4,
                         refine_k_factor=4, **kw)
        idx.train(x[:2000])
        idx.add(x)
        idx.set_nprobe(8)
        return idx

    _, ids_f32 = build(use_pallas=True).search(q, 10)
    _, ids_bf16 = build(use_pallas=True, adc_lut_bf16=True).search(q, 10)
    overlap = np.mean([
        len(set(ids_f32[i]) & set(ids_bf16[i])) / 10 for i in range(len(q))
    ])
    assert overlap >= 0.9, overlap


def test_tiny_list(rng):
    lut = rng.standard_normal((2, 4, 256)).astype(np.float32)
    codes = rng.integers(0, 256, (3, 4)).astype(np.uint8)
    got = np.asarray(adc_pallas.adc_scan_shared_pallas(lut, codes, interpret=True))
    np.testing.assert_allclose(got, np_adc(lut, codes), rtol=1e-5, atol=1e-5)


def test_nibble_kernel_golden(rng):
    nq, m, ksub, L = 5, 8, 256, 300  # L not a tile multiple
    lut = rng.standard_normal((nq, m, ksub)).astype(np.float32)
    codes = rng.integers(0, 256, (nq, L, m)).astype(np.uint8)
    got = np.asarray(adc_pallas.adc_scan_pallas_nibble(lut, codes, tile=128, interpret=True))
    want = np.zeros((nq, L), np.float32)
    for qi in range(nq):
        for mi in range(m):
            want[qi] += lut[qi, mi, codes[qi, :, mi].astype(np.int64)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_nibble_matches_onehot_kernel(rng):
    """Nibble decomposition must reproduce the one-hot kernel (same rounding
    class: f32 accumulation of exact LUT values)."""
    nq, m, ksub, L = 4, 64, 256, 520  # flagship m
    lut = rng.standard_normal((nq, m, ksub)).astype(np.float32)
    codes = rng.integers(0, 256, (nq, L, m)).astype(np.uint8)
    a = np.asarray(adc_pallas.adc_scan_pallas_nibble(lut, codes, tile=256, interpret=True))
    b = np.asarray(adc_pallas.adc_scan_pallas(lut, codes, tile=256, interpret=True))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_nibble_bf16_lut(rng):
    nq, m, ksub, L = 3, 16, 256, 200
    import jax.numpy as jnp

    lut = rng.standard_normal((nq, m, ksub)).astype(np.float32)
    codes = rng.integers(0, 256, (nq, L, m)).astype(np.uint8)
    got = np.asarray(adc_pallas.adc_scan_pallas_nibble(
        jnp.asarray(lut).astype(jnp.bfloat16), codes, tile=128, interpret=True))
    want = np.asarray(pq.adc_scan(lut, codes))
    # bf16 LUT rounding only (~0.4% rel)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_nibble_auto_dispatch(rng, monkeypatch):
    """adc_scan_auto picks nibble when geometry allows, one-hot otherwise."""
    calls = []
    orig_nib = adc_pallas.adc_scan_pallas_nibble
    orig_old = adc_pallas.adc_scan_pallas

    def spy_nib(*a, **k):
        calls.append("nibble")
        return orig_nib(*a, **k)

    def spy_old(*a, **k):
        calls.append("onehot")
        return orig_old(*a, **k)

    monkeypatch.setattr(adc_pallas, "adc_scan_pallas_nibble", spy_nib)
    monkeypatch.setattr(adc_pallas, "adc_scan_pallas", spy_old)
    lut8 = rng.standard_normal((2, 8, 256)).astype(np.float32)
    codes8 = rng.integers(0, 256, (2, 64, 8)).astype(np.uint8)
    adc_pallas.adc_scan_auto(lut8, codes8)
    lut4 = rng.standard_normal((2, 4, 256)).astype(np.float32)
    codes4 = rng.integers(0, 256, (2, 64, 4)).astype(np.uint8)
    adc_pallas.adc_scan_auto(lut4, codes4)  # m=4 -> one-hot fallback
    assert calls == ["nibble", "onehot"]


def test_auto_forwards_explicit_tile(rng, monkeypatch):
    """An explicit tile reaches whichever kernel dispatches; tile=None lets
    each kernel use its own tuned default (ADVICE r3)."""
    seen = {}
    orig_nib = adc_pallas.adc_scan_pallas_nibble

    def spy_nib(lut, codes, **k):
        seen.update(k)
        return orig_nib(lut, codes, **k)

    monkeypatch.setattr(adc_pallas, "adc_scan_pallas_nibble", spy_nib)
    lut = rng.standard_normal((1, 8, 256)).astype(np.float32)
    codes = rng.integers(0, 256, (1, 64, 8)).astype(np.uint8)
    adc_pallas.adc_scan_auto(lut, codes)
    assert "tile" not in seen
    adc_pallas.adc_scan_auto(lut, codes, tile=256)
    assert seen["tile"] == 256


def test_pallas_degrade_ladder(rng, monkeypatch):
    """A nibble-kernel failure falls back to the one-hot pallas kernel, not
    straight to XLA; a one-hot failure then falls to XLA (ADVICE r3)."""
    from distributed_faiss_tpu.models import ivf as ivfmod
    from distributed_faiss_tpu.models.ivf import IVFPQIndex

    n, d, m = 1500, 32, 8
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((6, d)).astype(np.float32)
    idx = IVFPQIndex(d, 8, m=m, metric="dot", kmeans_iters=3, pq_iters=3,
                     use_pallas=True)
    idx.train(x)
    idx.add(x)
    idx.set_nprobe(4)
    ref = IVFPQIndex(d, 8, m=m, metric="dot", kmeans_iters=3, pq_iters=3,
                     use_pallas=False)
    ref.centroids, ref.codebooks = idx.centroids, idx.codebooks
    ref.lists = idx.lists
    ref._n = idx._n
    ref.set_nprobe(4)
    want_d, want_i = ref.search(q, 5)

    def boom(*a, **k):
        raise RuntimeError("kernel abort (injected)")

    # this ladder is the older dispatcher's (adc_scan_auto: nibble, then
    # one-hot), which a forced index still runs wherever the three-plane
    # kernel does not take its geometry — so refuse every geometry here
    monkeypatch.setattr(adc_pallas, "planes_supported", lambda m, ksub, L: False)
    # drop compiled variants so the injected failure is actually reached
    ivfmod._ivf_pq_search.clear_cache()
    monkeypatch.setattr(adc_pallas, "USE_NIBBLE", True)
    monkeypatch.setattr(adc_pallas, "NIBBLE_SWEPT", False)
    monkeypatch.setattr(adc_pallas, "NIBBLE_EXCUSES_LEFT", 8)
    monkeypatch.setattr(ivfmod, "_BOTH_FAILED_SIGS", set())
    monkeypatch.setattr(adc_pallas, "adc_scan_pallas_nibble", boom)

    # a user error (bad dim) re-raises from the XLA oracle with every
    # kernel flag untouched — no demotion, no cache wipe
    with pytest.raises(Exception):
        idx.search(rng.standard_normal((2, d + 1)).astype(np.float32), 5)
    assert adc_pallas.USE_NIBBLE is True
    assert idx._pallas_runtime_ok

    got_d, got_i = idx.search(q, 5)
    assert adc_pallas.USE_NIBBLE is False, "nibble not demoted"
    assert idx._pallas_runtime_ok, "one-hot pallas abandoned with the nibble"
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-4, atol=1e-4)

    # now the one-hot kernel breaks too. The first failure is excused as a
    # possible stale pre-demotion executable (ADVICE r4: caches swept, the
    # request served from the XLA result in hand, NO synchronous re-trace);
    # the second failure — necessarily a fresh trace — demotes pallas.
    ivfmod._ivf_pq_search.clear_cache()
    monkeypatch.setattr(adc_pallas, "adc_scan_pallas", boom)
    got_d, got_i = idx.search(q, 5)
    assert idx._pallas_runtime_ok, "demoted on the excusable first failure"
    assert adc_pallas.NIBBLE_SWEPT is True
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-4, atol=1e-4)
    got_d, got_i = idx.search(q, 5)
    assert not idx._pallas_runtime_ok
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-4, atol=1e-4)


def test_nibble_consumer_registry_complete():
    """Every jitted program that bakes the adc_scan_auto dispatch in at
    trace time must be registered, or disable_nibble leaves a stale
    nibble executable behind and the ladder misattributes the next fault."""
    from distributed_faiss_tpu.models import ivf as ivfmod
    from distributed_faiss_tpu.parallel import mesh as meshmod

    registered = {id(f) for f in adc_pallas.NIBBLE_JIT_CONSUMERS}
    expected = [
        ivfmod._ivf_pq_search, ivfmod._ivf_pq_search_fused,
        meshmod._sharded_ivf_pq_search, meshmod._sharded_ivf_pq_search_fused,
        meshmod._sharded_ivf_pq_search_routed,
    ]
    assert all(id(f) in registered for f in expected)
    assert len(adc_pallas.NIBBLE_JIT_CONSUMERS) == len(expected)

    # tripwire against silent drift: a NEW adc_scan_auto call site means a
    # new (possibly unregistered) consumer — this count forces whoever adds
    # one to register its enclosing jitted program(s) and update both lists
    import inspect

    sites = sum(inspect.getsource(mod).count("adc_scan_auto(")
                for mod in (ivfmod, meshmod))
    assert sites == 3, (
        "adc_scan_auto call-site count changed: register the new consumer "
        "in NIBBLE_JIT_CONSUMERS and update this test")


def test_both_failed_repeat_demotes_nibble(monkeypatch):
    """When kernel AND oracle fail with messages that normalize equal (e.g.
    OOMs differing only in byte counts), the first request is read as 'bad
    request' (no demotion, no cache wipe), but a repeat of the SAME failure
    signature demotes the nibble kernel — never-demoting would re-fault
    every search forever. Distinct bad requests never accumulate."""
    from distributed_faiss_tpu.models import ivf as ivfmod

    class FakeIdx:
        use_pallas = True
        _pallas_runtime_ok = True

    def oom_call(use_pallas):
        if use_pallas:
            raise RuntimeError("RESOURCE_EXHAUSTED allocating 8589934592 bytes")
        raise RuntimeError("RESOURCE_EXHAUSTED allocating 17179869184 bytes")

    def other_bad_call(use_pallas):
        raise RuntimeError("dim mismatch: got 33, want 32")

    monkeypatch.setattr(adc_pallas, "USE_NIBBLE", True)
    monkeypatch.setattr(ivfmod, "_BOTH_FAILED_SIGS", set())
    assert adc_pallas.nibble_supported(8, 256)

    with pytest.raises(RuntimeError):
        ivfmod.pallas_guarded(FakeIdx(), oom_call, 8, 256)
    assert adc_pallas.USE_NIBBLE is True, "one bad request must not demote"

    # a DIFFERENT bad request in between must not count toward the repeat
    with pytest.raises(RuntimeError):
        ivfmod.pallas_guarded(FakeIdx(), other_bad_call, 8, 256)
    assert adc_pallas.USE_NIBBLE is True, "distinct signatures accumulated"

    # the OOM signature repeating demotes — the interleaved unrelated bad
    # request must NOT have displaced it (signature set, not single slot)
    with pytest.raises(RuntimeError):
        ivfmod.pallas_guarded(FakeIdx(), oom_call, 8, 256)
    assert adc_pallas.USE_NIBBLE is False, "repeated signature must demote"

    # genuinely distinct failures demote immediately (reset state first)
    monkeypatch.setattr(adc_pallas, "USE_NIBBLE", True)
    monkeypatch.setattr(ivfmod, "_BOTH_FAILED_SIGS", set())

    def distinct_call(use_pallas):
        if use_pallas:
            raise RuntimeError("kernel abort")
        raise ValueError("one-hot materialization OOM")

    with pytest.raises(ValueError):
        ivfmod.pallas_guarded(FakeIdx(), distinct_call, 8, 256)
    assert adc_pallas.USE_NIBBLE is False


def test_stale_executable_excuse_covers_concurrent_inflight(monkeypatch):
    """Two in-flight searches whose traces predate a concurrent nibble
    demotion must BOTH be excused (served via XLA, pallas kept) — the sweep
    epoch moves under the first excuse, covering the second (r5 review)."""
    from distributed_faiss_tpu.models import ivf as ivfmod

    class FakeIdx:
        use_pallas = True
        _pallas_runtime_ok = True

    monkeypatch.setattr(adc_pallas, "USE_NIBBLE", False)  # demotion landed
    monkeypatch.setattr(adc_pallas, "NIBBLE_SWEPT", True)  # excuse spent
    monkeypatch.setattr(adc_pallas, "NIBBLE_EXCUSES_LEFT", 2)
    epoch0 = adc_pallas.NIBBLE_SWEEP_EPOCH
    monkeypatch.setattr(adc_pallas, "NIBBLE_SWEEP_EPOCH", epoch0)

    # pallas_guarded captures the epoch at entry; emulate "this call's trace
    # started before the concurrent demotion's sweep" by rewinding the epoch
    # before each entry and bumping it from inside the failing pallas call
    # (the moment the demotion sweep would land)
    def stale_exec(use_pallas):
        if use_pallas:
            adc_pallas.NIBBLE_SWEEP_EPOCH = epoch0 + 1
            raise RuntimeError("stale nibble executable abort")
        return "xla-result"

    idx_a, idx_b = FakeIdx(), FakeIdx()
    adc_pallas.NIBBLE_SWEEP_EPOCH = epoch0
    assert ivfmod.pallas_guarded(idx_a, stale_exec, 8, 256) == "xla-result"
    assert idx_a._pallas_runtime_ok, "in-flight stale executable demoted pallas"
    adc_pallas.NIBBLE_SWEEP_EPOCH = epoch0
    assert ivfmod.pallas_guarded(idx_b, stale_exec, 8, 256) == "xla-result"
    assert idx_b._pallas_runtime_ok, "second in-flight victim demoted pallas"

    # budget exhausted: a further "stale-looking" failure is no longer
    # excused — a genuinely broken one-hot kernel under constant concurrency
    # must converge to the XLA path, not excuse itself forever (r5 review)
    assert adc_pallas.NIBBLE_EXCUSES_LEFT == 0
    idx_c = FakeIdx()
    adc_pallas.NIBBLE_SWEEP_EPOCH = epoch0
    assert ivfmod.pallas_guarded(idx_c, stale_exec, 8, 256) == "xla-result"
    assert idx_c._pallas_runtime_ok is False, "budget spent yet still excused"


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


@pytest.mark.parametrize("m", [8, 64])
@pytest.mark.parametrize("cap", [128, 1024])
def test_planes_kernel_golden(rng, m, cap):
    lut = wide_tables(rng, (3, m, 256))
    codes = rng.integers(0, 256, (3, cap, m)).astype(np.uint8)
    got = np.asarray(adc_pallas.adc_scan_pallas_planes(lut, codes, interpret=True))
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
    got = np.asarray(adc_pallas.adc_scan_pallas_planes(lut, codes, interpret=True))
    np.testing.assert_array_equal(got, np_adc_f64(lut, codes).astype(np.float32))


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
    (True, 64, 1024, False, False),  # an explicit False forces
], ids=["cpu", "tpu", "tpu-m64", "cap96", "cap64", "m512", "force-on", "force-off"])
def test_the_index_chooses_its_adc_kernel(monkeypatch, tpu, m, cap, forced, fused):
    from distributed_faiss_tpu.models import ivf as ivfmod

    monkeypatch.setattr(adc_pallas, "on_tpu", lambda: tpu)
    idx = ivfmod.IVFPQIndex(2 * m, 4, m=m, use_pallas=forced)
    idx.lists = _Lists(cap)
    assert ivfmod.pallas_wanted(idx) is fused
    if forced is None:
        assert idx._fused_adc_applies() is fused


def small_pq(rng, **kw):
    from distributed_faiss_tpu.models.ivf import IVFPQIndex

    n, d = 3000, 32
    x = rng.standard_normal((n, d)).astype(np.float32)
    idx = IVFPQIndex(d, 8, m=8, metric="l2", kmeans_iters=3, pq_iters=3,
                     refine_k_factor=4, **kw)
    idx.train(x[:2000])
    idx.add(x)
    idx.set_nprobe(4)
    assert idx.lists.cap % 128 == 0
    return idx, x


def xla_twin(idx):
    """The same trained index, forced onto the XLA one-hot."""
    from distributed_faiss_tpu.models.ivf import IVFPQIndex

    ref = IVFPQIndex.from_state_dict({**idx.state_dict(), "pallas_adc": False})
    assert ref.use_pallas is False
    return ref


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

    def spy(lut, codes, **kw):
        calls.append(tuple(lut.shape))
        return orig(lut, codes, interpret=True)

    monkeypatch.setattr(adc_pallas, "on_tpu", lambda: True)
    monkeypatch.setattr(adc_pallas, "adc_scan_pallas_planes", spy)
    ivfmod._ivf_pq_search.clear_cache()
    got_d, got_i = idx.search(x[:20], 5)
    ivfmod._ivf_pq_search.clear_cache()  # the spy is baked into the traces
    assert calls and idx._adc_validated and idx._pallas_runtime_ok
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("saved,loads_as", [
    ({"use_pallas": False}, None),                       # the old default: choose
    ({"use_pallas": True}, True),                        # the old pallas_adc=True
    ({"use_pallas": False, "pallas_adc": None}, None),   # today's default
    ({"use_pallas": False, "pallas_adc": False}, False), # today's forced off
    ({"use_pallas": True, "pallas_adc": True}, True),
], ids=["old-default", "old-forced", "choose", "off", "on"])
def test_a_snapshots_kernel_intent(saved, loads_as):
    from distributed_faiss_tpu.models.ivf import IVFPQIndex

    state = IVFPQIndex(16, 4, m=4).state_dict()
    del state["use_pallas"], state["pallas_adc"]
    idx = IVFPQIndex.from_state_dict({**state, **saved})
    assert idx.use_pallas is loads_as
    again = IVFPQIndex.from_state_dict(idx.state_dict())
    assert again.use_pallas is loads_as


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
    assert build(adc_lut_bf16=False).adc_lut_bf16 is False


def test_first_fused_scan_with_wrong_scores_demotes_and_serves_xla(rng, monkeypatch):
    """A kernel that runs and returns wrong numbers (one bf16 pass where
    three were meant: PR 21's nibble finding) raises nothing for
    pallas_guarded to catch: the first-use check does, before a caller
    sees a score."""
    from distributed_faiss_tpu.models import ivf as ivfmod

    idx, x = small_pq(rng, use_pallas=True)
    want_d, want_i = xla_twin(idx).search(x[:20], 5)
    orig = adc_pallas.adc_scan_pallas_planes

    def one_plane(lut, codes, **kw):
        import jax.numpy as jnp

        return orig(lut.astype(jnp.bfloat16).astype(jnp.float32), codes, **kw)

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
    blocked = base.blocked_search
    monkeypatch.setattr(
        base, "blocked_search",
        lambda q, k, metric, fn, block=256, fused_fn=None, refine_fn=None:
        blocked(q, k, metric, fn, block, None, refine_fn))
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
