"""The yardstick's own arithmetic: each configuration's plain reference, the
comparison that decides ``correct`` and its control, the bytes a search
needs, and the reduction of a profiler trace."""

import json
import os

import numpy as np
import pytest

from perfbench import control as control_rows
from perfbench import (corpus, correctness, load_gen, loader, search_bytes, stats,
                       trace_reduce)
from pb_helpers import PRETEND, REPO

# every configuration's directory: the benchmark's, and the tests' pretend
# one, which is the only one on the dot metric so far
CONFIG_DIRS = {name: os.path.join(REPO, "perfbench", "configs", name)
               for name in sorted(os.listdir(os.path.join(REPO, "perfbench", "configs")))}
CONFIG_DIRS["pretend"] = os.path.join(PRETEND, "configs", "pretend")
CONFIGS = sorted(CONFIG_DIRS)


def smoke_exact_topk(x, q, k, metric):
    """``chip_smoke.exact_topk``'s scan, the one PR 21 proved on the chip,
    written out here so the test imports no script: the nearest by squared
    L2, or the largest inner products."""
    if metric == "l2":
        score = (q * q).sum(1)[:, None] - 2.0 * (q @ x.T) + (x * x).sum(1)[None, :]
    else:
        score = -(q @ x.T)
    return np.argsort(score, axis=1, kind="stable")[:, :k]


def tiny_corpus(seed=5, n=3000, d=32, nq=40):
    mix = corpus.LowRankMixture(seed, d, 8, 4, 4, 1.5)
    chunks = [mix.chunk(corpus.CORPUS, i, n // 3) for i in range(3)]
    return mix, chunks, mix.chunk(corpus.QUERIES, 0, nq)


def reference_of(name):
    return loader.load_module(os.path.join(CONFIG_DIRS[name], "reference.py"))


# ------------------------------------------------------------------ corpus


def test_the_same_seed_gives_the_same_rows_and_another_seed_others():
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    a = corpus.LowRankMixture(big, 32, 8, 4, 4, 1.5).chunk(corpus.CORPUS, 2, 50)
    b = corpus.LowRankMixture(big, 32, 8, 4, 4, 1.5).chunk(corpus.CORPUS, 2, 50)
    c = corpus.LowRankMixture(big + 1, 32, 8, 4, 4, 1.5).chunk(corpus.CORPUS, 2, 50)
    assert a.dtype == np.float32 and a.shape == (50, 32)
    assert np.array_equal(a, b) and not np.allclose(a, c)
    other_chunk = corpus.LowRankMixture(big, 32, 8, 4, 4, 1.5).chunk(corpus.CORPUS, 3, 50)
    queries = corpus.LowRankMixture(big, 32, 8, 4, 4, 1.5).chunk(corpus.QUERIES, 2, 50)
    assert not np.allclose(a, other_chunk) and not np.allclose(a, queries)


# --------------------------------------------------------------- reference


@pytest.mark.parametrize("name", CONFIGS)
def test_each_reference_agrees_with_the_smokes_exact_scan(name):
    """By the configuration's own metric. A score is what the client serves:
    a squared distance, or an inner product negated, so the best comes first
    and the scores ascend under either."""
    metric = loader.read_json(os.path.join(CONFIG_DIRS[name], "config.json"))["index"]["metric"]
    ref = reference_of(name)
    _, chunks, q = tiny_corpus()
    x = np.concatenate(chunks)
    dist, ids = ref.exact_topk(chunks, q, 10)
    assert ids.dtype == np.int64 and ids.shape == (40, 10)
    assert np.array_equal(ids, smoke_exact_topk(x, q, 10, metric))
    exact = ref.exact_distances(x[ids], q)
    assert exact.dtype == np.float64
    assert np.allclose(dist, exact, rtol=1e-4)
    assert (np.diff(exact, axis=1) >= -1e-6).all()  # best first
    assert (exact >= 0).all() if metric == "l2" else (exact < 0).any()


@pytest.mark.parametrize("name", CONFIGS)
def test_a_reference_imports_nothing_of_the_program(name):
    with open(os.path.join(CONFIG_DIRS[name], "reference.py")) as f:
        imports = [ln for ln in f if ln.startswith(("import ", "from "))]
    assert imports == ["import numpy as np\n"]


# ------------------------------------------------- the comparison, its control


def served_by(chunks_as_stored, pool, rows_per_request, requests, k=10):
    """Requests answered by an exact scan over ``chunks_as_stored`` — the
    reference put in the program's place, at whatever precision the stored
    rows were kept in."""
    ref = reference_of("knnlm")
    out = []
    for i in range(requests):
        first = i * rows_per_request
        q = pool[first:first + rows_per_request]
        _, ids = ref.exact_topk(chunks_as_stored, q, k)
        dist = ref.exact_distances(correctness.gather_rows(chunks_as_stored, ids), q)
        out.append(load_gen.Result(0.0, 1.0, first, rows_per_request, True, dist, ids))
    return out


CONFIG = {"k": 10, "index": {"metric": "l2"}, "guarantees": {"recall_at_k_min": 0.95},
          "limits": {"sample_rows": 64, "distance_gap_rel_max": 1e-2}}


def decide(stored, chunks, pool, seed=3):
    checks = correctness.Checks()
    correctness.compare_window(checks, CONFIG, reference_of("knnlm"), chunks, pool,
                               served_by(stored, pool, 16, 6), seed)
    return checks


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_float16_rows_are_correct_and_the_int8_control_is_not(seed):
    _, chunks, pool = tiny_corpus(seed, nq=96)
    sound = decide(control_rows.as_float16(chunks), chunks, pool, seed)
    assert sound.correct, sound.rows
    control = decide(control_rows.as_int8(chunks), chunks, pool, seed)
    assert not control.correct
    gap = {row[0]: row for row in control.rows}["distance_gap_rel"]
    sound_gap = {row[0]: row for row in sound.rows}["distance_gap_rel"]
    assert gap[1] > 3 * sound_gap[1], "the control must stand well clear of sound runs"


def test_a_wrong_id_a_failed_request_and_an_empty_window_are_not_correct():
    _, chunks, pool = tiny_corpus(nq=96)
    results = served_by(chunks, pool, 16, 6)
    ok = correctness.Checks()
    correctness.compare_window(ok, CONFIG, reference_of("knnlm"), chunks, pool, results, 1)
    assert ok.correct
    for r in results:  # every answer names the neighbours of another query
        r.ids = np.roll(r.ids, 1, axis=0)
    wrong = correctness.Checks()
    correctness.compare_window(wrong, CONFIG, reference_of("knnlm"), chunks, pool, results, 1)
    assert not wrong.correct
    failed = correctness.Checks()
    bad = served_by(chunks, pool, 16, 6) + [
        load_gen.Result(0.0, 1.0, 0, 16, False, error="boom")]
    correctness.compare_window(failed, CONFIG, reference_of("knnlm"), chunks, pool, bad, 1)
    assert not failed.correct
    empty = correctness.Checks()
    correctness.compare_window(empty, CONFIG, reference_of("knnlm"), chunks, pool, [], 1)
    assert not empty.correct and not correctness.Checks().correct
    out_of_range = served_by(chunks, pool, 16, 6)
    out_of_range[0].ids[0, 0] = 10**9
    far = correctness.Checks()
    correctness.compare_window(far, CONFIG, reference_of("knnlm"), chunks, pool,
                               out_of_range, 1)
    assert not far.correct


def test_the_sample_is_drawn_from_the_seed_and_holds_enough_rows():
    results = [load_gen.Result(0, 1, i * 16, 16, i != 3) for i in range(20)]
    a = correctness.sample_requests(results, 9, 64)
    b = correctness.sample_requests(results, 9, 64)
    c = correctness.sample_requests(results, 10, 64)
    assert [r.first_row for r in a] == [r.first_row for r in b]
    assert [r.first_row for r in a] != [r.first_row for r in c]
    assert sum(r.rows for r in a) == 64 and all(r.ok for r in a)
    assert len(correctness.sample_requests(results, 9, 10**6)) == 19


def test_gather_rows_reads_across_chunks():
    _, chunks, _ = tiny_corpus()
    x = np.concatenate(chunks)
    ids = np.array([[0, 999, 1000], [2999, 1500, 1]])
    assert np.array_equal(correctness.gather_rows(chunks, ids), x[ids])


# ------------------------------------------------------------------ traffic


def test_warm_up_covers_every_window_the_mix_can_merge():
    batch = {"kind": "closed_loop", "callers": 4, "rows_per_request": 64}
    online = {"kind": "closed_loop", "callers": 16, "rows_per_request": 1}
    assert load_gen.request_sizes(batch, 256) == [64, 128, 192, 256]
    assert load_gen.request_sizes(online, 256) == list(range(1, 17))
    tier = loader.read_json(os.path.join(REPO, "perfbench", "traffic", "batch16x64.json"))
    assert (tier["callers"], tier["rows_per_request"]) == (16, 64)
    # sixteen callers fill a window four at a time: batch4x64's shapes, no more
    assert load_gen.request_sizes(tier, 256) == [64, 128, 192, 256]
    wide = {"kind": "closed_loop", "callers": 3, "rows_per_request": 300}
    assert load_gen.request_sizes(wide, 256) == [300]
    with pytest.raises(ValueError, match="unknown traffic kind"):
        load_gen.request_sizes({"kind": "poisson"}, 256)


class FakeClient:
    """Answers every search at once, with the right shape."""

    def __init__(self, fail_every=0):
        self.calls, self.fail_every = 0, fail_every

    def search(self, q, k, index_id):
        self.calls += 1
        if self.fail_every and self.calls % self.fail_every == 0:
            raise RuntimeError("shed")
        return np.zeros((q.shape[0], k), np.float32), [[7] * k for _ in range(q.shape[0])]


def test_the_closed_loop_counts_every_request_and_drains_before_it_ends():
    pool = np.zeros((64, 4), np.float32)
    mix = {"kind": "closed_loop", "callers": 3, "rows_per_request": 8, "stagger_s": 0.001}
    client = FakeClient(fail_every=5)
    results, t0, t1 = load_gen.drive(client, "i", 10, pool, mix, 2**31 + 1, 0.2)
    assert len(results) == client.calls and t1 > t0 >= 0
    assert sum(not r.ok for r in results) == client.calls // 5
    assert all(r.rows == 8 and r.first_row % 8 == 0 for r in results)
    assert all(r.ids.shape == (8, 10) for r in results if r.ok)
    assert max(r.end for r in results) == t1
    again, _, _ = load_gen.drive(FakeClient(), "i", 10, pool, mix, 2**31 + 1, 0.05)
    assert {r.first_row for r in again} <= {8 * i for i in range(8)}


# ------------------------------------------------------- bytes and the peak


KNNLM = {"index_builder_type": "knnlm", "dim": 768, "centroids": 4096, "code_size": 64,
         "nbits": 8, "refine_k_factor": 8, "nprobe": 32}
IVFSQ = {"index_builder_type": "ivfsq", "dim": 512, "centroids": 1024, "nprobe": 64}


def test_least_bytes_and_operations_on_hand_computed_shapes():
    # knnlm, 1e6 rows, one launch of 256 query rows, k=10
    rows, nq, k = 1_000_000, 256, 10
    centroids = 4096 * 768 * 4                      # 12,582,912
    queries = 256 * 768 * 4                         # 786,432
    lists = 32 * (rows / 4096) * (64 + 4)           # 531,250
    refine = 256 * 10 * 8 * 768 * 2                 # 31,457,280
    answer = 256 * 10 * 8                           # 20,480
    assert search_bytes.least_bytes(KNNLM, rows, k, nq) == pytest.approx(
        centroids + queries + lists + refine + answer)
    coarse = 2 * 256 * 4096 * 768
    adc = 256 * 32 * (rows / 4096) * 64
    lut = 2 * 256 * 32 * 256 * 768
    rerank = 2 * 256 * 10 * 8 * 768
    assert search_bytes.least_ops(KNNLM, rows, k, nq) == pytest.approx(
        coarse + adc + lut + rerank)
    # ivfsq: float16 rows + id + stored norm, no refine
    assert search_bytes.least_bytes(IVFSQ, rows, k, 64) == pytest.approx(
        1024 * 512 * 4 + 64 * 512 * 4 + 64 * (rows / 1024) * (1024 + 8) + 64 * 10 * 8)
    assert search_bytes.least_ops(IVFSQ, rows, k, 64) == pytest.approx(
        2 * 64 * 1024 * 512 + 64 * 64 * (rows / 1024) * 1024)


def test_the_peak_comes_from_one_table_and_an_unknown_device_is_an_error():
    assert search_bytes.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    least, bound = search_bytes.roofline_seconds(KNNLM, 1_000_000, 10, 256, "TPU v5 lite")
    assert bound == "memory"
    assert least == pytest.approx(search_bytes.least_bytes(KNNLM, 1_000_000, 10, 256) / 819e9)
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        search_bytes.peak("cpu")
    with pytest.raises(ValueError, match="no byte model"):
        search_bytes.least_bytes({**IVFSQ, "index_builder_type": "hnswsq"}, 10, 10, 1)


# ------------------------------------------------------ the program's spans


def test_a_windows_mean_is_exact_from_two_snapshots():
    before = {"search": {"count": 10, "total_s": 1.0, "p50_s": 0.1}}
    after = {"search": {"count": 14, "total_s": 3.0, "p50_s": 0.63}}
    assert stats.window_mean(before, after, ("search",)) == pytest.approx(0.5)
    assert stats.window_count(before, after, ("search",)) == 4
    assert stats.window_mean(after, after, ("search",)) is None
    assert stats.window_mean({}, after, ("search",)) == pytest.approx(3.0 / 14)
    assert stats.window_mean(before, after, ("engine", "x", "device_search_s")) is None
    obs = {"stats_before": [before, before], "stats_after": [after, before]}
    assert stats.per_rank(obs, ("search",)) is None  # one rank served nothing
    assert stats.per_rank({}, ("search",)) is None


# -------------------------------------------------------------- the trace


def recorded():
    with open(os.path.join(REPO, "tests", "perfbench", "data", "trace_small.json")) as f:
        return [tuple(row) for row in json.load(f)]


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_the_reduction_of_a_small_recorded_trace():
    rows = recorded()
    out = trace_reduce.reduce(rows)
    ops = [(s, s + d) for p, line, _, s, d in rows
           if p.startswith("/device:TPU:") and line == trace_reduce.OPS_LINE]
    # busy time by the definition, the slow way: count covered nanoseconds
    lo = min(s for s, _ in ops)
    covered = np.zeros(max(e for _, e in ops) - lo, bool)
    for s, e in ops:
        covered[s - lo:e - lo] = True
    assert out["chips"] == 1
    assert out["busy_s"] == pytest.approx(covered.sum() / 1e9)
    assert out["span_s"] == pytest.approx(len(covered) / 1e9)
    assert 0 < out["busy_s"] < out["span_s"]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["device_ops"] == sorted(out["device_ops"], key=lambda r: -r[1])
    total_gap = sum(s for _, s in out["idle_gaps"])
    assert total_gap <= out["span_s"] - out["busy_s"] + 1e-12
    names = {n for _, _, n, _, _ in rows}
    assert {n for n, _ in out["device_ops"]} <= names
    assert trace_reduce.outline(rows)


def test_a_trace_without_a_device_plane_is_an_error_not_a_zero():
    rows = [("/host:CPU", "python3", "x", 0, 10)]
    assert "error" in trace_reduce.reduce(rows)
    assert trace_reduce.reduce(rows, "/host:CPU")["busy_s"] == pytest.approx(1e-8)
