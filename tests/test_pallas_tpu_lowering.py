"""The Pallas kernels must lower — and compile — for a TPU at the geometries
the deployments emit, checked here on the CPU so a cast, block shape, vector
load or VMEM/SMEM demand the chip would refuse fails tier-1 instead of being
demoted to the XLA path by ``pallas_guarded`` on the first served request."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import pallas_tpu_cases

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


@pytest.mark.parametrize("name,fn,sig", pallas_tpu_cases.cases(),
                         ids=[c[0] for c in pallas_tpu_cases.cases()])
def test_kernel_lowers_for_tpu(name, fn, sig):
    """interpret=False, platform tpu: what Pallas itself refuses (at the
    parent commit every sq8 flat-scan case: ``uint8 -> float32``)."""
    lowered = pallas_tpu_cases.lower_for_tpu(fn, sig, _sds)
    assert "tpu_custom_call" in lowered.as_text()


@pytest.fixture(scope="module")
def compiled_for_v5e():
    """The rows ``pallas_tpu_cases.py`` prints, run once as a script in a
    child (libtpu stays out of the test process)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "pallas_tpu_cases.py")],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.pathsep.join([REPO, os.path.join(REPO, "tests")])})
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    assert proc.returncode == 0 and rows, proc.stderr[-2000:]
    if "unavailable" in rows[0]:
        pytest.skip(f"no TPU compile-only topology: {rows[0]['unavailable']}")
    return rows


def test_kernels_compile_for_v5e(compiled_for_v5e):
    """Mosaic + XLA:TPU compile of every case from libtpu's compile-only
    v5e topology. What it refused at the chip bring-up: IEEE-half vector
    loads, 2 x 512 KB of scalar prefetch against 1 MB of SMEM, and 35.6 MB
    of scoped VMEM in an ADC kernel."""
    rows = [r for r in compiled_for_v5e if "case" in r]
    assert len(rows) == len(pallas_tpu_cases.cases())
    refused = [r for r in rows if not r["ok"]]
    assert not refused, refused


def test_the_exact_scan_compiled_for_v5e_sorts_no_wide_row(compiled_for_v5e):
    """``lax.top_k`` over a wide row is a full sort of it on this chip, and
    that sort was 79% of ``flat768-batch``'s device time (ledger, PR 28:
    every 2048-wide segment of every chunk). With the prefilter the widest
    row the scan program sorts is the 512 segment maxima of a chunk."""
    from distributed_faiss_tpu.ops import distance

    (row,) = [r for r in compiled_for_v5e if "exact_scan_sort_widths" in r]
    assert row["exact_scan_sort_widths"], "no sort found: the parse is stale"
    assert max(row["exact_scan_sort_widths"]) <= distance.SCAN_CHUNK // 128


def test_the_list_major_scan_compiles_for_v5e_and_copies_no_store(compiled_for_v5e):
    """``ivfsq-batch``'s program, with its traced trip count, at the 128-
    and the 256-row bucket. Its scratch is the score buffer and a query's
    gathered candidates, 0.85 and 1.2 GB: a gather slice of a whole (4096,
    512) list makes XLA:TPU copy the 4.3 GB store into slabs in every loop
    step (``temp_size`` 4.3 GB: the parent's 1.1 s a launch, PERF.md section
    6, PR 31); no gather of the compiled program takes a slice over 512 kB
    (a sub-block of a list is 256 kB)."""
    rows = [r for r in compiled_for_v5e if "program" in r]
    assert len(rows) == len(pallas_tpu_cases.listmajor_programs()) == 2
    assert all(r["ok"] for r in rows), rows
    assert max(r["temp_bytes"] for r in rows) < 2 << 30, rows
    slices = [r["largest_gather_slice_bytes"] for r in rows]
    assert 0 < max(slices) <= 512 << 10, rows
