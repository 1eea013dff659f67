#!/usr/bin/env python3
"""Compiled-on-TPU validation of every Pallas kernel against numpy goldens.

tests/test_adc_pallas.py and tests/test_flat_pallas.py run the kernels
through the Pallas interpreter on the CPU; this script runs them compiled
by Mosaic (interpret=False) on the chip, at the geometries the deployments
emit, and asserts parity with a plain numpy reference:

  - ADC three-plane one-hot (fp32 table values in one bf16 MXU pass) at the
    benchmark cells' geometry (m=64, lists of capacity 1024) and at m=8,
    on tables whose entries need all three planes, and on a ragged list;
    beside it the XLA one-hot (ops/pq.adc_scan), the oracle it is held to;
    and with lists that end short of their capacity (PR 35): bit-equal to
    its own scan of whole lists up to each list's last sub-tile, -inf past it
  - fused flat list scan for the f32 / f16 / sq8 codecs x l2 / dot at
    d=512 (the ivfsq width), plus the bf16 scan mode

Prints one JSON line per case; exits nonzero on any mismatch, or when the
process is not on a TPU. chip_smoke.py runs it as its direct-kernel phase.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def np_adc_per_query(lut, codes):
    """lut (P, m, ksub), codes (P, L, m) -> (P, L); the golden adds in float64."""
    out = np.zeros(codes.shape[:2], np.float64)
    for mi in range(codes.shape[2]):
        out += np.take_along_axis(lut[:, mi, :].astype(np.float64),
                                  codes[:, :, mi].astype(np.int64), axis=1)
    return out


def np_flat_scan(q, data, ids, sizes, li, metric, norms):
    block = data[li]  # (nq, g, cap, d) decoded fp32
    ip = np.einsum("qd,qgcd->qgc", q.astype(np.float64), block.astype(np.float64))
    if metric == "dot":
        s = ip
    else:
        qn = np.sum(q.astype(np.float64) ** 2, axis=1)[:, None, None]
        s = -(qn - 2.0 * ip + norms[li])
    cap = data.shape[1]
    valid = (np.arange(cap)[None, None, :] < sizes[li][:, :, None]) & (ids[li] >= 0)
    return np.where(valid, s, -np.inf).astype(np.float32)


def _report(name, got, want, dt, rtol, atol, **extra):
    """``atol`` may be an array: a bound of its own for every element."""
    finite = np.isfinite(want)
    bound = np.broadcast_to(atol, want.shape)[finite] + rtol * np.abs(want[finite])
    ok = bool(np.array_equal(finite, np.isfinite(got))
              and np.all(np.abs(got[finite] - want[finite]) <= bound))
    err = float(np.max(np.abs(got[finite] - want[finite]))) if finite.any() else 0.0
    print(json.dumps({
        "case": name, **extra, "compiled": True, "max_abs_err": round(err, 7),
        "ok": ok, "first_call_s": round(dt, 2),
    }), flush=True)
    return 0 if ok else 1


def adc_cases(rng):
    import jax.numpy as jnp

    from distributed_faiss_tpu.ops import adc_pallas, pq

    failures = 0
    cases = [
        # (name, kernel, pairs, m, L)
        # the served knnlm scan: one table a (query, probe) pair, capacity
        # 1024; a wrong split or a dropped plane shows only compiled (the
        # interpreter multiplies in f32: PR 21's finding)
        ("planes_knnlm_cell", "planes", 128, 64, 1024),
        ("planes_exact_grid", "planes-grid", 16, 8, 1024),
        ("planes_knnlm_wide", "planes-wide", 32, 64, 1024),
        ("planes_m8_cap128", "planes-wide", 16, 8, 128),
        ("planes_ragged_L", "planes", 8, 64, 700),
        # the XLA arm, the oracle of the first-use check and of the guard
        ("xla_knnlm_cell", "xla", 128, 64, 1024),
        ("xla_knnlm_wide", "xla-wide", 32, 64, 1024),
    ]
    kernels = {
        "planes": lambda lut, codes: adc_pallas.adc_scan_pallas_planes(
            lut, codes, jnp.full((codes.shape[0],), codes.shape[1], jnp.int32),
            interpret=False),
        "xla": pq.adc_scan,
    }
    for name, kind, nq, m, L in cases:
        ksub = 256
        kind, _, table_kind = kind.partition("-")
        table = rng.standard_normal((nq, m, ksub))
        if table_kind == "wide":
            # magnitudes 1e-3 to 1e3, both signs: every entry needs its
            # mid and lo planes, and the sums cancel
            table = np.sign(table) * 10.0 ** rng.uniform(-3, 3, table.shape)
        elif table_kind == "grid":
            # 20 significant bits (all three planes) on a 2**-13 grid under
            # 2**7: f32 holds every sum of m=8 exactly, so whatever order
            # the MXU adds in the result is the golden bit for bit — a
            # dropped or rounded plane cannot hide in accumulation noise
            table = rng.integers(-(1 << 20), 1 << 20, table.shape) * 2.0 ** -13
        lut_np = table.astype(np.float32)
        codes = rng.integers(0, ksub, (nq, L, m)).astype(np.uint8)
        t0 = time.time()
        got = np.asarray(kernels[kind](jnp.asarray(lut_np), codes))
        dt = time.time() - t0
        want = np_adc_per_query(lut_np, codes)
        rtol, atol = 1e-4, 1e-4
        if table_kind == "grid":
            rtol = atol = 0.0
        elif table_kind == "wide":
            # entries of 1e3 leave f32 sums of m terms an error of up to
            # m * 2**-24 of the summed magnitudes, under HIGHEST as here
            mag = np_adc_per_query(np.abs(lut_np), codes)
            atol = atol + m * 2.0 ** -24 * mag
        failures += _report(name, got, want, dt, rtol, atol, nq=nq, m=m, L=L)
    return failures + adc_sized_cases(rng)


def adc_sized_cases(rng):
    """The kernel stops at the end of each pair's list (PR 35): against its
    own scan of whole lists, compiled, a column of a sub-tile that holds a
    row is the same bits and a sub-tile past the list is -inf; at the cells'
    geometry, with every boundary size and a mix like the probed lists'."""
    import jax.numpy as jnp

    from distributed_faiss_tpu.ops import adc_pallas

    failures = 0
    for name, pairs, m, L in (("planes_sized_knnlm_cell", 512, 64, 1024),
                              ("planes_sized_m8_cap256", 64, 8, 256),
                              ("planes_sized_cap4096", 32, 64, 4096)):
        lut = jnp.asarray(rng.standard_normal((pairs, m, 256)).astype(np.float32))
        codes = jnp.asarray(rng.integers(0, 256, (pairs, L, m)).astype(np.uint8))
        sizes = (80 * rng.integers(0, L // 80 + 1, pairs)).astype(np.int32)
        sizes[:8] = [0, 1, 127, 128, 129, L - 1, L, L // 2]
        t0 = time.time()
        got = np.asarray(adc_pallas.adc_scan_pallas_planes(
            lut, codes, jnp.asarray(sizes), interpret=False))
        dt = time.time() - t0
        whole = np.asarray(adc_pallas.adc_scan_pallas_planes(
            lut, codes, jnp.full((pairs,), L, jnp.int32), interpret=False))
        sub = adc_pallas._SUB_TILE
        computed = np.arange(L)[None, :] < (-(-sizes // sub) * sub)[:, None]
        ok = bool(np.array_equal(got[computed], whole[computed])
                  and np.all(got[~computed] == -np.inf)
                  and int(adc_pallas.scanned_columns(jnp.asarray(sizes), L))
                  == int(computed.sum()))
        print(json.dumps({
            "case": name, "nq": pairs, "m": m, "L": L, "sub_tile": sub,
            "compiled": True, "computed_share": round(float(computed.mean()), 4),
            "bit_equal_to_whole_lists": ok, "ok": ok, "first_call_s": round(dt, 2),
        }), flush=True)
        failures += 0 if ok else 1
    return failures


def flat_cases(rng):
    import jax.numpy as jnp

    from distributed_faiss_tpu.ops import flat_pallas

    failures = 0
    nq, d, nlist, cap, g = 64, 512, 32, 512, 2
    q = rng.standard_normal((nq, d)).astype(np.float32)
    raw = rng.standard_normal((nlist, cap, d)).astype(np.float32) * 2.0
    raw[0, 0, :4] = [0.0, -0.0, 3e-6, -4e-5]  # fp16 zeros and subnormals
    ids = rng.integers(-1, 1 << 20, (nlist, cap)).astype(np.int32)
    sizes = rng.integers(0, cap + 1, (nlist,)).astype(np.int32)
    li = rng.integers(0, nlist, (nq, g)).astype(np.int32)
    vmin = raw.min(axis=(0, 1))
    span = raw.max(axis=(0, 1)) - vmin
    stored = {
        "f32": (raw, raw),
        "f16": (raw.astype(np.float16), raw.astype(np.float16).astype(np.float32)),
    }
    codes = np.clip(np.round((raw - vmin) / span * 255.0), 0, 255).astype(np.uint8)
    stored["sq8"] = (codes, vmin + codes.astype(np.float32) * (span / 255.0))
    for codec, (data, decoded) in stored.items():
        norms = np.sum(decoded.astype(np.float64) ** 2, axis=2).astype(np.float32)
        for metric, scan_bf16 in (("l2", False), ("dot", False), ("l2", True)):
            if scan_bf16 and codec != "f16":
                continue
            t0 = time.time()
            got = np.asarray(flat_pallas.flat_list_scan_pallas(
                jnp.asarray(q), jnp.asarray(data), jnp.asarray(ids),
                jnp.asarray(li), jnp.asarray(sizes[li]),
                jnp.asarray(norms) if metric == "l2" else None,
                jnp.asarray(vmin) if codec == "sq8" else None,
                jnp.asarray(span) if codec == "sq8" else None,
                metric=metric, codec=codec, scan_bf16=scan_bf16,
                interpret=False))
            dt = time.time() - t0
            want = np_flat_scan(q, decoded, ids, sizes, li, metric, norms)
            # scores are ~1e3 at d=512: 1e-2 absolute is 1e-5 relative; the
            # bf16 scan mode is bounded by bf16's 8-bit mantissa instead
            rtol, atol = (2e-2, 2.0) if scan_bf16 else (1e-5, 1e-2)
            failures += _report(
                f"flat_{codec}_{metric}" + ("_bf16" if scan_bf16 else ""),
                got, want, dt, rtol, atol, nq=nq, d=d, cap=cap, g=g)
    return failures


def main():
    import jax

    from distributed_faiss_tpu.utils import envutil

    envutil.place_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"not on TPU (platform={dev.platform})"}))
        return 1
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind,
                      "count": len(jax.devices())}), flush=True)
    rng = np.random.default_rng(0)
    return min(1, adc_cases(rng) + flat_cases(rng))


if __name__ == "__main__":
    sys.exit(main())
