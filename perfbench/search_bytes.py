"""The least one search launch has to read, and the least it has to compute,
from shapes alone — the numerator of ``kernel.search_roofline``.

Copied in spirit from ``benchmarks/adc_roofline.py`` (whose bytes function
was right and whose peak was hard-coded): the count is the least *any*
implementation of an IVF search would need for one merged device window of
``nq`` query rows, so the share of the roofline it yields cannot pass 100%
whatever the program does:

  centroid table   nlist x d x 4 bytes, read once per launch
  queries          nq x d x 4
  probed lists     read once per launch however many queries probe them. The
                   distinct lists lie between nprobe and min(nlist, nq x
                   nprobe); the benchmark cannot see which queries shared a
                   launch, so it takes the lower end, nprobe lists of mean
                   length rows / nlist
  refine rows      nq x k x refine_k_factor float16 rows (``knnlm``)
  answer           nq x k x (4 + 4)

and the operations: coarse scan 2 nq nlist d; per probed list the ADC
look-up table 2 nprobe ksub d per query (``knnlm``, L2 residuals) and one
add per code byte, or 2 d per stored row (``ivfsq``); refine 2 d per row.
"""

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind):
    """The table's row for this device; a device it lacks is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in {PEAKS}; "
                       f"it has {sorted(table)}")
    return table[device_kind]


def _row_bytes(index):
    """(bytes scanned per stored row, operations per stored row per query)."""
    d = int(index["dim"])
    if index["index_builder_type"] == "knnlm":
        m = int(index.get("code_size", 64))
        return m + 4, m  # PQ code + id; one table add per code byte
    if index["index_builder_type"] == "ivfsq":
        return 2 * d + 4 + 4, 2 * d  # fp16 row + id + stored norm
    raise ValueError(f"no byte model for builder {index['index_builder_type']!r}")


def least_bytes(index, rows_on_rank, k, nq):
    d, nlist, nprobe = int(index["dim"]), int(index["centroids"]), int(index["nprobe"])
    per_row, _ = _row_bytes(index)
    mean_list = rows_on_rank / nlist
    refine = int(index.get("refine_k_factor", 0)) if index["index_builder_type"] == "knnlm" else 0
    return (nlist * d * 4 + nq * d * 4 + nprobe * mean_list * per_row
            + nq * k * refine * d * 2 + nq * k * 8)


def least_ops(index, rows_on_rank, k, nq):
    d, nlist, nprobe = int(index["dim"]), int(index["centroids"]), int(index["nprobe"])
    _, per_row = _row_bytes(index)
    mean_list = rows_on_rank / nlist
    ops = 2.0 * nq * nlist * d + nq * nprobe * mean_list * per_row
    if index["index_builder_type"] == "knnlm":
        ksub = 2 ** int(index.get("nbits", 8))
        ops += 2.0 * nq * nprobe * ksub * d
        ops += 2.0 * nq * k * int(index.get("refine_k_factor", 0)) * d
    return ops


def roofline_seconds(index, rows_on_rank, k, nq, device_kind):
    """(least seconds one launch could take on this device, which bound)."""
    p = peak(device_kind)
    by_bytes = least_bytes(index, rows_on_rank, k, nq) / p["hbm_bytes_per_s"]
    by_ops = least_ops(index, rows_on_rank, k, nq) / p["bf16_flops_per_s"]
    return (by_bytes, "memory") if by_bytes >= by_ops else (by_ops, "compute")
