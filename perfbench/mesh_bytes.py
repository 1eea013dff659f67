"""The least ONE chip of a mesh rank has to read, and the least it has to
compute, for one search launch, from shapes alone — the numerator of
``kernel.mesh_roofline``.

Beside ``search_bytes.py`` (a one-chip rank's count; its ``peak`` and its
per-row model are used here too). A rank whose inverted lists are
partitioned over ``chips`` chips (``parallel/mesh.py``: list l on chip
l % chips) gives every chip the whole query window and the whole centroid
table, and a share of the lists. The count is the least *any* implementation
of that layout needs on one chip for one merged device window of ``nq``
query rows that probe independently, so the share of the roofline it yields
cannot pass 100% on such a window whatever the program does:

  centroid table   nlist x d x 4 bytes, replicated: read once a chip
  queries          nq x d x 4, replicated: once a chip
  probed lists     the chip's share, 1 / chips, of the distinct lists a
                   window probes, each of mean length rows / nlist and read
                   once a launch however many queries probe it.
                   ``search_bytes`` takes the lower end, nprobe lists (every
                   query of the window probing the same ones), which a
                   256-row window never meets and which left this share at
                   0.05%; here the count is what nq queries probe when each
                   takes its nprobe lists independently,
                   nlist x (1 - (1 - nprobe / nlist) ** nq), never more
                   than min(nlist, nq x nprobe): 3546 of 4096 lists for 256
                   queries of 32 probes. The benchmark cannot see which
                   lists a window probed; its queries are drawn from the
                   whole mixture, which is what the count assumes
  refine rows      the chip's share of nq x k x refine_k_factor float16 rows
                   (the program rescores a whole shortlist on every chip
                   before the merge; the least is the global shortlist once)
  answer           nq x k x (4 + 4), replicated: once a chip

and the operations: the coarse scan 2 nq nlist d on every chip (replicated),
and the chip's share of the look-up tables of the pairs it owns (2 nprobe
ksub d a query), of one add per code byte and of the refine (2 d a row).
With one chip and one query the count is ``search_bytes``' own.
"""

from perfbench import search_bytes


def probed_lists(nlist, nprobe, nq):
    """Distinct lists a window of ``nq`` queries probes, each taking its
    ``nprobe`` of ``nlist`` independently: between nprobe (one query) and
    min(nlist, nq x nprobe)."""
    return nlist * (1.0 - (1.0 - nprobe / nlist) ** nq)


def least_bytes(index, rows, k, nq, chips):
    d, nlist, nprobe = int(index["dim"]), int(index["centroids"]), int(index["nprobe"])
    per_row, _ = search_bytes._row_bytes(index)
    refine = int(index.get("refine_k_factor", 0))
    shared = nlist * d * 4 + nq * d * 4 + nq * k * 8
    owned = (probed_lists(nlist, nprobe, nq) * (rows / nlist) * per_row
             + nq * k * refine * d * 2)
    return shared + owned / chips


def least_ops(index, rows, k, nq, chips):
    d, nlist, nprobe = int(index["dim"]), int(index["centroids"]), int(index["nprobe"])
    _, per_row = search_bytes._row_bytes(index)
    ksub = 2 ** int(index.get("nbits", 8))
    owned = (2.0 * nq * nprobe * ksub * d + nq * nprobe * (rows / nlist) * per_row
             + 2.0 * nq * k * int(index.get("refine_k_factor", 0)) * d)
    return 2.0 * nq * nlist * d + owned / chips


def roofline_seconds(index, rows, k, nq, chips, device_kind):
    """(least seconds one launch could take on one chip of the mesh, which
    bound)."""
    p = search_bytes.peak(device_kind)
    by_bytes = least_bytes(index, rows, k, nq, chips) / p["hbm_bytes_per_s"]
    by_ops = least_ops(index, rows, k, nq, chips) / p["bf16_flops_per_s"]
    return (by_bytes, "memory") if by_bytes >= by_ops else (by_ops, "compute")
