"""The least one exact-search launch has to read, and the least it has to
compute, from shapes alone — the numerator of ``kernel.flat_roofline``.

Beside ``search_bytes.py`` (the IVF builders' count; its ``peak`` is used
here too). The count is the least *any* exact scan of ``rows`` stored rows
of ``dim`` float32 dimensions needs for one merged device window of ``nq``
query rows, so the share it yields cannot pass 100% whatever the program
does:

  stored rows   rows x dim x 4 bytes, read once a launch — the rows stored,
                not the store's capacity: padding is the program's choice
  queries       nq x dim x 4
  answer        nq x k x (4 + 4)

and the operations: the product, 2 nq rows dim (norms, masks and the top-k
are left out: a floor). The peak it is held against is the bf16 one, as for
the other builders, though the configuration's product runs at
``Precision.HIGHEST`` (several bf16 passes): a floor again.
"""

from perfbench import search_bytes


def least_bytes(dim, rows, k, nq):
    return rows * dim * 4 + nq * dim * 4 + nq * k * 8


def least_ops(dim, rows, nq):
    return 2.0 * nq * rows * dim


def roofline_seconds(dim, rows, k, nq, device_kind):
    """(least seconds one launch could take on this device, which bound)."""
    p = search_bytes.peak(device_kind)
    by_bytes = least_bytes(dim, rows, k, nq) / p["hbm_bytes_per_s"]
    by_ops = least_ops(dim, rows, nq) / p["bf16_flops_per_s"]
    return (by_bytes, "memory") if by_bytes >= by_ops else (by_ops, "compute")
