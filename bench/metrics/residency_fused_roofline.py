"""Residency transaction's share of its HBM roofline, in %.

The least time for the bytes the transaction needs (`bench/roofline.py`,
from the cell's shapes and the ledger's counts over the traced window) at
the chip's HBM peak (`bench/peaks.py`), over the kernel's device time."""
from roofline import residency_bytes, roofline_pct
from trace_reduce import kernel_s

KERNEL = "residency_fused"


def read(tr, ctx):
    s = kernel_s(tr, KERNEL)
    if not s:
        return None
    return roofline_pct(residency_bytes(ctx), s, ctx["device_kind"])
