"""Latent residency transaction's share of its HBM roofline, in %.

The least time for the bytes the transaction needs with one latent
tensor a page (`bench/roofline_latent.py`, from the cell's shapes and the
ledger's `landings` and `local_hits` over the traced window) at the chip's
HBM peak (`bench/peaks.py`), over the kernel's device time."""
from roofline import roofline_pct
from roofline_latent import residency_bytes
from trace_reduce import kernel_s

KERNEL = "residency_fused"


def read(tr, ctx):
    s = kernel_s(tr, KERNEL)
    nbytes = residency_bytes(ctx)
    if not s or nbytes is None:
        return None
    return roofline_pct(nbytes, s, ctx["device_kind"])
