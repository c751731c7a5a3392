"""Remote fetch's share of its HBM roofline, in %: the latent rows the
store gathered from the tier (the ledger's `remote_rows` over the traced
window), each read and written once (`bench/roofline_latent.py`), at the
chip's HBM peak (`bench/peaks.py`), over the `paged_gather` kernel's
device time."""
from roofline import roofline_pct
from roofline_latent import gather_bytes
from trace_reduce import kernel_s

KERNEL = "paged_gather"


def read(tr, ctx):
    s = kernel_s(tr, KERNEL)
    nbytes = gather_bytes(ctx)
    if not s or nbytes is None:
        return None
    return roofline_pct(nbytes, s, ctx["device_kind"])
