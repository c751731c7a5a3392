"""Remote critical fetch: the paged-gather kernel's device time ÷ decode steps, in ms."""
from trace_reduce import kernel_s

KERNEL = "paged_gather"


def read(tr, ctx):
    s = kernel_s(tr, KERNEL)
    if s is None or not ctx.get("units"):
        return None
    return s / ctx["units"] * 1e3
