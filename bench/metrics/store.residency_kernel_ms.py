"""Residency transaction: the fused kernel's device time ÷ decode steps, in ms.

The kernel is found by its stable name, `residency_fused`."""
from trace_reduce import kernel_s

KERNEL = "residency_fused"


def read(tr, ctx):
    s = kernel_s(tr, KERNEL)
    if s is None or not ctx.get("units"):
        return None
    return s / ctx["units"] * 1e3
