"""Writeback scan: device time under `store.writeback_scan` (each store's
scan over its dirty evictions, inside the fold) ÷ decode steps, in ms."""
from scopes import per_step_ms


def read(tr, ctx):
    return per_step_ms(tr, ctx, "store.writeback_scan")
