"""Request scan: device time under `store.request_scan` (each store's
scan over its requests, inside the fold) ÷ decode steps, in ms."""
from scopes import per_step_ms


def read(tr, ctx):
    return per_step_ms(tr, ctx, "store.request_scan")
