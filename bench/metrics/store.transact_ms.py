"""Residency transaction: device time under `store.transact` (the fused
kernel or the chain, with the engine poll and retire) ÷ decode steps,
in ms."""
from scopes import per_step_ms


def read(tr, ctx):
    return per_step_ms(tr, ctx, "store.transact")
