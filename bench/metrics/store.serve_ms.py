"""Serve: device time under `store.serve` (the remote critical fetch and the
merge of local and remote k/v) ÷ decode steps, in ms."""
from scopes import per_step_ms


def read(tr, ctx):
    return per_step_ms(tr, ctx, "store.serve")
