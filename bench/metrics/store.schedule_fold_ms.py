"""Scheduling fold: device time under `store.schedule_fold` (the scan over
stores, its two inner scans included) ÷ decode steps, in ms."""
from scopes import per_step_ms


def read(tr, ctx):
    return per_step_ms(tr, ctx, "store.schedule_fold")
