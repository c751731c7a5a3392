"""Simulator step phase `desim.unit_state` (the compute unit's slice of
the state on entry, and the writes that put it back): its device time ÷
scan steps in the traced window, in us."""
from scopes import per_scan_step_us


def read(tr, ctx):
    return per_scan_step_us(tr, "desim.unit_state")
