"""Simulator step phase `desim.engine` (the §4.2 granularity decision
and the gated engine insertions): its device time ÷ scan steps in the
traced window, in us."""
from scopes import per_scan_step_us


def read(tr, ctx):
    return per_scan_step_us(tr, "desim.engine")
