"""Simulator step phase `desim.residency` (the victim, the writeback
pricing, and insert, touch and mark): its device time ÷ scan steps in
the traced window, in us."""
from scopes import per_scan_step_us


def read(tr, ctx):
    return per_scan_step_us(tr, "desim.residency")
