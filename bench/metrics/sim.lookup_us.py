"""Simulator step phase `desim.lookup` (the residency lookup and the
retiring of arrived pages): its device time ÷ scan steps in the traced
window, in us."""
from scopes import per_scan_step_us


def read(tr, ctx):
    return per_scan_step_us(tr, "desim.lookup")
