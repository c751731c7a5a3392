"""Simulator step phase `desim.fabric` (repartitioning, the bus and link
service and the serve time): its device time ÷ scan steps in the traced
window, in us."""
from scopes import per_scan_step_us


def read(tr, ctx):
    return per_scan_step_us(tr, "desim.fabric")
