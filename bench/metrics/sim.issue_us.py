"""Simulator step phase `desim.issue` (compute-unit sharding and the MLP
issue window): its device time ÷ scan steps in the traced window, in us."""
from scopes import per_scan_step_us


def read(tr, ctx):
    return per_scan_step_us(tr, "desim.issue")
