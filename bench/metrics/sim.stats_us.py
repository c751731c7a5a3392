"""Simulator step phase `desim.stats` (the stats and the telemetry): its
device time ÷ scan steps in the traced window, in us."""
from scopes import per_scan_step_us


def read(tr, ctx):
    return per_scan_step_us(tr, "desim.stats")
