"""Simulator step: device busy time in the traced window ÷ scan steps run
in it (each step advances all lattice cells by one request), in us."""
from trace_reduce import scan_steps


def read(tr, ctx):
    steps = scan_steps(tr)
    if not steps or ctx["busy"]["busy_s"] <= 0:
        return None
    return ctx["busy"]["busy_s"] / steps * 1e6
