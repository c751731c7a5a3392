"""Device idle share of a lattice's period, in %: the gap between two
lattices, read from a trace across their boundary, plus the idle time
inside a lattice at the rate the rest of the trace shows, over the
period from one lattice's dispatch to the next one's (host clock)."""
from trace_reduce import boundary_idle_pct


def read(tr, ctx):
    return boundary_idle_pct(tr, ctx.get("period_s"))
