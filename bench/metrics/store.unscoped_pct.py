"""Share of device busy time in the store step under no `store.*` scope,
in %: ops outside the named phases, or named alike by two live programs."""
from scopes import unscoped_pct


def read(tr, ctx):
    return unscoped_pct(tr, "store.")
