"""Store step: device busy time in the traced window ÷ decode steps, in ms."""


def read(tr, ctx):
    if not ctx.get("units") or ctx["busy"]["busy_s"] <= 0:
        return None
    return ctx["busy"]["busy_s"] / ctx["units"] * 1e3
