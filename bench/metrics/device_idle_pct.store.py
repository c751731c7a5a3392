"""Device idle share of the traced store window, in %."""


def read(tr, ctx):
    b = ctx["busy"]
    if b["window_s"] <= 0 or b["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])
