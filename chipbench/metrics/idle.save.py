"""Share of the traced save window in which no operation ran on the
device: 1 minus the union of the device op intervals over the window."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
