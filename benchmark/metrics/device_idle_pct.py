"""Device: share of the traced window in which no operation ran on a
device, averaged over the cell's devices."""


def read(ctx):
    t = ctx["trace"]
    if not t.devices:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
