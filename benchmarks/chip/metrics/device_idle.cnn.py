"""device_idle.cnn: the share of the traced CNN retrain window in which
no operation ran on the device (100 x (1 - busy / window))."""


def read(ctx):
    if ctx.window_s <= 0 or ctx.busy_s() <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s() / ctx.window_s)
