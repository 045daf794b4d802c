"""Device layer (one TPU v5e): the share of the traced window in which no
operation ran on the device, in percent."""


def read(ctx):
    red = ctx["reduction"]
    if red.window_s <= 0:
        return None
    return (1.0 - red.busy_s / red.window_s) * 100.0
