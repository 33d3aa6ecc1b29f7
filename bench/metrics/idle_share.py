"""idle_share: share of the traced slice in which no operation ran on
the device, in percent."""


def read(ctx):
    red = ctx["trace"]
    if red is None or red.window_s <= 0:
        return None
    return (1.0 - red.busy_s / red.window_s) * 100.0
