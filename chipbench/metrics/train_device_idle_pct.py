"""Share of the traced training window in which no operation ran on the
device (1 - union of device op intervals / window)."""


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    t = ctx["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s)
