"""Device idle share of the traced window: 1 - busy / window, in %, with
busy the union of the intervals in which an operation ran on the chip."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
