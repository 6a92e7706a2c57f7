"""Share of the traced sampling window in which no operation ran on the
chip (1 - busy / window), from the device trace."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
