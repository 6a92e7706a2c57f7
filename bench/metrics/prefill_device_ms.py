"""Mean device time of one admit (prefill of a prompt into its pages),
over every execution of the engine's admit programs in the traced window."""


def read(ctx):
    mods = [m for name, m in ctx["trace"]["modules"].items() if "_admit" in name]
    count = sum(m["count"] for m in mods)
    return sum(m["seconds"] for m in mods) / count * 1e3 if count else None
