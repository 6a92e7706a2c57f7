"""Device time of the coupled sampler's exchange with no compute beside it,
per exchange: the collectives' exposed time in the traced window over the
exchanges the window held (one every ``sync_every`` steps)."""


def read(ctx):
    t, sync_every = ctx["trace"], ctx["mix"].get("sync_every")
    if not sync_every or not t["collective_s"]:
        return None
    return 1000.0 * t["collective_exposed_s"] / (ctx["steps"] / sync_every)
