"""sims_per_s: simulations completed in the window over the time from its
start to the end of its last unit."""


def read(ctx):
    sims = sum(u["sims"] for u in ctx["units"])
    span = ctx["w1"] - ctx["w0"]
    return sims / span if sims and span > 0 else None
