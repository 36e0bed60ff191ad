"""Device idle share of a population window: 1 - (union of device op
intervals) / window, from the profiler trace, averaged over the chips."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "population" or tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
