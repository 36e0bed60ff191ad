"""Relaxation engine: cohort states relaxed (``PopulationStats.dp_relaxes``)
per tick of the window."""


def read(ctx):
    if ctx.get("kind") != "population" or not ctx["ticks"]:
        return None
    return ctx["counters"]["dp_relaxes"] / ctx["ticks"]
