"""Cohort-state table: user-solves served by an already relaxed state
(``PopulationStats.dp_cache_hits``) over user-solves issued
(``PopulationStats.solves``) in the window, in percent."""


def read(ctx):
    if ctx.get("kind") != "population":
        return None
    c = ctx["counters"]
    if not c["solves"]:
        return None
    return 100.0 * c["dp_cache_hits"] / c["solves"]
