"""Relaxation engine: ``TickReport.t_relax_ms`` (a ``Population(
timing=True)`` span around every relaxation launch) per tick."""


def read(ctx):
    if ctx.get("kind") != "population" or not ctx["ticks"]:
        return None
    return ctx["spans_ms"]["t_relax_ms"] / ctx["ticks"]
