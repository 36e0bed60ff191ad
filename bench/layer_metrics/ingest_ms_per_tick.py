"""Ingest / signature layer: ``TickReport.t_ingest_ms`` (a ``Population(
timing=True)`` span) summed over the window's ticks, per tick."""


def read(ctx):
    if ctx.get("kind") != "population" or not ctx["ticks"]:
        return None
    return ctx["spans_ms"]["t_ingest_ms"] / ctx["ticks"]
