"""Exact post-pass: ``TickReport.t_post_ms`` (a ``Population(timing=True)``
span: solve time less relaxation) per tick."""


def read(ctx):
    if ctx.get("kind") != "population" or not ctx["ticks"]:
        return None
    return ctx["spans_ms"]["t_post_ms"] / ctx["ticks"]
