"""Admission: the time per serving step of the program span
``serve.admit`` (prefilling prompts into freed slots and gating their first
tokens, ``EngineStats.t_admit_ms``) over the window."""


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx.get("steps"):
        return None
    return ctx["spans_ms"]["t_admit_ms"] / ctx["steps"]
