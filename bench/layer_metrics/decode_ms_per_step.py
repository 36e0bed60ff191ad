"""Decode program: the time per serving step of the program span
``serve.decode`` (dispatching the decode step and waiting for its logits,
``EngineStats.t_decode_ms``) over the window."""


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx.get("steps"):
        return None
    return ctx["spans_ms"]["t_decode_ms"] / ctx["steps"]
