"""Exit gate: the time per serving step of the program span ``serve.gate``
(the ``ee_gate`` calls on every deployed head and their host reads, after
``serve.decode`` has waited for the logits; ``EngineStats.t_gate_ms``)
over the window."""


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx.get("steps"):
        return None
    return ctx["spans_ms"]["t_gate_ms"] / ctx["steps"]
