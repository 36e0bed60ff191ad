"""Orchestrator, hysteresis gate: the orchestrator tick's own time per tick,
the program span ``orch.tick`` less its child program spans on the same
thread line, on the profiler's host plane over the window: what no other
span names."""
from bench.program_spans import per_tick


def read(ctx):
    return per_tick(ctx, __file__, "orch.tick", "self_s")
