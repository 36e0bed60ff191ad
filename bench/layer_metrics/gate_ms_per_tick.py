"""Orchestrator, hysteresis gate: the gate's time per tick, the program span
``orch.gate`` (``TickReport.t_gate_ms``) summed on the profiler's host plane
over the window."""
from bench.program_spans import per_tick


def read(ctx):
    return per_tick(ctx, __file__, "orch.gate")
