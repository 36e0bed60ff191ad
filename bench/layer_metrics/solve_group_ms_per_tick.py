"""Exact post-pass: the solve's (state, bandwidth) grouping of the
re-placing users per tick, the program span ``pop.group``
(``TickReport.t_group_ms``) summed on the profiler's host plane over the
window."""
from bench.program_spans import per_tick


def read(ctx):
    return per_tick(ctx, __file__, "pop.group")
