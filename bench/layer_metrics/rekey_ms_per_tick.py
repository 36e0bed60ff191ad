"""Cohort-state table: the state re-key (a mask flip or a moved
quantization re-keys users into cohort states) per tick, the program span
``pop.rekey`` (``TickReport.t_rekey_ms``) summed on the profiler's host
plane over the window."""
from bench.program_spans import per_tick


def read(ctx):
    return per_tick(ctx, __file__, "pop.rekey")
