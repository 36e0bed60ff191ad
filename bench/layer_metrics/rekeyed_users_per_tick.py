"""Cohort-state table: users re-keyed per tick (``PopulationStats.
rekeyed_users``), read as the ``users`` argument of each ``pop.rekey`` span
on the profiler's host plane, summed over the window."""
from bench.program_spans import per_tick


def read(ctx):
    return per_tick(ctx, __file__, "pop.rekey", "args.users")
