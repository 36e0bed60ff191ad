"""Orchestrator, hysteresis gate: the share of the incumbents the gate
judged against an exact channel threshold or a constant, of all the
incumbents it judged.  Read as the ``users`` arguments of the program spans
``pop.gate.threshold`` (rows judged by threshold or constant) and
``pop.gate.exact`` (rows re-evaluated) on the profiler's host plane,
summed over the window.  None for a program that has neither span."""
from bench.program_spans import per_tick


def read(ctx):
    thr = per_tick(ctx, __file__, "pop.gate.threshold", "args.users")
    exact = per_tick(ctx, __file__, "pop.gate.exact", "args.users")
    if thr is None or exact is None or thr + exact <= 0:
        return None
    return 100.0 * thr / (thr + exact)
