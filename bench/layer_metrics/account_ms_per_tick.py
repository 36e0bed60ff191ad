"""Orchestrator, hysteresis gate: the tick's closing accounting (re-solve
ledgers, migration bits, the energy sum) per tick, the program span
``orch.account`` (``TickReport.t_account_ms``) summed on the profiler's host
plane over the window."""
from bench.program_spans import per_tick


def read(ctx):
    return per_tick(ctx, __file__, "orch.account")
