"""Decode program: the least HBM time of the window's decode steps and
admissions (``roofline_lm.window``: weights, tied table per head, K/V read
and written) at the chip's bandwidth, over the device time of the programs
that did that work (``jit_decode_step`` and ``jit_prefill_into_slot`` in
the trace, the driver's ``programs_s``), in percent."""
from bench import roofline_lm


def read(ctx):
    progs = ctx.get("programs_s")
    if ctx.get("kind") != "serve" or ctx["peaks"] is None or not progs:
        return None
    busy = sum(progs.values())
    if busy <= 0:
        return None
    _, nbytes = roofline_lm.window(ctx)
    t = ctx["roofline"].memory_time(nbytes, ctx["peaks"])
    return 100.0 * t / busy
