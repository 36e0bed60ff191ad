"""Kernel ``banded_minplus_chain`` (kernels/minplus): the least time the
chip needs for the relaxation work of the window, by its HBM bytes alone (``roofline.fin_relax``
over the cohort states relaxed, ``PopulationStats.dp_relaxes`` per app)
over the kernel's device time in the trace, in percent.  The work is
FIN's, not the launch's: rows padded to a bucket are not counted."""


def read(ctx):
    tr = ctx.get("trace")
    if ctx["peaks"] is None or ctx.get("kind") != "population" or tr is None:
        return None
    k = tr["kernels"].get("banded_minplus_chain")
    relaxed = ctx["relaxed_by_app"]
    if not k or k["seconds"] <= 0 or not sum(relaxed.values()):
        return None
    rf = ctx["roofline"]
    _ops, nbytes = rf.fin_relax(ctx["config"], relaxed)
    t = rf.memory_time(nbytes, ctx["peaks"])
    print(f"minplus_chain: {k['calls']} launches, {k['seconds']!r} s on "
          f"the device, least time {t!r} s (memory bound)", flush=True)
    return 100.0 * t / k["seconds"]
