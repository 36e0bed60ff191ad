"""Kernel ``ee_gate`` (kernels/ee_gate): the least time the chip needs to
read the logits the gate judged in the window (``roofline_lm.gate_bytes``)
over the kernel's device time in the trace, in percent (memory bound)."""
from bench import roofline_lm


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "serve" or ctx["peaks"] is None or tr is None:
        return None
    k = tr["kernels"].get("ee_gate")
    if not k or k["seconds"] <= 0:
        return None
    t = ctx["roofline"].memory_time(roofline_lm.gate_bytes(ctx),
                                    ctx["peaks"])
    print(f"ee_gate: {k['calls']} launches, {k['seconds']!r} s on the "
          f"device, least time {t!r} s (memory bound)", flush=True)
    return 100.0 * t / k["seconds"]
