"""Serving, whole step: model FLOPs of the window's decoded and prefilled
tokens (``roofline_lm.window``) over the window times the chip's bf16 peak,
in percent: the share of the whole step's peak."""
from bench import roofline_lm


def read(ctx):
    if ctx.get("kind") != "serve" or ctx["peaks"] is None:
        return None
    flops, _ = roofline_lm.window(ctx)
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["flops_bf16"])
