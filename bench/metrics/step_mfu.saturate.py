"""Images answered in the traced span times the served graph's operations
per image, over the span and the chip's int8 peak, %."""

from bench import roofline


def read(ctx):
    t = ctx.trace
    if not t["ops"] or not ctx.images_in_trace or t["window_s"] <= 0:
        return None
    ops = ctx.images_in_trace * roofline.ops_per_image(ctx.cfg)
    return 100.0 * ops / (t["window_s"] * ctx.peaks["int8_ops_per_s"])
