"""Images answered in the traced span times the served graph's operations
per image (twice the model module's multiply-accumulates), over the span
and the chip's int8 peak, %."""


def read(ctx):
    t = ctx.trace
    if not t["ops"] or not ctx.images_in_trace or t["window_s"] <= 0:
        return None
    ops = ctx.images_in_trace * 2 * ctx.model.macs_per_image(ctx.cfg)
    return 100.0 * ops / (t["window_s"] * ctx.peaks["int8_ops_per_s"])
