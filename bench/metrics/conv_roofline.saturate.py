"""The conv kernel's share of its roofline over the traced span, %: the
least time the chip needs for the calls' operations and bytes
(``bench/roofline.py``, at the padded batch each call ran, for the convs
the model module lists) over the calls' device time."""

from bench import kernels, roofline


def read(ctx):
    calls = kernels.conv_calls(ctx.trace["ops"], ctx.model.convs(ctx.cfg),
                               ctx.cfg)
    if not calls:
        return None
    least = sum(roofline.roofline_s(2 * roofline.conv_macs(l, n),
                                    roofline.conv_bytes(l, n, ctx.cfg),
                                    ctx.peaks)[0] for l, n, _ in calls)
    return 100.0 * least / sum(t for _, _, t in calls)
