"""Share of the traced span in which no operation ran on the device, %."""


def read(ctx):
    t = ctx.trace
    if not t["ops"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
