"""Mean length of the ``execute`` span per micro-batch of the window
(stack, pad, transfer, device, read back), ms."""

from bench import spans


def read(ctx):
    return spans.phase_ms_per_batch(ctx, "execute")
