"""Mean length of the scheduler's admission (``schedule`` span) per
micro-batch of the window, ms."""

from bench import spans


def read(ctx):
    return spans.phase_ms_per_batch(ctx, "schedule")
