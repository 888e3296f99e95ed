"""Mean length of the ``stack`` span per micro-batch, ms: the host
stacking the batch's single images into one array. Over the window's
batches that ended before the profiler started (``bench/batches.py``).
"""

from bench import batches


def read(ctx):
    return batches.ms_per_batch(ctx, "stack")
