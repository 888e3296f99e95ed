"""Mean length of the ``transfer`` span per micro-batch, ms: from the
start of the batch's device put to its input being ready on the device
(the host-to-device copy and its layout on the device). Over the window's
batches that ended before the profiler started (``bench/batches.py``).
"""

from bench import batches


def read(ctx):
    return batches.ms_per_batch(ctx, "transfer")
