"""Mean length of the ``device`` span per micro-batch, ms: from the
batch's input being ready on the device to its output being ready, as
the host sees it. Over the window's batches that ended before the
profiler started (``bench/batches.py``).
"""

from bench import batches


def read(ctx):
    return batches.ms_per_batch(ctx, "device")
