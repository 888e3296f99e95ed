"""Mean length of the ``readback`` span per micro-batch, ms: from the
output being ready on the device to its rows on the host. Over the
window's batches that ended before the profiler started
(``bench/batches.py``).
"""

from bench import batches


def read(ctx):
    return batches.ms_per_batch(ctx, "readback")
