"""Mean length of the ``form`` span per micro-batch, ms: the batcher
call that returned the batch, from its start to its return. Over the
window's batches that ended before the profiler started
(``bench/batches.py``).
"""

from bench import batches


def read(ctx):
    return batches.ms_per_batch(ctx, "form")
