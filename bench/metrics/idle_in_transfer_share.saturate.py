"""Share of the device's idle time, as the host sees it, that falls
inside some batch's ``transfer`` span (device put to input ready), %.
The device counts as busy inside each batch's ``device`` span. Over the
window's batches that ended before the profiler started, whose puts it
did not slow (``bench/batches.py``).
"""

from bench import batches


def read(ctx):
    return batches.idle_in(ctx, "transfer")
