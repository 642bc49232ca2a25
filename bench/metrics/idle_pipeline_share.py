"""idle_pipeline_share (%): device idle time in the gaps the trace labels
``trainer.collect`` (the main thread pulling a chunk's items from the data
pipeline, queue waits included) over the traced window, averaged over the
cell's devices.  A program without the ``trainer.*`` host spans labels no
gap so, and reads nothing."""
from bench.idle_spans import idle_share

SPANS = ("trainer.collect",)


def read(record, trace):
    return idle_share(trace, SPANS)
