"""idle_loop_share (%): device idle time in the gaps the trace labels by a
host-loop span of the program (``trainer.stack``, ``trainer.dispatch``,
``trainer.sync``, ``trainer.checkpoint``) over the traced window, averaged
over the cell's devices.  A program without the ``trainer.*`` host spans
labels no gap so, and reads nothing.

Chunk-launch time falls outside this metric: ``bench/trace._activity``
gives a gap to the shorter of two events that cover it equally, so JAX's
own ``PjitFunction(chunk_step)`` event, nested in ``trainer.dispatch``,
takes that idle time until the labelling prefers program spans."""
from bench.idle_spans import idle_share

SPANS = ("trainer.stack", "trainer.dispatch", "trainer.sync",
         "trainer.checkpoint")


def read(record, trace):
    return idle_share(trace, SPANS)
