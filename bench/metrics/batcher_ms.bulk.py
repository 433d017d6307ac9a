"""Host time of admission and batching per flushed batch: the program's
queue.view and batcher.decide counters and its queue.pop spans."""
from bench.metrics import _spans


def read(ctx):
    return _spans.batcher_ms(ctx)
