"""l1_device_ms: device time per micro-batch of every program outside
the serve executable: the eager L1 scoring of every document that
``System.batch_inputs`` enqueues, from the program-level events of the
profiler trace."""

from bench import trace_reduce as tr


def read(run):
    return tr.per_batch_ms(run.trace, len(run.batches), serve=False)
