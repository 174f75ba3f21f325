"""serve_exe_device_ms: device time of the AOT serve executable
(rollout, block scan, shard merge, L1 prune) per micro-batch, from the
program-level events of the profiler trace."""

from bench import trace_reduce as tr


def read(run):
    return tr.per_batch_ms(run.trace, len(run.batches), serve=True)
