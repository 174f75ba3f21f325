"""inputs_host_ms: host time of ``System.batch_inputs`` per micro-batch
(the engine's own clock around the call, from its telemetry).  The call
returns once the eager L1 scoring is enqueued, so this is the host part
of the input build: the occupancy bitmaps and the dispatch."""


def read(run):
    if not run.batches:
        return None
    return 1e3 * sum(b["t_inputs_s"] for b in run.batches) / len(run.batches)
