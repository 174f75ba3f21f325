"""batch_fill: real lanes over bucket lanes, over every micro-batch the
window executed (engine telemetry counts), in percent.  Layer: the
engine's shape-bucket batcher."""


def read(run):
    lanes = sum(b["bucket"] for b in run.batches)
    if not lanes:
        return None
    return 100.0 * sum(b["n_real"] for b in run.batches) / lanes
