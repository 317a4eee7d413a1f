"""Milliseconds of ``io.read_shard`` spans (shard reader threads, summed)
per window step."""

from perfbench.harness import tracing


def read(run):
    if run.spans is None:
        return None
    s = tracing.span_seconds(run.spans, "io.read_shard", run.window_ns)
    return s / run.steps * 1e3
