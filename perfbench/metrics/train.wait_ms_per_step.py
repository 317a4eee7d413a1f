"""Milliseconds the train loop waited for its next staged batch
(``train.wait_batch`` spans, recorded above 0.1 ms) per window step.
``train.wait_ms_per_step.<traffic>`` reads with it."""

from perfbench.harness import tracing


def read(run):
    if run.spans is None:
        return None
    s = tracing.span_seconds(run.spans, "train.wait_batch", run.window_ns)
    return s / run.steps * 1e3
