"""Milliseconds of ``fe.extract`` spans (the FE worker) per window step."""

from perfbench.harness import tracing


def read(run):
    if run.spans is None:
        return None
    s = tracing.span_seconds(run.spans, "fe.extract", run.window_ns)
    return s / run.steps * 1e3
