"""Milliseconds of ``h2d.stage`` spans (arena copy and transfer issue on
the feeder thread) per window step."""

from perfbench.harness import tracing


def read(run):
    if run.spans is None:
        return None
    s = tracing.span_seconds(run.spans, "h2d.stage", run.window_ns)
    return s / run.steps * 1e3
