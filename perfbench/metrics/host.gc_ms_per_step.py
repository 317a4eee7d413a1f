"""Milliseconds per window step of ``gc.collect`` spans: the collector's
pauses, as the program's own tracer records them, on any thread (host
clock). ``host.gc_ms_per_step.stream`` reads with it."""

from perfbench.harness import named_work


def read(run):
    return named_work.span_ms_per_step(run, "gc.collect")
