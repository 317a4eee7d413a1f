"""Milliseconds per window step of ``train.dispatch`` spans: the train
loop's call into the compiled step, up to its return (host clock)."""

from perfbench.harness import named_work


def read(run):
    return named_work.span_ms_per_step(run, "train.dispatch")
