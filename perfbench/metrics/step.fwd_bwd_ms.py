"""Device milliseconds per window step of the step's ``fwd_bwd`` phase:
forward and backward of the loss, the working-set gradient scatter-add
included, per chip."""

from perfbench.harness import named_work


def read(run):
    return named_work.phase_ms_per_step(run, "fwd_bwd")
