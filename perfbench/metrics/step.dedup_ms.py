"""Device milliseconds per window step of the step's ``dedup`` phase:
gathering the batch's ids, their concat and dedup (the mesh step's pool
all-gather and inverse search included), per chip. ``step.dedup_ms.mesh``
reads with it."""

from perfbench.harness import named_work


def read(run):
    return named_work.phase_ms_per_step(run, "dedup")
