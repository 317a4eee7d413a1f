"""Device milliseconds per window step of the step's ``embed_update``
phase: Adagrad on the working rows, and the row and accumulator scatters
into the table, per chip. ``step.embed_update_ms.mesh`` reads with it."""

from perfbench.harness import named_work


def read(run):
    return named_work.phase_ms_per_step(run, "embed_update")
