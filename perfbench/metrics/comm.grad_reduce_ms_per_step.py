"""Device milliseconds per window step of the mesh step's ``grad_reduce``
phase: the working-set and dense gradient reductions, the loss and count
``psum``s and the division by the chip count, per chip.
``comm.grad_reduce_ms_per_step.mesh`` reads with it."""

from perfbench.harness import named_work


def read(run):
    return named_work.phase_ms_per_step(run, "grad_reduce")
