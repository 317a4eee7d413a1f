"""Device milliseconds per window step of the mesh step's ``exchange``
phase: each chip's owned-row contribution to the working set and its
reduction over the mesh, pads and gathers included, per chip.
``comm.exchange_ms_per_step.mesh`` reads with it."""

from perfbench.harness import named_work


def read(run):
    return named_work.phase_ms_per_step(run, "exchange")
