"""Milliseconds per window step of collective operations in the device
trace (all-reduce, all-gather, reduce-scatter, permutes), per chip."""

def read(run):
    if run.trace is None or run.trace["collective_s"] <= 0:
        return None
    return run.trace["collective_s"] / run.steps * 1e3
