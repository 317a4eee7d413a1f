"""Seconds from process start to the window's open: traffic, weights,
compile or cache load, and the warm-up steps (host clock)."""

def read(run):
    return run.setup_s
