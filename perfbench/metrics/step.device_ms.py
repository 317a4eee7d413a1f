"""Device busy milliseconds per window step: the union of the device's
operation intervals in the traced window, over the steps."""

def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    return run.trace["busy_s"] / run.steps * 1e3
