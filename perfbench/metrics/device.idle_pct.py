"""Share of the traced window in which no operation ran on the device,
in percent (averaged over the chips used). ``device.idle_pct.<traffic>``
reads with it."""

def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    return (1 - run.trace["busy_s"] / run.trace["window_s"]) * 100
