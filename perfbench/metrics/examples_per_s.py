"""Examples whose step completed inside the window, over the window's
seconds (host clock), over all chips the cell uses. One reader for the
rate of every cell: ``examples_per_s.<traffic>`` reads with it."""

def read(run):
    return run.steps * run.rows_per_step / run.window_s
