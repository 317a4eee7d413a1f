"""Dense-part forward+backward FLOPs per example (the configuration's
reference model module) times examples/s, over chips times peak FLOP/s,
in percent (traced run's window). ``mfu.<traffic>`` reads with it."""

from perfbench.harness import counts


def read(run):
    rate = run.steps * run.rows_per_step / run.window_s
    peak = counts.peak(run.device_kind)["flops"] * run.n_devices
    return run.model.train_flops_per_example(run.cfg) * rate / peak * 100
