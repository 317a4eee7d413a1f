"""Roofline share of the embedding update, in percent: the least HBM time
to write a step's updated working set back into the table (per unique row,
its new row and accumulator read and written), over the device time per
step of the operations whose output is table-shaped (the row and
accumulator scatters), found in the trace by the table's row count."""

import re

from perfbench.harness import counts
from perfbench.reference import recsys


def read(run):
    if run.trace is None or not run.n_unique:
        return None
    rows = recsys.padded_rows(run.cfg) // run.n_devices
    shaped = re.compile(rf" \w+\[{rows}[,\]]")
    t = sum(s for label, s in run.trace["ops_by_name"].items() if shaped.search(label))
    if t <= 0:
        return None
    need = counts.embed_update_bytes(run.cfg, sum(run.n_unique) / len(run.n_unique))
    bw = counts.peak(run.device_kind)["hbm_bytes_per_s"]
    return need / bw / (t / run.steps) * 100
