"""Tiny cells that run a whole benchmark run on the CPU in seconds."""

import copy
import os

from perfbench.harness import spec

CELLS = {"preextracted": "dlrm-mlperf-rows1of8.preextracted",
         "stream": "dcn-v2.stream", "mesh": "dlrm-mlperf-rows1of2.mesh"}


def config(kind: str) -> dict:
    name = "dlrm-mlperf-rows1of8" if kind == "dlrm" else "dcn-v2"
    c = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", name + ".json"))
    c = copy.deepcopy(c)
    c["vocab_sizes"] = [min(v, 97 + 13 * i) for i, v in enumerate(c["vocab_sizes"])]
    c["embed_dim"] = 8
    if kind == "dlrm":
        c["bot_mlp"], c["top_mlp"] = [16, 8], [32, 16, 1]
    else:
        c["top_mlp"] = [32, 16]
    return c


def cell(kind: str, traffic: str, rows: int = 64, chips: int = 1, **mix) -> dict:
    t = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", traffic + ".json"))
    # warm-up past the pool's wrap, as at full size
    t.update(rows_per_step=rows, pool_steps=6, warmup_steps=8, **mix)
    if t["kind"] == "raw_log":
        t["populations"] = {k: 5000 for k in t["populations"]}
    c = config(kind)
    return {"workload": {"name": f"tiny-{kind}.{traffic}", "chips": chips},
            "config": c, "traffic": t,
            "limits": spec.load_json(os.path.join(spec.BENCH_DIR, "limits",
                                                  CELLS[traffic] + ".json")),
            "end_to_end": [{"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "device.idle_pct", "unit": "%"}],
            "model": spec.module(c["reference"]["model"]),
            "fe": spec.module(c["reference"]["fe"]),
            "bench_dir": spec.BENCH_DIR}
