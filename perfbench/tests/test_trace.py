import json
import os

import pytest

from perfbench.harness import tracing

MS = 1_000_000


def test_reduce_busy_collectives_and_named_gaps():
    ops = {0: [("fusion.1", 0, 10 * MS), ("all-reduce.2", 10 * MS, 14 * MS),
               ("fusion.1", 30 * MS, 40 * MS), ("fusion.3", 35 * MS, 45 * MS),
               ("fusion.1", 90 * MS, 120 * MS)],
           1: [("fusion.1", 0, 50 * MS)]}
    spans = [("train.step", "MainThread", 0, 60 * MS),
             ("train.wait_batch", "MainThread", 60 * MS, 100 * MS),
             ("io.read_shard", "shard-reader-0", 0, 100 * MS)]
    red = tracing.reduce(ops, spans, gc_pauses=[(20 * MS, 22 * MS)],
                         window=(5 * MS, 100 * MS))
    # device 0: [5,14] + [30,45] + [90,100] = 34 ms; device 1: [5,50] = 45 ms
    assert red["busy_s"] == pytest.approx((34 + 45) / 2 / 1e3)
    assert red["window_s"] == pytest.approx(0.095)
    assert red["collective_s"] == pytest.approx(4 / 2 / 1e3)
    # gaps of device 0: [14,30] in train.step with a gc pause, [45,90]
    # whose middle (67.5) is in train.wait_batch
    assert red["idle_gaps"] == [["train.wait_batch|io.read_shard", pytest.approx(0.045)],
                                ["gc:train.step|io.read_shard", pytest.approx(0.016)]]
    assert red["device_ops"][0][0] == "fusion.1"
    assert tracing.span_seconds(spans, "io.read_shard", (5 * MS, 100 * MS)) \
        == pytest.approx(0.095)


RECORDED = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


def test_reduce_a_recorded_trace():
    """A short excerpt of a chip run's device ops and program spans."""
    with open(RECORDED) as f:
        rec = json.load(f)
    ops = {int(k): [tuple(e) for e in v] for k, v in rec["ops"].items()}
    red = tracing.reduce(ops, [tuple(s) for s in rec["spans"]], rec["gc"],
                         tuple(rec["window"]))
    for k, v in rec["expect"].items():
        assert red[k] == pytest.approx(v, rel=1e-9), k
    assert red["idle_gaps"] == [[n, pytest.approx(v)] for n, v in rec["idle_gaps"]]
    assert 0 < red["busy_s"] <= red["window_s"]
    # the table-shaped row scatter is the longest op in this excerpt
    assert red["device_ops"][0][0] == "fusion.9 f32[23471104,128]"
