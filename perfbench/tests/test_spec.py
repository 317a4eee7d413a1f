"""A new cell, traffic mix or metric is new files and entries, no edit."""

import json
import os
import shutil

from perfbench.harness import bench, spec


def test_benchmark_names_resolve_to_files():
    b = spec.benchmark()
    for w in b["workloads"]:
        c = spec.cell(b, w["name"])
        assert c["config"]["name"] == w["config"]
        assert c["end_to_end"] and c["per_layer"]
        for m in c["end_to_end"] + c["per_layer"]:
            assert callable(spec.reader(m["name"]))


def test_a_new_cell_mix_and_metric_are_picked_up_by_name(tmp_path):
    root = tmp_path / "checkout"
    bdir = root / "perfbench"
    for sub in ("configs", "traffic", "limits", "metrics", "reference"):
        shutil.copytree(os.path.join(spec.BENCH_DIR, sub), bdir / sub)
    b = spec.benchmark()
    # the new files: a mix, the cell's limits, a per-layer reader
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", "preextracted.json"))
    (bdir / "traffic" / "uniform.json").write_text(json.dumps(dict(mix, zipf_s=0.0)))
    (bdir / "limits" / "dcn-v2.uniform.json").write_text(json.dumps({"loss_gap": 1.0}))
    (bdir / "metrics" / "steps.uniform.py").write_text(
        "def read(run):\n    return float(run.steps)\n")
    # the new entries
    b["workloads"].append({"name": "dcn-v2.uniform", "config": "dcn-v2",
                           "traffic": "uniform", "chips": 1, "why": "control"})
    b["per_layer"].append({"name": "steps.uniform", "unit": "steps", "better": "higher",
                           "source": "host_clock", "layer": "step",
                           "moves": "examples_per_s.preextracted",
                           "workloads": ["dcn-v2.uniform"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    c = spec.cell(spec.benchmark(str(root)), "dcn-v2.uniform", str(root), str(bdir))
    assert c["traffic"]["zipf_s"] == 0.0
    assert c["config"]["kind"] == "dcnv2"
    assert [m["name"] for m in c["per_layer"]] == ["steps.uniform"]
    run = bench.Run(cfg=c["config"], device_kind="TPU v5 lite",
                    n_devices=1, rows_per_step=8, t_open=0.0,
                    completions=[0.5, 1.0], setup_s=1.0, n_unique=[1, 1])
    assert spec.reader("steps.uniform", str(bdir))(run) == 2.0
    assert spec.reader("examples_per_s.preextracted", str(bdir))(run) == 16.0


def test_a_new_configuration_and_its_reference_are_picked_up_by_name(tmp_path):
    """A configuration file naming a reference module of its own: the cell
    built from it trains, is checked against that module, and ``mfu``
    counts that module's FLOPs."""
    from perfbench.tests import tiny
    root = tmp_path / "checkout"
    bdir = root / "perfbench"
    for sub in ("traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(spec.BENCH_DIR, sub), bdir / sub)
    (bdir / "configs").mkdir()
    (bdir / "reference").mkdir()
    # the new files: the configuration and its own reference model module
    src = open(os.path.join(spec.BENCH_DIR, "reference", "dcnv2.py")).read()
    (bdir / "reference" / "dcn_small.py").write_text(
        src + "\n\ndef train_flops_per_example(cfg):\n    return 1000.0\n")
    cfg = dict(tiny.config("dcnv2"), name="dcn-small",
               reference={"model": "perfbench/reference/dcn_small.py"})
    (bdir / "configs" / "dcn-small.json").write_text(json.dumps(cfg))
    (bdir / "limits" / "dcn-small.preextracted.json").write_text(json.dumps(
        spec.load_json(os.path.join(spec.BENCH_DIR, "limits",
                                    "dlrm-mlperf-rows1of8.preextracted.json"))))
    # the new entries
    b = spec.benchmark()
    b["configs"].append({"name": "dcn-small", "source": "https://arxiv.org/abs/2008.13535",
                         "file": "perfbench/configs/dcn-small.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "dcn-small.preextracted", "config": "dcn-small",
                           "traffic": "preextracted", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    c = spec.cell(spec.benchmark(str(root)), "dcn-small.preextracted", str(root), str(bdir))
    assert c["model"].__file__ == str(bdir / "reference" / "dcn_small.py")
    assert c["fe"] is None
    c["traffic"].update(rows_per_step=64, pool_steps=6, warmup_steps=8)
    out = bench.run_cell(c["workload"]["name"], 2**31 + 9, 0.3, False,
                         t_start=0.0, require_tpu=False, cell=c)
    assert out["correct"], out["checks"]
    run = bench.Run(cfg=c["config"], device_kind="TPU v5 lite", n_devices=1,
                    rows_per_step=8, t_open=0.0, completions=[1.0], setup_s=1.0,
                    n_unique=[1], model=c["model"])
    assert spec.reader("mfu.small", str(bdir))(run) == 1000.0 * 8 / 197e12 * 100
