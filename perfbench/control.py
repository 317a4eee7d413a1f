"""Readings of the correctness check's control and planted faults.

    python3 perfbench/control.py --workload <name> --seeds 1,2,3 [--only control]

For each seed, the plain reference stands in the program's place and is
compared with the float32 reference by the check's own numbers:

* ``control``: the reference in bfloat16 (weights, table, activations and
  gradients; matrix products at the default precision), the step below the
  configuration's float32 that a later change might take. The model casts
  the feature block it is given, which FE leaves in float32; its changes
  are measured from its state as held in bfloat16;
* ``control_fe`` (raw-log cells): FE's dense block rounded to bfloat16,
  read by the check's ``fe_dense_gap``;
* ``unchanged``: every step's state returned unchanged;
* ``half_batch``: the second half of every batch left out, the mean taken
  over the rest;
* ``token``: every row's first sparse id altered where it is produced;
* ``local_only`` (mesh cells): each chip's quarter of the batch alone, as
  when the exchange between chips is left out.

The benchmark's own runs never run this; it sets the upper readings the limits
in ``perfbench/limits`` sit below. It prints one JSON line per seed.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH, ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _rows(batches, part):
    out = []
    for b in batches:
        n = b["label"].shape[0]
        lo, hi = part(n)
        out.append({k: v[lo:hi] for k, v in b.items()})
    return out


def readings(cell, seed, devices, only=None):
    import jax.numpy as jnp
    import numpy as np

    from perfbench.harness import bench, system
    from perfbench.reference import recsys

    cfg, mix = cell["config"], cell["traffic"]
    picks = list(range(bench.CHECKED_STEPS))
    batches, _ = bench.reference_batches(cell, seed, picks)
    key = system.program_key(seed)
    ref_model = recsys.Reference(cfg, cell["model"], devices)
    ref = ref_model.train(key, batches)
    out = {}
    worst = {}
    out["control"] = bench.compare(
        ref_model.train(key, batches, dtype=jnp.bfloat16, precision="default"),
        ref, worst)
    out["control_worst"] = worst
    if mix["kind"] == "raw_log":
        out["control_fe"] = {"fe_dense_gap": max(
            float(np.max(np.abs(np.asarray(jnp.asarray(b["dense"], jnp.bfloat16),
                                           np.float32) - b["dense"])))
            for b in batches)}
    if only == "control":
        return out
    out["unchanged"] = bench.compare(
        ref_model.train(key, batches, frozen=True), ref)
    out["half_batch"] = bench.compare(ref_model.train(
        key, _rows(batches, lambda n: (0, n // 2))), ref)
    vocab = np.asarray(cfg["vocab_sizes"], np.int64)
    altered = []
    for b in batches:
        ids = b["ids"].copy()
        ids[:, 0] = (ids[:, 0] + 1) % vocab[0]
        altered.append(dict(b, ids=ids))
    out["token"] = bench.compare(ref_model.train(key, altered), ref)
    if mix.get("mesh"):
        n_dev = int(np.prod(mix["mesh"]))
        out["local_only"] = bench.compare(ref_model.train(
            key, _rows(batches, lambda n: (0, n // n_dev))), ref)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--only", choices=("control",), default=None,
                    help="read the control alone, not the planted faults")
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from perfbench.harness import bench, spec
    cell = spec.cell(spec.benchmark(), args.workload)
    devs = bench._devices(cell["workload"]["chips"], True)
    for s in args.seeds.split(","):
        print(json.dumps({"workload": args.workload, "seed": int(s),
                          **readings(cell, int(s), devs, args.only)}), flush=True)


if __name__ == "__main__":
    main()
