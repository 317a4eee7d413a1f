"""Run one benchmark cell once and print its result as the last line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration, traffic mix, limits, metrics) is looked up by name
from ``BENCHMARK.json`` and the files under ``perfbench/``. The run fails,
printing no result, when JAX finds no TPU or fewer chips than the cell
needs. JAX's persistent compilation cache is kept in ``perfbench/.jax_cache``
inside the checkout, so only a checkout's first run of a cell compiles.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH, ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from perfbench.harness.bench import run_cell

    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
