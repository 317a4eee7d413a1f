"""Benchmark harness: one module per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV (one row per measurement).

  PYTHONPATH=src python -m benchmarks.run             # all
  PYTHONPATH=src python -m benchmarks.run --only e2e  # substring filter
  PYTHONPATH=src python -m benchmarks.run --list      # suite names only
  PYTHONPATH=src python -m benchmarks.run --only pipeline \
      --json BENCH_pipeline.json                      # machine-readable dump
  PYTHONPATH=src python -m benchmarks.run --only trainfeed \
      --compare BENCH_trainfeed.json                  # regression gate

``--json PATH`` additionally writes every selected suite's rows (plus
failure markers) as JSON — the committed ``BENCH_*.json`` baselines CI
and future PRs compare against.

``--compare BASELINE`` loads a committed baseline, prints the per-row
delta for every matching row, and exits nonzero if any **gated** row
regressed by more than 25%. Rows opt into gating with ``gate: True``; a
gated row is compared on its ``metric`` value when it carries one
(deterministic, machine-independent counts/ratios — dispatches per step,
dedup unique ratio) and on ``us_per_call`` otherwise, lower always
better. CI's perf-smoke job runs the trainfeed comparison.

With ``--json`` the comparison is also machine-readable: the report
gains a ``compare`` object with one entry per row (``old``/``new``/
``delta_pct``/``gated``/``verdict``) and a top-level ``verdict``, so CI
annotations and dashboards read the gate outcome without parsing stderr.

Rows may carry ``flops`` / ``hbm_bytes`` (per-call, from loop-aware HLO
analysis — see ``repro.launch.hlo_stats``); these surface as the
``gflops_per_call`` / ``hbm_mib_per_call`` CSV columns and ride along in
the JSON payload for roofline-style comparisons across PRs.

Exit-code contract (what CI keys off):

* **0** — every selected suite ran; no gated row regressed.
* **1** — at least one suite raised (broken benchmark or library code).
  Takes precedence over 2: a failed suite can hide a regression.
* **2** — all suites ran, but a gated row regressed beyond the margin
  (or a gated baseline row went missing from this run).
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
import traceback

REGRESSION_MARGIN = 1.25  # gated rows fail beyond +25%


def _gate_value(row) -> float:
    """The comparison scalar of a row: its deterministic metric when it
    has one, else the measured time (lower is better for both)."""
    if row.get("metric") is not None:
        return float(row["metric"])
    return float(row["us_per_call"])


def compare_to_baseline(report, baseline_path: str) -> dict:
    """Per-row deltas vs a committed baseline, as a structured payload.

    Prints the human-readable comparison to stderr (unchanged format) and
    returns the machine-readable ``compare`` object ``main`` attaches to
    the ``--json`` report: ``{"baseline", "margin", "rows": [{"name",
    "old", "new", "delta_pct", "gated", "verdict"}], "regressions",
    "verdict"}``. Row verdicts: ``ok`` / ``regressed`` (gated, beyond the
    margin) / ``new`` (no baseline row) / ``missing`` (gated baseline row
    absent from this run — a gate failure, otherwise deleting a row would
    silently disable its check). Top-level ``verdict`` is ``ok`` or
    ``regressed``.
    """
    with open(baseline_path) as f:
        base = json.load(f)
    base_rows = {r["name"]: r
                 for s in base.get("suites", {}).values()
                 for r in s.get("rows", [])}
    regressions = []
    cmp_rows = []
    print(f"--- compare vs {baseline_path} " + "-" * 30, file=sys.stderr)
    seen = {r["name"] for s in report["suites"].values()
            for r in s.get("rows", [])}
    for suite_name, s in base.get("suites", {}).items():
        if suite_name not in report["suites"]:
            continue  # baseline covers suites the current selection skipped
        for r in s.get("rows", []):
            if r.get("gate") and r["name"] not in seen:
                print(f"{r['name']}: gated baseline row MISSING from this "
                      f"run", file=sys.stderr)
                regressions.append(f"{r['name']} (missing)")
                cmp_rows.append({"name": r["name"], "old": _gate_value(r),
                                 "new": None, "delta_pct": None,
                                 "gated": True, "verdict": "missing"})
    for suite in report["suites"].values():
        for row in suite.get("rows", []):
            old = base_rows.get(row["name"])
            gated = bool(row.get("gate"))
            if old is None:
                print(f"{row['name']}: new row (no baseline)", file=sys.stderr)
                cmp_rows.append({"name": row["name"], "old": None,
                                 "new": _gate_value(row), "delta_pct": None,
                                 "gated": gated, "verdict": "new"})
                continue
            new_v, old_v = _gate_value(row), _gate_value(old)
            if old_v <= 0:
                delta = "n/a" if new_v <= 0 else "+inf"
                delta_pct = None
                bad = gated and new_v > 0
            else:
                ratio = new_v / old_v
                delta_pct = round((ratio - 1) * 100, 1)
                delta = f"{delta_pct:+.1f}%"
                bad = gated and ratio > REGRESSION_MARGIN
            mark = " GATE-REGRESSED" if bad else (" [gated]" if gated else "")
            print(f"{row['name']}: {old_v:g} -> {new_v:g} ({delta}){mark}",
                  file=sys.stderr)
            cmp_rows.append({"name": row["name"], "old": old_v, "new": new_v,
                             "delta_pct": delta_pct, "gated": gated,
                             "verdict": "regressed" if bad else "ok"})
            if bad:
                regressions.append(row["name"])
    if regressions:
        print(f"gated rows regressed >{(REGRESSION_MARGIN - 1) * 100:.0f}%: "
              f"{', '.join(regressions)}", file=sys.stderr)
    return {"baseline": baseline_path, "margin": REGRESSION_MARGIN,
            "rows": cmp_rows, "regressions": regressions,
            "verdict": "regressed" if regressions else "ok"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter on suite name")
    ap.add_argument("--list", action="store_true",
                    help="print suite names and exit (no benchmarks run)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write the selected suites' rows to PATH "
                         "(BENCH_<suite>.json baseline format)")
    ap.add_argument("--compare", default="", metavar="BASELINE",
                    help="compare the selected suites' rows against a "
                         "committed BENCH_*.json; print per-row deltas and "
                         "exit nonzero if a gated row regressed >25%")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import bench_devicefeed, bench_end_to_end, \
        bench_feature_extraction, bench_hierarchy, bench_ingest, \
        bench_launch_overhead, bench_mesh, bench_pipeline, \
        bench_trainfeed, roofline

    suites = [
        ("launch_overhead(TableI)", bench_launch_overhead.run),
        ("feature_extraction(Fig6)", bench_feature_extraction.run),
        ("end_to_end(TableII)", bench_end_to_end.run),
        ("ingest(shard streaming)", bench_ingest.run),
        ("devicefeed(H2D overlap)", bench_devicefeed.run),
        ("pipeline(hot path)", bench_pipeline.run),
        ("trainfeed(stage->train)", bench_trainfeed.run),
        ("hierarchy(PS tiers)", bench_hierarchy.run),
        ("mesh(scale-out)", bench_mesh.run),
        ("roofline", roofline.run),
    ]
    if args.list:
        for name, _ in suites:
            print(name)
        return
    print("name,us_per_call,derived,gflops_per_call,hbm_mib_per_call")
    failed = []
    report = {"suites": {}, "python": platform.python_version(),
              "machine": platform.machine()}
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        try:
            rows = list(fn())
            for row in rows:
                derived = str(row.get("derived", "")).replace(",", ";")
                gflops = (f"{row['flops'] / 1e9:.3f}"
                          if row.get("flops") is not None else "")
                hbm = (f"{row['hbm_bytes'] / 2**20:.1f}"
                       if row.get("hbm_bytes") is not None else "")
                print(f"{row['name']},{row['us_per_call']:.2f},{derived},"
                      f"{gflops},{hbm}")
            out_rows = []
            for r in rows:
                out = {"name": r["name"],
                       "us_per_call": round(float(r["us_per_call"]), 2),
                       "derived": str(r.get("derived", ""))}
                if r.get("gate"):
                    out["gate"] = True
                if r.get("metric") is not None:
                    out["metric"] = float(r["metric"])
                for k in ("flops", "hbm_bytes"):
                    if r.get(k) is not None:
                        out[k] = float(r[k])
                out_rows.append(out)
            report["suites"][name] = {"rows": out_rows}
        except Exception:
            failed.append(name)
            traceback.print_exc()
            print(f"{name},NaN,SUITE FAILED,,")
            report["suites"][name] = {"failed": True}
    compare = (compare_to_baseline(report, args.compare)
               if args.compare else None)
    if compare is not None:
        report["compare"] = compare
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"wrote {args.json}", file=sys.stderr)
    if failed:
        print(f"FAILED suites: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)
    if compare is not None and compare["regressions"]:
        sys.exit(2)


if __name__ == "__main__":
    main()
