"""One run of one cell: set-up, warm-up, the measured window, the check.

The window drives the program's ``PipelinedRunner`` with ``ModelFeed``'s
fused, donated step. Its callback keeps one timestamp and the step's
metric handles per step and reads nothing back: the program's own step
already waits for each step to finish. The first three steps are part of
the warm-up and go through the same runner, feed and step as the window;
the harness copies what the check needs around them (the rows they touch,
the dense parameters, the first optimizer state, the staged batches). It
also copies the batch staged for the last warm-up step, once the feed's
ring, the loader and the batch pool have all wrapped, as the window finds
them. After the window the program's state is freed and the plain
reference replays the three steps from the seed; every copied batch is
compared with what the reference makes of the traffic.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import spec as spec_mod
from . import system as system_mod
from . import tracing
from . import traffic

CHECKED_STEPS = 3


class WindowClosed(Exception):
    """Raised by the window's callback once a step lands after the close."""


class CompileMeter:
    """XLA compiles and persistent-cache hits, from JAX's own events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = self.hits = self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def _take(x, idx):
    import jax.numpy as jnp
    return jnp.take(x, idx, axis=0)


class Snapshots:
    """What the check needs from the first steps, copied around them."""

    def __init__(self, sys_, cfg_json: Dict, with_fe: bool, rows: int):
        import jax
        self.sys, self.cfg, self.with_fe = sys_, cfg_json, with_fe
        self.offsets = np.concatenate(
            [[0], np.cumsum(np.asarray(cfg_json["vocab_sizes"], np.int64))[:-1]])
        self.cap = rows * cfg_json["n_sparse"]
        self.take = jax.jit(_take)
        self.seen = np.zeros(0, np.int64)
        self.row_chunks: List = []
        self.losses: List = []
        self.staged: List[Dict] = []
        self.wrapped: Optional[Dict] = None

    def _gather(self, x, ids, cap):
        pad = np.zeros(cap, np.int32)
        pad[:ids.size] = ids
        return np.asarray(self.take(x, pad))[:ids.size]

    def _staged(self, k: int, env) -> Dict:
        """Host copy of the batch staged for step ``k``; for raw logs, with
        the shard it was extracted from."""
        snap = {s: np.asarray(v) for s, v in env.items() if s.startswith("batch_")}
        snap["step"] = k
        if self.with_fe:
            rows = snap["batch_label"].shape[0]
            snap["shard"] = int(env["impressions"]["instance_id"][0]) // rows
        return snap

    def wrap(self, k: int, env) -> None:
        self.wrapped = self._staged(k, env)

    def before(self, k: int, state, env) -> None:
        ids = system_mod.model_ids(env, self.cfg, self.sys.split)
        self.staged.append(self._staged(k, env))
        uniq = np.unique(ids + self.offsets[None, :])
        new = np.setdiff1d(uniq, self.seen)
        self.row_chunks.append((new, self._gather(state["params"]["embed"], new, self.cap)))
        self.seen = np.union1d(self.seen, uniq)
        if k == 0:
            self.first_rows = uniq
            self.dense0 = {n: np.asarray(v) for n, v in state["params"].items()
                           if n != "embed"}

    def after(self, k: int, state, metrics) -> None:
        self.losses.append(metrics["loss"])
        if k == 0:
            self.m1 = {n: np.asarray(v) for n, v in state["opt"]["dense"]["m"].items()}
            self.acc1 = self._gather(state["opt"]["embed_accum"], self.first_rows, self.cap)
        if k == CHECKED_STEPS - 1:
            self.dense3 = {n: np.asarray(v) for n, v in state["params"].items()
                           if n != "embed"}
            self.rows3 = self._gather(state["params"]["embed"], self.seen,
                                      CHECKED_STEPS * self.cap)

    def readings(self) -> Dict:
        """The program's side of the check, on the host."""
        opt = self.cfg["optimizer"]
        grads = {n: float(np.linalg.norm(m / (1 - opt["b1"]))) for n, m in self.m1.items()}
        gsq = np.maximum(self.acc1.astype(np.float64) - opt["embed_accum_init"], 0.0)
        grads["embed"] = float(np.sqrt(gsq.sum()))
        change = {n: float(np.linalg.norm(self.dense3[n] - self.dense0[n]))
                  for n in self.dense0}
        ids = np.concatenate([c[0] for c in self.row_chunks])
        vals = np.concatenate([c[1] for c in self.row_chunks])
        rows0 = vals[np.argsort(ids)]
        change["embed"] = float(np.linalg.norm(
            self.rows3.astype(np.float64) - rows0.astype(np.float64)))
        return {"losses": [float(x) for x in self.losses], "grad_norms": grads,
                "change_norms": change, "rows": self.seen}


class Window:
    """The runner's step callback: warm-up, then the timed window."""

    def __init__(self, step, warmup: int, seconds: float, snaps: Snapshots,
                 meter: CompileMeter, capture: Optional[tracing.Capture]):
        if warmup <= CHECKED_STEPS:
            raise ValueError(f"warmup_steps {warmup} must exceed the "
                             f"{CHECKED_STEPS} checked steps")
        self.step, self.warmup, self.seconds = step, warmup, seconds
        self.snaps, self.meter, self.capture = snaps, meter, capture
        self.k = 0
        self.state = None
        self.t_open: Optional[float] = None
        self.t_close = math.inf
        self.done: List[float] = []
        self.metrics: List[Dict] = []
        self.compiles_at_open = 0

    def __call__(self, state, env):
        k = self.k
        if k < CHECKED_STEPS:
            self.snaps.before(k, state, env)
        elif k == self.warmup - 1:
            self.snaps.wrap(k, env)
        p, o, m = self.step(state["params"], state["opt"], env)
        t = time.perf_counter()
        state = self.state = {"params": p, "opt": o}
        self.k += 1
        if k < CHECKED_STEPS:
            self.snaps.after(k, state, m)
        if self.t_open is None:
            if self.capture is not None and self.k == self.warmup - 3:
                self.capture.start()
            if self.k == self.warmup:
                self.compiles_at_open = self.meter.compiles
                self.t_open = time.perf_counter()
                self.t_close = self.t_open + self.seconds
        elif t > self.t_close:
            raise WindowClosed
        else:
            self.done.append(t)
            self.metrics.append(m)
        return state


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cfg: Dict
    device_kind: str
    n_devices: int
    rows_per_step: int
    t_open: float
    completions: List[float]
    setup_s: float
    n_unique: List[int]
    model: Any = None             # the configuration's reference model module
    trace: Optional[Dict] = None
    spans: Optional[List] = None
    window_ns: Optional[tuple] = None

    @property
    def steps(self) -> int:
        return len(self.completions)

    @property
    def window_s(self) -> float:
        return self.completions[-1] - self.t_open


def _devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"perfbench: no TPU (JAX found {devs[0].platform}); "
                         f"nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"perfbench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}; nothing was run")
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} count={len(devs)}",
          file=sys.stderr)
    return devs[:chips]


def _gap(a: float, b: float, scale: float) -> float:
    return abs(a - b) / max(scale, 1e-30)


def compare(prog: Dict, ref: Dict, worst: Optional[Dict] = None) -> Dict[str, float]:
    """The numbers compared: the worst step's relative loss gap; the worst
    leaf's gap of first-gradient norms and of change norms over the checked
    steps, each against the larger of that leaf's reference norm and the
    median leaf's. Leaves whose reference gradient is under a thousandth of
    the median leaf's move by round-off alone and are left out of the
    change. ``worst``, if given, is filled with the leaf or step behind each."""
    loss = [_gap(p, r, abs(r)) for p, r in zip(prog["losses"], ref["losses"])]
    rg = ref["grad_norms"]
    med_g = statistics.median(rg.values())
    grad = {k: _gap(prog["grad_norms"][k], v, max(v, med_g)) for k, v in rg.items()}
    kept = [k for k, v in rg.items() if v >= 1e-3 * med_g]
    rc = ref["change_norms"]
    med_c = statistics.median(rc[k] for k in kept)
    change = {k: _gap(prog["change_norms"][k], rc[k], max(rc[k], med_c)) for k in kept}
    if worst is not None:
        worst.update(loss_step=int(np.argmax(loss)) + 1,
                     grad_leaf=max(grad, key=grad.get),
                     change_leaf=max(change, key=change.get))
    return {"loss_gap": max(loss), "grad_gap": max(grad.values()),
            "change_gap": max(change.values())}


def fe_compare(snaps: List[Dict], refs: List[Dict]) -> Dict[str, float]:
    """Ids, labels and bag entries that differ; widest dense gap."""
    bad, dense = 0, 0.0
    for s, r in zip(snaps, refs):
        fields = np.stack([s[f"batch_field_{f:02d}"] for f in range(r["sparse"].shape[1])],
                          axis=1)
        bad += int(np.sum(fields != r["sparse"]))
        bad += int(np.sum(s["batch_label"] != r["label"]))
        bad += int(np.sum(s["batch_seq_ids"] != r["bag"]))
        bad += int(np.sum(s["batch_seq_mask"] != r["bag_mask"]))
        dense = max(dense, float(np.max(np.abs(s["batch_dense"] - r["dense"]))))
    return {"fe_mismatches": float(bad), "fe_dense_gap": dense}


def feed_compare(snaps: List[Dict], gens: List[Dict]) -> Dict[str, float]:
    """Entries of the staged batches that differ from the generated ones."""
    bad = 0
    for s, g in zip(snaps, gens):
        if "batch_sparse" in s:
            n = sum(1 for k in g if k.startswith("batch_field_"))
            g = dict(g, batch_sparse=np.stack([g[f"batch_field_{f:02d}"]
                                               for f in range(n)], axis=1))
        for k, v in s.items():
            if k.startswith("batch_"):
                bad += int(np.sum(v != g[k]))
    return {"feed_mismatches": float(bad)}


def reference_batches(cell: Dict, seed: int, picks: List[int]) -> tuple:
    """The checked steps' inputs as the reference makes them from the
    traffic: pool batches, or for raw logs the shards ``picks`` through the
    reference feature extraction. Also returns what the program's staged
    batches are compared with: the reference's features, or the generated
    batches."""
    cfg, mix = cell["config"], cell["traffic"]
    vocab = np.asarray(cfg["vocab_sizes"], np.int64)
    batches, fe, gens = [], [], []
    for k in picks:
        if mix["kind"] == "raw_log":
            r = cell["fe"].extract(traffic.raw_views(mix, seed, k))
            fe.append(r)
            n = r["sparse"].shape[1]
            ids = r["sparse"][:, np.arange(len(vocab)) % n].astype(np.int64) % vocab
            reps = -(-cfg["n_dense"] // r["dense"].shape[1])
            batches.append({"ids": ids, "label": r["label"],
                            "dense": np.tile(r["dense"], (1, reps))[:, :cfg["n_dense"]]})
        else:
            b = traffic.preextracted_batch(mix, cfg["vocab_sizes"], seed, k)
            gens.append(b)
            ids = np.stack([b[f"batch_field_{f:02d}"] for f in range(len(vocab))],
                           axis=1).astype(np.int64) % vocab
            batches.append({"ids": ids, "dense": b["batch_dense"],
                            "label": b["batch_label"]})
    return batches, fe or gens


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             cell: Optional[Dict] = None) -> Dict:
    import jax

    cell = cell or spec_mod.cell(spec_mod.benchmark(), name)
    cfg, mix, w = cell["config"], cell["traffic"], cell["workload"]
    devs = _devices(w["chips"], require_tpu)
    meter = CompileMeter()
    tmp = tempfile.mkdtemp(prefix="perfbench_")
    try:
        return _run(name, cell, cfg, mix, devs, meter, seed, seconds, trace,
                    t_start, tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _run(name, cell, cfg, mix, devs, meter, seed, seconds, trace, t_start, tmp):
    import jax
    from repro.core import PipelinedRunner

    tracer = None
    if trace:
        from repro.obs.trace import Tracer, set_tracer
        tracer = Tracer(enabled=True)
        set_tracer(tracer)
    sys_ = system_mod.build(cfg, mix, seed, tmp)
    snaps = Snapshots(sys_, cfg, mix["kind"] == "raw_log", mix["rows_per_step"])
    capture = tracing.Capture(os.path.join(tmp, "profile")) if trace else None
    win = Window(sys_.step, mix["warmup_steps"], seconds, snaps, meter, capture)
    runner = PipelinedRunner(sys_.layers, win, prefetch=mix["prefetch"],
                             device_feed=sys_.feeder)
    state, sys_.state = sys_.state, None
    try:
        runner.run(state, sys_.source())
        raise SystemExit("perfbench: the batch source ran dry before the "
                         "window closed")
    except WindowClosed:
        pass
    finally:
        sys_.close()
    del state
    t_end = time.perf_counter()
    if capture is not None:
        capture.stop()
    compiles_in_window = meter.compiles - win.compiles_at_open
    print(f"compile: {meter.seconds:.3f} s in {meter.compiles} XLA compiles "
          f"(persistent cache hits={meter.hits} misses={meter.misses}); "
          f"{compiles_in_window} inside the window", file=sys.stderr)
    n_unique = [int(m["unique"]) for m in win.metrics]
    print(f"window: {len(win.done)} steps of {mix['rows_per_step']} rows in "
          f"{win.done[-1] - win.t_open:.3f} s; unique ids per step: mean "
          f"{np.mean(n_unique):.1f} of {mix['rows_per_step'] * cfg['n_sparse']} "
          f"(capacity {sys_.cfg.dedup_capacity})", file=sys.stderr)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    prog = snaps.readings()
    run = Run(cfg=cfg, device_kind=devs[0].device_kind,
              n_devices=len(devs), rows_per_step=mix["rows_per_step"],
              t_open=win.t_open, completions=win.done,
              setup_s=win.t_open - t_start, n_unique=n_unique,
              model=cell["model"])
    if capture is not None:
        lo, hi = int(win.t_open * 1e9), int(win.done[-1] * 1e9)
        spans = tracing.program_spans(tracer)
        red = tracing.reduce(capture.device_ops(), spans, capture.gc_pauses, (lo, hi))
        run.trace, run.spans, run.window_ns = red, spans, (lo, hi)
    # free the program's state before the reference runs
    win_open = win.t_open
    win.state = win.metrics = snaps.losses = None
    sys_ = win = runner = None
    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    print(f"device bytes live before the reference: {live}", file=sys.stderr)

    staged = snaps.staged + [snaps.wrapped]
    if mix["kind"] == "raw_log":
        picks = [f["shard"] for f in staged]
    else:
        picks = [f["step"] % mix["pool_steps"] for f in staged]
    print(f"staged batches compared: steps {[f['step'] for f in staged]} "
          f"({'shards' if mix['kind'] == 'raw_log' else 'pool entries'} {picks})",
          file=sys.stderr)
    t_ref = time.perf_counter()
    batches, expected = reference_batches(cell, seed, picks)
    from perfbench.reference import recsys as ref_model
    ref = ref_model.Reference(cfg, cell["model"], devs).train(
        system_mod.program_key(seed), batches[:CHECKED_STEPS])
    t_done = time.perf_counter()
    print(f"timing: setup {win_open - t_start:.1f} s, window {t_end - win_open:.1f} s, "
          f"after the window {t_ref - t_end:.1f} s, reference {t_done - t_ref:.1f} s",
          file=sys.stderr)
    worst: Dict = {}
    readings = compare(prog, ref, worst)
    print(f"worst: {worst}; losses {prog['losses']} (reference {ref['losses']})",
          file=sys.stderr)
    if mix["kind"] == "raw_log":
        readings.update(fe_compare(staged, expected))
    else:
        readings.update(feed_compare(staged, expected))
    readings["window_compiles"] = float(compiles_in_window)
    limits = cell["limits"]
    for k in sorted(set(readings) - set(limits)):
        print(f"reading {k}: {readings[k]!r} (not compared)", file=sys.stderr)
    checks = {k: {"value": readings[k], "limit": lim} for k, lim in limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    for m in (cell["per_layer"] if run.trace is not None else cell["end_to_end"]):
        v = spec_mod.reader(m["name"], cell["bench_dir"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": run.steps,
           "failed": 0 if correct else run.steps, "metrics": metrics,
           "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    return out
