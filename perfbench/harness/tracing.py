"""Device trace capture, and its reduction to busy time, ops and idle gaps.

A traced run records three things on one clock (``time.perf_counter_ns``):

* the device operations, from JAX's profiler (the ``XLA Ops`` line of each
  TPU plane), shifted onto the host clock by sync markers: annotations
  whose host time is taken just before and after each is emitted;
* the program's own spans (``repro.obs`` tracer), already on that clock;
* the collector's pauses (``gc.callbacks``), so that an idle gap during a
  collection is named as such.

:func:`reduce` turns these into the numbers the per-layer readers take. It
works on plain lists, so it is tested on a small recorded trace.
"""

from __future__ import annotations

import gc
import glob
import os
import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"psum|allreduce|allgather", re.IGNORECASE)


class Capture:
    """Profiler session plus collector pauses, for one traced window."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.syncs: List[Tuple[str, int]] = []
        self.gc_pauses: List[Interval] = []
        self._gc_t0: Optional[int] = None

    def _on_gc(self, phase, info):
        now = time.perf_counter_ns()
        if phase == "start":
            self._gc_t0 = now
        elif self._gc_t0 is not None:
            self.gc_pauses.append((self._gc_t0, now))
            self._gc_t0 = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        gc.callbacks.append(self._on_gc)
        self.sync()

    def sync(self) -> None:
        import jax
        name = f"perfbench_sync_{len(self.syncs)}"
        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(name):
            pass
        self.syncs.append((name, (t0 + time.perf_counter_ns()) // 2))

    def stop(self) -> None:
        import jax
        self.sync()
        gc.callbacks.remove(self._on_gc)
        jax.profiler.stop_trace()

    def device_ops(self) -> Dict[int, List[Tuple[str, int, int]]]:
        """``{device: [(op name, start, end)]}`` on the host clock."""
        from jax.profiler import ProfileData
        paths = sorted(glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under {self.log_dir}")
        pd = ProfileData.from_file(paths[-1])
        marks = {n: t for n, t in self.syncs}
        offsets, ops = [], {}
        for plane in pd.planes:
            tpu = re.match(r"/device:TPU:(\d+)$", plane.name)
            for line in plane.lines:
                if tpu and line.name == "XLA Ops":
                    ops[int(tpu.group(1))] = [
                        (op_label(ev.name), int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
                elif plane.name.startswith("/host:") and line.name.startswith("python"):
                    for ev in line.events:
                        if ev.name in marks:
                            mid = ev.start_ns + ev.duration_ns / 2
                            offsets.append(marks[ev.name] - mid)
        if not offsets:
            raise RuntimeError("no sync marker found in the host trace")
        offsets.sort()
        shift = int(offsets[len(offsets) // 2])
        return {d: [(n, a + shift, b + shift) for n, a, b in evs]
                for d, evs in sorted(ops.items())}


def op_label(hlo: str) -> str:
    """``name type`` of an HLO instruction's text, layouts dropped:
    ``%fusion.9 = f32[8,128]{1,0} fusion(...)`` -> ``fusion.9 f32[8,128]``."""
    m = re.match(r"%?(\S+) = (\S+)", hlo)
    if not m:
        return hlo
    return f"{m.group(1)} {re.sub(r'{[^}]*}', '', m.group(2))}"


def program_spans(tracer) -> List[Tuple[str, str, int, int]]:
    """``[(span, thread, start, end)]`` from a ``repro.obs`` tracer, on the
    host clock (the tracer exports microseconds from its own epoch, which a
    reference span with known stamps recovers)."""
    ref = time.perf_counter_ns()
    tracer.complete("perfbench.clock", ref, ref)
    trace = tracer.to_dict()["traceEvents"]
    threads = {e["tid"]: e["args"]["name"] for e in trace
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    epoch = None
    for e in reversed(trace):
        if e.get("name") == "perfbench.clock" and e["ph"] == "B":
            epoch = ref - int(round(e["ts"] * 1e3))
            break
    stacks: Dict[int, List[Tuple[str, int]]] = {}
    out = []
    for e in trace:
        if e.get("ph") == "B":
            stacks.setdefault(e["tid"], []).append((e["name"], int(round(e["ts"] * 1e3))))
        elif e.get("ph") == "E" and stacks.get(e["tid"]):
            name, t0 = stacks[e["tid"]].pop()
            out.append((name, threads.get(e["tid"], str(e["tid"])),
                        t0 + epoch, int(round(e["ts"] * 1e3)) + epoch))
    return [s for s in out if s[0] != "perfbench.clock"]


# ------------------------------------------------------------ reduction
def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def overlap(a: Interval, spans: Sequence[Interval]) -> int:
    return sum(max(0, min(a[1], b) - max(a[0], s)) for s, b in spans)


def reduce(ops: Dict[int, List[Tuple[str, int, int]]],
           spans: Sequence[Tuple[str, str, int, int]],
           gc_pauses: Sequence[Interval], window: Interval,
           main_thread: str = "MainThread") -> Dict:
    """Window-clipped device busy time, op totals, collective time and the
    longest idle gaps of the first device, each named by the innermost
    span the train loop's thread was in at the gap's middle, then those of
    the other threads after ``|`` (``gc:`` in front when a collection ran
    during the gap)."""
    lo, hi = window
    n = max(len(ops), 1)
    busy = 0
    per_op: Dict[str, float] = {}
    coll = 0
    for dev, evs in ops.items():
        iv = clip([(a, b) for _, a, b in evs], lo, hi)
        busy += sum(b - a for a, b in merge(iv))
        coll += sum(b - a for a, b in merge(clip(
            [(a, b) for name, a, b in evs if COLLECTIVE.search(name)], lo, hi)))
        for name, a, b in evs:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                per_op[name] = per_op.get(name, 0) + d
    gaps = []
    if ops:
        first = merge(clip([(a, b) for _, a, b in ops[min(ops)]], lo, hi))
        edges = [lo] + [x for iv in first for x in iv] + [hi]
        idle = sorted(((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a),
                      reverse=True)[:10]
        for d, a, b in idle:
            mid = (a + b) // 2
            inner: Dict[str, Tuple[int, str]] = {}
            for s, thread, t0, t1 in spans:
                if t0 <= mid < t1 and t0 >= inner.get(thread, (-1, ""))[0]:
                    inner[thread] = (t0, s)
            main = inner.pop(main_thread, (0, "none"))[1]
            others = sorted({s for _, s in inner.values()})
            name = "|".join([main] + others)
            if overlap((a, b), gc_pauses):
                name = "gc:" + name
            gaps.append((name, d / 1e9))
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy / n / 1e9, "window_s": (hi - lo) / 1e9,
            "collective_s": coll / n / 1e9,
            "device_ops": [[k, v / n / 1e9] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps],
            "ops_by_name": {k: v / n / 1e9 for k, v in per_op.items()}}


def span_seconds(spans, name: str, window: Interval) -> float:
    """Seconds of span ``name`` inside the window, summed over threads."""
    lo, hi = window
    return sum(max(0, min(b, hi) - max(a, lo)) for s, _, a, b in spans
               if s == name) / 1e9
