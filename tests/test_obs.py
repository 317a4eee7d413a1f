"""repro.obs: tracer semantics, trace-export structure, metrics registry,
and the end-to-end four-track pipeline timeline.

The contracts under test:

* **zero cost when disabled** — a disabled tracer answers ``span()`` with
  the shared ``NULL_SPAN`` singleton (identity-asserted: no per-call
  allocation beyond the flag check) and records nothing;
* **valid Chrome trace JSON** — exported traces load, timestamps are
  monotone per track, and every B has a matching same-name E (including
  spans a thread abandoned mid-flight: end-capped at export);
* **spans survive exceptions** — work recorded before a pipeline failure
  is present in the export, and the span open at unwind is closed with an
  ``error`` tag;
* **bit-effect-free** — a pipeline run with tracing enabled produces
  bit-identical outputs to the same run with tracing disabled;
* **the e2e timeline** — a streaming arena run produces >= 4 distinct
  tracks (shard readers, FE worker, H2D feeder, train loop) whose FE and
  train spans overlap in wall-clock, and ``PipelineStats.
  overlap_fraction > 0`` agrees.
"""

import dataclasses
import json
import threading

import numpy as np
import pytest
from conftest import recording_step

from repro.obs import (
    NULL_SPAN,
    MetricsRegistry,
    TraceError,
    Tracer,
    harvest,
    overlap_seconds,
    pipeline_rollup,
    set_tracer,
    span_intervals,
    validate_trace,
)


@pytest.fixture
def traced():
    """Install a fresh enabled tracer; restore the previous one after."""
    tracer = Tracer(enabled=True)
    prev = set_tracer(tracer)
    yield tracer
    set_tracer(prev)


# ----------------------------------------------------------------- tracer
def test_disabled_tracer_is_noop_singleton():
    t = Tracer(enabled=False)
    # identity, not just equality: the disabled path allocates nothing
    assert t.span("a") is NULL_SPAN
    assert t.span("b", batch=1) is NULL_SPAN
    with t.span("a"):
        pass
    t.instant("x")
    t.counter("q", 3)
    t.complete("c", 0, 10)
    assert t.n_events == 0
    assert t.track_names() == {}


def test_span_records_matched_events_and_validates():
    t = Tracer(enabled=True)
    with t.span("outer", batch=0):
        with t.span("inner"):
            pass
        t.instant("mark", kind="test")
    t.counter("depth", 2)
    summary = validate_trace(t.to_dict())
    assert summary["n_spans"] == 2
    assert summary["n_instants"] == 1
    assert summary["n_counters"] == 1
    assert summary["span_names"] == ["inner", "outer"]
    assert list(summary["tracks"].values()) == [threading.current_thread().name]


def test_spans_survive_exceptions():
    t = Tracer(enabled=True)
    with t.span("before"):
        pass
    with pytest.raises(RuntimeError):
        with t.span("doomed", batch=3):
            with t.span("inner"):
                raise RuntimeError("boom")
    trace = t.to_dict()
    summary = validate_trace(trace)  # every B matched despite the raise
    assert summary["span_names"] == ["before", "doomed", "inner"]
    closes = [ev for ev in trace["traceEvents"]
              if ev.get("ph") == "E" and ev.get("args", {}).get("error")]
    assert {ev["name"] for ev in closes} == {"doomed", "inner"}
    assert all(ev["args"]["error"] == "RuntimeError" for ev in closes)


def test_abandoned_span_is_end_capped_at_export():
    t = Tracer(enabled=True)

    def worker():
        t.span("left.open").__enter__()  # thread dies without __exit__

    th = threading.Thread(target=worker, name="dying-thread")
    th.start()
    th.join()
    trace = t.to_dict()
    validate_trace(trace)  # would raise on an unmatched B
    caps = [ev for ev in trace["traceEvents"]
            if ev.get("args", {}).get("capped")]
    assert len(caps) == 1 and caps[0]["name"] == "left.open"


def test_complete_records_retroactive_span():
    t = Tracer(enabled=True)
    t0 = t.now_ns()
    t1 = t0 + 5_000_000  # 5 ms
    t.complete("stall", t0, t1, pending=2)
    ivals = span_intervals(t.to_dict(), "stall")
    assert len(ivals) == 1
    start, end, name, _ = ivals[0]
    assert name == "stall"
    assert end - start == pytest.approx(5_000.0)  # us


def test_complete_on_virtual_track():
    """complete_on places retroactive spans on a named virtual track (the
    ``gc`` track of collector pauses) without violating the per-track
    timestamp monotonicity the validator enforces."""
    t = Tracer(enabled=True)
    with t.span("train.step"):
        pass
    t0 = t.now_ns()
    t.complete_on("gc", "gc.collect",
                  t0 - 4_000_000, t0 - 2_000_000, generation=2)
    t.complete_on("gc", "gc.collect",
                  t0 - 2_000_000, t0, generation=2)
    d = t.to_dict()
    names = set(t.track_names().values())
    assert "gc" in names
    ivals = span_intervals(d, "gc.")
    assert len(ivals) == 2
    assert ivals[0][1] - ivals[0][0] == pytest.approx(2_000.0)  # us
    args = [e["args"] for e in d["traceEvents"]
            if e.get("ph") == "B" and e["name"] == "gc.collect"]
    assert args and all(a["generation"] == 2 for a in args)
    # retroactive spans starting before the last wall-clock event on
    # ANOTHER track must not trip the validator's monotonicity check
    validate_trace(d)


def test_collections_recorded_on_gc_track_while_installed():
    """An installed, enabled tracer records each collection as a
    ``gc.collect`` span on the ``gc`` track; replacing it removes its
    callback, and a disabled tracer installs none."""
    import gc

    t = Tracer(enabled=True)
    n_callbacks = len(gc.callbacks)
    prev = set_tracer(t)
    try:
        assert len(gc.callbacks) == n_callbacks + 1
        with t.span("train.step"):
            gc.collect()
        d = t.to_dict()
    finally:
        set_tracer(prev)
    assert len(gc.callbacks) == n_callbacks
    validate_trace(d)
    gc_tid = {tid for tid, name in t.track_names().items() if name == "gc"}
    spans = [e for e in d["traceEvents"]
             if e.get("ph") == "B" and e["name"] == "gc.collect"]
    assert spans and {e["tid"] for e in spans} == gc_tid
    assert any(e["args"]["generation"] == 2 for e in spans)
    assert all(e["args"]["collected"] >= 0 for e in spans)
    step = span_intervals(d, "train.step")[0]
    assert any(step[0] <= e["ts"] <= step[1] for e in spans)
    n = len(spans)
    gc.collect()  # no longer installed: not recorded
    assert len(span_intervals(t.to_dict(), "gc.collect")) == n

    off = Tracer(enabled=False)
    prev = set_tracer(off)
    try:
        assert len(gc.callbacks) == n_callbacks
    finally:
        set_tracer(prev)


def test_gc_spans_from_many_threads_lose_nothing():
    """Collections run on whichever thread allocates, also while that
    thread holds the tracer's lock: every one is recorded once, with no
    deadlock, and the trace stays well formed."""
    import gc
    import sys

    t = Tracer(enabled=True)
    stops = []

    def count(phase, info):
        if phase == "stop":
            stops.append(info["generation"])

    def churn(i):
        for j in range(300):
            with t.span("work", i=i):
                a, b = [], []
                a.append(b)
                b.append(a)  # a cycle: only the collector frees it
            t.instant("mark")

    interval = sys.getswitchinterval()
    prev = set_tracer(t)
    gc.callbacks.append(count)
    threshold = gc.get_threshold()
    gc.set_threshold(50)
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=churn, args=(i,), name=f"churn-{i}")
                   for i in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(interval)
        gc.set_threshold(*threshold)
        gc.callbacks.remove(count)
        set_tracer(prev)
    d = t.to_dict()
    validate_trace(d)
    assert len(span_intervals(d, "gc.collect")) == len(stops) > 0


def test_clear_removes_the_gc_callback(traced):
    import gc

    assert traced._on_gc in gc.callbacks
    traced.clear()
    assert traced._on_gc not in gc.callbacks
    gc.collect()
    assert traced.n_events == 0


def test_export_states_its_clock_and_annotations():
    t = Tracer(enabled=True)
    t0 = t.now_ns()
    t.complete("stall", t0, t0 + 2_000_000)
    t.annotate("step.phases", {"fusion.1": "dedup"})
    md = t.to_dict()["metadata"]
    assert md["clock"] == "perf_counter_ns"
    assert md["step.phases"] == {"fusion.1": "dedup"}
    # a span's exported microseconds, on the stated epoch, give back its
    # perf_counter_ns stamps: a profile taken alongside aligns on them
    start, end, _, _ = span_intervals(t.to_dict(), "stall")[0]
    assert round(start * 1e3) + md["epoch_ns"] == t0
    assert round(end * 1e3) + md["epoch_ns"] == t0 + 2_000_000
    off = Tracer(enabled=False)
    off.annotate("k", 1)
    assert "k" not in off.metadata


def test_tracks_named_after_threads():
    t = Tracer(enabled=True)
    with t.span("main.work"):
        pass

    def worker():
        with t.span("side.work"):
            pass

    th = threading.Thread(target=worker, name="side-thread")
    th.start()
    th.join()
    names = set(t.track_names().values())
    assert names == {threading.current_thread().name, "side-thread"}


def test_export_roundtrips_through_json(tmp_path, traced):
    with traced.span("a"):
        traced.instant("i")
    path = str(tmp_path / "trace.json")
    traced.export(path)
    with open(path) as f:
        loaded = json.load(f)
    assert validate_trace(loaded)["n_spans"] == 1
    assert loaded["traceEvents"][0]["name"] == "process_name"


# -------------------------------------------------------------- validator
def _base(events):
    return {"traceEvents": events}


def test_validator_rejects_unmatched_and_misnested():
    with pytest.raises(TraceError, match="no open B"):
        validate_trace(_base(
            [{"ph": "E", "pid": 1, "tid": 0, "ts": 1.0, "name": "x"}]))
    with pytest.raises(TraceError, match="improper nesting"):
        validate_trace(_base([
            {"ph": "B", "pid": 1, "tid": 0, "ts": 1.0, "name": "a"},
            {"ph": "B", "pid": 1, "tid": 0, "ts": 2.0, "name": "b"},
            {"ph": "E", "pid": 1, "tid": 0, "ts": 3.0, "name": "a"},
        ]))
    with pytest.raises(TraceError, match="unmatched B"):
        validate_trace(_base(
            [{"ph": "B", "pid": 1, "tid": 0, "ts": 1.0, "name": "a"}]))


def test_validator_rejects_backwards_time_and_bad_events():
    with pytest.raises(TraceError, match="ran backwards"):
        validate_trace(_base([
            {"ph": "i", "pid": 1, "tid": 0, "ts": 5.0, "name": "a", "s": "t"},
            {"ph": "i", "pid": 1, "tid": 0, "ts": 4.0, "name": "b", "s": "t"},
        ]))
    # per-track monotonicity only: another track may be earlier
    validate_trace(_base([
        {"ph": "i", "pid": 1, "tid": 0, "ts": 5.0, "name": "a", "s": "t"},
        {"ph": "i", "pid": 1, "tid": 1, "ts": 1.0, "name": "b", "s": "t"},
    ]))
    with pytest.raises(TraceError, match="missing ph"):
        validate_trace(_base([{"name": "x"}]))
    with pytest.raises(TraceError, match="traceEvents"):
        validate_trace({"events": []})


def test_validator_cli_on_garbage_file(tmp_path):
    from repro.obs.validate import main
    bad = tmp_path / "bad.json"
    bad.write_text("not json{")
    assert main([str(bad)]) == 1


def test_overlap_seconds_on_synthetic_trace():
    trace = _base([
        {"ph": "B", "pid": 1, "tid": 0, "ts": 0.0, "name": "fe.x"},
        {"ph": "E", "pid": 1, "tid": 0, "ts": 100.0, "name": "fe.x"},
        {"ph": "B", "pid": 1, "tid": 1, "ts": 60.0, "name": "train.step"},
        {"ph": "E", "pid": 1, "tid": 1, "ts": 160.0, "name": "train.step"},
    ])
    assert overlap_seconds(trace, "fe.", "train.") == pytest.approx(40e-6)
    assert overlap_seconds(trace, "fe.", "h2d.") == 0.0


# ---------------------------------------------------------------- metrics
def test_harvest_numeric_fields_and_properties():
    @dataclasses.dataclass
    class S:
        n: int = 3
        t: float = 1.5
        flag: bool = True
        name: str = "skip-me"
        items: list = dataclasses.field(default_factory=list)

        @property
        def rate(self) -> float:
            return self.n / 2.0

        @property
        def broken(self) -> float:
            raise ZeroDivisionError

        @property
        def label(self) -> str:
            return "skip-me-too"

    m = harvest(S())
    assert m == {"n": 3, "t": 1.5, "flag": 1, "rate": 1.5}


def test_registry_snapshot_prefixes_and_sources():
    reg = MetricsRegistry()
    reg.register("a", {"x": 1, "y": 2.0, "junk": "no"})
    reg.register("b", lambda: {"z": 3})
    reg.gauge("flops", 7.0)
    snap = reg.snapshot()
    assert snap == {"a.x": 1, "a.y": 2.0, "b.z": 3, "flops": 7.0}
    assert reg.tiers == ("a", "b")
    assert json.loads(reg.to_json()) == snap


def test_all_stats_tiers_implement_as_metrics():
    from repro.core.devicefeed import FeedStats
    from repro.core.metakernel import ExecutionStats
    from repro.core.pipeline import PipelineStats
    from repro.embedding.hierarchy import TierStats
    from repro.fe.modelfeed import TrainFeedStats
    from repro.io.stream import IngestStats
    from repro.train.loop import LoopStats

    for cls, key in ((IngestStats, "bytes_read"),
                     (FeedStats, "bytes_staged"),
                     (ExecutionStats, "n_device_dispatches"),
                     (PipelineStats, "overlap_fraction"),
                     (TrainFeedStats, "unique_ratio"),
                     (LoopStats, "steps"),
                     (TierStats, "host_hit_rate")):
        m = cls().as_metrics()
        assert key in m, f"{cls.__name__} missing {key}"
        assert all(isinstance(v, (int, float)) for v in m.values()), cls


def test_registry_from_pipeline_and_rollup():
    from repro.core.pipeline import PipelineStats
    from repro.io.stream import IngestStats

    stats = PipelineStats(batches=4, fe_seconds=1.0, train_seconds=2.0,
                          wall_seconds=2.5)
    stats.ingest = IngestStats(bytes_read=1000, read_seconds=0.25,
                               reader_stall_seconds=0.5)
    reg = MetricsRegistry.from_pipeline(stats)
    snap = reg.snapshot()
    assert snap["pipeline.batches"] == 4
    assert snap["ingest.bytes_read"] == 1000
    assert snap["rollup.stall_loader_backpressure_seconds"] == 0.5
    assert snap["rollup.overlap_fraction"] == stats.overlap_fraction
    assert "exec.n_device_dispatches" in snap
    roll = pipeline_rollup(stats)
    assert roll["train_busy_fraction"] == pytest.approx(2.0 / 2.5)
    # keys are stable even when tiers are absent
    bare = pipeline_rollup(PipelineStats())
    assert bare["disk_bytes"] == 0 and bare["h2d_seconds"] == 0.0


def test_tier_stats_eviction_accounting(tmp_path):
    from repro.embedding.hierarchy import HierarchicalPS

    ps = HierarchicalPS(str(tmp_path / "table.bin"), total_rows=64, dim=4,
                        host_cache_rows=8)
    ps.pull(np.arange(32))  # 32 unique rows through an 8-row cache
    assert ps.stats.evictions == 32 - 8
    assert ps.host_cache_size == 8
    ps.pull(np.arange(24, 32))  # cached tail: all host hits, no eviction
    assert ps.stats.host_hits == 8
    assert ps.stats.evictions == 32 - 8
    m = ps.stats.as_metrics()
    assert m["evictions"] == 24
    assert 0.0 < m["host_hit_rate"] < 1.0


# ------------------------------------------------------------ pipeline e2e
def _ads_plan():
    from repro.fe import featureplan, get_spec
    return featureplan.compile(get_spec("ads_ctr"))


def test_tracing_is_bit_effect_free(traced):
    """Same batches, tracing on vs off: bit-identical recorded outputs."""
    from repro.core import PipelinedRunner
    from repro.fe.datagen import gen_views

    plan = _ads_plan()
    batches = [gen_views(32, seed=7 + i) for i in range(3)]

    outs = []
    for enabled in (True, False):
        traced.enabled = enabled
        seen = []
        runner = PipelinedRunner.from_plan(plan, recording_step(seen),
                                           feed="arena", rows_hint=32)
        runner.run({"batches": 0}, [dict(b) for b in batches])
        outs.append(seen)
    on, off = outs
    assert len(on) == len(off) == 3
    for a, b in zip(on, off):
        assert set(a) == set(b)
        for k in a:
            assert a[k].dtype == b[k].dtype
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    assert traced.n_events > 0  # the traced pass really recorded


def test_e2e_streaming_trace_four_tracks_and_overlap(tmp_path, traced):
    """The acceptance timeline: disk -> FE -> H2D -> train on >= 4 tracks,
    FE/train spans overlapping, PipelineStats.overlap_fraction > 0."""
    import time

    from repro.core import PipelinedRunner
    from repro.fe.datagen import write_log_shards
    from repro.io.dataset import ShardDataset
    from repro.io.stream import StreamingLoader

    plan = _ads_plan()
    write_log_shards(str(tmp_path), n_shards=6, rows_per_shard=64, seed=0)
    loader = StreamingLoader(ShardDataset(str(tmp_path)), workers=2,
                             prefetch=2, columns=plan.required_columns)

    def slow_train(state, env):
        time.sleep(0.03)  # make train long enough that FE must overlap it
        return {"batches": state["batches"] + 1}

    runner = PipelinedRunner.from_plan(plan, slow_train, feed="arena",
                                       rows_hint=loader.rows_hint)
    state = runner.run({"batches": 0}, loader)
    runner.stats.ingest = loader.stats
    assert state["batches"] == 6

    path = str(tmp_path / "trace.json")
    trace = traced.export(path)
    summary = validate_trace(path)
    names = set(summary["tracks"].values())
    # loader readers + FE worker + H2D feeder + train loop
    assert {"fe-worker", "h2d-feeder"} <= names
    assert any(n.startswith("shard-reader") for n in names)
    assert threading.current_thread().name in names
    assert len(names) >= 4
    # the pipelining claim, measured two independent ways:
    assert runner.stats.overlap_fraction > 0, runner.stats
    assert overlap_seconds(trace, "fe.", "train.step") > 0
    # the stats tiers all made it into one snapshot
    snap = MetricsRegistry.from_pipeline(runner.stats).snapshot()
    assert snap["rollup.disk_bytes"] > 0
    assert snap["feed.batches"] == 6
    assert snap["pipeline.overlap_fraction"] > 0
