"""Pipelined (FeatureBox) vs staged (MapReduce-style) executors:
identical results, intermediate I/O eliminated (paper Table II semantics)."""

import tempfile

from conftest import pipeline_threads_gone

import numpy as np

from repro.core import (
    PipelinedRunner,
    StagedRunner,
    build_schedule,
    compile_layers,
)
from repro.fe.datagen import gen_views
from repro.fe.pipeline_graph import build_fe_graph


def _batches(n, rows=64):
    return [gen_views(rows, seed=100 + i) for i in range(n)]


def _train_step_factory():
    """Accumulate a checksum + count of consumed batches as 'training'."""
    def train_step(state, env):
        s = float(np.asarray(env["batch_dense"]).sum()) + float(
            np.asarray(env["batch_sparse"]).sum())
        return {"sum": state["sum"] + s, "batches": state["batches"] + 1}
    return train_step


def test_pipelined_equals_staged_and_saves_io():
    layers = compile_layers(build_schedule(build_fe_graph()))
    batches = _batches(3)

    pipe = PipelinedRunner(layers, _train_step_factory(), prefetch=2)
    s_pipe = pipe.run({"sum": 0.0, "batches": 0}, [dict(b) for b in batches])

    staged = StagedRunner(layers, _train_step_factory(),
                          workdir=tempfile.mkdtemp())
    s_staged = staged.run({"sum": 0.0, "batches": 0}, [dict(b) for b in batches])

    assert s_pipe["batches"] == s_staged["batches"] == 3
    np.testing.assert_allclose(s_pipe["sum"], s_staged["sum"], rtol=1e-6)

    # the Table II claim: pipelining eliminates ALL intermediate I/O
    assert pipe.stats.intermediate_bytes == 0
    assert staged.stats.intermediate_bytes > 10_000
    assert staged.stats.batches == pipe.stats.batches


def test_pipelined_overlaps_host_and_device():
    """FE for batch i+1 runs while training batch i (wall < fe + train)."""
    import time

    layers = compile_layers(build_schedule(build_fe_graph()))

    def slow_train(state, env):
        time.sleep(0.05)
        return state

    pipe = PipelinedRunner(layers, slow_train, prefetch=2)
    pipe.run({}, [dict(b) for b in _batches(4)])
    overlap = pipe.stats.fe_seconds + pipe.stats.train_seconds - pipe.stats.wall_seconds
    assert overlap > 0, (
        f"no overlap: fe={pipe.stats.fe_seconds:.3f} train={pipe.stats.train_seconds:.3f} "
        f"wall={pipe.stats.wall_seconds:.3f}")


def test_train_step_error_releases_fe_worker():
    """A failing train_step must not leave the FE worker blocked on a full
    prefetch queue (thread + decoded-batch leak per failed run)."""
    import threading
    import time

    layers = compile_layers(build_schedule(build_fe_graph()))

    def bad_step(state, env):
        raise ValueError("train blew up")

    pipe = PipelinedRunner(layers, bad_step, prefetch=1)
    import pytest
    with pytest.raises(ValueError, match="train blew up"):
        pipe.run({}, [dict(b) for b in _batches(4)])
    deadline = time.perf_counter() + 5.0
    while time.perf_counter() < deadline and any(
            t.name == "fe-worker" for t in threading.enumerate()):
        time.sleep(0.05)
    assert not [t for t in threading.enumerate() if t.name == "fe-worker"]


def test_pipeline_propagates_worker_errors():
    layers = compile_layers(build_schedule(build_fe_graph()))
    pipe = PipelinedRunner(layers, lambda s, e: s)

    def bad_batches():
        yield {"impressions": None}  # malformed -> FE worker raises

    import pytest
    with pytest.raises(KeyError):  # the malformed batch's missing view
        pipe.run({}, bad_batches())


def test_train_step_error_mid_run_stops_and_joins_worker():
    """train_step raising mid-run (not on batch 0) must drain the queue and
    join the FE worker within the timeout, with partial progress recorded."""
    import pytest

    layers = compile_layers(build_schedule(build_fe_graph()))
    calls = {"n": 0}

    def explode_later(state, env):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("mid-run failure")
        return state

    pipe = PipelinedRunner(layers, explode_later, prefetch=1)
    with pytest.raises(RuntimeError, match="mid-run failure"):
        pipe.run({}, [dict(b) for b in _batches(5)])
    assert calls["n"] == 2
    assert pipe.stats.batches == 1  # only the pre-failure batch counted
    assert pipeline_threads_gone()
    assert pipe.stats.wall_seconds > 0  # finally-path accounting still runs


def test_batch_source_error_surfaces_original_exception():
    """An iterator raising mid-stream must surface *its* exception (not a
    bare _DONE/stop artifact), after the prior good batches trained."""
    import pytest

    layers = compile_layers(build_schedule(build_fe_graph()))

    def flaky_batches():
        yield dict(_batches(1)[0])
        raise OSError("shard rot at offset 42")

    pipe = PipelinedRunner(layers, lambda s, e: s, prefetch=2)
    with pytest.raises(OSError, match="shard rot at offset 42"):
        pipe.run({}, flaky_batches())
    assert pipe.stats.batches == 1
    assert pipeline_threads_gone()


def test_staged_drain_time_accounted():
    """StagedRunner: time draining a slow batch source must land in
    drain_seconds — not in the wall - fe - train gap — so the accounting
    closes (the gap no longer misreads ingest time as overhead)."""
    import time

    layers = compile_layers(build_schedule(build_fe_graph()))
    delay = 0.08

    def slow_source():
        for b in _batches(3, rows=32):
            time.sleep(delay)
            yield dict(b)

    staged = StagedRunner(layers, _train_step_factory(),
                          workdir=tempfile.mkdtemp())
    staged.run({"sum": 0.0, "batches": 0}, slow_source())
    s = staged.stats
    assert s.drain_seconds >= 3 * delay * 0.9
    overhead = s.wall_seconds - s.fe_seconds - s.train_seconds - s.drain_seconds
    assert overhead >= 0
    assert overhead < 3 * delay  # the drain time left the "overhead" gap


def test_pipelined_drain_seconds_zero():
    """The pipelined runner never drains up front: the field stays 0."""
    layers = compile_layers(build_schedule(build_fe_graph()))
    pipe = PipelinedRunner(layers, _train_step_factory(), prefetch=2)
    pipe.run({"sum": 0.0, "batches": 0}, [dict(b) for b in _batches(2)])
    assert pipe.stats.drain_seconds == 0.0


# -------------------------------------------------------- train-feed tier
def _adapting_step_factory(adapt_delay=0.0):
    """Train step carrying modelfeed-style feed_stats: the runners must
    adopt them into PipelineStats.train_feed and split adapt from train."""
    import time

    from repro.fe.modelfeed import TrainFeedStats

    stats = TrainFeedStats()

    def train_step(state, env):
        t0 = time.perf_counter()
        if adapt_delay:
            time.sleep(adapt_delay)
        stats.adapt_seconds += time.perf_counter() - t0
        stats.steps += 1
        stats.fused_steps += 1
        return {"sum": state["sum"], "batches": state["batches"] + 1}

    train_step.feed_stats = stats
    return train_step


def test_runners_adopt_train_feed_stats():
    layers = compile_layers(build_schedule(build_fe_graph()))
    for make in (
        lambda s: PipelinedRunner(layers, s, prefetch=2),
        lambda s: StagedRunner(layers, s, workdir=tempfile.mkdtemp()),
    ):
        step = _adapting_step_factory()
        runner = make(step)
        runner.run({"sum": 0.0, "batches": 0},
                   [dict(b) for b in _batches(2, rows=32)])
        assert runner.stats.train_feed is step.feed_stats
        assert runner.stats.train_feed.steps == 2


def test_train_feed_splits_adapt_from_train():
    """The adapt share measured by the boundary step is split out of the
    train bucket: train_net_seconds + adapt_seconds == train_seconds."""
    delay = 0.05
    layers = compile_layers(build_schedule(build_fe_graph()))
    step = _adapting_step_factory(adapt_delay=delay)
    runner = PipelinedRunner(layers, step, prefetch=2)
    runner.run({"sum": 0.0, "batches": 0},
               [dict(b) for b in _batches(3, rows=32)])
    s = runner.stats
    assert s.adapt_seconds >= 3 * delay * 0.9
    assert s.train_net_seconds <= s.train_seconds - s.adapt_seconds + 1e-9
    assert abs((s.train_net_seconds + s.adapt_seconds) - s.train_seconds) \
        < 1e-6


def test_train_feed_absent_without_feed_stats():
    layers = compile_layers(build_schedule(build_fe_graph()))
    runner = PipelinedRunner(layers, _train_step_factory(), prefetch=2)
    runner.run({"sum": 0.0, "batches": 0},
               [dict(b) for b in _batches(1, rows=16)])
    assert runner.stats.train_feed is None
    assert runner.stats.adapt_seconds == 0.0
    assert runner.stats.train_net_seconds == runner.stats.train_seconds


# ------------------------------------------- derived accounting invariants
def test_accounting_identity_exact_arithmetic():
    """Overhead is the wall time left once busy time is counted with the
    measured overlap taken out once: never negative, the overlap never
    exceeds the smaller stage, and wall == busy - overlap + overhead holds
    to float precision. A run can show both (start-up overhead around a
    pipelined middle), which wall - busy alone could not."""
    from repro.core.pipeline import PipelineStats

    cases = [
        # (fe, train, drain, overlap, wall)
        (1.0, 2.0, 0.0, 0.0, 3.5),   # serial-ish: overhead 0.5
        (1.0, 2.0, 0.0, 0.6, 2.4),   # pipelined: overlap 0.6
        (1.0, 2.0, 0.5, 0.0, 3.5),   # exact: overhead == overlap == 0
        (0.0, 0.0, 0.0, 0.0, 0.0),   # empty run
        (0.3, 5.0, 0.0, 0.3, 5.05),  # train-bound: FE hidden, 0.05 start-up
    ]
    for fe, train, drain, overlap, wall in cases:
        s = PipelineStats(fe_seconds=fe, train_seconds=train,
                          drain_seconds=drain, overlap_seconds=overlap,
                          wall_seconds=wall)
        assert s.busy_seconds == fe + train + drain
        assert s.overhead_seconds >= 0.0
        assert 0.0 <= s.overlap_seconds <= min(fe, train)
        assert abs(s.wall_seconds
                   - (s.busy_seconds + s.overhead_seconds
                      - s.overlap_seconds)) < 1e-12
        # the ISSUE invariant: wall <= fe + train_net + adapt + drain + overhead
        assert s.wall_seconds <= (s.fe_seconds + s.train_net_seconds
                                  + s.adapt_seconds + s.drain_seconds
                                  + s.overhead_seconds + 1e-12)
        assert 0.0 <= s.overlap_fraction <= 1.0
    s = PipelineStats(fe_seconds=0.3, train_seconds=5.0, overlap_seconds=0.3,
                      wall_seconds=5.05)
    assert abs(s.overhead_seconds - 0.05) < 1e-12
    assert s.overlap_fraction == 1.0


def test_overlap_fraction_bounds_and_degenerate_cases():
    from repro.core.pipeline import PipelineStats

    # full overlap: the shorter stage entirely hidden
    s = PipelineStats(fe_seconds=1.0, train_seconds=3.0, overlap_seconds=1.0,
                      wall_seconds=3.0)
    assert s.overlap_fraction == 1.0
    # no train stage at all: fraction defined as 0, not a ZeroDivision
    s = PipelineStats(fe_seconds=1.0, train_seconds=0.0, wall_seconds=1.0)
    assert s.overlap_fraction == 0.0
    # overlap can exceed min(fe, train) only through float noise: clamped
    s = PipelineStats(fe_seconds=0.5, train_seconds=10.0,
                      overlap_seconds=0.5 + 1e-12, wall_seconds=10.0)
    assert s.overlap_fraction == 1.0


def test_overlap_measured_from_stage_intervals_despite_slow_start():
    """The overlap comes from the FE and train stages' own busy intervals:
    a batch source that is slow to start (time neither stage is busy) adds
    to overhead, and does not hide the overlap behind it."""
    import time

    layers = compile_layers(build_schedule(build_fe_graph()))

    def slow_source():
        time.sleep(0.3)
        yield from (dict(b) for b in _batches(4))

    def slow_train(state, env):
        time.sleep(0.03)
        return state

    pipe = PipelinedRunner(layers, slow_train, prefetch=2)
    pipe.run({}, slow_source())
    s = pipe.stats
    assert s.busy_seconds < s.wall_seconds  # wall - busy would read 0
    assert 0.0 < s.overlap_seconds <= min(s.fe_seconds, s.train_seconds)
    assert s.overhead_seconds >= 0.3
    assert abs(s.wall_seconds - (s.busy_seconds - s.overlap_seconds
                                 + s.overhead_seconds)) < 1e-9


def test_shared_seconds_intersects_interval_lists():
    from repro.core.pipeline import _shared_seconds

    assert _shared_seconds([0.0, 1.0, 2.0, 3.0], [0.5, 2.5]) == 1.0
    assert _shared_seconds([0.0, 1.0], [1.0, 2.0]) == 0.0
    assert _shared_seconds([], [0.0, 1.0]) == 0.0
    assert _shared_seconds([0.0, 4.0], [1.0, 2.0, 3.0, 5.0]) == 2.0


def test_accounting_invariant_real_pipelined_run():
    """A real pipelined run: overhead never negative, identity closes, and
    the overlap the run was built to produce is visible."""
    import time

    layers = compile_layers(build_schedule(build_fe_graph()))

    def slow_train(state, env):
        time.sleep(0.03)
        return {"sum": state["sum"], "batches": state["batches"] + 1}

    pipe = PipelinedRunner(layers, slow_train, prefetch=2)
    pipe.run({"sum": 0.0, "batches": 0}, [dict(b) for b in _batches(4)])
    s = pipe.stats
    assert s.overhead_seconds >= 0.0
    assert s.wall_seconds <= (s.fe_seconds + s.train_net_seconds
                              + s.adapt_seconds + s.drain_seconds
                              + s.overhead_seconds + 1e-9)
    assert s.overlap_fraction > 0.0


def test_accounting_invariant_serial_staged_run():
    """StagedRunner is serial: busy time can never exceed wall, so the
    identity holds with equality (overlap exactly 0)."""
    layers = compile_layers(build_schedule(build_fe_graph()))
    staged = StagedRunner(layers, _train_step_factory(),
                          workdir=tempfile.mkdtemp())
    staged.run({"sum": 0.0, "batches": 0}, [dict(b) for b in _batches(3)])
    s = staged.stats
    assert s.overlap_seconds == 0.0
    assert s.overlap_fraction == 0.0
    assert abs(s.wall_seconds - (s.fe_seconds + s.train_net_seconds
                                 + s.adapt_seconds + s.drain_seconds
                                 + s.overhead_seconds)) < 1e-9
