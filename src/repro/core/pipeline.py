"""End-to-end pipelined execution (paper Fig. 1 lower / Fig. 3).

FeatureBox's headline mechanism: feature extraction and training share the
same servers and run as a mini-batch pipeline, so extracted features are fed
directly into the trainer without materializing intermediates.

Two executors are provided so the benchmarks can reproduce Table II:

* :class:`PipelinedRunner` — FeatureBox mode. A host prefetch thread runs the
  FE schedule for batch i+1 while the device trains on batch i (double
  buffering). JAX's async dispatch provides the device-side overlap; the
  bounded queue provides backpressure. With ``device_feed`` set to a
  :class:`~repro.core.devicefeed.DeviceFeeder`, a third stage is inserted —
  *read+extract -> H2D stage -> train* — where a dedicated thread stages
  batch i+1 through a buffer-ring staging arena (block-planned async
  transfers) while batch i trains, so host->device transfer leaves the training
  critical path too. ``device_feed=None`` keeps the two-stage behavior.
* :class:`StagedRunner` — the MapReduce-style baseline: stage after stage,
  each stage writes its full output to disk (the "intermediate files" of
  Fig. 1 upper) and the next stage reads it back. Tracks intermediate bytes
  so the I/O-elimination claim is measurable.

Both runners take any iterable of raw batches — in particular a
``repro.io.StreamingLoader``, in which case the pipelined runner's FE worker
overlaps *disk read + extract* with training and the loader's
``IngestStats`` are attached to :attr:`PipelineStats.ingest` after the run.
"""

from __future__ import annotations

import dataclasses
import os
import queue
import threading
import time
from array import array
from typing import (Any, Callable, Dict, Iterable, Iterator, List, Mapping,
                    Optional, Sequence)

import numpy as np

from repro.check.annotations import single_writer
from repro.core.devicefeed import DeviceFeeder
from repro.core.metakernel import ExecutionStats, LayerExecutable, run_layers
from repro.obs.metrics import harvest
from repro.obs.trace import get_tracer

# Sentinel for end-of-stream in the prefetch queue.
_DONE = object()


@dataclasses.dataclass
class PipelineStats:
    batches: int = 0
    fe_seconds: float = 0.0
    train_seconds: float = 0.0
    # PS-feeder stage only (hierarchical embedding backend): thread time
    # spent pulling working sets + waiting on write-back consistency.
    ps_seconds: float = 0.0
    # StagedRunner only: time draining the batch source up front (disk reads
    # with no compute overlap). Accounted so wall == fe + train + drain +
    # small overhead instead of misreading the gap as overhead.
    drain_seconds: float = 0.0
    wall_seconds: float = 0.0
    # Stage seconds hidden by pipelining: the time the FE worker and the
    # train loop were both busy, from each stage's own per-batch busy
    # intervals (PipelinedRunner; 0 for the serial StagedRunner).
    overlap_seconds: float = 0.0
    intermediate_bytes: int = 0  # bytes written to disk between stages
    exec_stats: ExecutionStats = dataclasses.field(default_factory=ExecutionStats)
    # When the batch source is a repro.io.StreamingLoader, its IngestStats
    # (disk bytes/s, queue stalls) are attached here after run().
    ingest: Optional[Any] = None
    # When a DeviceFeeder staged the batches, its FeedStats (h2d bytes/s,
    # arena rewinds, buffer stalls) are attached here after run().
    feed: Optional[Any] = None
    # When the train step is a compiled boundary step (repro.fe.modelfeed
    # make_step), its TrainFeedStats (adapt time/dispatches, dedup unique
    # ratio) are attached here after run(), splitting "adapt" out of the
    # train bucket.
    train_feed: Optional[Any] = None
    # When a HierarchyFeed pulled working sets (ps_feed stage), its
    # PsFeedStats + the PS TierStats are attached here after run().
    ps: Optional[Any] = None
    # When the train step runs on a device mesh, its CommStats (static
    # collective-byte plan x steps) are attached here after run().
    comm: Optional[Any] = None
    # When the batch source is a lease-based StreamingLoader, its
    # FaultStats (reissued leases, retries, backup wins, reap latency)
    # are attached here after run() — the recovery story of the run.
    fault: Optional[Any] = None

    @property
    def adapt_seconds(self) -> float:
        """Host time spent adapting staged batches to the model's layout
        (0 when the train step carries no train-feed stats)."""
        return (self.train_feed.adapt_seconds
                if self.train_feed is not None else 0.0)

    @property
    def train_net_seconds(self) -> float:
        """train_seconds with the measurable adapt share split out."""
        return max(self.train_seconds - self.adapt_seconds, 0.0)

    # ------------------------------------------------- derived accounting
    # The accounting both runners satisfy (asserted in
    # tests/test_pipeline.py):
    #     wall <= fe + train_net + adapt + drain + overhead
    # with overhead >= 0 always, overlap == 0 for the serial (Staged)
    # runner, and, where only FE and train ran,
    #     wall == busy - overlap + overhead
    # Start-up, queue waits and teardown are overhead; the time two stages
    # shared is overlap, measured, not inferred from wall.

    @property
    def busy_seconds(self) -> float:
        """Stage time summed across threads: fe + ps + train + drain.
        Exceeds wall exactly when pipelining hid stage time behind another
        stage."""
        return (self.fe_seconds + self.ps_seconds + self.train_seconds
                + self.drain_seconds)

    @property
    def overhead_seconds(self) -> float:
        """Wall time no stage accounts for (queue waits, thread startup,
        end-of-stream drain): wall beyond the stages' busy time once the
        overlap is counted once. Never negative."""
        return max(self.wall_seconds - self.busy_seconds
                   + self.overlap_seconds, 0.0)

    @property
    def overlap_fraction(self) -> float:
        """How much of the smaller stage (FE vs train) was hidden behind
        the other, in [0, 1]. 0 = fully serial; 1 = the cheaper stage ran
        entirely in the other's shadow — the paper's pipelining claim as
        one number."""
        denom = min(self.fe_seconds, self.train_seconds)
        if denom <= 0.0:
            return 0.0
        return min(self.overlap_seconds / denom, 1.0)

    def as_metrics(self) -> Dict[str, float]:
        """Flat numeric snapshot (fields + derived properties) for the
        :class:`repro.obs.MetricsRegistry`; nested tiers register
        themselves separately."""
        return harvest(self)


def _shared_seconds(a: Sequence[float], b: Sequence[float]) -> float:
    """Seconds two stages were busy at once. Each argument holds one
    stage's busy intervals as flat ``start, end, start, end, ...`` stamps,
    in order and disjoint (one thread's per-batch work)."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i + 1], b[j + 1]) - max(a[i], b[j]))
        if a[i + 1] < b[j + 1]:
            i += 2
        else:
            j += 2
    return total


def _capture_ingest(stats: PipelineStats, batches: Any) -> None:
    """Adopt ingest stats from a StreamingLoader-like batch source.

    Duck-typed so core stays import-independent of :mod:`repro.io`.
    """
    src_stats = getattr(batches, "stats", None)
    if src_stats is not None and hasattr(src_stats, "bytes_read"):
        stats.ingest = src_stats


def _capture_fault(stats: PipelineStats, batches: Any) -> None:
    """Adopt recovery stats from a lease-based StreamingLoader source.

    Duck-typed off ``fault_stats`` so core stays import-independent of
    :mod:`repro.io` / :mod:`repro.train`.
    """
    fs = getattr(batches, "fault_stats", None)
    if fs is not None and hasattr(fs, "reissued"):
        stats.fault = fs


def _capture_train_feed(stats: PipelineStats, train_step: Any) -> None:
    """Adopt train-feed stats from a modelfeed-compiled boundary step.

    Duck-typed off the step's ``feed_stats`` attribute so core stays
    import-independent of :mod:`repro.fe`.
    """
    fs = getattr(train_step, "feed_stats", None)
    if fs is not None and hasattr(fs, "adapt_seconds"):
        stats.train_feed = fs


def _capture_comm(stats: PipelineStats, train_step: Any) -> None:
    """Adopt mesh collective stats from the train step's ``comm_stats``.

    Duck-typed off :class:`repro.train.compression.CommStats` so core stays
    import-independent of :mod:`repro.train`.
    """
    cs = getattr(train_step, "comm_stats", None)
    if cs is not None and hasattr(cs, "interpod_bytes_total"):
        stats.comm = cs


# Thread contract (verified by `python -m repro.check` / repro.check.lockset):
# PipelineStats is shared without a lock because every field has exactly one
# writing thread — the fe-worker owns fe_seconds, the main train loop owns
# the rest (it only reads them after joining the workers). Any new field
# written from more than one thread must move to a @guarded_by lock.
@single_writer("stats.fe_seconds",                       # fe-worker thread
               "stats.ps_seconds",                       # ps-feeder thread
               "stats.train_seconds", "stats.batches",   # main train loop
               "stats.wall_seconds", "stats.overlap_seconds",
               "stats.feed", "stats.ps")
class PipelinedRunner:
    """FeatureBox: FE for batch i+1 overlaps training on batch i.

    With ``device_feed`` set, an H2D staging thread is inserted between the
    FE worker and the train loop (three-stage pipeline); ``None`` keeps the
    two-stage path and hands host environments straight to ``train_step``.

    With ``ps_feed`` set (a :class:`repro.embedding.psfeed.HierarchyFeed`),
    a PS-pull stage runs between the FE worker and the H2D/train stages:
    batch i+1's dedup'd working set is pulled from the hierarchical
    parameter server while batch i trains — the paper's pre-built working
    parameter set, as a pipeline stage.
    """

    def __init__(
        self,
        layers: List[LayerExecutable],
        train_step: Callable[[Any, Mapping[str, Any]], Any],
        *,
        prefetch: int = 2,
        device=None,
        device_feed: Optional[DeviceFeeder] = None,
        ps_feed: Optional[Callable[[Mapping[str, Any]], Dict[str, Any]]] = None,
    ) -> None:
        self.layers = layers
        self.train_step = train_step
        self.prefetch = prefetch
        self.device = device
        self.device_feed = device_feed
        self.ps_feed = ps_feed
        self.stats = PipelineStats()

    @classmethod
    def from_plan(cls, plan: Any, train_step: Callable[[Any, Mapping[str, Any]], Any],
                  *, prefetch: int = 2, device=None, feed: str = "off",
                  split_sparse_fields: bool = False,
                  rows_hint: Optional[int] = None,
                  buffers: int = 3) -> "PipelinedRunner":
        """Wire a compiled ``repro.fe.featureplan.FeaturePlan`` into a runner.

        ``feed`` selects the H2D tier:

        * ``"off"``   — two-stage pipeline; the train step receives host
          arrays (per-tensor transfer on the training critical path);
        * ``"stage"`` — three-stage: a :class:`DeviceFeeder` memcpys each
          batch's outputs into the block-planned staging arena and
          transfers them asynchronously (PR 3 behavior);
        * ``"arena"`` — zero-copy feed: FE assembles the ``batch_*``
          outputs **directly into claimed arena views**
          (``plan.arena_binding()``), eliminating the per-batch
          env->arena memcpy (``FeedStats.copies_elided``).

        Duck-typed on the plan (``layers`` / ``feed_layout`` /
        ``arena_binding``) so core stays import-independent of repro.fe.
        """
        if feed == "off":
            return cls(plan.layers, train_step, prefetch=prefetch,
                       device=device)
        if feed == "stage":
            feeder = DeviceFeeder(
                plan.feed_layout(split_sparse_fields=split_sparse_fields),
                rows_hint=rows_hint, buffers=buffers, device=device)
            return cls(plan.layers, train_step, prefetch=prefetch,
                       device=device, device_feed=feeder)
        if feed == "arena":
            ab = plan.arena_binding(split_sparse_fields=split_sparse_fields)
            feeder = ab.make_feeder(rows_hint=rows_hint, buffers=buffers,
                                    device=device)
            return cls(ab.layers, train_step, prefetch=prefetch,
                       device=device, device_feed=feeder)
        raise ValueError(
            f"feed must be 'off', 'stage', or 'arena', got {feed!r}")

    def _fe_worker(self, batches: Iterator[Mapping[str, Any]],
                   q: "queue.Queue", stop: threading.Event,
                   busy: array) -> None:
        tracer = get_tracer()
        try:
            for bi, raw in enumerate(batches):
                if stop.is_set():  # consumer died: don't extract the rest
                    break
                t0 = time.perf_counter()
                with tracer.span("fe.extract", batch=bi):
                    env = dict(raw)
                    run_layers(self.layers, env, device=self.device,
                               stats=self.stats.exec_stats)
                t1 = time.perf_counter()
                self.stats.fe_seconds += t1 - t0
                busy.extend((t0, t1))
                self._put(q, env, stop)
        except BaseException as e:  # surface worker failures to the consumer
            tracer.instant("fe.error", kind=type(e).__name__)
            self._put(q, e, stop)
        finally:
            self._put(q, _DONE, stop)

    @staticmethod
    def _put(q: "queue.Queue", item: Any, stop: threading.Event) -> None:
        """Backpressured put that gives up once the consumer is gone, so a
        failed train_step can't leave the FE worker blocked forever."""
        while True:
            try:
                q.put(item, timeout=0.1)
                return
            except queue.Full:
                if stop.is_set():
                    return

    def _feed_worker(self, q: "queue.Queue", out: "queue.Queue",
                     stop: threading.Event) -> None:
        """H2D stage: pull extracted envs, stage batch i+1 while i trains.

        Sentinels and FE-worker exceptions pass through unchanged so the
        consumer sees the original failure, not a feed artifact.
        """
        try:
            while True:
                try:
                    item = q.get(timeout=0.1)
                except queue.Empty:
                    if stop.is_set():
                        return
                    continue
                if item is _DONE:
                    self._put(out, _DONE, stop)
                    return
                if isinstance(item, BaseException):
                    self._put(out, item, stop)
                    continue  # _DONE follows from the FE worker
                self._put(out, self.device_feed.stage(item), stop)
        except BaseException as e:  # staging failure: surface + terminate
            self._put(out, e, stop)
            self._put(out, _DONE, stop)

    def _ps_worker(self, q: "queue.Queue", out: "queue.Queue",
                   stop: threading.Event) -> None:
        """PS stage: pull batch i+1's working set while batch i trains.

        Same pass-through contract as :meth:`_feed_worker` — sentinels and
        upstream exceptions flow downstream unchanged.
        """
        try:
            while True:
                try:
                    item = q.get(timeout=0.1)
                except queue.Empty:
                    if stop.is_set():
                        return
                    continue
                if item is _DONE:
                    self._put(out, _DONE, stop)
                    return
                if isinstance(item, BaseException):
                    self._put(out, item, stop)
                    continue  # _DONE follows from the FE worker
                t0 = time.perf_counter()
                prepared = self.ps_feed(item)
                self.stats.ps_seconds += time.perf_counter() - t0
                self._put(out, prepared, stop)
        except BaseException as e:  # pull/consistency failure: surface it
            self._put(out, e, stop)
            self._put(out, _DONE, stop)

    def run(self, state: Any, batches: Iterable[Mapping[str, Any]]) -> Any:
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()
        # Each stage's own busy intervals, written by that stage's thread
        # alone and intersected after the join.
        fe_busy, train_busy = array("d"), array("d")
        t_start = time.perf_counter()
        worker = threading.Thread(
            target=self._fe_worker, args=(iter(batches), q, stop, fe_busy),
            daemon=True, name="fe-worker",
        )
        threads = [worker]
        queues = [q]
        out_q = q
        if self.ps_feed is not None:
            # Working sets hold device buffers: keep at most one prepared
            # batch queued ahead of the train loop (single-batch pull-ahead;
            # the consistency protocol in HierarchyFeed assumes it).
            ps_q: "queue.Queue" = queue.Queue(maxsize=1)
            ps_feeder = threading.Thread(
                target=self._ps_worker, args=(out_q, ps_q, stop),
                daemon=True, name="ps-feeder",
            )
            threads.append(ps_feeder)
            queues.append(ps_q)
            out_q = ps_q
        if self.device_feed is not None:
            # Bounded by the buffer ring: with one batch held by the train
            # loop and one being staged, at most buffers-2 more fit in the
            # queue before the feeder would block reclaiming a ring slot.
            feed_q: "queue.Queue" = queue.Queue(
                maxsize=max(1, self.device_feed.buffers - 2))
            feeder = threading.Thread(
                target=self._feed_worker, args=(out_q, feed_q, stop),
                daemon=True, name="h2d-feeder",
            )
            threads.append(feeder)
            queues.append(feed_q)
            out_q = feed_q
        for t in threads:
            t.start()
        tracer = get_tracer()
        try:
            while True:
                if tracer.enabled:
                    # Record the wait for the next extracted/staged batch
                    # only when it actually stalled the train loop: the
                    # gap is the pipeline's backpressure signal.
                    w0 = tracer.now_ns()
                    item = out_q.get()
                    w1 = tracer.now_ns()
                    if w1 - w0 > 100_000:  # >0.1 ms
                        tracer.complete("train.wait_batch", w0, w1)
                else:
                    item = out_q.get()
                if item is _DONE:
                    break
                if isinstance(item, BaseException):
                    raise item
                t0 = time.perf_counter()
                with tracer.span("train.step", batch=self.stats.batches):
                    state = self.train_step(state, item)
                t1 = time.perf_counter()
                self.stats.train_seconds += t1 - t0
                train_busy.extend((t0, t1))
                self.stats.batches += 1
                # Release the env before blocking on the next get so batch
                # memory is reclaimed as soon as the device is done with it.
                del item
        finally:
            stop.set()
            if self.ps_feed is not None:
                # Unblock a prepare() waiting on a write-back that will
                # never arrive (duck-typed; HierarchyFeed.close never
                # raises). Drain/flush is the driver's job, not teardown's.
                close = getattr(self.ps_feed, "close", None)
                if close is not None:
                    close()
            for qq in queues:  # release workers blocked on a full queue
                try:
                    while True:
                        qq.get_nowait()
                except queue.Empty:
                    pass
            for t in threads:
                t.join(timeout=5.0)
            if self.device_feed is not None:
                # Drain in-flight transfers so wall time covers them and
                # FeedStats.stall_seconds reflects the end-of-stream wait —
                # but only once the h2d feeder is confirmed dead: join can
                # time out with the thread still inside stage(), and flush
                # must not race the ring it is draining.
                if not any(t.is_alive() for t in threads):
                    self.device_feed.flush()
                self.stats.feed = self.device_feed.stats
            if self.ps_feed is not None and hasattr(self.ps_feed, "as_metrics"):
                self.stats.ps = self.ps_feed
            self.stats.wall_seconds = time.perf_counter() - t_start
            self.stats.overlap_seconds = _shared_seconds(fe_busy, train_busy)
            _capture_ingest(self.stats, batches)
            _capture_fault(self.stats, batches)
            _capture_train_feed(self.stats, self.train_step)
            _capture_comm(self.stats, self.train_step)
        return state


class StagedRunner:
    """Baseline: materialize every stage's output before the next stage runs.

    Mirrors the paper's Fig. 1 (upper): MapReduce jobs write intermediate
    files to the DFS; the trainer then streams the final features back. Here
    each scheduled layer plays the role of one MapReduce job and writes its
    produced slots to ``workdir`` as .npy files.
    """

    def __init__(
        self,
        layers: List[LayerExecutable],
        train_step: Callable[[Any, Mapping[str, Any]], Any],
        *,
        workdir: str,
        device=None,
    ) -> None:
        self.layers = layers
        self.train_step = train_step
        self.workdir = workdir
        self.device = device
        self.stats = PipelineStats()
        os.makedirs(workdir, exist_ok=True)

    def _materialize(self, env: Dict[str, Any], stage: int, batch: int) -> Dict[str, Any]:
        """Write every slot to disk and read it back (stage boundary).

        Slots may be arrays, dicts of columns (views), or ragged columns —
        each is written like the MapReduce intermediates it stands in for.
        """
        out: Dict[str, Any] = {}
        for slot, val in env.items():
            out[slot] = self._roundtrip(val, f"b{batch}_s{stage}_{_safe(slot)}")
        return out

    def _roundtrip(self, val: Any, stem: str) -> Any:
        if isinstance(val, dict):
            return {k: self._roundtrip(v, f"{stem}__{_safe(str(k))}")
                    for k, v in val.items()}
        if hasattr(val, "values") and hasattr(val, "lengths"):  # RaggedColumn
            vals = self._roundtrip(np.asarray(val.values), stem + "__values")
            lens = self._roundtrip(np.asarray(val.lengths), stem + "__lengths")
            return type(val)(values=vals, lengths=lens)
        arr = np.asarray(val)
        path = os.path.join(self.workdir, stem + ".npy")
        np.save(path, arr, allow_pickle=True)  # string columns are object arrays
        # Count the on-disk size: for object (string) columns arr.nbytes is
        # just 8-byte pointers, which would undercount the I/O eliminated.
        self.stats.intermediate_bytes += os.path.getsize(path)
        return np.load(path, allow_pickle=True)

    def run(self, state: Any, batches: Iterable[Mapping[str, Any]]) -> Any:
        tracer = get_tracer()
        t_start = time.perf_counter()
        # A StreamingLoader source is drained up front: the staged baseline
        # by definition has no read/compute overlap. That read time is its
        # own accounting bucket (drain_seconds), not fe/train overhead.
        with tracer.span("staged.drain"):
            all_batches = list(batches)
        self.stats.drain_seconds = time.perf_counter() - t_start
        _capture_ingest(self.stats, batches)
        _capture_fault(self.stats, batches)
        # Stage-after-stage: run *every* batch through layer k, materialize,
        # then move to layer k+1 — the defining property of the baseline.
        envs: List[Dict[str, Any]] = [dict(b) for b in all_batches]
        for li, layer in enumerate(self.layers):
            t0 = time.perf_counter()
            with tracer.span("fe.stage", layer=li, batches=len(envs)):
                for bi, env in enumerate(envs):
                    run_layers([layer], env, device=self.device,
                               stats=self.stats.exec_stats)
                    envs[bi] = self._materialize(env, li, bi)
            self.stats.fe_seconds += time.perf_counter() - t0
        for bi, env in enumerate(envs):
            t0 = time.perf_counter()
            with tracer.span("train.step", batch=bi):
                state = self.train_step(state, env)
            self.stats.train_seconds += time.perf_counter() - t0
            self.stats.batches += 1
        self.stats.wall_seconds = time.perf_counter() - t_start
        _capture_train_feed(self.stats, self.train_step)
        _capture_comm(self.stats, self.train_step)
        return state


def _safe(slot: str) -> str:
    return "".join(c if c.isalnum() or c in "-_" else "_" for c in slot)
