"""Compiled spec->arch batch adaptation: the stage->train boundary, jitted.

A compiled :class:`~repro.fe.featureplan.FeaturePlan` emits a spec-dependent
``batch_*`` layout (e.g. ads_ctr: 8 sparse fields, 9 dense feats, 48 seq
positions); an arch config usually wants a different width, so fields are
remapped / re-hashed into the config's vocabularies and missing blocks are
synthesized. The legacy adapter (kept verbatim below as
:func:`fe_env_to_model_batch_ref`, the reference oracle) did this with ~10
eager jnp dispatches per step — every one of them on the training critical
path, *after* the device-feed stage had already paid to put the batch on
device.

:func:`compile` moves all of that to compile time. It derives a
:class:`ModelFeed` plan from the plan's :class:`~repro.fe.compiler.
OutputLayout` + the arch config: which spec field feeds which model field
(static remap indices), the per-field vocab modulo vector, and how to
synthesize dense / behavior-sequence blocks when the spec has none. The
plan's :meth:`ModelFeed.apply` is pure jnp over static constants, so
:meth:`ModelFeed.make_step` traces it **inside** the train step's jit — the
whole stage->train boundary is ONE fused dispatch per step (the train step
itself), with zero eager adaptation ops. Outputs are asserted bit-identical
to the oracle in ``tests/test_modelfeed.py``.

The plan also closes the two remaining gaps on this boundary:

* **per-field dedup'd embedding feed** — with ``split_sparse_fields=True``
  the plan consumes the arena binding's per-field ``batch_field_NN`` id
  vectors directly (no packed intermediate on the host), and
  :func:`dedup_capacity_hint` sizes the working set of the sparse train
  step (``MultiTable.lookup_dedup`` / ``make_sparse_train_step``) from the
  loader's ``rows_hint`` — so the streaming driver runs the
  FeatureBox/[37] working-set path by default, with dedup saturation
  surfaced in :attr:`TrainFeedStats.overflows`;
* **donated staged buffers** — ``make_step(donate=True)`` donates the
  staged batch (and params/optimizer) through the jit, so arena-fed device
  slots are reused in place; the consumer side of the
  :meth:`~repro.core.devicefeed.DeviceFeeder.donation_fence` handshake.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.embedding.dedup import expected_unique
from repro.fe.compiler import OutputLayout, field_slot, field_slots
from repro.obs import phases
from repro.obs.metrics import harvest
from repro.obs.trace import get_tracer

_DONATE_MSG = "Some donated buffers were not usable"


class ModelFeedError(ValueError):
    """A batch (or config) violates the compiled adaptation contract."""


# ---------------------------------------------------------------- oracle
def fe_env_to_model_batch_ref(env: Mapping[str, Any], cfg) -> Dict[str, Any]:
    """Reference adapter: FE-pipeline outputs -> recsys model batch.

    This is the pre-compilation implementation, kept verbatim as the
    oracle :meth:`ModelFeed.apply` is asserted bit-identical against
    (``tests/test_modelfeed.py``). Columns are tiled / re-hashed into the
    config's field vocabularies; specs without a dense block (bst) or
    sequence block (dlrm-as-plain) degrade gracefully: missing blocks are
    synthesized from the sparse fields. Pure jnp, but every op here is an
    eager per-step dispatch — the cost the compiled path removes.
    """
    sparse = jnp.asarray(env["batch_sparse"])
    idx = np.arange(cfg.n_sparse) % sparse.shape[1]
    vocab = np.asarray(cfg.vocab_sizes[:cfg.n_sparse], np.int32)
    batch: Dict[str, Any] = {
        "sparse": (sparse[:, idx] % vocab).astype(jnp.int32),
        "label": jnp.asarray(env["batch_label"]).astype(jnp.float32),
    }
    if cfg.n_dense:
        if "batch_dense" in env:
            dense = jnp.asarray(env["batch_dense"]).astype(jnp.float32)
        else:  # spec emits no dense block: log-scaled sparse ids stand in
            dense = jnp.log1p(sparse.astype(jnp.float32))
        reps = -(-cfg.n_dense // dense.shape[1])  # ceil
        batch["dense"] = jnp.tile(dense, (1, reps))[:, :cfg.n_dense]
    if cfg.kind == "bst":
        seq = (jnp.asarray(env["batch_seq_ids"])
               if "batch_seq_ids" in env else sparse)
        reps = -(-cfg.seq_len // seq.shape[1])
        batch["seq"] = (jnp.tile(seq, (1, reps))[:, :cfg.seq_len]
                        % cfg.vocab_sizes[0]).astype(jnp.int32)
    return batch


# ------------------------------------------------------- capacity heuristic
def dedup_capacity_hint(cfg, rows: int, *, mode: str = "worst",
                        safety: float = 1.15, multiple: int = 64) -> int:
    """Working-set capacity for a batch of ``rows`` instances.

    ``mode="worst"`` (default) is the exact upper bound on unique packed
    ids — ``sum_f min(rows, vocab_f)`` plus the behavior-sequence field for
    bst — so dedup can never overflow as long as batches respect the rows
    hint. ``mode="expected"`` uses the uniform-draw expectation
    ``E[unique] = v(1 - (1 - 1/v)^n`` (x ``safety``), capped at the worst
    case — tighter at scale, but a skewed batch can saturate it
    (surfaced as :attr:`TrainFeedStats.overflows`). The result is rounded
    up to ``multiple`` so the working set shards evenly.
    """
    rows = int(rows)
    if rows <= 0:
        raise ModelFeedError(f"rows must be > 0, got {rows}")
    vocabs = cfg.vocab_sizes[:cfg.n_sparse]
    seq_rows = rows * (cfg.seq_len + 1) if cfg.kind == "bst" else 0
    worst = sum(min(rows, v) for v in vocabs)
    # Behavior-sequence ids are produced modulo vocab_sizes[0] (see the
    # reference adapter), NOT the item field's vocab — bound with the
    # id space they actually range over.
    if seq_rows:
        worst += min(seq_rows, cfg.vocab_sizes[0])
    if mode == "worst":
        cap = worst
    elif mode == "expected":
        exp = sum(expected_unique(rows, v) for v in vocabs)
        if seq_rows:
            exp += expected_unique(seq_rows, cfg.vocab_sizes[0])
        cap = min(worst, int(exp * safety) + 1)
    else:
        raise ModelFeedError(f"mode must be 'worst' or 'expected', got {mode!r}")
    return max(multiple, -(-cap // multiple) * multiple)


# ------------------------------------------------------------------- stats
@dataclasses.dataclass
class TrainFeedStats:
    """The train-feed tier: where the stage->train boundary's time went.

    Attached to :class:`~repro.core.pipeline.PipelineStats.train_feed` by
    the runners (duck-typed off the train step's ``feed_stats`` attribute)
    so "adapt" is measurable separately from "train".
    """

    steps: int = 0
    fused_steps: int = 0        # steps whose adaptation ran inside the train jit
    adapt_seconds: float = 0.0  # host time preparing the feed (select + eager apply)
    adapt_dispatches: int = 0   # eager device dispatches spent adapting (0 when fused)
    unique_ids: int = 0         # sum over steps of the dedup'd working-set count
    total_ids: int = 0          # sum over steps of ids referenced (batch x fields)
    overflows: int = 0          # steps whose unique count saturated the capacity
    # mesh two-stage dedup only: sum over steps of stage-1 (per-device)
    # unique counts — the pooled-exchange volume before the global unique
    local_unique_ids: int = 0

    @property
    def adapt_dispatches_per_step(self) -> float:
        return self.adapt_dispatches / max(self.steps, 1)

    @property
    def dispatches_per_step(self) -> float:
        """Total stage->train boundary dispatches per step: the eager
        adaptation ops plus the single train-jit call. 1.0 means the whole
        boundary is one fused dispatch."""
        return (self.adapt_dispatches + self.steps) / max(self.steps, 1)

    @property
    def pool_ratio(self) -> float:
        """stage-1 unique ids / referenced ids — how much the local dedup
        shrinks the cross-device id pool before the global unique (0 when
        the step reports no stage-1 counts, i.e. single-device)."""
        return self.local_unique_ids / max(self.total_ids, 1)

    @property
    def unique_ratio(self) -> float:
        """unique ids / referenced ids — the dedup win ([37]: collective
        traffic is proportional to this, not to batch x fields)."""
        return self.unique_ids / max(self.total_ids, 1)

    def as_metrics(self) -> Dict[str, float]:
        """Flat numeric snapshot for :class:`repro.obs.MetricsRegistry`."""
        return harvest(self)

    def summary(self) -> str:
        return (f"steps={self.steps} (fused={self.fused_steps}) "
                f"adapt={self.adapt_seconds:.3f}s "
                f"dispatches/step={self.dispatches_per_step:.1f} "
                f"unique_ratio={self.unique_ratio:.3f} "
                f"overflows={self.overflows}")


# --------------------------------------------------------------- the plan
@dataclasses.dataclass
class ModelFeed:
    """Compile-time spec->arch adaptation plan (build via :func:`compile`).

    All remap indices, modulo vectors, and synthesis/tile plans are static
    numpy/python constants, so :meth:`apply` is traceable: the fused step
    from :meth:`make_step` runs the whole adaptation inside the train jit.
    """

    config: Any                       # arch config, dedup capacity tuned
    slots: Tuple[str, ...]            # env slots apply() consumes
    split: bool                       # consume per-field batch_field_NN vectors
    n_spec_fields: int
    field_sources: np.ndarray         # (n_model_fields,) spec field per model field
    vocab: np.ndarray                 # (n_model_fields,) int32 modulo vector
    dense_from: Optional[str]         # "batch_dense" | "sparse" | None
    seq_from: Optional[str]           # "batch_seq_ids" | "sparse" | None
    dedup_capacity: int
    stats: TrainFeedStats = dataclasses.field(default_factory=TrainFeedStats)
    _eager_ops: Optional[int] = dataclasses.field(default=None, repr=False)

    # ------------------------------------------------------------- select
    def select(self, env: Mapping[str, Any]) -> Dict[str, Any]:
        """Filter an environment down to the slots :meth:`apply` consumes.

        Host-side dict work only (no dispatches); validates the static
        shape contract so a mis-wired env fails loudly instead of tracing
        garbage into the jit.
        """
        try:
            feed = {s: env[s] for s in self.slots}
        except KeyError as e:
            raise ModelFeedError(
                f"batch is missing adapted slot {e.args[0]!r} (feed slots: "
                f"{self.slots}; batch slots: "
                f"{sorted(k for k in env if k.startswith('batch_'))})"
            ) from None
        width = (feed[field_slot(0)].ndim if self.split
                 else feed["batch_sparse"].shape[1])
        want = 1 if self.split else self.n_spec_fields
        if width != want:
            raise ModelFeedError(
                f"sparse feed shape mismatch: got width {width}, compiled "
                f"for {want} ({'split' if self.split else 'packed'} layout)")
        return feed

    # -------------------------------------------------------------- apply
    def apply(self, feed: Mapping[str, Any]) -> Dict[str, Any]:
        """Adapt one feed (see :meth:`select`) to a model batch.

        Pure jnp over compile-time constants — call it eagerly (the
        benchmark baseline) or let :meth:`make_step` trace it inside the
        train jit. Bit-identical to :func:`fe_env_to_model_batch_ref`.
        """
        cfg = self.config
        if self.split:
            fields = [jnp.asarray(feed[field_slot(i)])
                      for i in range(self.n_spec_fields)]
            sel = jnp.stack([fields[i] for i in self.field_sources], axis=1)
            packed = (jnp.stack(fields, axis=1)
                      if "sparse" in (self.dense_from, self.seq_from) else None)
        else:
            packed = jnp.asarray(feed["batch_sparse"])
            sel = packed[:, self.field_sources]
        vocab = jnp.asarray(self.vocab)
        batch: Dict[str, Any] = {
            "sparse": (sel % vocab).astype(jnp.int32),
            "label": jnp.asarray(feed["batch_label"]).astype(jnp.float32),
        }
        if self.dense_from is not None:
            if self.dense_from == "batch_dense":
                dense = jnp.asarray(feed["batch_dense"]).astype(jnp.float32)
            else:
                dense = jnp.log1p(packed.astype(jnp.float32))
            reps = -(-cfg.n_dense // dense.shape[1])  # ceil
            batch["dense"] = jnp.tile(dense, (1, reps))[:, :cfg.n_dense]
        if self.seq_from is not None:
            seq = (jnp.asarray(feed["batch_seq_ids"])
                   if self.seq_from == "batch_seq_ids" else packed)
            reps = -(-cfg.seq_len // seq.shape[1])
            batch["seq"] = (jnp.tile(seq, (1, reps))[:, :cfg.seq_len]
                            % cfg.vocab_sizes[0]).astype(jnp.int32)
        return batch

    def model_ids_np(self, env: Mapping[str, Any]
                     ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Host twin of :meth:`apply`'s *id* arithmetic: the model batch's
        ``sparse`` (and bst ``seq``) blocks, as numpy, straight from a
        pre-staging env.

        Integer remap + modulo only, so the values are bitwise-identical to
        the device path — the hierarchical-PS prefetch stage
        (:class:`repro.embedding.psfeed.HierarchyFeed`) uses this to build
        the working set *before* the batch reaches the device.
        """
        cfg = self.config
        if self.split:
            fields = [np.asarray(env[field_slot(i)])
                      for i in range(self.n_spec_fields)]
            sel = np.stack([fields[i] for i in self.field_sources], axis=1)
            packed = (np.stack(fields, axis=1)
                      if self.seq_from == "sparse" else None)
        else:
            packed = np.asarray(env["batch_sparse"])
            sel = packed[:, self.field_sources]
        sparse = (sel % self.vocab).astype(np.int32)
        seq = None
        if self.seq_from is not None:
            src = (np.asarray(env["batch_seq_ids"])
                   if self.seq_from == "batch_seq_ids" else packed)
            reps = -(-cfg.seq_len // src.shape[1])
            seq = (np.tile(src, (1, reps))[:, :cfg.seq_len]
                   % cfg.vocab_sizes[0]).astype(np.int32)
        return sparse, seq

    def eager_adapt_ops(self, feed: Mapping[str, Any]) -> int:
        """Device dispatches one eager :meth:`apply` costs (jaxpr op count,
        cached — the feed's static shape contract makes it batch-invariant)."""
        if self._eager_ops is None:
            jaxpr = jax.make_jaxpr(self.apply)(
                {k: np.asarray(v) for k, v in feed.items()})
            self._eager_ops = len(jaxpr.jaxpr.eqns)
        return self._eager_ops

    # --------------------------------------------------------------- step
    def make_step(self, train_step: Callable, *, fused: bool = True,
                  donate: bool = True,
                  fence_cb: Optional[Callable[[Any], None]] = None,
                  extra_slots: Tuple[str, ...] = ()):
        """Wrap an unjitted ``(params, opt_state, batch) -> (params,
        opt_state, metrics)`` train step into the compiled boundary step
        ``(params, opt_state, env) -> (params, opt_state, metrics)``.

        ``fused=True`` traces :meth:`apply` inside the train jit (one
        dispatch covers adapt + train); ``fused=False`` keeps the eager
        adaptation (the measurable before). ``donate=True`` donates params,
        optimizer state, AND the staged batch through the jit, so
        arena-staged device slots are reused in place — pair with
        :meth:`~repro.core.devicefeed.DeviceFeeder.donation_fence` via
        ``fence_cb`` (called with a step output after every call) so the
        feeder's completion gate can account the donated buffers.

        ``extra_slots`` names env slots forwarded *verbatim* into the train
        step's batch, bypassing :meth:`apply` — the hierarchical-PS backend
        rides its pulled working-set arrays (``_ws_rows``/``_ws_unique``/...)
        through the boundary this way. They are part of the donated batch
        argument, so working-set buffers are donated into the jit like any
        staged slot.

        The returned callable carries ``feed_stats`` (this plan's
        :class:`TrainFeedStats`), which the pipeline runners adopt into
        ``PipelineStats.train_feed``.

        Under an enabled tracer each call records ``train.adapt`` (host
        feed selection), ``train.dispatch`` (the jit call) and
        ``train.sync`` (:meth:`_record`'s blocking readback) spans. Before
        its first call under a given enabled tracer, the step records on it
        which compiled instruction runs in which device phase
        (:func:`repro.obs.phases.phase_map`, annotation ``step.phases``).
        """
        donate_args = (0, 1, 2) if donate else ()
        extra_slots = tuple(extra_slots)
        if fused:
            def _boundary(params, opt_state, feed):
                with jax.named_scope(phases.FEED):
                    batch = self.apply(feed)
                batch.update({k: feed[k] for k in extra_slots})
                return train_step(params, opt_state, batch)
            jitted = jax.jit(_boundary, donate_argnums=donate_args)
        else:
            jitted = jax.jit(train_step, donate_argnums=donate_args)
        stats = self.stats

        def _select_with_extras(env):
            feed = self.select(env)
            try:
                feed.update({k: env[k] for k in extra_slots})
            except KeyError as e:
                raise ModelFeedError(
                    f"batch is missing extra slot {e.args[0]!r} (extra "
                    f"slots: {extra_slots}) — is the working-set prefetch "
                    f"stage wired in?") from None
            return feed

        # The enabled tracer that holds this step's phase map, if any.
        mapped_for = [None]

        def step(params, opt_state, env):
            tracer = get_tracer()
            w0 = tracer.now_ns() if tracer.enabled else 0
            t0 = time.perf_counter()
            feed = _select_with_extras(env)
            if fused:
                stats.fused_steps += 1
            else:
                stats.adapt_dispatches += self.eager_adapt_ops(self.select(env))
                extras = {k: feed[k] for k in extra_slots}
                feed = self.apply(feed)  # eager: each op its own dispatch
                feed.update(extras)
            stats.adapt_seconds += time.perf_counter() - t0
            if tracer.enabled:
                tracer.complete("train.adapt", w0, tracer.now_ns(),
                                fused=fused)
            with warnings.catch_warnings():
                if donate:
                    # The staged batch rarely aliases an output shape; the
                    # donation is still wanted (params/opt DO alias, and
                    # the feeder accounts batch donation via the fence).
                    warnings.filterwarnings("ignore", message=_DONATE_MSG)
                if tracer.enabled and mapped_for[0] is not tracer:
                    # Once per tracer, before the step runs: which compiled
                    # instruction is in which phase (shares the jit's
                    # compile, so nothing compiles twice).
                    mapped_for[0] = tracer
                    compiled = jitted.lower(params, opt_state, feed).compile()
                    tracer.annotate(phases.STEP_PHASES,
                                    phases.phase_map(compiled.as_text()))
                with tracer.span("train.dispatch"):
                    new_params, new_opt, metrics = jitted(params, opt_state,
                                                          feed)
            stats.steps += 1
            # Register the fence BEFORE touching metric values: _record
            # blocks on the step's results, and the feeder may already be
            # waiting on this step's fence to reclaim a donated buffer.
            if fence_cb is not None:
                fence = metrics.get("loss")
                if fence is None and metrics:
                    fence = next(iter(metrics.values()))
                fence_cb(fence)
            with tracer.span("train.sync"):
                self._record(metrics)
            return new_params, new_opt, metrics

        step.feed_stats = stats
        # Expose the underlying jit so drivers/benchmarks can lower it for
        # HLO cost analysis (repro.launch.hlo_stats.step_cost) without
        # re-deriving the boundary function; select_feed builds the exact
        # feed argument the jit expects (extra slots included).
        step.jitted = jitted
        step.select_feed = _select_with_extras
        return step

    def _record(self, metrics: Mapping[str, Any]) -> None:
        u = metrics.get("unique")
        if u is None:
            return  # non-working-set step (e.g. the dense nodedup baseline)
        u = int(u)
        self.stats.unique_ids += u
        n = metrics.get("n_ids")
        if n is not None:
            self.stats.total_ids += int(n)
        lu = metrics.get("local_unique")
        if lu is not None:
            self.stats.local_unique_ids += int(lu)
        if self.dedup_capacity and u >= self.dedup_capacity:
            if self.stats.overflows == 0:
                warnings.warn(
                    f"dedup working set saturated (unique={u} >= capacity="
                    f"{self.dedup_capacity}): ids beyond the capacity are "
                    f"silently dropped from the working set — raise the "
                    f"rows hint / dedup_capacity", RuntimeWarning,
                    stacklevel=2)
            self.stats.overflows += 1


# ----------------------------------------------------------------- compile
def compile(plan, cfg, *, split_sparse_fields: bool = False,
            rows_hint: Optional[int] = None, capacity_mode: str = "worst",
            safety: float = 1.15) -> ModelFeed:
    """Derive the :class:`ModelFeed` adaptation plan for ``plan`` x ``cfg``.

    ``plan`` is a compiled :class:`~repro.fe.featureplan.FeaturePlan` (or a
    bare :class:`~repro.fe.compiler.OutputLayout`). ``split_sparse_fields``
    selects the per-field ``batch_field_NN`` feed form the arena binding
    stages (one id vector per spec field, no packed host intermediate).
    When ``cfg.dedup_capacity`` is 0 and ``rows_hint`` is given, the
    returned plan's :attr:`ModelFeed.config` carries a
    :func:`dedup_capacity_hint`-tuned capacity, so building the sparse
    train step from it runs the working-set path by default.
    """
    layout: OutputLayout = getattr(plan, "layout", plan)
    emitted = set(getattr(plan, "output_slots", ())
                  or (name for name, *_ in layout.feed_slots()))
    if layout.n_sparse_fields <= 0 or "batch_sparse" not in emitted:
        raise ModelFeedError(
            f"model feed needs a sparse block; layout emits {sorted(emitted)}")
    if getattr(cfg, "n_sparse", 0) <= 0:
        raise ModelFeedError("arch config has no sparse fields")

    n_spec = layout.n_sparse_fields
    field_sources = np.arange(cfg.n_sparse) % n_spec
    vocab = np.asarray(cfg.vocab_sizes[:cfg.n_sparse], np.int32)
    dense_from = None
    if cfg.n_dense:
        dense_from = ("batch_dense" if "batch_dense" in emitted else "sparse")
    seq_from = None
    if cfg.kind == "bst":
        seq_from = ("batch_seq_ids" if "batch_seq_ids" in emitted
                    else "sparse")

    slots = ["batch_label"]
    slots.extend(field_slots(n_spec) if split_sparse_fields
                 else ("batch_sparse",))
    if dense_from == "batch_dense":
        slots.append("batch_dense")
    if seq_from == "batch_seq_ids":
        slots.append("batch_seq_ids")

    if getattr(cfg, "dedup_capacity", 0) == 0 and rows_hint:
        cfg = dataclasses.replace(
            cfg, dedup_capacity=dedup_capacity_hint(
                cfg, rows_hint, mode=capacity_mode, safety=safety))

    return ModelFeed(
        config=cfg,
        slots=tuple(slots),
        split=split_sparse_fields,
        n_spec_fields=n_spec,
        field_sources=field_sources,
        vocab=vocab,
        dense_from=dense_from,
        seq_from=seq_from,
        dedup_capacity=int(getattr(cfg, "dedup_capacity", 0)),
    )
