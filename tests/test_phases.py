"""The train step names its own work: device phases and host sub-spans.

* every top-level instruction of the compiled sparse step (dlrm and dcn-v2
  smoke) and of the mesh step (a simulated 2x2) that does work is in one of
  the phases ``repro.obs.phases`` lists;
* ``ModelFeed``'s step records that map on its tracer once, before it runs,
  and never with tracing off;
* ``train.dispatch`` and ``train.sync`` nest inside ``train.step``, and the
  benchmark's trace reduction names an idle gap by them;
* the benchmark's readers of these names (``perfbench/metrics``) read the
  right number from a small synthetic run, and nothing without a trace.
"""

import ast
import dataclasses
import os
import subprocess
import sys
import threading

import pytest

import jax

from repro.configs import get_arch
from repro.fe import featureplan, get_spec
from repro.fe.datagen import gen_views
from repro.models import recsys as R
from repro.obs import NULL_SPAN, Tracer, phases, set_tracer
from repro.train.optimizer import adamw

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # the benchmark harness lives at the checkout root
    sys.path.insert(0, REPO)

pytestmark = pytest.mark.filterwarnings(
    "ignore:Some donated buffers were not usable")

ROWS = 32
SPARSE_PHASES = {"feed", "dedup", "lookup", "fwd_bwd", "dense_update",
                 "embed_update"}
MESH_PHASES = {"dedup", "exchange", "fwd_bwd", "grad_reduce",
               "dense_update", "embed_update"}


@pytest.fixture
def traced():
    tracer = Tracer(enabled=True)
    prev = set_tracer(tracer)
    yield tracer
    set_tracer(prev)


def _sparse_step(arch, spec="dlrm"):
    """ModelFeed's fused, donated step over the sparse step, its state, and
    a few staged batches, at smoke size."""
    plan = featureplan.compile(get_spec(spec))
    mf = plan.model_feed(get_arch(arch).smoke(), rows_hint=ROWS)
    raw, init_opt, _ = R.make_sparse_train_step(mf.config, adamw(1e-3))
    params = R.init_params(mf.config, jax.random.PRNGKey(0))
    state = {"params": params, "opt": init_opt(params)}
    envs = [plan.run(gen_views(ROWS, seed=i)) for i in range(3)]
    return mf.make_step(raw, donate=True), state, envs


def _assert_named(text, want):
    instrs = phases.entry_instructions(text)
    assert phases.unmapped_work(text) == []
    assert set(phases.phase_map(text).values()) == want
    # step code under no scope would show as a jit op_name without a phase
    unscoped = [n for n, (_, meta, _) in instrs.items()
                if meta and meta.startswith("jit(") and phases.phase_of(meta) is None]
    assert unscoped == []


# ------------------------------------------------------------ phase map
HLO = """HloModule m, is_scheduled=true

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %inner = f32[4]{0} negate(%p), metadata={op_name="jit(f)/fwd_bwd/neg"}
}

ENTRY %main (x: f32[4], y: s32[]) -> (f32[4], f32[4], s32[]) {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %y = s32[] parameter(1)
  %sort.1 = f32[4]{0} sort(%x), dimensions={0}, metadata={op_name="jit(f)/dedup/jit(sort)/sort"}
  %copy.2 = f32[4]{0} copy(%sort.1)
  %call.3 = f32[4]{0} call(%copy.2), to_apply=%body, metadata={op_name="jit(f)/fwd_bwd/call"}
  %copy.4 = f32[4]{0} copy(%call.3)
  %constant.5 = s32[] constant(7)
  %copy.6 = s32[] copy(%constant.5)
  ROOT %tuple = (f32[4]{0}, f32[4]{0}, s32[]) tuple(%call.3, %copy.4, %copy.6)
}
"""


def test_phase_map_reads_op_names_and_falls_back_to_neighbours():
    pm = phases.phase_map(HLO)
    assert pm["sort.1"] == "dedup"           # its own op_name
    assert pm["call.3"] == "fwd_bwd"
    assert pm["copy.2"] == "fwd_bwd"         # no metadata: its consumer's
    assert pm["copy.4"] == "fwd_bwd"         # consumer is the tuple: its operand's
    assert pm["copy.6"] == "fwd_bwd"         # a constant's copy: scheduled before it
    assert "inner" not in pm                 # nested computations are not top-level
    assert phases.unmapped_work(HLO) == []
    instrs = phases.entry_instructions(HLO)
    assert instrs["call.3"][0] == "call" and instrs["call.3"][2] == ["copy.2"]
    assert instrs["tuple"][2] == ["call.3", "copy.4", "copy.6"]


def test_phase_of_takes_the_innermost_phase():
    assert phases.phase_of("jit(f)/shard_map/dedup/jit(searchsorted)/while") == "dedup"
    assert phases.phase_of("jit(f)/fwd_bwd/transpose(jvp(jit(_take)))/scatter-add") \
        == "fwd_bwd"
    assert phases.phase_of("jit(f)/reduce_sum") is None
    assert phases.phase_of(None) is None


@pytest.mark.parametrize("arch", ["dlrm-mlperf", "dcn-v2"])
def test_sparse_step_instructions_all_map_to_phases(arch):
    step, state, envs = _sparse_step(arch)
    feed = step.select_feed(envs[0])
    text = step.jitted.lower(state["params"], state["opt"], feed).compile().as_text()
    _assert_named(text, SPARSE_PHASES)


def test_mesh_step_instructions_all_map_to_phases():
    code = """
import numpy as np, jax, jax.numpy as jnp
import repro.models.recsys as R
from repro.launch.mesh import make_train_mesh
from repro.obs import phases
from repro.train.optimizer import adamw
cfg = R.RecsysConfig(name="t", kind="dlrm", n_dense=13, n_sparse=6,
                     embed_dim=16, vocab_sizes=(64, 32, 128, 16, 8, 40),
                     bot_mlp=(32, 16), top_mlp=(64, 32, 1),
                     dedup_capacity=256, row_align=8)
assert len(jax.devices()) == 4
mesh = make_train_mesh(2, 2)
step, init, _ = R.make_mesh_train_step(cfg, adamw(1e-3), mesh=mesh,
                                       compress=None, local_dedup_capacity=64)
params, opt = R.shard_train_state(mesh, R.init_params(cfg, jax.random.PRNGKey(0)),
                                  init(R.init_params(cfg, jax.random.PRNGKey(0))))
b = {"dense": jnp.zeros((64, 13)), "label": jnp.zeros((64,)),
     "sparse": jnp.zeros((64, 6), jnp.int32)}
text = jax.jit(step).lower(params, opt, b).compile().as_text()
pm = phases.phase_map(text)
print("UNMAPPED", phases.unmapped_work(text))
print("PHASES", sorted(set(pm.values())))
print("COLLECTIVES", sorted({pm[n] for n, (op, _, _) in
      phases.entry_instructions(text).items() if op.startswith("all-")}))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    lines = dict(line.split(" ", 1) for line in out.stdout.splitlines())
    assert lines["UNMAPPED"] == "[]"
    assert lines["PHASES"] == str(sorted(MESH_PHASES))
    # the pool all-gather is dedup's; every other collective is the
    # working-set exchange's or the gradient reduction's
    assert set(ast.literal_eval(lines["COLLECTIVES"])) <= {"dedup", "exchange", "grad_reduce"}


# ------------------------------------------------- recording on the tracer
def test_phase_map_recorded_once_before_the_step_runs(traced, monkeypatch):
    step, state, envs = _sparse_step("dlrm-mlperf")
    seen = []
    real = traced.annotate

    def annotate(key, value):
        before = {e["name"] for e in traced.to_dict()["traceEvents"]
                  if e.get("ph") == "B"}
        seen.append((key, before))
        real(key, value)

    monkeypatch.setattr(traced, "annotate", annotate)
    p, o = state["params"], state["opt"]
    for env in envs:
        p, o, _ = step(p, o, env)
    assert [k for k, _ in seen] == [phases.STEP_PHASES]
    assert "train.adapt" in seen[0][1]         # recorded on the first call,
    assert "train.dispatch" not in seen[0][1]  # before the step ran
    mapped = traced.metadata[phases.STEP_PHASES]
    assert set(mapped.values()) == SPARSE_PHASES
    # exported with the trace, beside the clock it is on
    md = traced.to_dict()["metadata"]
    assert md[phases.STEP_PHASES] == mapped
    assert md["clock"] == "perf_counter_ns"


def test_nothing_compiled_or_recorded_with_tracing_off(monkeypatch):
    off = Tracer(enabled=False)
    prev = set_tracer(off)
    try:
        from repro.fe import modelfeed
        monkeypatch.setattr(modelfeed.phases, "phase_map", lambda text: 1 / 0)
        step, state, envs = _sparse_step("dlrm-mlperf")
        p, o = state["params"], state["opt"]
        for env in envs:
            p, o, _ = step(p, o, env)
    finally:
        set_tracer(prev)
    assert off.span("train.dispatch") is NULL_SPAN
    assert phases.STEP_PHASES not in off.metadata
    assert off.n_events == 0


def test_dispatch_and_sync_nest_in_train_step_and_name_a_gap(traced):
    from perfbench.harness import tracing

    from repro.core import PipelinedRunner

    step, state, envs = _sparse_step("dlrm-mlperf")

    def train(st, env):
        p, o, _ = step(st["params"], st["opt"], env)
        return {"params": p, "opt": o}

    PipelinedRunner([], train, prefetch=2).run(state, envs)
    spans = tracing.program_spans(traced)
    me = threading.current_thread().name
    steps = [(a, b) for n, t, a, b in spans if n == "train.step" and t == me]
    assert len(steps) == 3
    for name in ("train.adapt", "train.dispatch", "train.sync"):
        inner = [(a, b) for n, t, a, b in spans if n == name and t == me]
        assert len(inner) == 3, name
        assert all(any(s <= a and b <= e for s, e in steps) for a, b in inner)
    # a device gap whose middle falls in the dispatch is named by it
    d0, d1 = next((a, b) for n, t, a, b in spans if n == "train.dispatch")
    mid = (d0 + d1) // 2
    lo, hi = steps[0][0], steps[-1][1]
    ops = {0: [("fusion.1 f32[8]", lo, mid - 1), ("fusion.2 f32[8]", mid + 1, hi)]}
    red = tracing.reduce(ops, spans, [], (lo, hi), main_thread=me)
    assert red["idle_gaps"][0][0].split("|")[0] == "train.dispatch"


# ------------------------------------------------------ benchmark readers
def _run(trace=True, spans=None):
    from perfbench.harness import bench
    run = bench.Run(cfg={}, device_kind="TPU v5 lite", n_devices=1,
                    rows_per_step=8, t_open=0.0, completions=[1.0, 2.0],
                    setup_s=1.0, n_unique=[1, 1])
    if trace:
        run.trace = {"ops_by_name": {
            "fusion.1 s32[64]": 0.004, "while.2 (s32[],": 0.002,
            "fusion.3 f32[8,8]": 0.010, "fusion.4 f32[64,8]": 0.006,
            "all-reduce.5 f32[64]": 0.003, "all-reduce.6 f32[64]": 0.001,
            "fusion.77 s32[64]": 0.5}}   # nested in while.2: in no map
    run.spans = spans
    run.window_ns = (0, 10 * 10**9)
    return run


PHASE_MAP = {"fusion.1": "dedup", "while.2": "dedup", "fusion.3": "fwd_bwd",
             "fusion.4": "embed_update", "all-reduce.5": "exchange",
             "all-reduce.6": "grad_reduce"}


@pytest.mark.parametrize("metric,ms", [
    ("step.dedup_ms", 3.0), ("step.fwd_bwd_ms", 5.0),
    ("step.embed_update_ms", 3.0), ("step.dedup_ms.mesh", 3.0),
    ("step.embed_update_ms.mesh", 3.0),
    ("comm.exchange_ms_per_step.mesh", 1.5),
    ("comm.grad_reduce_ms_per_step.mesh", 0.5)])
def test_device_phase_reader(traced, metric, ms):
    from perfbench.harness import spec

    read = spec.reader(metric)
    assert read(_run()) is None               # no map on the tracer yet
    traced.annotate(phases.STEP_PHASES, PHASE_MAP)
    assert read(_run()) == pytest.approx(ms)  # seconds over 2 steps, in ms
    assert read(_run(trace=False)) is None


@pytest.mark.parametrize("metric,span", [
    ("train.dispatch_ms_per_step", "train.dispatch"),
    ("host.gc_ms_per_step.stream", "gc.collect")])
def test_span_reader(metric, span):
    from perfbench.harness import spec

    read = spec.reader(metric)
    s = 10**9
    spans = [("train.step", "MainThread", 0, 4 * s),
             (span, "MainThread" if span.startswith("train") else "gc",
              s, s + 3 * 10**6),
             (span, "gc", 11 * s, 12 * s)]      # outside the window
    assert read(_run(spans=spans)) == pytest.approx(1.5)
    assert read(_run(spans=spans[:1])) is None  # the program names no such span
    assert read(_run(spans=None)) is None


def test_readers_read_nothing_from_a_program_without_a_map(monkeypatch):
    """A tracer from before the phase map has no metadata at all."""
    from perfbench.harness import named_work

    from repro.obs import trace

    @dataclasses.dataclass
    class OldTracer:
        enabled: bool = True

    monkeypatch.setattr(trace, "_tracer", OldTracer())
    assert named_work.phase_map() is None
    assert named_work.phase_ms_per_step(_run(), "dedup") is None
