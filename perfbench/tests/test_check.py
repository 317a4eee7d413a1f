"""The correctness check passes sound runs and catches broken ones.

Each case drives a whole benchmark run of a tiny cell on the CPU, past the
harness's look for a chip, with the program's timed path broken underneath,
and reads ``correct``.
"""

import time

import jax.numpy as jnp
import pytest

from perfbench import control
from perfbench.harness import bench
from perfbench.tests import tiny

CASES = [("dlrm", "preextracted"), ("dcnv2", "stream"), ("dlrm", "mesh")]


def _run(kind, traffic, seed=2**31 + 5):
    c = tiny.cell(kind, traffic, chips=4 if traffic == "mesh" else 1)
    return bench.run_cell(c["workload"]["name"], seed, 0.3, False,
                          t_start=time.perf_counter(), require_tpu=False, cell=c)


@pytest.mark.parametrize("kind,traffic", CASES)
def test_sound_run_is_correct(kind, traffic):
    out = _run(kind, traffic)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


def _wrap(monkeypatch, name, body):
    from repro.models import recsys
    orig = getattr(recsys, name)

    def make(*a, **kw):
        step, init, abstract = orig(*a, **kw)
        return (lambda p, o, b: body(step, p, o, b)), init, abstract
    monkeypatch.setattr(recsys, name, make)


def _step_name(traffic):
    return "make_mesh_train_step" if traffic == "mesh" else "make_sparse_train_step"


@pytest.mark.parametrize("kind,traffic", CASES)
def test_state_returned_unchanged_is_caught(monkeypatch, kind, traffic):
    def unchanged(step, p, o, b):
        return (p, o, step(p, o, b)[2])
    _wrap(monkeypatch, _step_name(traffic), unchanged)
    assert not _run(kind, traffic)["correct"]


@pytest.mark.parametrize("kind,traffic", CASES)
def test_half_batch_left_out_is_caught(monkeypatch, kind, traffic):
    def half(step, p, o, b):
        n = b["label"].shape[0] // 2
        return step(p, o, {k: v[:n] for k, v in b.items()})
    if traffic == "mesh":  # the mesh splits the rows: keep the count, twice over
        def half(step, p, o, b):  # noqa: F811
            n = b["label"].shape[0] // 2
            return step(p, o, {k: jnp.concatenate([v[:n], v[:n]]) for k, v in b.items()})
    _wrap(monkeypatch, _step_name(traffic), half)
    assert not _run(kind, traffic)["correct"]


def test_exchange_between_chips_left_out_is_caught(monkeypatch):
    from repro.train import compression
    monkeypatch.setattr(compression, "hierarchical_psum",
                        lambda vec, *a, residual=None, **kw: (vec, residual))
    assert not _run("dlrm", "mesh")["correct"]


@pytest.mark.parametrize("where", ["feed", "step"])
@pytest.mark.parametrize("kind,traffic", CASES)
def test_token_altered_where_produced_is_caught(monkeypatch, kind, traffic, where):
    if where == "feed" and traffic == "stream":  # an FE hash altered
        from repro.fe import ops
        orig_hash = ops.fmix32
        monkeypatch.setattr(ops, "fmix32", lambda x: orig_hash(x) ^ jnp.uint32(1))
    elif where == "feed":  # the batch handed to the staging altered
        import numpy as np
        from repro.core import pipeline
        orig_layers = pipeline.run_layers

        def run_layers(layers, env, **kw):
            out = orig_layers(layers, env, **kw)
            key = "batch_sparse" if "batch_sparse" in env else "batch_field_00"
            ids = np.array(env[key])
            if ids.ndim == 1:
                ids //= 2
            else:
                ids[:, 0] //= 2
            env[key] = ids
            return out
        monkeypatch.setattr(pipeline, "run_layers", run_layers)
    else:  # the adapted model batch altered inside the step
        from repro.fe.modelfeed import ModelFeed
        orig_apply = ModelFeed.apply

        def apply(self, feed):
            out = orig_apply(self, feed)
            out["sparse"] = out["sparse"].at[:, 0].set(
                (out["sparse"][:, 0] + 1) % int(self.vocab[0]))
            return out
        monkeypatch.setattr(ModelFeed, "apply", apply)
    assert not _run(kind, traffic)["correct"]


@pytest.mark.parametrize("kind,traffic", CASES)
def test_staging_fault_once_the_ring_wraps_is_caught(monkeypatch, kind, traffic):
    """A batch altered only once the checked steps' batches are staged, as
    a fault on slot reuse would be, is caught by the copy of the last
    warm-up step's batch."""
    import numpy as np
    if traffic == "mesh":  # host arrays, no arena: alter what is handed on
        from repro.core import pipeline
        orig_layers = pipeline.run_layers
        calls = [0]

        def run_layers(layers, env, **kw):
            out = orig_layers(layers, env, **kw)
            calls[0] += 1
            if calls[0] > bench.CHECKED_STEPS:
                ids = np.array(env["batch_sparse"])
                ids[:, 0] //= 2
                env["batch_sparse"] = ids
            return out
        monkeypatch.setattr(pipeline, "run_layers", run_layers)
    else:  # the arena slot altered in place before its transfer
        from repro.core.devicefeed import DeviceFeeder
        orig_transfer = DeviceFeeder._transfer

        def transfer(self, env, claim, t0):
            if self.stats.batches >= bench.CHECKED_STEPS:
                claim.views["batch_field_00"][:] //= 2
            return orig_transfer(self, env, claim, t0)
        monkeypatch.setattr(DeviceFeeder, "_transfer", transfer)
    out = _run(kind, traffic)
    assert not out["correct"]
    failed = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    assert failed <= {"feed_mismatches", "fe_mismatches"}, out["checks"]


@pytest.mark.parametrize("kind,traffic", CASES)
def test_control_fails_the_limits(kind, traffic):
    """The reference in bfloat16, in the program's place, is not correct."""
    import jax
    c = tiny.cell(kind, traffic, rows=256)
    out = control.readings(c, 11, jax.devices()[:1])["control"]
    assert any(v > c["limits"][k] for k, v in out.items()), out
