"""Compile the main path's programs for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached: what it refuses here (an op Mosaic cannot lower, a
program that does not fit the chip's HBM) would fail on the chip. Nothing
runs, so these tests say nothing about results or speed. The topology is
described inside a fixture, never at import: only one process at a time may
load the TPU library, and every test worker imports this file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

HBM_BYTES = 16 * 2**30   # one v5e chip
ROWS = 4096              # rows per step, as chip_smoke.py trains

# The staged feed rarely aliases an output; the driver filters this too.
pytestmark = pytest.mark.filterwarnings(
    "ignore:Some donated buffers were not usable")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def dlrm_plan():
    from repro.fe import featureplan, get_spec
    return featureplan.compile(get_spec("dlrm"))


def _abstract(tree, sharding):
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _feed_shapes(plan, mf, sharding=None):
    """ShapeDtypeStructs of the feed the fused step consumes at ROWS rows."""
    import jax
    slots = {s.name: s for s in
             plan.feed_layout(split_sparse_fields=mf.split).slots}
    out = {}
    for name in mf.slots:
        s = slots[name]
        shape = (ROWS,) if s.rank1 else (ROWS, s.width)
        out[name] = jax.ShapeDtypeStruct(shape, np.dtype(s.dtype),
                                         sharding=sharding)
    return out


def _dcn_v2_step(plan, make_step, **step_kw):
    """The driver's fused, donated step at DCN-v2's published widths."""
    from repro.configs import get_arch
    from repro.train.optimizer import adamw

    mf = plan.model_feed(get_arch("dcn-v2").config, split_sparse_fields=True,
                         rows_hint=ROWS)
    raw_step, init_opt, _ = make_step(mf.config, adamw(1e-3), **step_kw)
    return mf, mf.make_step(raw_step, donate=True), init_opt


def _state_shapes(cfg, init_opt):
    import jax
    from repro.models import recsys as R

    def _init(k):
        p = R.init_params(cfg, k)
        return p, init_opt(p)
    return jax.eval_shape(_init, jax.random.PRNGKey(0))


# ------------------------------------------------------------------ kernel
@pytest.mark.parametrize("n", ["block", "dlrm_slots"])
def test_mempool_alloc_kernel_compiles(one_chip, dlrm_plan, n):
    import jax
    import jax.numpy as jnp

    from repro.kernels.mempool_alloc.kernel import BLOCK, alloc_offsets

    size = (BLOCK if n == "block"
            else len(dlrm_plan.feed_layout(split_sparse_fields=True).slots))
    x = jax.ShapeDtypeStruct((size,), jnp.int32, sharding=one_chip)
    compiled = alloc_offsets.lower(x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------------- one-chip full step
@pytest.fixture(scope="module")
def one_chip_step(one_chip, dlrm_plan):
    """The fused, donated one-chip step at DCN-v2's published widths,
    compiled for one v5e: its config and compiled program."""
    from repro.models import recsys as R

    mf, step, init_opt = _dcn_v2_step(dlrm_plan, R.make_sparse_train_step)
    params, opt = _state_shapes(mf.config, init_opt)
    compiled = step.jitted.lower(
        _abstract(params, one_chip), _abstract(opt, one_chip),
        _feed_shapes(dlrm_plan, mf, one_chip)).compile()
    return mf.config, compiled


def test_dcn_v2_published_step_fits_one_chip(one_chip_step):
    cfg, compiled = one_chip_step
    mem = compiled.memory_analysis()
    table = cfg.padded_rows * cfg.embed_dim * 4
    accum = cfg.padded_rows * 4
    # donation updates the table and its accumulators in place
    assert mem.alias_size_in_bytes >= table + accum
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def _assert_every_instruction_has_a_phase(compiled, want):
    from repro.obs import phases

    text = compiled.as_text()
    assert phases.unmapped_work(text) == []
    assert set(phases.phase_map(text).values()) == set(want)


def test_dcn_v2_published_step_names_its_phases(one_chip_step):
    """Every top-level instruction that runs on the chip, as the TPU's
    compiler leaves the program, is in one of the step's phases."""
    _assert_every_instruction_has_a_phase(one_chip_step[1], (
        "feed", "dedup", "lookup", "fwd_bwd", "dense_update", "embed_update"))


# ------------------------------------------------------------ 2x2 mesh step
@pytest.fixture(scope="module")
def mesh_step(topo, dlrm_plan):
    """The 2x2 mesh step at DCN-v2's published widths, compiled for four
    v5e chips: its config and compiled program."""
    import jax
    from jax.sharding import Mesh

    from repro.configs import get_arch
    from repro.fe.modelfeed import dedup_capacity_hint
    from repro.models import recsys as R

    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2), ("pod", "data"))
    local_cap = dedup_capacity_hint(get_arch("dcn-v2").config, ROWS // 4)
    mf, step, init_opt = _dcn_v2_step(
        dlrm_plan, R.make_mesh_train_step, mesh=mesh, compress=None,
        local_dedup_capacity=local_cap)
    cfg = mf.config
    params, opt = _state_shapes(cfg, init_opt)
    p_sh, o_sh = R.train_state_shardings(mesh, params, opt)
    args = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        (params, opt), (p_sh, o_sh))
    compiled = step.jitted.lower(*args, _feed_shapes(dlrm_plan, mf)).compile()
    return cfg, compiled


def test_dcn_v2_mesh_step_splits_table_over_four_chips(mesh_step):
    cfg, compiled = mesh_step
    per_device = compiled.memory_analysis().argument_size_in_bytes
    table = cfg.padded_rows * (cfg.embed_dim + 1) * 4   # rows + accumulators
    assert per_device == pytest.approx(table / 4, rel=0.02)


def test_dcn_v2_mesh_step_names_its_phases(mesh_step):
    """The same on the 2x2 mesh, whose step exchanges the working set and
    reduces its gradients across chips."""
    _assert_every_instruction_has_a_phase(mesh_step[1], (
        "feed", "dedup", "exchange", "fwd_bwd", "grad_reduce",
        "dense_update", "embed_update"))


def test_dcn_v2_mesh_step_dedup_runs_no_loop(mesh_step):
    """The mesh step's dedup composes the two sorts' inverses: no loop (a
    binary search over the global uniques was one) is left in its phase."""
    from repro.obs import phases

    text = mesh_step[1].as_text()
    dedup_ops = {name for name, phase in phases.phase_map(text).items()
                 if phase == phases.DEDUP}
    assert dedup_ops
    loops = [name for name, (opcode, _, _)
             in phases.entry_instructions(text).items()
             if opcode == "while" and name in dedup_ops]
    assert loops == []
