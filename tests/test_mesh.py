"""Multi-device scale-out: mesh train step, two-stage dedup, compressed
hierarchical collectives.

jax locks the device count at first init, so every multi-device check runs
in a subprocess with XLA_FLAGS set before import (same pattern as
tests/test_sharding.py). In-process tests cover the 1x1 degenerate mesh —
the shape the bitwise-equivalence guarantee is stated for — plus the pure
analytics (CommPlan byte model, mesh-spec parsing, codec normalization).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = """
cfg = R.RecsysConfig(name="t", kind="dlrm", n_dense=13, n_sparse=6,
                     embed_dim=16, vocab_sizes=(64, 32, 128, 16, 8, 40),
                     bot_mlp=(32, 16), top_mlp=(64, 32, 1),
                     dedup_capacity=256, row_align=8)

B = 64
def make_batch(i):
    r = np.random.default_rng(i)
    return {
        "dense": jnp.asarray(r.normal(size=(B, 13)).astype(np.float32)),
        "sparse": jnp.asarray(np.stack(
            [r.integers(0, v, B) for v in cfg.vocab_sizes], 1
        ).astype(np.int32)),
        "label": jnp.asarray(r.integers(0, 2, B).astype(np.float32)),
    }
"""


def run_sub(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


# ------------------------------------------------------------ 1x1 guarantee
def test_mesh_1x1_bitwise_identical_to_sparse_step():
    """On a 1x1 mesh with compression off, the mesh step IS the
    single-device step: every collective is an identity, the grad average
    is statically skipped, and five steps stay bitwise equal across
    losses, params, dense optimizer leaves, and the Adagrad accumulator."""
    import jax

    import repro.models.recsys as R
    from repro.launch.mesh import make_train_mesh
    from repro.train.optimizer import adamw

    ns = {"R": R, "np": np, "jnp": jax.numpy}
    exec(CFG, ns)
    cfg, make_batch = ns["cfg"], ns["make_batch"]

    params = R.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw(1e-3)
    step_s, init_s, _ = R.make_sparse_train_step(cfg, opt)
    step_m, init_m, abstract_m = R.make_mesh_train_step(
        cfg, opt, mesh=make_train_mesh(1, 1), compress=None)

    # no codec -> no residual in the state, identical to the sparse init
    assert set(init_m(params)) == set(init_s(params))
    assert "comm_residual" not in abstract_m(params)

    ps, os_ = dict(params), init_s(params)
    pm, om = dict(params), init_m(params)
    js, jm = jax.jit(step_s), jax.jit(step_m)
    for i in range(5):
        b = make_batch(i)
        ps, os_, ms = js(ps, os_, b)
        pm, om, mm = jm(pm, om, b)
        assert float(ms["loss"]) == float(mm["loss"]), i
        assert int(ms["unique"]) == int(mm["unique"])
        assert int(ms["n_ids"]) == int(mm["n_ids"])
    assert int(mm["local_unique"]) == int(mm["unique"])  # stage 1 == stage 2
    for k in ps:
        assert (np.asarray(ps[k]) == np.asarray(pm[k])).all(), k
    for a, b2 in zip(jax.tree.leaves(os_["dense"]),
                     jax.tree.leaves(om["dense"])):
        assert (np.asarray(a) == np.asarray(b2)).all()
    assert (np.asarray(os_["embed_accum"])
            == np.asarray(om["embed_accum"])).all()


# ------------------------------------------------------- 2x4 vs one device
def test_mesh_2x4_matches_single_device():
    """Sharded 2x4 training (row-sharded table, two-stage dedup,
    hierarchical uncompressed reduction) tracks the single-device step
    within fp32 reduction-order tolerance over 8 steps."""
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
import repro.models.recsys as R
from repro.train.optimizer import adamw
from repro.launch.mesh import make_train_mesh
""" + CFG + """
assert len(jax.devices()) == 8
params = R.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw(1e-3)
step_s, init_s, _ = R.make_sparse_train_step(cfg, opt)
mesh = make_train_mesh(2, 4)
step_m, init_m, _ = R.make_mesh_train_step(
    cfg, opt, mesh=mesh, compress=None, local_dedup_capacity=64)

ps, os_ = dict(params), init_s(params)
pm, om = R.shard_train_state(mesh, dict(params), init_m(params))
js, jm = jax.jit(step_s), jax.jit(step_m)
for i in range(8):
    b = make_batch(i)
    ps, os_, ms = js(ps, os_, b)
    pm, om, mm = jm(pm, om, b)
    np.testing.assert_allclose(float(ms["loss"]), float(mm["loss"]), rtol=2e-5)
    assert int(ms["unique"]) == int(mm["unique"])
    assert int(ms["n_ids"]) == int(mm["n_ids"])
    assert int(mm["local_unique"]) >= int(mm["unique"])  # pool over-counts
for k in ps:
    np.testing.assert_allclose(np.asarray(ps[k]), np.asarray(pm[k]),
                               rtol=3e-5, atol=2e-6, err_msg=k)
np.testing.assert_allclose(np.asarray(os_["embed_accum"]),
                           np.asarray(om["embed_accum"]),
                           rtol=3e-5, atol=2e-6)

# batch rows must split over the mesh
try:
    jm(pm, om, {k: v[:63] if v.shape[0] == B else v
                for k, v in make_batch(0).items()})
except ValueError as e:
    assert "does not split" in str(e), e
else:
    raise AssertionError("63-row batch on 8 devices should raise")
print("MESH 2x4 OK")
""")


def test_mesh_compressed_drift_bounds():
    """Satellite: bf16/int8 wire compression with fp32 accumulation and
    error feedback stays within a small drift bound of uncompressed
    training after 8 steps, and the residual state is actually carried."""
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
import repro.models.recsys as R
from repro.train.optimizer import adamw
from repro.launch.mesh import make_train_mesh
""" + CFG + """
params = R.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw(1e-3)
mesh = make_train_mesh(2, 4)
step_m, init_m, _ = R.make_mesh_train_step(
    cfg, opt, mesh=mesh, compress=None, local_dedup_capacity=64)
pm, om = R.shard_train_state(mesh, dict(params), init_m(params))
jm = jax.jit(step_m)
for i in range(8):
    pm, om, mm = jm(pm, om, make_batch(i))

for codec, bound in (("bf16", 5e-3), ("int8", 5e-2)):
    step_c, init_c, abstract_c = R.make_mesh_train_step(
        cfg, opt, mesh=mesh, compress=codec, local_dedup_capacity=64)
    oc0 = init_c(params)
    assert "comm_residual" in oc0 and "comm_residual" in abstract_c(params)
    pc, oc = R.shard_train_state(mesh, dict(params), oc0)
    jc = jax.jit(step_c)
    for i in range(8):
        pc, oc, mc = jc(pc, oc, make_batch(i))
    drift = max(float(np.max(np.abs(np.asarray(pc[k]) - np.asarray(pm[k]))))
                for k in pc)
    assert drift < bound, (codec, drift)
    assert float(np.max(np.abs(np.asarray(oc["comm_residual"])))) > 0, codec
    print(codec, "drift", drift)
print("MESH COMPRESSED OK")
""")


# ------------------------------------------------- two-stage dedup property
def test_two_stage_dedup_matches_flat_dedup():
    """Satellite property test: on a 2x4 mesh, local->global dedup agrees
    with flat single-array dedup — same unique set, and an inverse that
    reconstructs every device's ids — including FILL padding in the input
    and ids near MAX_ID."""
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.embedding.dedup import FILL, MAX_ID, dedup, dedup_two_stage_local
from repro.launch.mesh import make_train_mesh

mesh = make_train_mesh(2, 4)
N_LOCAL, CAP, LOCAL_CAP = 96, 512, 96

def body(ids):
    u, inv, cnt, lcnt = dedup_two_stage_local(
        ids[0], capacity=CAP, local_capacity=LOCAL_CAP,
        gather_axes=("pod", "data"))
    return u[None], inv[None], cnt[None], lcnt[None]

f = jax.jit(jax.shard_map(
    body, mesh=mesh,
    in_specs=P(("pod", "data")),
    out_specs=(P(("pod", "data")), P(("pod", "data")),
               P(("pod", "data")), P(("pod", "data"))),
    check_vma=False))

rng = np.random.default_rng(7)
for trial in range(6):
    ids = rng.integers(0, 500, size=(8, N_LOCAL)).astype(np.int32)
    if trial % 3 == 1:   # FILL padding mixed into the input
        ids[rng.random(ids.shape) < 0.2] = int(FILL)
    if trial % 3 == 2:   # ids hugging the top of the id space
        ids[rng.random(ids.shape) < 0.3] = MAX_ID - 1 - rng.integers(0, 3)
    u, inv, cnt, lcnt = f(jnp.asarray(ids))
    u, inv = np.asarray(u), np.asarray(inv)

    flat_u, flat_inv, flat_cnt = dedup(jnp.asarray(ids.ravel()), capacity=CAP)
    flat_u = np.asarray(flat_u)

    # every device computed the same pooled unique array, == flat dedup's
    for d in range(8):
        assert (u[d] == flat_u).all(), (trial, d)
        assert int(cnt[d]) == int(flat_cnt)
        # inverse reconstructs this device's ids (real and FILL alike:
        # FILL sorts last so searchsorted points at a FILL slot or cnt)
        real = ids[d] != int(FILL)
        assert (u[d][inv[d][real]] == ids[d][real]).all(), (trial, d)
        assert int(lcnt[d]) == len(np.unique(ids[d][real]))
print("TWO-STAGE DEDUP OK")

# capacity overflow: pooled uniques exceed the global capacity -> the kept
# set is exactly the CAP smallest uniques (jnp.unique truncation order) and
# every inverse that lands in range still reconstructs its id
CAP2 = 64
def body2(ids):
    u, inv, cnt, lcnt = dedup_two_stage_local(
        ids[0], capacity=CAP2, local_capacity=LOCAL_CAP,
        gather_axes=("pod", "data"))
    return u[None], inv[None], cnt[None], lcnt[None]
f2 = jax.jit(jax.shard_map(
    body2, mesh=mesh, in_specs=P(("pod", "data")),
    out_specs=(P(("pod", "data")),) * 4, check_vma=False))
ids = rng.integers(0, 100_000, size=(8, N_LOCAL)).astype(np.int32)
u, inv, cnt, lcnt = (np.asarray(x) for x in f2(jnp.asarray(ids)))
true_u = np.unique(ids)
assert len(true_u) > CAP2
assert (u[0] == true_u[:CAP2]).all()
for d in range(8):
    ok = inv[d] < CAP2
    assert (u[d][inv[d][ok]] == ids[d][ok]).all()
    # dropped ids are exactly those larger than the kept range
    assert (ids[d][~ok] > true_u[CAP2 - 1]).all()
print("OVERFLOW OK")

# local-capacity overflow: stage 1 truncates per device; the global set is
# then a subset of the true uniques, never an invented id
LC = 16
def body3(ids):
    u, inv, cnt, lcnt = dedup_two_stage_local(
        ids[0], capacity=CAP, local_capacity=LC,
        gather_axes=("pod", "data"))
    return u[None], inv[None], cnt[None], lcnt[None]
f3 = jax.jit(jax.shard_map(
    body3, mesh=mesh, in_specs=P(("pod", "data")),
    out_specs=(P(("pod", "data")),) * 4, check_vma=False))
u, inv, cnt, lcnt = (np.asarray(x) for x in f3(jnp.asarray(ids)))
kept = u[0][u[0] != int(FILL)]
assert int(cnt[0]) == len(kept) <= 8 * LC
assert np.isin(kept, true_u).all()
assert (lcnt == LC).all()  # every device overflowed stage 1
print("LOCAL OVERFLOW OK")
""")


# ------------------------------------------------------------ byte model
def test_comm_plan_byte_model():
    from repro.train.compression import CommPlan

    plan = CommPlan.for_step(
        n_pods=2, inner=4, compress="bf16", hierarchical=True,
        capacity=256, embed_dim=16, n_dense_elems=1000,
        local_capacity=64, ids_per_device=48)
    n = plan.allreduce_elems
    assert n == 256 * 16 + 1000
    # flat ring all-reduce moves ~2*n fp32 elements over the pod boundary;
    # hierarchical moves 2*(n/inner) wire elements
    assert plan.allreduce_interpod_bytes_flat == 2 * n * 4
    assert plan.allreduce_interpod_bytes == 2 * -(-n // 4) * 2
    # the acceptance ratio: pod_size x (fp32/bf16) = 4 * 2 = 8
    assert plan.allreduce_reduction == pytest.approx(8.0, rel=1e-3)
    assert plan.interpod_reduction > 4.0  # whole step, exchange included
    # dedup pool: (n_dev - inner) local uniques cross pods vs flat raw ids
    assert plan.dedup_interpod_bytes == (8 - 4) * 64 * 4
    assert plan.dedup_interpod_bytes_flat == (8 - 4) * 48 * 4

    int8 = CommPlan.for_step(
        n_pods=2, inner=4, compress="int8", hierarchical=True,
        capacity=256, embed_dim=16, n_dense_elems=1000,
        local_capacity=64, ids_per_device=48)
    assert int8.allreduce_interpod_bytes == 2 * -(-n // 4) * 1 + 8
    assert int8.allreduce_reduction > 12.0  # ~ 4 * 4x minus scale overhead

    one = CommPlan.for_step(
        n_pods=1, inner=1, compress=None, hierarchical=True,
        capacity=256, embed_dim=16, n_dense_elems=1000,
        local_capacity=64, ids_per_device=48)
    assert one.interpod_bytes_per_step == 0
    assert one.interpod_reduction == 1.0

    m = plan.as_metrics()
    assert m["allreduce_reduction"] == plan.allreduce_reduction
    assert m["n_devices"] == 8


def test_comm_stats_accumulates():
    from repro.train.compression import CommPlan, CommStats

    plan = CommPlan.for_step(
        n_pods=2, inner=2, compress="bf16", hierarchical=True,
        capacity=64, embed_dim=8, n_dense_elems=100,
        local_capacity=32, ids_per_device=24)
    cs = CommStats(plan=plan)
    for _ in range(3):
        cs.on_step()
    assert cs.steps == 3
    assert cs.interpod_bytes_total == 3 * plan.interpod_bytes_per_step
    assert cs.interpod_bytes_total_flat == 3 * plan.interpod_bytes_per_step_flat
    assert cs.as_metrics()["plan_n_pods"] == 2
    assert "codec=bf16" in cs.summary()


# ------------------------------------------------------------ parsing/misc
def test_parse_mesh_spec():
    from repro.launch.mesh import parse_mesh_spec

    assert parse_mesh_spec("2x4") == (2, 4)
    assert parse_mesh_spec("1X1") == (1, 1)
    assert parse_mesh_spec("2×4") == (2, 4)
    for bad in ("", "2", "2x4x8", "0x4", "ax4"):
        with pytest.raises(ValueError):
            parse_mesh_spec(bad)


def test_codec_name_normalization():
    from repro.train.compression import codec_name

    assert codec_name(None) is None
    assert codec_name(False) is None
    assert codec_name("off") is None
    assert codec_name("none") is None
    assert codec_name(True) == "bf16"
    assert codec_name("bf16") == "bf16"
    assert codec_name("int8") == "int8"
    with pytest.raises(ValueError):
        codec_name("fp8")


def test_make_train_mesh_rejects_oversubscription():
    import jax

    from repro.launch.mesh import make_train_mesh

    n = len(jax.devices())
    with pytest.raises(ValueError, match="device_count"):
        make_train_mesh(n + 1, 2)


def test_shard_bounds():
    from repro.embedding.table import shard_bounds

    assert shard_bounds(512, 8, 0) == (0, 64)
    assert shard_bounds(512, 8, 7) == (448, 512)
    with pytest.raises(ValueError):
        shard_bounds(100, 8, 0)


# ------------------------------------------------------- EF psum property
def test_hierarchical_psum_error_feedback_converges():
    """With a constant gradient, error feedback makes the *cumulative*
    compressed sum track the exact sum (error stays O(one quantization
    step) instead of growing linearly)."""
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.train.compression import flat_psum, hierarchical_psum
from repro.launch.mesh import make_train_mesh

mesh = make_train_mesh(2, 4)
N = 64
x = np.linspace(-1.3, 1.7, 8 * N).reshape(8, N).astype(np.float32)

def step(xs, res):
    out, new_res = hierarchical_psum(
        xs[0], compress="int8", residual=res[0])
    return out[None], new_res[None]

f = jax.jit(jax.shard_map(
    step, mesh=mesh,
    in_specs=(P(("pod", "data")), P(("pod", "data"))),
    out_specs=(P(("pod", "data")), P(("pod", "data"))),
    check_vma=False))

exact = np.asarray(jax.jit(jax.shard_map(
    lambda xs: flat_psum(xs[0])[None], mesh=mesh,
    in_specs=P(("pod", "data")), out_specs=P(("pod", "data")),
    check_vma=False))(jnp.asarray(x)))[0]

# the residual lives on the scattered block: N / inner elements per device
res = jnp.zeros((8, N // 4), jnp.float32)
total = np.zeros(N, np.float64)
T = 16
for t in range(T):
    out, res = f(jnp.asarray(x), res)
    total += np.asarray(out)[0]
one_step_err = float(np.max(np.abs(np.asarray(out)[0] - exact)))
cum_err = float(np.max(np.abs(total - T * exact.astype(np.float64))))
# without EF the cumulative error would be ~T * one_step_err
assert cum_err < 4 * one_step_err, (cum_err, one_step_err)
assert float(np.max(np.abs(np.asarray(res)))) > 0
print("EF OK", one_step_err, cum_err)
""")
