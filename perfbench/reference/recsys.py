"""Plain float32 training of the benchmark's CTR models, and their optimizer.

The model itself is a module of its own, named by the configuration's
``reference.model`` (``dlrm.py``, ``dcnv2.py`` beside this file), which
gives ``param_shapes(cfg)``, ``logits(cfg, params, emb, dense_x)`` and
``train_flops_per_example(cfg)``. This module holds what they share: the
packed table, the init, the loss and the optimizer, in straightforward
``jax.numpy`` with every matrix product at ``highest`` precision. It
imports nothing of the program under test.

The weights are made from the seed's key by the recipe the configuration
states (``init`` in each configuration file): parameter ``i`` of the ordered
parameter list takes ``fold_in(key, i)``; the packed embedding table is
uniform in ``[-1/sqrt(D), 1/sqrt(D))``; matrices are He-normal; biases are
zero. The embedding optimizer is row-wise Adagrad on the rows a batch
touches; the dense optimizer is Adam with global-norm clipping.

The reference works on the rows the compared steps touch only: the packed
table is generated in one program that gathers those rows, so the whole
table is never held twice.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------- shapes
def mlp_shapes(prefix: str, d_in: int, dims: Sequence[int]):
    out = []
    for i, d in enumerate(dims):
        out.append((f"{prefix}_w{i}", (d_in, d)))
        out.append((f"{prefix}_b{i}", (d,)))
        d_in = d
    return out


def mlp_macs(d_in: int, dims: Sequence[int]) -> List[int]:
    """Multiply-adds of each layer of an MLP, for one example."""
    out = []
    for d in dims:
        out.append(d_in * d)
        d_in = d
    return out


def padded_rows(cfg: Dict) -> int:
    rows = sum(cfg["vocab_sizes"])
    align = cfg["row_align"]
    return -(-rows // align) * align


def table_offsets(cfg: Dict) -> np.ndarray:
    """First packed row of each field's table (tables packed end to end)."""
    v = np.asarray(cfg["vocab_sizes"], np.int64)
    return np.concatenate([[0], np.cumsum(v)[:-1]])


def embed_shape(cfg: Dict) -> Tuple[int, int]:
    """The packed table: parameter 0 of every model's ordered list."""
    return (padded_rows(cfg), cfg["embed_dim"])


# ------------------------------------------------------------------ init
def init_dense(model, cfg: Dict, key) -> Dict[str, jax.Array]:
    out = {}
    for i, (name, shape) in enumerate(model.param_shapes(cfg)):
        if name == "embed":
            continue
        if len(shape) == 1:
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * np.float32(np.sqrt(2.0 / shape[0])))
    return out


# --------------------------------------------------------------- forward
def mlp(x, p, prefix, n, final_relu):
    for i in range(n):
        x = x @ p[f"{prefix}_w{i}"].astype(x.dtype) + p[f"{prefix}_b{i}"].astype(x.dtype)
        if i < n - 1 or final_relu:
            x = jax.nn.relu(x)
    return x


def bce(z: jax.Array, y: jax.Array) -> jax.Array:
    z = z.astype(jnp.float32)
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


# ------------------------------------------------------------- training
class Reference:
    """The reference for one configuration and its ``model`` module, its
    compiled programs kept for every run and precision asked of it. With
    several ``devices`` the table is generated split by rows over them."""

    def __init__(self, cfg: Dict, model, devices=None):
        self.cfg, self.model = cfg, model
        self._steps: Dict = {}
        shape = embed_shape(cfg)
        scale = np.float32(1.0 / np.sqrt(cfg["embed_dim"]))
        split = None
        if devices is not None and len(devices) > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            split = NamedSharding(Mesh(np.asarray(devices), ("rows",)),
                                  PartitionSpec("rows", None))

        @jax.jit
        def gather(k, idx):
            table = jax.random.uniform(jax.random.fold_in(k, 0), shape,
                                       jnp.float32, -scale, scale)
            if split is not None:
                table = jax.lax.with_sharding_constraint(table, split)
            return jnp.take(table, idx, axis=0)
        self._gather = gather

    def init_embed_rows(self, key, rows: np.ndarray) -> jax.Array:
        """Initial values of the packed table at ``rows``."""
        return self._gather(key, jnp.asarray(rows, jnp.int32))

    def _step(self, dtype):
        name = jnp.dtype(dtype).name
        if name in self._steps:
            return self._steps[name]
        cfg, model = self.cfg, self.model
        opt = cfg["optimizer"]
        b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
        lr, clip = opt["lr"], opt["clip_norm"]
        elr, eeps = opt["embed_lr"], opt["embed_eps"]

        @jax.jit
        def step(dense, m, v, t, emb, accum, at, pos, dense_x, label):
            # at: (cap,) the step's unique rows' slots in emb, padded past its
            # end; pos: (B, F) each id's place in ``at``
            rows = emb.at[at].get(mode="fill", fill_value=0)
            acc = accum.at[at].get(mode="fill", fill_value=0)

            def loss_of(dp, r):
                dp = jax.tree.map(lambda a: a.astype(dtype), dp)
                e = jnp.take(r.astype(dtype), pos, axis=0)
                return bce(model.logits(cfg, dp, e, dense_x.astype(dtype)), label)

            loss, (gd, gr) = jax.value_and_grad(loss_of, argnums=(0, 1))(dense, rows)
            gd = jax.tree.map(lambda g: g.astype(jnp.float32), gd)
            gr = gr.astype(jnp.float32)
            gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(gd)))
            gd = jax.tree.map(lambda g: g * jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9)), gd)
            t = t + 1
            m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, gd)
            v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, gd)
            c1, c2 = 1 - b1 ** t, 1 - b2 ** t
            dense = jax.tree.map(
                lambda w, a, s: (w - lr * (a / c1) / (jnp.sqrt(s / c2) + eps)).astype(w.dtype),
                dense, m, v)
            acc = acc + jnp.sum(gr * gr, axis=-1)
            rows = (rows - (elr / (jnp.sqrt(acc) + eeps))[:, None] * gr).astype(rows.dtype)
            emb = emb.at[at].set(rows, mode="drop")
            accum = accum.at[at].set(acc, mode="drop")
            gnorms = {k: jnp.linalg.norm(g) for k, g in gd.items()}
            gnorms["embed"] = jnp.linalg.norm(gr)
            return dense, m, v, t, emb, accum, loss, gnorms

        self._steps[name] = step
        return step

    def train(self, key, batches: Sequence[Dict[str, np.ndarray]], *,
              dtype=jnp.float32, precision: str = "highest",
              frozen: bool = False) -> Dict:
        """Run the reference over ``batches`` from the seed's initial state.

        Each batch holds ``ids`` (B, F) per-field local ids, ``dense`` (B, n)
        and ``label`` (B,). Returns the per-step losses, the first step's
        gradient norm of each leaf as the optimizer gets it (dense gradients
        after clipping), and the norm of each leaf's change over all steps,
        from the state as held in ``dtype`` (so a lower precision's change
        leaves out the rounding of the initial state, which a program
        holding its state in that precision would not see either), with
        ``rows`` the packed rows those steps touched. Arrays are padded to
        sizes fixed by the batch shape, so each cell compiles one step.
        ``frozen`` keeps every step's state unchanged (a planted fault: what
        the state then says of the first gradient is nought).
        """
        cfg = self.cfg
        offs = table_offsets(cfg)
        gids = [np.asarray(b["ids"], np.int64) + offs[None, :] for b in batches]
        rows = np.unique(np.concatenate([g.reshape(-1) for g in gids]))
        cap = max(g.size for g in gids)
        total = cap * len(batches)
        padded = np.zeros(total, np.int64)
        padded[:rows.size] = rows
        with jax.default_matmul_precision(precision):
            emb0 = self.init_embed_rows(key, padded)
            dense0 = init_dense(self.model, cfg, key)
            if dtype != jnp.float32:
                dense0 = jax.tree.map(lambda a: a.astype(dtype), dense0)
                emb0 = emb0.astype(dtype)
            emb = emb0
            zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), dense0)
            dense, m, v, t = dense0, zeros, zeros, jnp.zeros((), jnp.float32)
            accum = jnp.full((total,), cfg["optimizer"]["embed_accum_init"], jnp.float32)
            step = self._step(dtype)
            losses, first = [], None
            for b, g in zip(batches, gids):
                uniq, inv = np.unique(g.reshape(-1), return_inverse=True)
                at = np.full(cap, total, np.int32)
                at[:uniq.size] = np.searchsorted(rows, uniq)
                new = step(dense, m, v, t, emb, accum, jnp.asarray(at),
                           jnp.asarray(inv.reshape(g.shape), jnp.int32),
                           jnp.asarray(b["dense"]), jnp.asarray(b["label"]))
                loss, gn = new[6], new[7]
                if not frozen:
                    dense, m, v, t, emb, accum = new[:6]
                losses.append(float(loss))
                if first is None:
                    first = {k: 0.0 if frozen else float(x) for k, x in gn.items()}
        n = rows.size
        change = {k: float(jnp.linalg.norm(dense[k].astype(jnp.float32)
                                           - dense0[k].astype(jnp.float32)))
                  for k in dense}
        change["embed"] = float(np.linalg.norm(
            np.asarray(emb[:n], np.float32) - np.asarray(emb0[:n], np.float32)))
        return {"losses": losses, "grad_norms": first, "change_norms": change,
                "rows": rows}
