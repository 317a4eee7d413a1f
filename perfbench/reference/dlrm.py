"""Plain DLRM (arXiv 1906.00091): bottom MLP over the dense features, the
pairwise dot interaction of the bottom output and the 26 embedding rows,
and the top MLP over the bottom output and the interaction's lower
triangle. Named by a configuration's ``reference.model``."""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.recsys import embed_shape, mlp, mlp_macs, mlp_shapes


def param_shapes(cfg: Dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every parameter in the order the init key enumerates them."""
    f = cfg["n_sparse"] + 1
    return ([("embed", embed_shape(cfg))]
            + mlp_shapes("bot", cfg["n_dense"], cfg["bot_mlp"])
            + mlp_shapes("top", f * (f - 1) // 2 + cfg["bot_mlp"][-1], cfg["top_mlp"]))


def logits(cfg: Dict, p: Dict, emb: jax.Array, dense_x: jax.Array) -> jax.Array:
    """(B, F, D) embedding rows and (B, n_dense) features -> (B,) logits."""
    bot = mlp(dense_x, p, "bot", len(cfg["bot_mlp"]), True)
    z = jnp.concatenate([bot[:, None, :], emb], axis=1)
    f = z.shape[1]
    dots = jnp.einsum("bfd,bgd->bfg", z, z)
    r, c = np.tril_indices(f, k=-1)
    top_in = jnp.concatenate([bot, dots[:, r, c]], axis=1)
    return mlp(top_in, p, "top", len(cfg["top_mlp"]), False)[:, 0]


def train_flops_per_example(cfg: Dict) -> float:
    """Forward + backward FLOPs of the dense parts for one example: the
    MLPs and ``z @ z.T`` over the F+1 vectors, at 2 FLOPs per multiply-add.
    Backward takes the weight gradient of every layer, the input gradient
    of all but the first bottom layer (its input is the raw features), and
    both operands of the interaction."""
    bot = mlp_macs(cfg["n_dense"], cfg["bot_mlp"])
    f = cfg["n_sparse"] + 1
    top = mlp_macs(f * (f - 1) // 2 + cfg["bot_mlp"][-1], cfg["top_mlp"])
    inter = f * f * cfg["embed_dim"]
    fwd = sum(bot) + sum(top) + inter
    bwd = (sum(bot) + sum(top)) + (sum(bot) - bot[0] + sum(top)) + 2 * inter
    return 2.0 * (fwd + bwd)
