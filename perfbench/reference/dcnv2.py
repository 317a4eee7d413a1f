"""Plain DCN-v2 (arXiv 2008.13535), stacked: ``x0`` is the dense features
and the flattened embedding rows; each full-rank cross layer makes
``x0 * (x @ W + b) + x``; the deep tower and a final unit follow. Named by
a configuration's ``reference.model``."""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from perfbench.reference.recsys import embed_shape, mlp, mlp_macs, mlp_shapes


def _width(cfg: Dict) -> int:
    return cfg["n_dense"] + cfg["n_sparse"] * cfg["embed_dim"]


def param_shapes(cfg: Dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every parameter in the order the init key enumerates them."""
    d0 = _width(cfg)
    shapes = [("embed", embed_shape(cfg))]
    for i in range(cfg["n_cross_layers"]):
        shapes += [(f"cross_w{i}", (d0, d0)), (f"cross_b{i}", (d0,))]
    return shapes + mlp_shapes("deep", d0, list(cfg["top_mlp"]) + [1])


def logits(cfg: Dict, p: Dict, emb: jax.Array, dense_x: jax.Array) -> jax.Array:
    """(B, F, D) embedding rows and (B, n_dense) features -> (B,) logits."""
    x0 = jnp.concatenate([dense_x, emb.reshape(emb.shape[0], -1)], axis=1)
    x = x0
    for i in range(cfg["n_cross_layers"]):
        x = x0 * (x @ p[f"cross_w{i}"].astype(x.dtype)
                  + p[f"cross_b{i}"].astype(x.dtype)) + x
    return mlp(x, p, "deep", len(cfg["top_mlp"]) + 1, False)[:, 0]


def train_flops_per_example(cfg: Dict) -> float:
    """Forward + backward FLOPs of the cross layers and the deep tower for
    one example, at 2 FLOPs per multiply-add; backward takes the weight and
    the input gradient of every product (the input, ``x0``, has embedding
    rows in it)."""
    d0 = _width(cfg)
    macs = [d0 * d0] * cfg["n_cross_layers"]
    macs += mlp_macs(d0, list(cfg["top_mlp"]) + [1])
    return 2.0 * 3 * sum(macs)
