"""What ``--seed`` draws: the feature table, the labels, the model's weights
and the minibatches. The table and the weights are made on the device, each
in one jitted call, in the type they are served in (float32) and in the
layout the program takes: the table owner-sharded as (P, V/P, F) over the
``data`` axis, the weights replicated. The minibatches are the benchmark's
own copy of the draw that the program's sampler makes, so that the traffic
stays fixed whatever a later change to the sampler does.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` uint32 words from any non-negative integer seed (SeedSequence
    takes integers of any size, so seeds past 2**32 stay distinct)."""
    return np.random.SeedSequence(int(seed)).generate_state(n)


def seed_key(seed: int, stream: int) -> jax.Array:
    """A JAX key for one named stream of ``seed``."""
    w = seed_words(seed, 2)
    return jax.random.fold_in(jax.random.PRNGKey(int(w[0])),
                              int(w[1]) ^ stream)


TABLE_STREAM, WEIGHT_STREAM = 1, 2


def make_table(seed: int, n_vertices: int, n_features: int, mesh) -> jax.Array:
    """(P, V/P, F) float32 standard normals, each shard drawn on its own
    chip."""
    n = mesh.shape["data"]
    assert n_vertices % n == 0, (n_vertices, n)
    shape = (n, n_vertices // n, n_features)
    sharding = NamedSharding(mesh, P("data"))

    def draw(key):
        return jax.random.normal(key, shape, jnp.float32)

    return jax.jit(draw, out_shardings=sharding)(seed_key(seed, TABLE_STREAM))


def param_shapes(n_features: int, hidden: int, n_classes: int,
                 n_layers: int = 2) -> Dict[str, Tuple[int, ...]]:
    """Parameter shapes of the 2-layer concat GraphSAGE/GCN model: layer i
    maps [self ‖ aggregate] (2·d_in) to ``hidden``; a linear head maps
    ``hidden`` to the classes."""
    out, d_in = {}, n_features
    for i in range(n_layers):
        out[f"w{i}"] = (2 * d_in, hidden)
        out[f"b{i}"] = (hidden,)
        d_in = hidden
    out["w_out"] = (d_in, n_classes)
    out["b_out"] = (n_classes,)
    return out


def make_params(seed: int, shapes: Dict[str, Tuple[int, ...]], mesh
                ) -> Dict[str, jax.Array]:
    """LeCun-normal matrices (std 1/sqrt(fan_in)) and small normal biases
    (std 0.01, so that no bias starts at a gradient-free zero), float32,
    replicated over the mesh, in one jitted call."""
    names = sorted(shapes)
    rep = NamedSharding(mesh, P())

    def draw(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            shp = shapes[name]
            std = 1.0 / math.sqrt(shp[0]) if len(shp) == 2 else 0.01
            out[name] = std * jax.random.normal(k, shp, jnp.float32)
        return out

    return jax.jit(draw, out_shardings={n: rep for n in names})(
        seed_key(seed, WEIGHT_STREAM))


def make_labels(seed: int, n_vertices: int, n_classes: int) -> np.ndarray:
    """(V,) int32 class labels, uniform over the classes."""
    rng = np.random.default_rng(seed_words(seed, 4))
    return rng.integers(0, n_classes, n_vertices, dtype=np.int32)


def _neighbours(rng, indptr: np.ndarray, indices: np.ndarray,
                own: np.ndarray, k: int):
    """k neighbours of each vertex of ``own``, uniform over its out-edges
    with replacement; a vertex with none repeats itself, masked off."""
    lo, hi = indptr[own], indptr[own + 1]
    deg = (hi - lo).astype(np.int64)
    offs = (rng.random((own.shape[0], k))
            * np.maximum(deg, 1)[:, None]).astype(np.int64)
    nbrs = indices[np.minimum(lo[:, None] + offs, indices.shape[0] - 1)]
    mask = np.broadcast_to(deg[:, None] > 0, nbrs.shape)
    return np.where(mask, nbrs, own[:, None]).astype(np.int32), mask


def minibatch(stream_seed: int, step: int, indptr: np.ndarray,
              indices: np.ndarray, labels: np.ndarray, n_parts: int,
              batch: int, k1: int, k2: int) -> Dict[str, np.ndarray]:
    """The minibatch of ``step``: (n_parts, batch) seeds uniform over the
    vertices, k1 neighbours of each seed, then k2 neighbours of each of the
    batch·(1 + k1) layer-1 vertices (the seed and its k1), all from
    ``default_rng(SeedSequence([stream_seed, step]))`` in that order.
    ``indptr``/``indices`` are the CSR of the source-sorted edge list."""
    rng = np.random.default_rng(np.random.SeedSequence([stream_seed, step]))
    P, B = n_parts, batch
    seeds = rng.integers(0, indptr.shape[0] - 1, (P, B)).astype(np.int32)
    flat = seeds.reshape(-1)
    n1, m1 = _neighbours(rng, indptr, indices, flat, k1)
    lay1 = np.concatenate([flat[:, None], n1], axis=1).reshape(-1)
    n2, m2 = _neighbours(rng, indptr, indices, lay1, k2)
    return {"seeds": seeds,
            "nbrs1": n1.reshape(P, B, k1), "mask1": m1.reshape(P, B, k1),
            "nbrs2": n2.reshape(P, B * (1 + k1), k2),
            "mask2": m2.reshape(P, B * (1 + k1), k2),
            "labels": labels[seeds].astype(np.int32)}
