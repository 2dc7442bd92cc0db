"""The plain reference: GraphSAGE and GCN in straightforward ``jax.numpy``.

It shares no code with the program and takes nothing the program made: the
table, labels, weights, graph and minibatches are the benchmark's own draws.
Aggregation runs shard by shard, in bounded blocks of table rows and of
request rows (or of edges), so that a table of several GB a chip is read
where it lies and no call holds more than one block of it. Dense layers use
the TPU's default matmul precision, as the configurations state.

``dtype`` is float32 for the reference and bfloat16 for the control (the
reference put in the program's place one precision lower). The
``half_batch`` and ``exchange`` switches plant two of the faults the
correctness limits are read against.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# sampled aggregation over the sharded table
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dtype", "rows"))
def _block_partial(shard, ids, lo, dtype, rows: int):
    """Sum and count of the rows of ``ids`` (R, K) that lie in table rows
    [lo, lo + rows) of ``shard`` (1, V/P, F), ``lo`` relative to the shard.
    Only that block is sliced and cast, so the call's temporaries stay
    bounded by the block, whatever the size of the shard."""
    blk = jax.lax.dynamic_slice_in_dim(shard[0], lo, rows, 0).astype(dtype)
    rel = ids - lo
    own = (rel >= 0) & (rel < rows)
    got = jnp.take(blk, jnp.clip(rel, 0, rows - 1), axis=0)
    got = jnp.where(own[..., None], got, jnp.zeros((), dtype))
    return got.sum(1, dtype=dtype), own.sum(1).astype(jnp.int32)


def sampled_means(shards: Sequence[jax.Array], ids: np.ndarray, dtype,
                  exchange: bool = True, block: int = 8192,
                  table_block: int = 1 << 19) -> jax.Array:
    """(P, R, F) mean of each request row's K table rows, on device 0.

    ``shards[p]`` is chip p's (1, V/P, F) slice of the table, on chip p;
    ``ids`` is (P, R, K) global vertex ids, chip q's requests at [q], -1
    where a sample is masked off (a row with none reads 0). Each call reads
    ``block`` request rows against ``table_block`` table rows. With
    ``exchange=False`` chip q's rows aggregate only what its own shard holds
    (the cross-chip exchange left out)."""
    P, R, K = ids.shape
    part = shards[0].shape[1]
    rows = min(part, table_block)
    assert part % rows == 0, (part, rows)
    dev0 = next(iter(shards[0].devices()))
    outs = []
    for q in range(P):
        rows_q = []
        for r0 in range(0, R, block):
            blk = ids[q, r0:r0 + block]
            s_acc, c_acc = None, None
            for p in range(P):
                if not exchange and p != q:
                    continue
                dev = next(iter(shards[p].devices()))
                rel = jax.device_put(blk - p * part, dev)
                for t0 in range(0, part, rows):
                    s, c = _block_partial(shards[p], rel, t0, dtype, rows)
                    s, c = jax.device_put((s, c), dev0)
                    s_acc = s if s_acc is None else s_acc + s
                    c_acc = c if c_acc is None else c_acc + c
            rows_q.append(s_acc / jnp.maximum(c_acc, 1).astype(dtype)[:, None])
        outs.append(jnp.concatenate(rows_q, 0))
    return jnp.stack(outs)


# ---------------------------------------------------------------------------
# 2-layer concat GraphSAGE, its loss and AdamW
# ---------------------------------------------------------------------------

def _dense(x, w, b):
    return jnp.einsum("...i,io->...o", x, w) + b


def sage_logits(params, x_self, x_agg, mask1, k1: int):
    """x_self/x_agg: (N, B·(1+k1), F) the layer-1 vertices' own rows and
    2-hop means; mask1 (N, B, k1). Returns (N, B, C) logits."""
    N, R, _ = x_self.shape
    B = R // (1 + k1)
    h1 = jax.nn.relu(_dense(jnp.concatenate([x_self, x_agg], -1),
                            params["w0"], params["b0"]))
    h1 = h1.reshape(N, B, 1 + k1, -1)
    m = mask1[..., None].astype(h1.dtype)
    agg1 = (h1[:, :, 1:] * m).sum(2) / jnp.maximum(m.sum(2), 1)
    h2 = jax.nn.relu(_dense(jnp.concatenate([h1[:, :, 0], agg1], -1),
                            params["w1"], params["b1"]))
    return _dense(h2, params["w_out"], params["b_out"])


def nll(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0].mean()


@functools.partial(jax.jit, static_argnames=("k1", "half"))
def loss_and_grads(params, x_self, x_agg, mask1, labels, k1: int,
                   half: bool = False):
    """Mean NLL over every seed (over the first half of each chip's seeds
    with ``half``, a planted fault) and its gradients."""
    def f(p):
        logits = sage_logits(p, x_self, x_agg, mask1, k1)
        if half:
            B = labels.shape[1] // 2
            return nll(logits[:, :B], labels[:, :B])
        return nll(logits, labels)
    return jax.value_and_grad(f)(params)


def lr_at(count: int, opt: Dict[str, float]) -> float:
    """Linear warm-up then cosine decay to ``min_lr_ratio``."""
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((count - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    scale = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return opt["learning_rate"] * warm * scale


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "clip"))
def adamw(params, grads, m, v, count, lr, b1, b2, eps, wd, clip):
    """One AdamW step on globally norm-clipped gradients (count >= 1).
    Returns (params, m, v, clipped grads)."""
    dt = jax.tree.leaves(params)[0].dtype
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
    grads = {k: (g * scale).astype(dt) for k, g in grads.items()}
    bc1 = (1 - b1 ** count).astype(dt)
    bc2 = (1 - b2 ** count).astype(dt)
    new_p, new_m, new_v = {}, {}, {}
    for k in params:
        g = grads[k]
        new_m[k] = b1 * m[k] + (1 - b1) * g
        new_v[k] = b2 * v[k] + (1 - b2) * g * g
        step = (new_m[k] / bc1) / (jnp.sqrt(new_v[k] / bc2) + eps)
        new_p[k] = params[k] - lr * (step + wd * params[k])
    return new_p, new_m, new_v, grads


def sage_train(params0, shards, batches, labels: np.ndarray, k1: int,
               opt: Dict[str, float], dtype, half: bool = False,
               exchange: bool = True):
    """The reference's first len(batches) train steps from ``params0``.

    Returns per-step losses, the clipped gradient of step 1 and the
    parameters after the last step (all float32 numpy)."""
    params = {k: jnp.asarray(v, dtype) for k, v in params0.items()}
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    v = {k: jnp.zeros_like(x) for k, x in params.items()}
    losses, g1 = [], None
    for i, b in enumerate(batches):
        ids1 = np.concatenate([b["seeds"][..., None], b["nbrs1"]], -1)
        P = ids1.shape[0]
        ids1 = ids1.reshape(P, -1)
        x_self = sampled_means(shards, ids1[..., None], dtype, exchange)
        x_agg = sampled_means(shards, np.where(b["mask2"], b["nbrs2"], -1),
                              dtype, exchange)
        dev0 = next(iter(x_self.devices()))
        mask1, lab = jax.device_put((b["mask1"], labels[b["seeds"]]), dev0)
        loss, grads = loss_and_grads(params, x_self, x_agg, mask1, lab, k1,
                                     half)
        count = i + 1
        params, m, v, g = adamw(
            params, grads, m, v, jnp.asarray(count, dtype),
            jnp.asarray(lr_at(count, opt), dtype), b1=opt["beta1"],
            b2=opt["beta2"], eps=opt["eps"], wd=opt["weight_decay"],
            clip=opt["grad_clip"])
        losses.append(float(loss))
        if i == 0:
            g1 = {k: np.asarray(x, np.float32) for k, x in g.items()}
    return (np.asarray(losses), g1,
            {k: np.asarray(x, np.float32) for k, x in params.items()})


# ---------------------------------------------------------------------------
# full-graph 2-layer concat GCN
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n",))
def _edge_block(h, src, dst, w, n):
    rows = jnp.take(h, src, axis=0) * w[:, None].astype(h.dtype)
    return jax.ops.segment_sum(rows, dst, num_segments=n)


@jax.jit
def _layer(h, agg, w, b):
    return jax.nn.relu(_dense(jnp.concatenate([h, agg], -1), w, b))


def gcn_logits(params, table, src, dst, weights, dtype,
               block: int = 1 << 18) -> jax.Array:
    """(V, C) logits of the full-graph concat GCN: each layer aggregates
    weight · h[src] into dst, edge block by edge block."""
    p = {k: jnp.asarray(v, dtype) for k, v in params.items()}
    h = jnp.asarray(table, dtype)
    V = h.shape[0]
    n_layers = sum(1 for k in p if k.startswith("w") and k != "w_out")
    for i in range(n_layers):
        agg = jnp.zeros_like(h)
        for e0 in range(0, src.shape[0], block):
            agg = agg + _edge_block(h, src[e0:e0 + block], dst[e0:e0 + block],
                                    weights[e0:e0 + block], V)
        h = _layer(h, agg, p[f"w{i}"], p[f"b{i}"])
    return _dense(h, p["w_out"], p["b_out"])


# ---------------------------------------------------------------------------
# the numbers compared
# ---------------------------------------------------------------------------

def rel_l2_gap(a: np.ndarray, ref: np.ndarray) -> float:
    """||a - ref|| / ||ref|| over all elements."""
    a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(a - ref) / max(np.linalg.norm(ref), 1e-30))


def worst_leaf_norm_gap(a: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                        keep: Sequence[str]) -> float:
    """Over the leaves ``keep``: the gap between the two norms of a leaf,
    over the larger of the reference's norm of that leaf and of the median
    leaf."""
    norms = {k: float(np.linalg.norm(ref[k])) for k in keep}
    med = float(np.median(list(norms.values())))
    return max(abs(float(np.linalg.norm(a[k])) - norms[k])
               / max(norms[k], med, 1e-30) for k in keep)


def moving_leaves(g_ref: Dict[str, np.ndarray]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: norm at
    least a thousandth of the median leaf's."""
    norms = {k: float(np.linalg.norm(v)) for k, v in g_ref.items()}
    med = float(np.median(list(norms.values())))
    return sorted(k for k, n in norms.items() if n >= 1e-3 * med)
