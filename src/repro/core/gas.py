"""The gather-and-scatter (GAS) engine — FAST-GAS semantics on TPU.

The paper's engine couples a CAM (parallel *match* of edge endpoints) with a
FAST SRAM (*row-parallel in-place update* of matched rows). The TPU-native
re-expression (DESIGN §2):

  * match     → equality-compare broadcast / one-hot mask (MXU-contractable)
  * update    → masked vectorized reduce into the accumulator rows
  * idle-skip → tile-occupancy check that skips empty (row-block × edge-tile)
                pairs (realized with ``pl.when`` in the Pallas kernel)

Public primitives (all fixed-shape, jit-friendly):

  gas_scatter(dst, values, n_rows, op)   — scatter-reduce values into rows
  gas_match(keys, queries)               — CAM match mask
  gas_gather(table, ids)                 — row gather (the "find" of
                                           find-and-compute)

``impl`` selects the backend: "xla" (jnp reference semantics, the oracle) or
"pallas" (the kernel, interpret-mode on CPU). Kernels live in
``repro.kernels.gas_scatter``.

**Differentiation (the backward pass is also GAS work).** ``pallas_call``
has no transpose rule, so the pallas backend carries ``jax.custom_vjp``
rules here — the same forward-only pattern the embedding lookup proved
(``repro.models.embedding``): fwd and bwd are each plain forward kernel
dispatches, and no transpose machinery ever touches the kernel. The rules
exploit the paper's own symmetry:

  * the backward of a scatter-add is a *gather* — the cotangent-to-values of
    ``gas_scatter_weighted(op="add")`` is a masked weighted gather of the
    output cotangent, and the cotangent-to-weights is a per-edge row-dot;
  * the backward of a gather is a *scatter* — ``gas_gather(impl="pallas")``
    scatter-adds its cotangent rows through the FAST-GAS kernel;
  * for ``op="max"/"min"`` the cotangent is routed through a recomputed
    ``gas_match``-style equality mask against the saved output — the CAM
    consumed as a grad router (match lines gate the cotangent directly,
    never priority-decoded into argmax addresses) — with the tie count
    itself produced by a kernel scatter, matching XLA's even-split-among-
    ties convention; ``op="or"`` is flat almost everywhere (the XLA oracle
    differentiates to exact zeros through its int cast), so its cotangents
    are zeros.

**Locality scheduling (the idle-skip actually firing).** ``schedule_edges``
bins the edge stream by destination row block (paper Fig 11(c)): with binned
edges each kernel edge tile touches one or two row blocks, so the idle-skip
occupancy collapses to a thin band and ``pl.when`` skips almost every
(row-block × edge-tile) round. The schedule is computed ONCE per
(partition, batch) — the dataflow permutes the edge LIST, so gathered value
streams arrive binned for free — and the same schedule serves every layer,
every feature block, and the backward pass (the max/min tie-count scatter
reuses it; cotangents to permuted inputs un-permute through the transpose of
the ``take`` that applied the permutation). On the pallas backend the
scheduled scatter additionally runs FUSED: mask and edge weights enter the
kernel (dead-row convention + match-line scaling), so no ``values*weights``
or mask-fill E×F stream is ever staged in HBM.
"""

from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp
import numpy as np

Op = Literal["add", "max", "min", "or"]

_INIT = {
    "add": 0.0,
    "max": -jnp.inf,
    "min": jnp.inf,
    "or": 0,
}


def _tick(kind: str) -> None:
    """Tick the shared trace-time dispatch counter (see ``count_dispatches``)."""
    from repro.kernels.gas_scatter import ops as gas_ops
    gas_ops._tick(kind)


def count_dispatches():
    """Context manager counting GAS dispatches at trace time — the
    deterministic "how many engine calls does this program issue" view.

    Engine-level keys ticked from this module: ``find`` (one per
    ``gas_gather`` — the find of find-and-compute, both backends) and
    ``reduce`` (one per weighted scatter reduction, both backends; the K=1
    pure-find specialization never reduces, so it never ticks). The kernel
    layer (``repro.kernels.gas_scatter.ops``) ticks ``kernel_scatter`` into
    the same counter for every actual pallas dispatch. This is what the
    request-coalescing tier asserts on: the coalesced ``sage_forward`` fetch
    runs ONE ``find`` (and its VJP one backward ``kernel_scatter``) where
    the separate two-stream form ran two.
    """
    from repro.kernels.gas_scatter import ops as gas_ops
    return gas_ops.count_dispatches()


def _segment_reduce_xla(dst: jax.Array, values: jax.Array, n_rows: int, op: Op):
    if op == "add":
        return jax.ops.segment_sum(values, dst, num_segments=n_rows)
    if op == "max":
        return jax.ops.segment_max(values, dst, num_segments=n_rows)
    if op == "min":
        return jax.ops.segment_min(values, dst, num_segments=n_rows)
    if op == "or":
        out = jax.ops.segment_max(values.astype(jnp.int32), dst, num_segments=n_rows)
        # empty segments come back as INT32_MIN; the or-identity is 0
        return jnp.maximum(out, 0).astype(values.dtype)
    raise ValueError(op)


def schedule_edges(dst: jax.Array, mask: Optional[jax.Array], n_rows: int, *,
                   assume_sorted: bool = False):
    """Destination-binned edge schedule (see ``kernels.gas_scatter.ops``).

    Returns an ``EdgeSchedule`` — a stable counting-sort permutation of the
    edges by ``dst // ROW_BLOCK`` plus the per-edge-tile live-block band the
    idle-skip occupancy collapses to. Compute it once per (partition, batch)
    and thread it through ``gas_scatter_weighted(schedule=...)`` with
    edge arrays permuted by ``.perm``; ``assume_sorted=True`` skips the sort
    for streams binned by construction (e.g. sampled-path seed rows).
    """
    from repro.kernels.gas_scatter import ops as gas_ops
    with jax.named_scope("gas.schedule"):
        return gas_ops.schedule_edges(dst, mask, n_rows,
                                      assume_sorted=assume_sorted)


def gas_scatter(dst: jax.Array, values: jax.Array, n_rows: int, *,
                op: Op = "add", impl: str = "xla") -> jax.Array:
    """Scatter-reduce ``values`` (E,) or (E, F) into ``n_rows`` rows by ``dst``.

    Rows with no incoming edge hold the op identity for max/min (±inf) — mask
    with a degree count if needed. ``impl="pallas"`` routes through the
    FAST-GAS kernel (CAM match + MXU one-hot contraction + idle-skip); that
    raw kernel entry is forward-only — differentiate through
    ``gas_scatter_weighted``/``gas_gather``, which carry the custom VJPs.
    """
    if impl == "pallas":
        from repro.kernels.gas_scatter import ops as gas_ops
        return gas_ops.gas_scatter(dst, values, n_rows, op=op)
    return _segment_reduce_xla(dst, values, n_rows, op)


def _zero_cotangent(x: jax.Array):
    """Symbolic-zero cotangent with the right tangent type: float zeros for
    inexact primals, ``float0`` for int/bool primals (ids, masks)."""
    if jnp.issubdtype(x.dtype, jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(np.shape(x), jax.dtypes.float0)


# ---------------------------------------------------------------------------
# gather (+ its kernel-routed VJP: the backward of a gather is a scatter)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _gather_pallas(n_rows: int):
    """Row gather whose VJP scatter-adds the cotangent through the FAST-GAS
    kernel — the in-SSD grad aggregation (no raw table rows move in either
    direction, and no XLA scatter silently replaces the kernel)."""

    @jax.custom_vjp
    def gather(table, ids):
        return jnp.take(table, ids, axis=0)

    def fwd(table, ids):
        # the zero-size residual carries the table dtype into the bwd cast
        return gather(table, ids), (ids, jnp.zeros((0,), table.dtype))

    def bwd(res, g):
        ids, like = res
        gf = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
        # fused dispatch (mask/weights-free): out-of-range ids ride the
        # dead-row convention inside the kernel wrapper, no E×F staging
        dtab = _scatter_weighted_impl(ids.reshape(-1), gf, None, None,
                                      n_rows, "add", "pallas")
        return dtab.astype(like.dtype), np.zeros(np.shape(ids), jax.dtypes.float0)

    gather.defvjp(fwd, bwd)
    return gather


def gas_gather(table: jax.Array, ids: jax.Array, *, impl: str = "xla") -> jax.Array:
    """Row gather — local by construction under the src-owner partition.

    ``impl="pallas"`` keeps the forward a plain take but routes the VJP's
    scatter-add (the backward of a gather IS a scatter) through the FAST-GAS
    kernel, so the reverse pass of a dataflow stays in the in-SSD regime.
    """
    _tick("find")
    if impl == "pallas" and table.ndim != 2:
        # a silent jnp.take fallback here would hand the backward to an
        # XLA scatter — the exact regression the grad tier forbids
        raise NotImplementedError(
            f"gas_gather(impl='pallas') routes its VJP through the "
            f"FAST-GAS kernel and requires a 2-D (rows, F) table; got "
            f"ndim={table.ndim}. Use impl='xla' for other ranks.")
    with jax.named_scope("gas.find"):
        if impl == "pallas":
            return _gather_pallas(table.shape[0])(table, ids)
        return jnp.take(table, ids, axis=0)


def gas_match(keys: jax.Array, queries: jax.Array) -> jax.Array:
    """CAM match: (R,) keys vs (Q,) queries → (Q, R) bool match-line matrix.

    This is the decoder-free use the paper argues for: the match lines are
    consumed directly as row-enable masks (here: a mask/one-hot fed straight
    into the compute), never priority-decoded into addresses. The max/min
    VJP below consumes the same match-line idea as a *grad router*.
    """
    return queries[:, None] == keys[None, :]


# ---------------------------------------------------------------------------
# weighted scatter (+ its custom VJP for the pallas backend)
# ---------------------------------------------------------------------------

def _scatter_weighted_impl(dst, src_vals, weights, mask, n_rows, op: Op,
                           impl: str, schedule=None):
    """The primal computation shared by both backends (see the public
    ``gas_scatter_weighted`` for semantics). ``schedule`` is the banded
    idle-skip bounds for pre-permuted inputs (pallas backend only)."""
    _tick("reduce")
    if impl == "pallas":
        # fused dispatch: mask → dead-row convention, weights → match-line
        # scaling, both INSIDE the kernel — no E×F staging array exists.
        from repro.kernels.gas_scatter import ops as gas_ops
        if op == "or":
            # boolean-or ignores edge weights: scaling by a zero or negative
            # weight before the max would silently flip set bits. The int
            # round-trip matches the XLA oracle's truncation exactly, so
            # both backends agree even on non-{0,1} values.
            vals = src_vals.astype(jnp.int32).astype(jnp.float32)
            out = gas_ops.gas_scatter_fused(dst, vals, None, mask, n_rows,
                                            op="max", schedule=schedule)
            return jnp.maximum(out, 0).astype(src_vals.dtype)
        w = weights if op == "add" else None
        return gas_ops.gas_scatter_fused(dst, src_vals, w, mask, n_rows,
                                         op=op, schedule=schedule)
    if op in ("max", "min"):
        fill = jnp.asarray(_INIT[op], src_vals.dtype)
        vals = jnp.where(mask[:, None], src_vals, fill)
    elif op == "or":
        # boolean-or ignores edge weights (see the fused branch above)
        vals = jnp.where(mask[:, None], src_vals, 0)
    else:
        vals = src_vals * weights[:, None].astype(src_vals.dtype)
        vals = jnp.where(mask[:, None], vals, 0)
    safe_dst = jnp.where(mask, dst, n_rows)
    out = gas_scatter(safe_dst, vals, n_rows + 1, op=op, impl=impl)
    return out[:n_rows]


@functools.lru_cache(maxsize=None)
def _scatter_weighted_pallas(n_rows: int, op: Op):
    """``gas_scatter_weighted`` on the kernel backend with a custom VJP.

    The rules mirror what autodiff derives for the XLA oracle — the grad
    parity tier (``tests/test_cgtrans_grad.py``) asserts the match:

      add      d_vals[e]  = mask[e] · w[e] · g[dst[e]]      (weighted gather)
               d_w[e]     = mask[e] · ⟨src_vals[e], g[dst[e]]⟩      (row-dot)
      max/min  d_vals[e,f] = eq[e,f] · g[dst[e],f] / ties[dst[e],f]
               with eq = mask ∧ (src_vals == out[dst]) — the CAM match lines
               recomputed against the saved output and consumed as the grad
               router (no argmax decode); ties counted by a kernel scatter,
               matching XLA's even-split convention. d_w = 0 (weights are
               not consumed by the compare ops).
    Both the tie-count scatter and (via ``gas_gather(impl="pallas")`` at the
    dataflow layer) the feature-table scatter run through the FAST-GAS
    kernel: the backward pass is itself GAS work — and the tie-count scatter
    reuses the SAME edge schedule as the forward (its dst stream IS the
    forward's), so the idle-skip band serves the reverse pass too.
    (``op="or"`` never reaches here — it is flat, so the public entry stops
    gradients instead of carrying residuals for an all-zero bwd.)
    """

    @jax.custom_vjp
    def scatter(dst, src_vals, weights, mask, schedule):
        return _scatter_weighted_impl(dst, src_vals, weights, mask,
                                      n_rows, op, "pallas", schedule)

    def fwd(dst, src_vals, weights, mask, schedule):
        out = _scatter_weighted_impl(dst, src_vals, weights, mask,
                                     n_rows, op, "pallas", schedule)
        res = (dst, src_vals, weights, mask, schedule) + (
            (out,) if op in ("max", "min") else ())
        return out, res

    def bwd(res, g):
        dst, src_vals, weights, mask, schedule = res[:5]
        d_dst = _zero_cotangent(dst)
        d_mask = _zero_cotangent(mask)
        d_sched = jax.tree.map(_zero_cotangent, schedule)
        # live = contributed to the forward: the fused kernel treats masked
        # AND out-of-range edges as dead, so the cotangent must gate on both
        # (mask alone would hand an out-of-range edge the clipped row's grad)
        live = mask & (dst >= 0) & (dst < n_rows)
        safe = jnp.clip(dst, 0, n_rows - 1)       # dead edges read junk rows
        g_rows = jnp.take(g, safe, axis=0)        # …zeroed by `live` below
        if op == "add":
            d_vals = jnp.where(live[:, None],
                               g_rows * weights[:, None].astype(g.dtype),
                               0).astype(src_vals.dtype)
            d_w = jnp.where(
                live,
                (src_vals.astype(jnp.float32) * g_rows.astype(jnp.float32)
                 ).sum(-1),
                0).astype(weights.dtype)
            return d_dst, d_vals, d_w, d_mask, d_sched

        out = res[5]
        # CAM match lines as the grad router: an edge's value participates in
        # the row extremum iff it equals the saved output there (and is live)
        eq = live[:, None] & (src_vals == jnp.take(out, safe, axis=0))
        # tie count via the kernel — the backward scatter is itself FAST-GAS
        # work sharing the forward's dst stream, hence its schedule; masked/
        # out-of-range edges ride the dead-row convention
        ties = _scatter_weighted_impl(dst, eq.astype(jnp.float32), None, mask,
                                      n_rows, "add", "pallas", schedule)
        share = g_rows / jnp.maximum(jnp.take(ties, safe, axis=0), 1.0)
        d_vals = jnp.where(eq, share, 0).astype(src_vals.dtype)
        return d_dst, d_vals, _zero_cotangent(weights), d_mask, d_sched

    scatter.defvjp(fwd, bwd)
    return scatter


def gas_scatter_weighted(dst: jax.Array, src_vals: jax.Array, weights: jax.Array,
                         mask: jax.Array, n_rows: int, *, op: Op = "add",
                         impl: str = "xla", schedule=None) -> jax.Array:
    """Masked, edge-weighted scatter — the paper's aggregation atom.

    src_vals: (E, F); weights/mask: (E,). Invalid edges are routed to a
    dead row and sliced off, keeping shapes static. On the pallas backend
    the dispatch is FUSED — mask and weights enter the kernel, no E×F
    staging. ``schedule`` (an ``EdgeSchedule`` whose ``.perm`` order the
    inputs are already in) swaps the dense grid for the banded walk, so
    off-band rounds are never even visited. Differentiable
    on BOTH backends: the XLA oracle through native autodiff, the pallas
    kernel through the custom VJP above (pallas ≡ xla gradients is asserted
    by ``tests/test_cgtrans_grad.py``); the schedule is reused by the
    backward (tie counts) and cotangents un-permute through the transpose
    of the caller's ``take``.
    """
    # the scope encloses the custom-VJP call, so the backward carries it
    # too; the gathers' backward scatters call ``_scatter_weighted_impl``
    # directly and stay under their own ``gas.find``
    with jax.named_scope("gas.reduce"):
        if impl == "pallas":
            if op == "or":
                # flat almost everywhere (the oracle differentiates to exact
                # zeros through its int cast): stop the gradients instead of
                # paying custom-VJP residuals for an all-zero backward
                return _scatter_weighted_impl(
                    dst, jax.lax.stop_gradient(src_vals),
                    jax.lax.stop_gradient(weights), mask, n_rows, op, impl,
                    schedule)
            return _scatter_weighted_pallas(n_rows, op)(
                dst, src_vals, weights, mask, schedule)
        return _scatter_weighted_impl(dst, src_vals, weights, mask, n_rows,
                                      op, impl, schedule)
