"""jit'd public wrappers for the FAST-GAS scatter kernel.

Three layers:

* ``schedule_edges`` — the locality pass (paper Fig 11(c)): a stable
  counting-sort of the edge stream by destination row block. Binned edges
  make each edge tile touch only one or two row blocks, so the idle-skip
  occupancy map collapses from an arbitrary bitmap to a thin band described
  by per-tile (min, max) block bounds — and ``pl.when`` actually skips.
* ``occupancy_map`` — the unscheduled fallback's exact bitmap, computed by a
  bincount over (block, tile) pairs: O(E + R·T), replacing the old
  O(R·T·edge_tile) broadcast-compare that was re-traced per shard.
* ``gas_scatter`` / ``gas_scatter_fused`` — padding + dispatch. The fused
  entry takes mask and edge weights INTO the kernel (mask via the dead-row
  convention, weights via match-line scaling), so no ``values * weights`` or
  mask-fill edge stream is ever staged as a full E×F array in HBM.
"""

from __future__ import annotations

import contextlib
import functools
from collections import Counter
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.gas_scatter import kernel as K
from repro.kernels.gas_scatter.ref import gas_scatter_ref


# ---------------------------------------------------------------------------
# dispatch counting (the deterministic "how many kernel calls" view)
# ---------------------------------------------------------------------------

_DISPATCH_COUNTS: Optional[Counter] = None


@contextlib.contextmanager
def count_dispatches():
    """Count GAS dispatches at TRACE time while the context is active.

    The public wrappers below tick a shared counter from plain (un-jit'd)
    Python before entering their jitted bodies, so every *dispatch site* is
    counted exactly once per trace — immune to jit caching of the inner
    functions and to XLA's combiner/DCE passes. Trace the program under
    test inside the context (``jax.make_jaxpr(fn)(*args)``, or an eager
    call) and read the Counter:

        with count_dispatches() as counts:
            jax.make_jaxpr(jax.grad(loss))(x)
        assert counts["kernel_scatter"] == 1

    Keys ticked here: ``kernel_scatter`` (one per pallas scatter dispatch —
    plain or fused). ``repro.core.gas`` ticks the engine-level keys
    ``find`` (table gathers) and ``reduce`` (weighted scatter reductions,
    either backend) into the same counter. Like jaxpr counting, a scan body
    counts once, not once per iteration. Contexts nest: the innermost
    counter receives the ticks.
    """
    global _DISPATCH_COUNTS
    prev = _DISPATCH_COUNTS
    _DISPATCH_COUNTS = Counter()
    try:
        yield _DISPATCH_COUNTS
    finally:
        _DISPATCH_COUNTS = prev


def _tick(kind: str) -> None:
    if _DISPATCH_COUNTS is not None:
        _DISPATCH_COUNTS[kind] += 1


def _pad_to(x: jax.Array, mult: int, axis: int, fill):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


# ---------------------------------------------------------------------------
# the edge schedule: destination-binned order + banded idle-skip bounds
# ---------------------------------------------------------------------------

class EdgeSchedule(NamedTuple):
    """Destination-binned edge schedule for one (partition, batch).

    ``perm`` reorders the edge stream so destinations ascend by row block
    (stable within a block, so intra-block edge order is preserved); dead
    edges (masked / out-of-range) sort to the end. ``blk_min``/``blk_max``
    are the per-edge-tile live row-block bounds of the PERMUTED, tile-padded
    stream — the banded form of the idle-skip buffer: tile ``t`` can only
    match row blocks in ``[blk_min[t], blk_max[t]]`` (``blk_max < blk_min``
    marks an all-dead tile). ``work`` is those bounds compiled into the
    kernel's walk order — (W, 4) rows of [row_block, tile, live, init],
    W = T + 2·row_blocks statically, covering every live (row-block, tile)
    pair exactly once plus one init-only row per empty block — so the
    scheduled grid iterates each row block's own tile range instead of
    R×T. Computed once per (partition, batch) and reused across layers,
    feature blocks, and the backward pass.
    """
    perm: jax.Array      # (E,) int32
    blk_min: jax.Array   # (T,) int32; T = tile-padded E // EDGE_TILE
    blk_max: jax.Array   # (T,) int32; -1 on all-dead tiles
    work: jax.Array      # (W, 4) int32 [row_block, tile, live, init]


def _edge_bins(dst: jax.Array, mask: Optional[jax.Array], n_rows: int):
    """Row-block bin per edge; dead edges get the one-past-the-end bin."""
    n_blocks = -(-n_rows // K.ROW_BLOCK)
    ok = (dst >= 0) & (dst < n_rows)
    if mask is not None:
        ok = ok & mask
    bins = jnp.where(ok, dst // K.ROW_BLOCK, n_blocks)
    return bins.astype(jnp.int32), n_blocks


def _tile_bounds(bins: jax.Array, n_blocks: int, edge_tile: int):
    """Per-tile (min, max) live block of a (padded) bin stream."""
    t = _pad_to(bins, edge_tile, 0, n_blocks).reshape(-1, edge_tile)
    live = t < n_blocks
    blk_min = jnp.min(jnp.where(live, t, n_blocks), axis=1).astype(jnp.int32)
    blk_max = jnp.max(jnp.where(live, t, -1), axis=1).astype(jnp.int32)
    return blk_min, blk_max


def _work_list(blk_min: jax.Array, blk_max: jax.Array,
               n_blocks: int) -> jax.Array:
    """Compile per-tile band bounds into the banded kernel's walk order.

    Returns (W, 4) int32 rows [row_block, tile, live, init] ordered by row
    block (output revisits stay consecutive), where each row block's run is
    its own contiguous tile range. W = T + 2·n_blocks is a static bound: on
    a binned stream the live pairs form a staircase (Σ spans ≤ T + n_blocks
    − 1) and each empty row block adds one init-only row. Trailing rows are
    dead filler pinned to the last block.
    """
    T = blk_min.shape[0]
    W = T + 2 * n_blocks
    dead = blk_max < 0
    # monotone envelopes: interior all-dead tiles (possible on
    # assume_sorted streams with interleaved masks) inherit neighbor
    # bounds, restoring the ascending order searchsorted needs — visiting
    # such a tile is a zero-match no-op, never a miss
    hi_env = jax.lax.cummax(jnp.where(dead, -1, blk_max))
    lo_env = jax.lax.cummin(
        jnp.where(dead, n_blocks, blk_min)[::-1])[::-1]
    r = jnp.arange(n_blocks, dtype=jnp.int32)
    t_lo = jnp.searchsorted(hi_env, r, side="left")      # first tile ∋ r
    t_hi = jnp.maximum(jnp.searchsorted(lo_env, r, side="right"), t_lo)
    cnt = jnp.maximum(t_hi - t_lo, 1)                    # empty block: init
    offs = jnp.concatenate([jnp.zeros((1,), cnt.dtype), jnp.cumsum(cnt)])
    w = jnp.arange(W)
    rb = jnp.searchsorted(offs[1:], w, side="right")     # block of step w
    rb_c = jnp.minimum(rb, n_blocks - 1)
    j = w - offs[rb_c]
    tile = jnp.clip(t_lo[rb_c] + j, 0, T - 1)
    live = (rb < n_blocks) & (j < (t_hi - t_lo)[rb_c])
    init = (rb < n_blocks) & (j == 0)
    return jnp.stack(
        [rb_c, tile, live.astype(jnp.int32), init.astype(jnp.int32)],
        axis=1).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("n_rows", "edge_tile", "assume_sorted"))
def schedule_edges(dst: jax.Array, mask: Optional[jax.Array], n_rows: int, *,
                   edge_tile: Optional[int] = None,
                   assume_sorted: bool = False) -> EdgeSchedule:
    """Bin the edge stream by destination row block (stable counting sort).

    ``dst``: (E,) destination rows in ``[0, n_rows)``; entries that are
    masked or out of range are treated as dead and sort last. The sort key
    is ``dst // ROW_BLOCK`` only, so edges of one block keep their relative
    order (the gather stream stays as sequential as the input allows).

    ``assume_sorted=True`` skips the sort (``perm`` is the identity) and
    only derives the banded bounds — for streams that are binned by
    construction, e.g. the sampled path's ``repeat(arange(R), K)`` seeds.

    ``edge_tile`` defaults to the kernel's ``EDGE_TILE`` — pass it
    explicitly only to study other tilings.
    """
    if edge_tile is None:
        edge_tile = K.EDGE_TILE
    bins, n_blocks = _edge_bins(dst, mask, n_rows)
    iota = jnp.arange(dst.shape[0], dtype=jnp.int32)
    if assume_sorted:
        sorted_bins, perm = bins, iota
    else:
        sorted_bins, perm = jax.lax.sort((bins, iota), num_keys=1,
                                         is_stable=True)
    blk_min, blk_max = _tile_bounds(sorted_bins, n_blocks, edge_tile)
    return EdgeSchedule(perm, blk_min, blk_max,
                        _work_list(blk_min, blk_max, n_blocks))


def schedule_skip_stats(sched: EdgeSchedule):
    """(live_rounds, total_rounds) of a schedule — how many (row-block ×
    edge-tile) rounds the banded walk executes vs the dense R×T grid. The
    difference is the idle-skip win (paper Fig 11(c)), measurable without
    running the kernel."""
    n_blocks = int(sched.work[:, 0].max()) + 1
    total = n_blocks * sched.blk_min.shape[0]
    return int(sched.work[:, 2].sum()), total


def dense_skip_stats(dst: jax.Array, mask: Optional[jax.Array],
                     n_rows: int):
    """(live_rounds, total_rounds) of the UNSCHEDULED dense grid for the
    same edge stream — the dead-row routing and tile padding reproduce
    exactly what ``gas_scatter_fused`` dispatches without a schedule, so
    benchmarks and tests count the grid the kernel actually runs."""
    et = K.EDGE_TILE
    R = ((n_rows + K.ROW_BLOCK - 1) // K.ROW_BLOCK) * K.ROW_BLOCK
    ok = (dst >= 0) & (dst < n_rows)
    if mask is not None:
        ok = ok & mask
    dstp = _pad_to(jnp.where(ok, dst, R), et, 0, R)
    occ = occupancy_map(dstp, R // K.ROW_BLOCK, et)
    return int(occ.sum()), int(occ.size)


def occupancy_map(dst: jax.Array, n_row_blocks: int, edge_tile: int) -> jax.Array:
    """(row_blocks, edge_tiles) int32: does edge tile e touch row block r?

    This is the idle-skip buffer content (paper Fig 11(c)) for an UNBINNED
    edge stream — computed once per (graph partition, batch) and reused
    across feature blocks. One bincount over (block, tile) pairs:
    O(E + R·T), never the O(R·T·edge_tile) dense compare.
    """
    E = dst.shape[0]
    T = E // edge_tile
    blk = dst // K.ROW_BLOCK
    dead = (blk < 0) | (blk >= n_row_blocks)
    idx = jnp.where(dead, n_row_blocks, blk)                 # overflow bin
    flat = idx * T + jnp.arange(E, dtype=dst.dtype) // edge_tile
    counts = jnp.zeros(((n_row_blocks + 1) * T,), jnp.int32).at[flat].add(1)
    return (counts[: n_row_blocks * T].reshape(n_row_blocks, T) > 0
            ).astype(jnp.int32)


# ---------------------------------------------------------------------------
# dispatch wrappers
# ---------------------------------------------------------------------------

def gas_scatter(dst: jax.Array, values: jax.Array, n_rows: int, *,
                op: str = "add", interpret: bool | None = None) -> jax.Array:
    """Scatter-reduce ``values`` (E, F) into (n_rows, F) by ``dst`` (E,).

    Matches ``ref.gas_scatter_ref`` exactly (out-of-range dst ignored).
    One public call = one kernel dispatch (the or/1-D rewrites happen
    inside), ticked into ``count_dispatches``.
    """
    _tick("kernel_scatter")
    return _gas_scatter_jit(dst, values, n_rows, op=op, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_rows", "op", "interpret"))
def _gas_scatter_jit(dst: jax.Array, values: jax.Array, n_rows: int, *,
                     op: str = "add",
                     interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if op == "or":
        # boolean-or over {0,1} = max with an or-identity of 0 for empty
        # rows. The dtype rewrite happens exactly ONCE, before the ndim
        # dispatch: rewriting after the 1-D recursion re-entered the public
        # wrapper with op="or" still set, sending 1-D int values through the
        # float32 max round-trip at both recursion depths.
        out = _gas_scatter_jit(dst, values.astype(jnp.float32), n_rows,
                               op="max", interpret=interpret)
        return jnp.maximum(out, 0).astype(values.dtype)
    if values.ndim == 1:
        return _gas_scatter_jit(dst, values[:, None], n_rows, op=op,
                                interpret=interpret)[:, 0]

    F = values.shape[1]
    et = K.EDGE_TILE
    R = ((n_rows + K.ROW_BLOCK - 1) // K.ROW_BLOCK) * K.ROW_BLOCK

    # dead-row padding: invalid/padded edges target row R (outside all blocks)
    ok = (dst >= 0) & (dst < n_rows)
    dstp = jnp.where(ok, dst, R)
    dstp = _pad_to(dstp, et, 0, R)
    fill = {"add": 0.0, "max": -jnp.inf, "min": jnp.inf}[op]
    valp = jnp.where(ok[:, None], values, fill)
    valp = _pad_to(valp, et, 0, fill)
    valp = _pad_to(valp, K.FEAT_BLOCK, 1, fill)

    occ = occupancy_map(dstp, R // K.ROW_BLOCK, et)
    out = K.gas_scatter_pallas(dstp, valp, occ, R, op=op, interpret=interpret)
    return out[:n_rows, :F]


def gas_scatter_fused(dst: jax.Array, values: jax.Array,
                      weights: Optional[jax.Array], mask: Optional[jax.Array],
                      n_rows: int, *, op: str = "add", schedule=None,
                      interpret: bool | None = None) -> jax.Array:
    """Masked, weighted scatter-reduce in ONE kernel dispatch.

    The paper's aggregation atom without the XLA staging: the mask folds
    into the dead-row convention (a masked edge's dst becomes the padded
    row block past the end, so its CAM match lines are all zero — its value
    is never filled, only never matched), and for ``op="add"`` the weights
    ride into the kernel and scale the match lines before the MXU
    contraction. Compare ops ignore ``weights`` (pass None). ``values`` at
    masked positions must be finite (they are zero-matched, not replaced —
    a NaN times a zero match line would still poison a sum).

    ``schedule``: an ``EdgeSchedule`` — its ``work`` list swaps the dense
    R×T grid for the banded walk (each row block iterates only its own tile
    range; idle rounds are never even visited). The CALLER guarantees
    ``dst``/``values``/``weights``/``mask`` are already in ``schedule.perm``
    order — this wrapper never permutes (the dataflow permutes the edge
    LIST once, so gathered values arrive binned for free).

    One public call = one kernel dispatch, ticked into
    ``count_dispatches``.
    """
    _tick("kernel_scatter")
    return _gas_scatter_fused_jit(dst, values, weights, mask, n_rows, op=op,
                                  schedule=schedule, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_rows", "op", "interpret"))
def _gas_scatter_fused_jit(dst: jax.Array, values: jax.Array,
                           weights: Optional[jax.Array],
                           mask: Optional[jax.Array],
                           n_rows: int, *, op: str = "add", schedule=None,
                           interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    assert op in ("add", "max", "min"), op
    if values.ndim == 1:
        return _gas_scatter_fused_jit(dst, values[:, None], weights, mask,
                                      n_rows, op=op, schedule=schedule,
                                      interpret=interpret)[:, 0]

    F = values.shape[1]
    et = K.EDGE_TILE
    R = ((n_rows + K.ROW_BLOCK - 1) // K.ROW_BLOCK) * K.ROW_BLOCK

    ok = (dst >= 0) & (dst < n_rows)
    if mask is not None:
        ok = ok & mask
    dstp = _pad_to(jnp.where(ok, dst, R), et, 0, R)
    valp = _pad_to(_pad_to(values, et, 0, 0), K.FEAT_BLOCK, 1, 0)
    wp = None
    if op == "add" and weights is not None:
        wp = _pad_to(weights, et, 0, 0)

    n_blocks = R // K.ROW_BLOCK
    if schedule is None:
        occ = occupancy_map(dstp, n_blocks, et)
        out = K.gas_scatter_pallas(dstp, valp, occ, R, op=op, weights=wp,
                                   interpret=interpret)
    else:
        T = dstp.shape[0] // et
        assert schedule.blk_min.shape[0] == T, (
            f"schedule has {schedule.blk_min.shape[0]} tile bounds but the "
            f"padded edge stream has {T} tiles — was the schedule built for "
            f"a different edge count or tile size?")
        assert schedule.work.shape[0] == T + 2 * n_blocks, (
            f"schedule work list sized for a different row space: "
            f"{schedule.work.shape[0]} != {T} + 2·{n_blocks}")
        work = schedule.work
        if op == "add":
            # feature-block liveness rides the work list: per (edge tile ×
            # feature block) value occupancy, gathered onto each work row by
            # its tile index. The kernel then skips all-zero feature blocks
            # exactly like idle tiles — safe for add only (zero is its
            # identity and x + (-0.0) ≡ x, so skipping a zero block is
            # bit-exact). Derived from the value STREAM at dispatch time, so
            # sparse gathers (repro.core.sparse) shrink the round count with
            # no schedule or VJP changes — the backward pass re-derives it
            # from the fresh cotangent values.
            work = jnp.concatenate(
                [work, _feat_liveness(valp, work[:, 1])],
                axis=1)
        out = K.gas_scatter_banded(work, dstp, valp, R, op=op,
                                   weights=wp, interpret=interpret)
    return out[:n_rows, :F]


def _feat_liveness(valp: jax.Array, tiles: jax.Array) -> jax.Array:
    """(W, F//FEAT_BLOCK) int32: does work row w's edge tile have any
    nonzero value in feature block f? ``valp`` is the tile- and
    feature-padded value stream the kernel consumes."""
    et, fb = K.EDGE_TILE, K.FEAT_BLOCK
    T, Fp = valp.shape[0] // et, valp.shape[1]
    tile_live = (valp.reshape(T, et, Fp // fb, fb) != 0).any(axis=(1, 3))
    return jnp.take(tile_live.astype(jnp.int32), tiles, axis=0)


def feat_skip_stats(schedule: EdgeSchedule, values: jax.Array):
    """(live_rounds, band_rounds) of a scheduled add dispatch over these
    values — how many (row-block × edge-tile × feature-block) rounds the
    feature-skipping walk executes vs the banded walk without value
    occupancy (band rounds × feature blocks). The gap is the compressed-
    sparse win one level below the byte counters: rounds scale with the
    values' measured block density. Counted, not clocked."""
    valp = _pad_to(_pad_to(values, K.EDGE_TILE, 0, 0), K.FEAT_BLOCK, 1, 0)
    feat = _feat_liveness(valp, schedule.work[:, 1])
    live = schedule.work[:, 2] == 1
    return (int((feat * live[:, None].astype(jnp.int32)).sum()),
            int(live.sum()) * feat.shape[1])


__all__ = ["EdgeSchedule", "count_dispatches", "dense_skip_stats",
           "feat_skip_stats", "gas_scatter", "gas_scatter_fused",
           "gas_scatter_ref", "occupancy_map", "schedule_edges",
           "schedule_skip_stats"]
