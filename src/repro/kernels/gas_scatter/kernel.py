"""FAST-GAS scatter kernel (Pallas/TPU).

The paper's engine: CAM matches edge destinations against resident rows and
the match lines clock row-parallel updates in FAST SRAM; an idle-skip buffer
skips rounds with no match. TPU re-expression (DESIGN §2):

  * the accumulator row-block is the VMEM-resident "FAST SRAM" tile, pinned
    across the edge-tile grid dimension (BlockSpec index ignores ``e``);
  * the CAM match is an equality compare of the edge tile's dst ids against
    the row block's iota — producing the match-line matrix;
  * for sum-aggregation the match matrix is contracted with the value tile on
    the MXU (one-hot matmul): irregular scatter → dense matmul. Edge weights
    fuse here for free: scaling the match lines by ``w`` BEFORE the
    contraction makes the same matmul compute the weighted scatter, so no
    ``values * weights`` edge-stream is ever materialized in HBM;
  * idle-skip is a per-(row-block × edge-tile) occupancy bitmap computed on
    the host side of the op; ``pl.when`` skips the whole round — compute AND
    the value-tile traffic — exactly the paper's clock-gating. The skip only
    pays off when edges arrive destination-binned (``ops.schedule_edges``):
    binned tiles touch one or two row blocks, so the bitmap is a thin band
    instead of dense.

Grid: (row_blocks, feat_blocks, edge_tiles); edge innermost so the output
block is revisited (stays resident in VMEM while edges stream through).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# hardware-aligned tiles: rows/features/edges on 128 (the MXU dim and the
# lane width). Interpret mode (the CPU test tiers) runs the same tiles, so
# every CPU test executes the grid, the feature blocks and the compare loop
# the chip compiles. On a binned stream the live rounds are ≤ T +
# row_blocks − 1 regardless of tile width (the staircase argument), so the
# scheduled walk's round count is tile-size-robust.
ROW_BLOCK = 128
FEAT_BLOCK = 128
EDGE_TILE = 128

# The scalar-prefetch operand (the dense grid's occupancy map, the banded
# walk's work list) lives in SMEM, 1 MiB on v5e. Both are passed FLAT: SMEM
# pads a 2-D int32 array's minor dim to 128 words, so a (W, 9) work list
# would cost 512 B per row instead of 36. Half the SMEM is the budget; a
# list past it is refused here with the shapes that caused it instead of as
# a Mosaic allocation failure.
SMEM_PREFETCH_BYTES = 512 * 1024


def _check_prefetch(n_words: int, what: str) -> None:
    if 4 * n_words > SMEM_PREFETCH_BYTES:
        raise ValueError(
            f"{what} needs {4 * n_words} B of SMEM scalar prefetch, over the "
            f"{SMEM_PREFETCH_BYTES} B budget — dispatch fewer edges per call "
            f"(request_chunk) or use the scheduled walk")


def _identity(op: str) -> float:
    return {"add": 0.0, "max": -jnp.inf, "min": jnp.inf}[op]


def _add_round(rel, val_ref, out_ref, w_ref):
    """One scatter-add round: CAM match lines from the (1, et) lane-dense
    relative dst ids, optionally scaled by the (1, et) edge weights (the
    fused form of ``values * weights[:, None]`` followed by the unweighted
    scatter), contracted with the value tile on the MXU at full f32
    precision (the match lines are exact 0/1 or weights)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (ROW_BLOCK, rel.shape[-1]), 0)
    match = (rows == rel).astype(val_ref.dtype)             # CAM match lines
    if w_ref:
        match = match * w_ref[0][...].astype(val_ref.dtype)
    # row-parallel update: one-hot contraction on the MXU
    out_ref[...] += jax.lax.dot(match, val_ref[...],
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=out_ref.dtype)


def _cmp_round(rel, val_ref, out_ref, *, op: str):
    """One select-on-match round for max/min, a row of the block at a time.
    The tile's dst ids are transposed onto sublanes once (``rel_t[j, :]`` is
    edge j's id), so row i's extremum is a sublane reduction of the values
    whose id matches i — no 3-D select, and a VMEM peak of one (et, fb)
    tile at any tile width."""
    et, fb = val_ref.shape
    init = _identity(op)
    rel_t = jnp.broadcast_to(rel, (fb, et)).T               # (et, fb)
    val = val_ref[...]
    reduce_ = jnp.max if op == "max" else jnp.min
    combine = jnp.maximum if op == "max" else jnp.minimum

    def row(i, carry):
        red = reduce_(jnp.where(rel_t == i, val, init), axis=0, keepdims=True)
        out_ref[pl.ds(i, 1), :] = combine(out_ref[pl.ds(i, 1), :], red)
        return carry

    jax.lax.fori_loop(0, ROW_BLOCK, row, 0)


def _round(op, rel, val_ref, out_ref, w_ref):
    if op == "add":
        _add_round(rel, val_ref, out_ref, w_ref)
    else:
        _cmp_round(rel, val_ref, out_ref, op=op)


def _dense_kernel(occ_ref, dst_ref, *refs, op: str):
    """Grid (row_block, feat_block, edge_tile); ``occ_ref`` is the flat
    (row_blocks · edge_tiles) occupancy map in SMEM."""
    *w_ref, val_ref, out_ref = refs
    r, e = pl.program_id(0), pl.program_id(2)

    @pl.when(e == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _identity(op))

    @pl.when(occ_ref[r * pl.num_programs(2) + e] > 0)  # idle-skip: no match
    def _live():
        _round(op, dst_ref[...] - r * ROW_BLOCK, val_ref, out_ref, w_ref)


# ---------------------------------------------------------------------------
# the banded (scheduled) walk: grid = each row block's own tile range
# ---------------------------------------------------------------------------
#
# With a destination-binned edge stream the live (row-block × edge-tile)
# pairs form a staircase of ≤ T + R - 1 cells. Instead of scanning the full
# R×T grid and ``pl.when``-skipping the idle cells (each skipped cell still
# pays a grid-step round), the scheduled dispatch walks ONLY the live band:
# a scalar-prefetch work list of [row_block, tile, live, init] rows drives
# data-dependent BlockSpec index maps — the paper's idle-skip buffer
# consumed as a work queue rather than a gate. Work items are ordered by
# row block, so the output block's revisits stay consecutive (the TPU
# revisiting contract); ``init`` marks the first visit of each row block
# (empty blocks get one init-only step so every output row is defined).

def _banded_kernel(wk_ref, dst_ref, *refs, op: str, ncol: int):
    """Grid (feat_block, work_row); ``wk_ref`` is the flat (W · ncol) work
    list. With ``ncol > 4`` each row additionally carries one occupancy
    flag per feature block (columns 4…4+nfb — the compressed-sparse
    metadata riding the same scalar-prefetch list), so an all-zero value
    block skips its round exactly like an idle tile. Skipping is exact for
    add only: a zero block contributes the additive identity (and
    ``x + (-0.0) ≡ x``, so signed zeros can't leak)."""
    *w_ref, val_ref, out_ref = refs
    base = pl.program_id(1) * ncol

    @pl.when(wk_ref[base + 3] == 1)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _identity(op))

    live = wk_ref[base + 2] == 1
    if ncol > 4:
        live = jnp.logical_and(live, wk_ref[base + 4 + pl.program_id(0)] == 1)

    @pl.when(live)
    def _live():
        _round(op, dst_ref[...] - wk_ref[base] * ROW_BLOCK, val_ref, out_ref,
               w_ref)


def _edge_operands(dst, weights, op, index_map):
    """The per-edge streams as lane-dense (T, 1, EDGE_TILE) blocks — the
    layout Mosaic and XLA agree on for an int32/f32 id or weight tile."""
    T = dst.shape[0] // EDGE_TILE
    spec = pl.BlockSpec((None, 1, EDGE_TILE), index_map)
    specs, operands = [spec], [dst.reshape(T, 1, EDGE_TILE)]
    if weights is not None:
        assert op == "add", "compare ops do not consume edge weights"
        specs.append(spec)
        operands.append(weights.reshape(T, 1, EDGE_TILE))
    return specs, operands


@functools.partial(jax.jit, static_argnames=("n_rows", "op", "interpret"))
def gas_scatter_banded(work: jax.Array, dst: jax.Array, values: jax.Array,
                       n_rows: int, *, op: str = "add",
                       weights: jax.Array | None = None,
                       interpret: bool = False) -> jax.Array:
    """Scheduled FAST-GAS dispatch: the grid walks the live band only.

    work: (W, 4) int32 rows [row_block, tile, live, init], ordered by
    row_block (see ``ops.schedule_edges``); dst/values/weights as in
    ``gas_scatter_pallas`` and already destination-binned. An add-op work
    list may carry ``F // FEAT_BLOCK`` extra columns of per-(tile, feature
    block) value occupancy (``ops`` derives them from the value stream) —
    the kernel then skips all-zero feature blocks the way it skips idle
    tiles, so scheduled rounds track the values' ACTUAL nonzero blocks.
    """
    E, F = values.shape
    assert E % EDGE_TILE == 0 and F % FEAT_BLOCK == 0
    assert n_rows % ROW_BLOCK == 0
    W, ncol = work.shape
    assert ncol in (4, 4 + F // FEAT_BLOCK), work.shape
    _check_prefetch(W * ncol, f"banded work list ({W}, {ncol})")

    in_specs, operands = _edge_operands(
        dst, weights, op, lambda f, w, wk: (wk[w * ncol + 1], 0, 0))
    in_specs.append(pl.BlockSpec((EDGE_TILE, FEAT_BLOCK),
                                 lambda f, w, wk: (wk[w * ncol + 1], f)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(F // FEAT_BLOCK, W),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((ROW_BLOCK, FEAT_BLOCK),
                               lambda f, w, wk: (wk[w * ncol], f)),
    )
    return pl.pallas_call(
        functools.partial(_banded_kernel, op=op, ncol=ncol),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, F), values.dtype),
        interpret=interpret,
    )(work.reshape(-1), *operands, values)


@functools.partial(jax.jit, static_argnames=("n_rows", "op", "interpret"))
def gas_scatter_pallas(dst: jax.Array, values: jax.Array, occupancy: jax.Array,
                       n_rows: int, *, op: str = "add",
                       weights: jax.Array | None = None,
                       interpret: bool = False) -> jax.Array:
    """dst: (E,) int32 (pre-padded to tile multiple, dead rows ≥ n_rows_padded);
    values: (E, F) f32 (pre-padded); occupancy: (row_blocks, edge_tiles) int32;
    weights: optional (E,) edge weights fused into the add path's match lines
    (compare ops never consume weights — pass None).
    n_rows must be a multiple of ROW_BLOCK; F a multiple of FEAT_BLOCK."""
    E, F = values.shape
    assert E % EDGE_TILE == 0 and F % FEAT_BLOCK == 0
    assert n_rows % ROW_BLOCK == 0
    _check_prefetch(occupancy.size, f"occupancy map {occupancy.shape}")

    in_specs, operands = _edge_operands(dst, weights, op,
                                        lambda r, f, e, occ: (e, 0, 0))
    in_specs.append(pl.BlockSpec((EDGE_TILE, FEAT_BLOCK),
                                 lambda r, f, e, occ: (e, f)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_rows // ROW_BLOCK, F // FEAT_BLOCK, E // EDGE_TILE),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((ROW_BLOCK, FEAT_BLOCK),
                               lambda r, f, e, occ: (r, f)),
    )
    return pl.pallas_call(
        functools.partial(_dense_kernel, op=op),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, F), values.dtype),
        interpret=interpret,
    )(occupancy.reshape(-1), *operands, values)
