"""Compressed-sparse feature rows — per-row occupancy bitmap + packed
nonzero columns (the SGCN/LW-GCN layout for post-ReLU activations).

Real GCN activations go sparse after the first ReLU (SGCN measures 10–30%
density); moving dense R×F blocks then wastes exactly the bytes GRAPHIC's
50× claim is about. This module is the PURE codec layer (the wire.py
pattern): encode/decode transforms with no collectives and no kernel calls
of their own. The consumers live where those already are:

* ``repro.core.cgtrans`` gathers from a pre-packed table (two ``take``s —
  packed nonzeros + bitmap — instead of one dense row read: the SSD→host
  bytes scale with density) and, on the baseline dataflow, ships the raw
  row block as (packed ‖ bitmap) through ONE ``all_to_all``
  (``_sparse_all_to_all``, inside the collective-site allowlist);
* ``repro.kernels.gas_scatter`` consumes the same idea one level down:
  per-feature-block liveness rides the scalar-prefetch work list so the
  banded walk skips all-zero feature blocks like idle tiles.

The layout: a row ``x`` of width F becomes

* ``bitmap`` — ``ceil(F/32)`` int32 words, bit ``j`` of word ``w`` set iff
  ``x[32w + j] != 0`` (int32 on the wire, never uint — the dtype-flow rule);
* ``packed`` — the nonzero values in column order, left-justified into a
  static ``capacity`` columns (``FEAT_BLOCK``-aligned so the MXU
  contraction consumes it without repacking).

The decode is positional (a cumsum over the bitmap), so the round-trip is
EXACT — bit-for-bit, any dtype — whenever every row's popcount fits the
capacity. That fit is a STATIC gate (``sparse_fits``, the ``delta_ids_fit``
pattern): ``table_capacity`` measures the real table's worst row once on
the host, and a capacity that doesn't beat dense (capacity + bitmap words
≥ F) falls back to the unchanged dense path — never a silently-truncating
"compressed" one. cgtrans aggregation itself stays dense: aggregated
partials have UNION support (a sum of sparse rows is dense), so the format
compresses the gather and the raw-row shipment, not the partial shipment.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

#: feature modes every ``features=`` knob accepts
FEATURE_MODES = ("dense", "sparse")

#: packed-column alignment on wide tables — mirrors the kernel's MXU tile
#: (``kernels.gas_scatter.kernel.FEAT_BLOCK``; asserted equal by the sparse
#: tier so the two can never drift apart silently)
FEAT_ALIGN = 128

#: alignment for narrow tables (F not a FEAT_BLOCK multiple): the 8-lane
#: granule the interpret-mode kernel pads to
NARROW_ALIGN = 8

_WORD = 32  # bits per bitmap word


def validate_features(features: str) -> str:
    """The one place a ``features=`` string is checked (every entrypoint
    funnels through it, so a typo fails loudly at trace time)."""
    if features not in FEATURE_MODES:
        raise ValueError(
            f"unknown features mode {features!r} (have {FEATURE_MODES})")
    return features


def bitmap_words(n_features: int) -> int:
    """int32 words per row of the occupancy bitmap."""
    return -(-int(n_features) // _WORD)


def _align(n_features: int) -> int:
    return FEAT_ALIGN if n_features % FEAT_ALIGN == 0 else NARROW_ALIGN


def worst_case_capacity(n_features: int, density: float) -> int:
    """Static packed-column capacity for a target density, rounded up to
    the feature-block alignment and capped at F (density 1.0 ⇒ the gate
    falls back to dense — there is nothing to compress)."""
    a = _align(n_features)
    need = math.ceil(n_features * float(density))
    return min(int(n_features), -(-max(need, 1) // a) * a)


def table_capacity(feats) -> int:
    """The measured worst-row capacity of a concrete feature table — the
    max row popcount, alignment-rounded. Host-side, once per table (the
    ``schedule_edges`` economics): the result is a static Python int that
    bakes into the jaxpr as the packed width."""
    x = np.asarray(feats)
    F = x.shape[-1]
    nnz = int((x.reshape(-1, F) != 0).sum(axis=-1).max()) if x.size else 0
    a = _align(F)
    return min(int(F), -(-max(nnz, 1) // a) * a)


def sparse_fits(capacity: int, n_features: int) -> bool:
    """Static gate (the ``delta_ids_fit`` pattern): does the packed layout
    actually beat dense? Bytes per row are ``capacity + bitmap_words(F)``
    32-bit lanes vs ``F`` dense — equal-or-worse means the caller ships
    dense unchanged, never a silently-pointless "compression"."""
    return int(capacity) + bitmap_words(n_features) < int(n_features)


def density_stats(x) -> dict:
    """Measured density of a feature block — host floats for bench rows."""
    a = np.asarray(x)
    total = int(a.size)
    nnz = int((a != 0).sum())
    return {"nnz": nnz, "total": total,
            "density": (nnz / total) if total else 0.0}


def _nonzero(x: jnp.ndarray) -> jnp.ndarray:
    """Occupancy from the bit pattern, sign bit aside: XLA flushes
    subnormals to zero in a float compare, which would drop them from the
    packed row while the dense path (a plain copy) keeps them. Matches
    numpy's ``x != 0`` in ``table_capacity`` (-0.0 counts as zero)."""
    bits = x.dtype.itemsize * 8
    u = lax.bitcast_convert_type(x, jnp.dtype(f"uint{bits}"))
    return (u & jnp.asarray((1 << (bits - 1)) - 1, u.dtype)) != 0


def encode_rows(x: jnp.ndarray, capacity: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(…, F) rows → (packed (…, capacity) in x's dtype, bitmap (…, W)
    int32). Rows whose popcount exceeds ``capacity`` lose their trailing
    nonzeros (positionally) — the static ``sparse_fits``/``table_capacity``
    gate is what makes that impossible on the entrypoint paths."""
    F = x.shape[-1]
    W = bitmap_words(F)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, F)
    R = x2.shape[0]
    nz = _nonzero(x2)
    bits = jnp.pad(nz, ((0, 0), (0, W * _WORD - F)))
    words = (bits.reshape(R, W, _WORD).astype(jnp.uint32)
             << jnp.arange(_WORD, dtype=jnp.uint32)).sum(
                 -1, dtype=jnp.uint32)
    bitmap = lax.bitcast_convert_type(words, jnp.int32)
    # left-justify the nonzeros: zeros and over-capacity spill land in a
    # scratch column that the final slice drops
    pos = jnp.cumsum(nz, axis=-1) - 1
    col = jnp.where(nz & (pos < capacity), pos, capacity)
    packed = jnp.zeros((R, capacity + 1), x.dtype).at[
        jnp.arange(R)[:, None], col].set(x2)[:, :capacity]
    return (packed.reshape(*lead, capacity), bitmap.reshape(*lead, W))


def _unpack_bits(bitmap: jnp.ndarray, n_features: int) -> jnp.ndarray:
    """(…, W) int32 bitmap → (…, F) bool occupancy."""
    words = lax.bitcast_convert_type(bitmap, jnp.uint32)
    bits = (words[..., None] >> jnp.arange(_WORD, dtype=jnp.uint32)) & 1
    return (bits.reshape(*bitmap.shape[:-1], bitmap.shape[-1] * _WORD)
            [..., :n_features]).astype(bool)


def decode_rows(packed: jnp.ndarray, bitmap: jnp.ndarray,
                n_features: int) -> jnp.ndarray:
    """Inverse of ``encode_rows``: positional unpack through a cumsum over
    the occupancy bits. Exact whenever the row's popcount fit the packed
    capacity (the static gate's guarantee)."""
    C = packed.shape[-1]
    bits = _unpack_bits(bitmap, n_features)
    pos = jnp.cumsum(bits, axis=-1) - 1
    vals = jnp.take_along_axis(packed, jnp.clip(pos, 0, C - 1), axis=-1)
    return jnp.where(bits & (pos < C), vals, jnp.zeros((), packed.dtype))


def popcount(bitmap: jnp.ndarray) -> jnp.ndarray:
    """(…, W) int32 bitmap → (…,) int32 set-bit count (≡ the packed length
    the decode consumes — the property tests pin the equivalence)."""
    words = lax.bitcast_convert_type(bitmap, jnp.uint32)
    bits = (words[..., None] >> jnp.arange(_WORD, dtype=jnp.uint32)) & 1
    return bits.sum(axis=(-1, -2)).astype(jnp.int32)
