"""CGTrans — Compressive Graph Transmission (the paper's §3.2) on a mesh.

The storage tier is the ``data`` mesh axis: each shard owns a vertex interval
(features) and all edges whose *source* lies in it (gathers are local — the
in-SSD invariant). Two dataflows over identical math:

* ``baseline``  — GCNAX-style: ship **raw** gathered neighbor features to the
  destination owner, aggregate there. Interconnect bytes ∝ E·F (or B·K·F for
  sampled SAGE) — the paper's "slow SSD bus" regime.
* ``cgtrans``   — aggregate **at the owner** into per-destination partials and
  ship only those. Interconnect bytes ∝ V·F (or B·F): a fan-in/fan-out×
  compression — the paper's 50×.

Both are exposed full-graph (edge COO) and sampled (GraphSAGE fan-out), and
both run the per-shard reduction on either GAS backend: ``impl="xla"`` (the
jnp oracle) or ``impl="pallas"`` (the FAST-GAS kernel — CAM match + MXU
one-hot contraction + idle-skip; interpret-mode on CPU). ``pallas_call`` has
no shard_map replication rule, so the pallas dataflows trace with the
replication check disabled (``check_vma=False``) — the differential tier in
``tests/test_cgtrans_pallas.py`` is what guards their agreement instead.

``aggregate_sampled`` additionally supports a **chunked request stream**
(``request_chunk=``): instead of all-gathering the whole ``(B_loc, K)`` id
block, the seed block is streamed through a ``lax.scan`` in chunks — the
paper's SSD command-queue analogue — bounding per-shard peak gather memory at
``O(n·chunk·K·F)`` instead of ``O(n·B_loc·K·F)``. The chunked path is
bit-exact with the unchunked one (chunking partitions *seeds*, never a seed's
K contributions), which ``tests/test_cgtrans_pallas.py`` asserts.

**Coalesced request blocks.** ``aggregate_multi`` is the command-queue
batching applied across request *streams*: several sampled segments of
different fan-out (e.g. ``sage_forward``'s K=1 self-row lookup + its K2
2-hop block) concatenate into one (ids ‖ ``SegmentDescriptor``) command
block and run through ONE ``shard_map`` body — one ``all_gather`` of the
concatenated id stream (masks ride a ``-1`` encoding, so the request
broadcast is a single array), one kernel gather (``_multi_find``), one
``all_to_all`` of the concatenated partials (for ``op="add"`` the
contribution counts travel as one extra feature column instead of a second
collective), and — under ``impl="pallas"`` — one backward cotangent
scatter, split per segment by the static descriptor the VJP closes over.
``aggregate_sampled`` is its single-segment form, so the plain sampled path
inherits the single-collective request/response pair too; the K=1 segment
keeps the pure-find specialization (no kernel round-trip), and chunk
boundaries always respect segment boundaries. The coalesce tier
(``tests/test_cgtrans_coalesce.py``, ``ci.sh --tier coalesce``) asserts
coalesced ≡ separate bit-exactly (values and gradients) and pins the
counters: collectives-per-step 2 → 1, finds 2 → 1, backward scatters
2 → 1.

**Locality scheduling.** ``scheduled`` (default: on whenever
``impl="pallas"``) runs the paper's Fig 11(c) locality pass before the
per-shard reduction: ``gas.schedule_edges`` counting-sorts each shard's edge
stream by destination row block, the dataflow permutes the edge LIST once
(ids/weights/mask — O(E) ints; the gathered value stream then arrives binned
for free), and the kernel's idle-skip occupancy collapses to a thin band so
``pl.when`` actually skips. ``build_edge_schedule`` computes the schedule
once per (partition, batch) for reuse across layers (``gcn_forward_full``
hoists it out of its layer loop) and the backward pass; cotangents to the
permuted inputs un-permute through the transpose of the ``take`` that
applied the permutation, so gradients are schedule-invariant
(``tests/test_gas_schedule.py`` asserts bit-exactness on integer data). The
sampled path's seed rows are binned by construction, so its schedule is
sort-free (``assume_sorted``). The baseline dataflow schedules its
destination-side reduction after raw assembly (its shipped bytes are
unchanged — scheduling is always collective-neutral).

``benchmarks/collective_bytes.py`` lowers both on the production mesh and
diffs the collective bytes in the compiled HLO — the mechanism, measured.

**Both dataflows are differentiable on both backends.** The collectives
(``psum_scatter``/``all_gather``/``all_to_all``) carry JAX's own transpose
rules; the only op without one is ``pallas_call``, which is hidden behind the
forward-only custom VJPs in ``repro.core.gas`` (the embedding-lookup
pattern): the backward of the owner-side gather is a FAST-GAS scatter and
the backward of the seed scatter is a masked weighted gather — the reverse
pass is itself in-SSD GAS work, never a transpose through the kernel. Two
consequences visible in this file: the non-add cross-shard combine of
``aggregate_edges`` is an ``all_gather`` + local extremum (``lax.pmax`` has
no differentiation rule at all), and ``_finalize``/``_combine_shards`` mask
the ±inf max/min identity rows to 0 so no downstream ``0·inf`` ever turns a
train-step gradient into NaN. The grad parity tier
(``tests/test_cgtrans_grad.py``) asserts pallas ≡ xla ≡ finite differences
across the whole matrix.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import psum_scatter, shard_map
from repro.core import gas
from repro.core import sparse as sparsefmt
from repro.core import wire as wirefmt

AXIS = "data"  # the storage-tier axis

# The exchange's name in the trace: every cross-chip collective of a sharded
# body runs inside this scope (a transpose carries its forward's name), so a
# trace reduction can read the exchange apart from the work around it. It is
# not one of the scopes the per-scope readers attribute to, so their readings
# still put the exchange's ops under the scope that encloses it.
EXCHANGE_SCOPE = "cgtrans.exchange"


def _exchange():
    return jax.named_scope(EXCHANGE_SCOPE)


# ---------------------------------------------------------------------------
# the compressed wire (ROADMAP "make the C in CGTrans real"): the codecs live
# in repro.core.wire (pure transforms); the ONE collective they wrap lives
# here, inside the contract-covered module, so the collective-site allowlist
# never grows. wire="f32" keeps every pre-wire code path byte-identical.
# ---------------------------------------------------------------------------

def _wire_identity(op: gas.Op) -> float:
    """The op identity non-finite int8 codes decode back to (±inf for the
    max/min identity rows; add/or partials are finite so it never fires)."""
    return float(gas._INIT[op]) if op in ("max", "min") else 0.0


def _wired_a2a(x, wire: str, identity: float, n_exact: int):
    with _exchange():
        enc = wirefmt.encode_payload(x, wire, identity=identity,
                                     n_exact=n_exact)
        parts = lax.all_to_all(enc, AXIS, split_axis=0, concat_axis=0,
                               tiled=False)
        return wirefmt.decode_payload(parts, wire, identity=identity,
                                      n_exact=n_exact, out_dtype=x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _wire_all_to_all(x, wire: str, identity: float = 0.0, n_exact: int = 0):
    """``all_to_all`` with the payload encoded for transport and decoded
    (f32 math) on arrival. A ``custom_vjp`` so the codec's ``round``/
    ``where`` never meet autodiff: the backward ships the cotangent block
    through the SAME wire — split==concat axis makes the collective its own
    transpose — so the reverse pass pays the same compressed bytes."""
    return _wired_a2a(x, wire, identity, n_exact)


def _wire_a2a_fwd(x, wire, identity, n_exact):
    return _wired_a2a(x, wire, identity, n_exact), None


def _wire_a2a_bwd(wire, identity, n_exact, _res, g):
    # cotangents carry no ±inf identities (identity 0); the exact trailing
    # columns keep count cotangents exact — they are discarded into the
    # integer mask path anyway, but exactness keeps the wire's error model
    # one sentence: "quantization touches feature values only".
    return (_wired_a2a(g, wire, 0.0, n_exact),)


_wire_all_to_all.defvjp(_wire_a2a_fwd, _wire_a2a_bwd)


def _check_wire(wire: str, dataflow: str, features: str = "dense") -> str:
    """Validate a ``wire=`` knob at trace time. The baseline dataflow is the
    ship-raw strawman — compressing its wire would un-define the comparison
    the byte benches make — so only cgtrans accepts a narrow wire. With
    ``features="sparse"`` the baseline's shipment is the PACKED row block,
    which quantizes exactly like a cgtrans partial block does, so the narrow
    wire becomes legal there too (sparse nonzeros ship bf16/int8 + bitmap)."""
    wirefmt.validate(wire)
    if wire != "f32" and dataflow == "baseline" and features != "sparse":
        raise ValueError(
            "wire compression is a cgtrans-dataflow mechanism; the baseline "
            "strawman ships raw f32 by definition (features='sparse' is the "
            "exception: packed nonzeros quantize like partials)")
    return wire


# ---------------------------------------------------------------------------
# compressed-sparse features (repro.core.sparse): the codec is pure; the
# find that consumes the packed table and the ONE all_to_all that ships a
# packed row block both live HERE, inside the contract-covered module, so
# the collective-site allowlist and the dispatch-tick coverage never grow.
# ---------------------------------------------------------------------------

def _resolve_sparse(features: str, sparse_capacity: Optional[int],
                    n_features: int) -> Optional[int]:
    """``features=`` knob → the packed capacity to run with, or None for
    the dense path. ``features="sparse"`` requires an explicit capacity
    (``sparse.table_capacity(feats)`` — a static host-side measurement, the
    one thing trace-time code cannot derive); a capacity that fails the
    static ``sparse_fits`` gate falls back to dense UNCHANGED — the
    fallback ships exactly the pre-sparse bytes, never a truncated row."""
    if sparsefmt.validate_features(features) == "dense":
        if sparse_capacity is not None:
            raise ValueError(
                "sparse_capacity= only applies with features='sparse'")
        return None
    if sparse_capacity is None:
        raise ValueError(
            "features='sparse' needs sparse_capacity= — measure it once "
            "with sparse.table_capacity(feats) (a static host-side int)")
    cap = int(sparse_capacity)
    if cap < 1:
        raise ValueError(f"sparse_capacity must be ≥ 1, got {cap}")
    return cap if sparsefmt.sparse_fits(cap, n_features) else None


@functools.lru_cache(maxsize=None)
def _sparse_gather(n_rows: int, capacity: int, impl: str):
    """Row gather from the PACKED table — the SSD→host read that scales
    with density: two ``take``s (packed nonzeros in the table dtype + the
    int32 bitmap) move ``capacity + ceil(F/32)`` lanes per row instead of
    F. The decode is positional and the capacity gate is static, so the
    result is bit-exact with the dense gather — which is why ONE custom_vjp
    covers both backends: the backward is the same scatter-add of the
    cotangent rows the dense gather uses (``_gather_pallas`` under pallas —
    the FAST-GAS kernel; a segment-sum under xla, matching the take
    transpose), never a differentiation of the codec's cumsum."""

    @jax.custom_vjp
    def gather(table, ids):
        packed, bitmap = sparsefmt.encode_rows(table, capacity)
        rows = sparsefmt.decode_rows(
            jnp.take(packed, ids, axis=0), jnp.take(bitmap, ids, axis=0),
            table.shape[-1])
        return rows.astype(table.dtype)

    def fwd(table, ids):
        # the zero-size residual carries the table dtype into the bwd cast
        return gather(table, ids), (ids, jnp.zeros((0,), table.dtype))

    def bwd(res, g):
        ids, like = res
        gf = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
        if impl == "pallas":
            dtab = gas._scatter_weighted_impl(ids.reshape(-1), gf, None,
                                              None, n_rows, "add", "pallas")
        else:
            dtab = jax.ops.segment_sum(gf, ids.reshape(-1),
                                       num_segments=n_rows)
        return dtab.astype(like.dtype), np.zeros(np.shape(ids),
                                                 jax.dtypes.float0)

    gather.defvjp(fwd, bwd)
    return gather


def _find(table, ids, *, impl: str, sparse_cap: Optional[int] = None):
    """The find of find-and-compute, density-aware: dense tables route
    through ``gas.gas_gather`` unchanged; a packed capacity swaps in the
    compressed-table gather. Ticks ``find`` exactly once either way, so
    every dispatch budget is features-invariant."""
    if sparse_cap is None:
        return gas.gas_gather(table, ids, impl=impl)
    gas._tick("find")
    with jax.named_scope("gas.find"):
        return _sparse_gather(table.shape[0], sparse_cap, impl)(table, ids)


def _sparse_ship(x, wire: str, capacity: int):
    """Pack a raw (n, N, F) row block, ship (packed ‖ bitmap) through ONE
    ``all_to_all``, decode on arrival (f32 math under a narrow wire). The
    bitmap always travels as exact bitcast lanes — int16×2 / int8×4 per
    word — so only the nonzero VALUES ever quantize."""
    with _exchange():
        F = x.shape[-1]
        W = sparsefmt.bitmap_words(F)
        packed, bitmap = sparsefmt.encode_rows(x, capacity)
        if wire == "f32":
            payload = jnp.concatenate(
                [packed, lax.bitcast_convert_type(bitmap, x.dtype)], axis=-1)
            parts = lax.all_to_all(payload, AXIS, split_axis=0, concat_axis=0,
                                   tiled=False)
            pk = parts[..., :capacity]
            bm = lax.bitcast_convert_type(parts[..., capacity:], jnp.int32)
            return sparsefmt.decode_rows(pk, bm, F)
        enc = wirefmt.encode_payload(packed.astype(jnp.float32), wire)
        bits16 = lax.bitcast_convert_type(
            bitmap, enc.dtype).reshape(*bitmap.shape[:-1], -1)
        nb = bits16.shape[-1]
        parts = lax.all_to_all(jnp.concatenate([enc, bits16], axis=-1), AXIS,
                               split_axis=0, concat_axis=0, tiled=False)
        pk = wirefmt.decode_payload(parts[..., :parts.shape[-1] - nb], wire)
        bm = lax.bitcast_convert_type(
            parts[..., parts.shape[-1] - nb:].reshape(
                *parts.shape[:-1], W, nb // W), jnp.int32)
        return sparsefmt.decode_rows(pk, bm, F).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _sparse_all_to_all(x, wire: str, capacity: int):
    """The baseline dataflow's raw-row shipment on sparse features: bytes
    on the wire are ``capacity + ceil(F/32)`` lanes per row instead of F —
    the all_to_all bytes scale with density. A ``custom_vjp`` so the
    codec's cumsum/scatter never meets autodiff; the backward ships the
    DENSE cotangent through the plain wired collective (cotangent support
    is not statically knowable — rows that were zero forward can carry
    nonzero cotangents — so compressing it would need a runtime capacity;
    exactness over economy on the reverse path)."""
    return _sparse_ship(x, wire, capacity)


def _sparse_a2a_fwd(x, wire, capacity):
    return _sparse_ship(x, wire, capacity), None


def _sparse_a2a_bwd(wire, capacity, _res, g):
    return (_wired_a2a(g, wire, 0.0, 0),)


_sparse_all_to_all.defvjp(_sparse_a2a_fwd, _sparse_a2a_bwd)


def _check_vma(impl: str) -> Optional[bool]:
    """shard_map replication-check setting for a dataflow using ``impl``.

    ``pallas_call`` has no replication rule (NotImplementedError on trace), so
    pallas dataflows must disable the check; the xla dataflows keep the
    installed default.
    """
    return False if impl == "pallas" else None


def _resolve_scheduled(scheduled: Optional[bool], impl: str) -> bool:
    """The locality pass defaults on exactly where it pays: the kernel."""
    return (impl == "pallas") if scheduled is None else bool(scheduled)


def _permuted(sched, *arrays):
    """Apply an edge schedule's permutation to per-edge arrays. Autodiff
    transposes the ``take`` into the exact un-permuting scatter, so
    cotangents to weights (and values) return in original edge order."""
    with jax.named_scope("gas.schedule"):
        return tuple(jnp.take(a, sched.perm, axis=0) for a in arrays)


def is_sharded(mesh: Optional[Mesh]) -> bool:
    return (mesh is not None and AXIS in mesh.axis_names
            and mesh.shape[AXIS] > 1)


def build_edge_schedule(dst_global: jax.Array, mask: jax.Array,
                        n_vertices: int, *, mesh: Optional[Mesh] = None):
    """Destination-binned edge schedule for (P, E) edge arrays — computed
    ONCE per (partition, batch) and reused across layers, feature blocks,
    and the backward pass (pass it to ``aggregate_edges(schedule=...)``).

    On a sharded mesh the schedule is per-shard (every leaf keeps the
    leading P axis and shards with the edges); on the single-shard
    reference path it is one schedule over the flattened edge list.
    """
    if not is_sharded(mesh):
        return gas.schedule_edges(dst_global.reshape(-1), mask.reshape(-1),
                                  n_vertices)
    return jax.vmap(
        lambda d, m: gas.schedule_edges(d, m, n_vertices))(dst_global, mask)


def apply_edge_schedule(schedule, *edge_arrays):
    """Reorder per-shard (P, E) edge arrays into schedule order, ONCE.

    This is the SGCN-style data-format restructuring: pay the permutation
    at partition time, then every layer's aggregation (and its backward)
    consumes the binned edge list directly — pass the results to
    ``aggregate_edges(..., schedule=..., schedule_applied=True)``. Only
    meaningful for per-shard schedules (sharded-mesh layout); local src
    ids, weights and masks all permute shard-locally.
    """
    with jax.named_scope("gas.schedule"):
        return tuple(
            jax.vmap(lambda a, p: jnp.take(a, p, axis=0), in_axes=(0, 0))(
                a, schedule.perm)
            for a in edge_arrays)


# ---------------------------------------------------------------------------
# full-graph edge aggregation (GCN):  out[v] = Σ_{(u,v,w)∈E} w · feats[u]
# ---------------------------------------------------------------------------

def _agg_local(feats, src_local, dst_global, w, mask, n_vertices, op, impl,
               schedule=None, sparse_cap=None):
    """In-SSD step: local gather + segment-reduce into global dst bins.

    ``impl`` threads into BOTH halves: under pallas the scatter's VJP is the
    kernel's and the gather's VJP (a scatter of the feature cotangent) runs
    through the kernel too — the backward stays in the in-SSD regime.
    ``schedule``: banded idle-skip bounds for edge arrays that are already
    in schedule order (the caller permutes the edge list, so the gather
    emits the value stream binned). ``sparse_cap`` swaps the gather for the
    compressed-table read (``repro.core.sparse``) — the SSD→host bytes
    scale with density; the reduction itself stays dense (aggregated
    partials have union support).
    """
    gathered = _find(feats, src_local, impl=impl,
                     sparse_cap=sparse_cap)       # LOCAL by construction
    return gas.gas_scatter_weighted(
        dst_global, gathered, w, mask, n_vertices, op=op, impl=impl,
        schedule=schedule)


def aggregate_edges(
    feats: jax.Array,        # (P, part, F) owner-sharded vertex features
    src_local: jax.Array,    # (P, E) local src ids
    dst_global: jax.Array,   # (P, E) global dst ids
    weights: jax.Array,      # (P, E)
    mask: jax.Array,         # (P, E)
    *,
    mesh: Optional[Mesh] = None,
    dataflow: str = "cgtrans",      # cgtrans | baseline
    op: gas.Op = "add",
    impl: str = "xla",
    scheduled: Optional[bool] = None,   # None → on for impl="pallas"
    schedule=None,                      # precomputed build_edge_schedule(...)
    schedule_applied: bool = False,     # edge arrays already in perm order
    wire: str = "f32",                  # f32 | bf16 | int8 (cgtrans only)
    features: str = "dense",            # dense | sparse (repro.core.sparse)
    sparse_capacity: Optional[int] = None,
) -> jax.Array:
    """Returns (P, part, F) aggregated destination features, owner-sharded.

    ``scheduled`` runs the destination-binning locality pass before the
    per-shard reduction (see the module docstring); ``schedule`` supplies a
    precomputed ``build_edge_schedule`` result so multi-layer callers pay
    the counting sort once, and ``schedule_applied=True`` declares the edge
    arrays are ALREADY in schedule order (``apply_edge_schedule`` paid the
    permutation at partition time; sharded-mesh cgtrans flow only). The
    baseline dataflow bins its destination-side reduction after raw
    assembly (a precomputed V-space schedule does not apply there and is
    ignored). ``wire`` selects the transport format of the compressed
    transmission (``repro.core.wire``); the single-shard reference path has
    no interconnect, so there it is validated and otherwise a no-op.
    ``features="sparse"`` (with ``sparse_capacity=`` from
    ``sparse.table_capacity``) reads the feature table through the packed
    compressed-sparse layout — the in-SSD gather bytes scale with density;
    the partial shipments stay dense (union support) and every result is
    bit-exact with the dense path.
    """
    _check_wire(wire, dataflow, features)
    Pn, part, F = feats.shape
    sparse_cap = _resolve_sparse(features, sparse_capacity, F)
    V = Pn * part
    use_sched = _resolve_scheduled(scheduled, impl) or schedule is not None
    if schedule_applied:
        assert schedule is not None, "schedule_applied requires schedule="

    if not is_sharded(mesh):
        # single-shard reference: both dataflows degenerate to one reduction
        assert not schedule_applied, (
            "schedule_applied is a sharded-mesh layout (per-shard perms); "
            "the single-shard path flattens partitions and permutes itself")
        s = (src_local + (jnp.arange(Pn) * part)[:, None]).reshape(-1)
        d, w, m = (dst_global.reshape(-1), weights.reshape(-1),
                   mask.reshape(-1))
        sched = None
        if use_sched:
            sched = (schedule if schedule is not None
                     else gas.schedule_edges(d, m, V))
            s, d, w, m = _permuted(sched, s, d, w, m)
        out = _agg_local(feats.reshape(V, F), s, d, w, m, V, op, impl,
                         schedule=sched, sparse_cap=sparse_cap)
        return out.reshape(Pn, part, F)

    n = mesh.shape[AXIS]
    assert Pn == n, f"partitions ({Pn}) must equal data-axis size ({n})"

    if dataflow == "cgtrans":
        def shard_fn(f, s, d, w, m, *pre_sched):
            # f: (1, part, F); edge arrays (1, E). Per-shard E need not be
            # tile-aligned — the kernel wrapper pads and rebuilds the
            # occupancy map per shard from this shard's (padded) dst ids.
            s, d, w, m = s[0], d[0], w[0], m[0]
            sched = None
            if use_sched:
                sched = (jax.tree.map(lambda a: a[0], pre_sched[0])
                         if pre_sched else gas.schedule_edges(d, m, V))
                if not schedule_applied:
                    s, d, w, m = _permuted(sched, s, d, w, m)
            partial = _agg_local(f[0], s, d, w, m, V, op, impl,
                                 schedule=sched, sparse_cap=sparse_cap)
            # compressed transmission: reduce-scatter the (V, F) partials so
            # each shard receives exactly its owned interval, aggregated.
            if op == "add" and wire == "f32":
                with _exchange():
                    out = psum_scatter(partial.reshape(n, part, F), AXIS,
                                       scatter_dimension=0)
            elif op == "add":
                # a narrow wire cannot ride psum_scatter (it would SUM the
                # quantized codes on the wire — int8 codes from different
                # scales don't add); ship each owner its interval's encoded
                # partials and accumulate in f32 locally. Same bytes-on-wire
                # shape as the max/min path, ÷2 or ÷4 per the format.
                parts = _wire_all_to_all(partial.reshape(n, part, F), wire)
                out = parts.sum(0)
            else:
                # max/min/or have no fused reduce-scatter; ship each owner
                # its interval's partials (all_to_all: V·F bytes per shard,
                # like the add path's reduce-scatter) and reduce locally.
                # (Not lax.pmax/pmin: those have NO differentiation rule,
                # while all_to_all is its own transpose — the grad tier
                # differentiates this flow.) or-partials are ≥ 0, so max
                # realizes boolean-or.
                block = partial.reshape(n, part, F)
                if wire == "f32":
                    with _exchange():
                        parts = lax.all_to_all(block, AXIS, split_axis=0,
                                               concat_axis=0, tiled=False)
                else:
                    parts = _wire_all_to_all(block, wire, _wire_identity(op))
                out = parts.min(0) if op == "min" else parts.max(0)
            return out[None]

        args = (feats, src_local, dst_global, weights, mask)
        specs = (P(AXIS),) * 5
        if schedule is not None:
            args += (schedule,)
            specs += (P(AXIS),)
        return shard_map(
            shard_fn, mesh=mesh, in_specs=specs,
            out_specs=P(AXIS), check_vma=_check_vma(impl),
        )(*args)

    if dataflow == "baseline":
        def shard_fn(f, s, d, w, m):
            # raw transmission: gather locally, ship the full edge payload.
            # Weights scale contributions only under op="add" — max/min take
            # the raw feature and or ignores weights entirely (matching
            # gas_scatter_weighted, so baseline ≡ cgtrans ≡ reference).
            raw = _find(f[0], s[0], impl=impl, sparse_cap=sparse_cap)
            if op == "add":
                raw = raw * w[0][:, None].astype(raw.dtype)
            raw = jnp.where(m[0][:, None], raw, 0)
            with _exchange():
                all_raw = lax.all_gather(raw, AXIS)      # (n, E, F) — E·F·n bytes
                all_dst = lax.all_gather(d[0], AXIS)
                all_m = lax.all_gather(m[0], AXIS)
            # destination side ("the accelerator"): keep only owned interval
            lo = lax.axis_index(AXIS) * part
            rel = all_dst.reshape(-1) - lo
            ok = all_m.reshape(-1) & (rel >= 0) & (rel < part)
            vals = all_raw.reshape(-1, F)
            sched = None
            if use_sched:
                # baseline bins AFTER assembly: the scatter's row space is
                # this owner's interval, which only exists post-all_gather
                # (a precomputed V-space schedule cannot serve it)
                sched = gas.schedule_edges(rel, ok, part)
                rel, ok, vals = _permuted(sched, rel, ok, vals)
            out = gas.gas_scatter_weighted(
                jnp.clip(rel, 0, part - 1), vals,
                jnp.ones_like(rel, jnp.float32), ok, part, op=op, impl=impl,
                schedule=sched)
            return out[None]

        return shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=P(AXIS), check_vma=_check_vma(impl),
        )(feats, src_local, dst_global, weights, mask)

    raise ValueError(dataflow)


# ---------------------------------------------------------------------------
# sampled GraphSAGE aggregation: out[b] = reduce_k feats[nbrs[b, k]]
# ---------------------------------------------------------------------------

def _op_identity(dtype, op: gas.Op):
    """The reduction identity a no-sample row must hold, per dtype — matches
    the segment-reduce empty-segment convention (±inf on floats, the integer
    extremes on ints, 0 for add/or)."""
    if op in ("add", "or"):
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.inexact):
        return jnp.asarray(gas._INIT[op], dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.min if op == "max" else info.max, dtype)


def _seed_reduce_rows(rows, own, op: gas.Op, impl: str,
                      scheduled: bool = False):
    """Per-request-block GAS reduction on PRE-GATHERED candidate rows:
    (R, K, F) rows + (R, K) validity → (R, F) partials + (R,) own counts.

    This is the in-SSD step of the sampled path — the seed index is the
    destination row, so the fan-out reduction is exactly a FAST-GAS scatter
    (``impl`` selects the backend). Rows with no owned neighbor hold the op
    identity (0 for add/or, ±inf for max/min). The gather itself is the
    caller's (``aggregate_multi`` issues ONE combined gather for a whole
    coalesced command block and slices it per segment). The seed stream
    ``repeat(arange(R), K)`` is destination-binned by construction, so
    ``scheduled`` derives the idle-skip band sort-free (``assume_sorted``)
    — no permutation is ever applied here; the schedule is only built
    where it is consumed (the pallas kernel — XLA ignores it).
    """
    R, K, F = rows.shape
    if K == 1:
        # a single-sample request block is a pure *find*: the seed scatter
        # would be the identity permutation, so the reduction degenerates to
        # masking the gathered row with the op identity — no kernel
        # round-trip (the gather's VJP still scatters through the kernel
        # under pallas). This is the row-lookup path of ``sage_forward``.
        flat = rows.reshape(R, F)
        if op == "or":
            # mirror the scatter path's boolean-or normalization exactly:
            # int-cast the value, clamp the or-identity at 0 (a raw
            # passthrough would leak negative/fractional values)
            red = jnp.where(own.reshape(R, 1),
                            jnp.maximum(flat.astype(jnp.int32), 0),
                            0).astype(flat.dtype)
        else:
            red = jnp.where(own.reshape(R, 1), flat,
                            _op_identity(flat.dtype, op))
        return red, own.sum(-1)
    seed = jnp.repeat(jnp.arange(R, dtype=jnp.int32), K)
    sched = (gas.schedule_edges(seed, own.reshape(-1), R, assume_sorted=True)
             if scheduled and impl == "pallas" else None)
    red = gas.gas_scatter_weighted(
        seed, rows.reshape(R * K, F), jnp.ones((R * K,), jnp.float32),
        own.reshape(-1), R, op=op, impl=impl, schedule=sched)
    return red, own.sum(-1)


def _mask_identity_rows(out, op: gas.Op):
    """Zero the ±inf max/min identity rows (seeds with no valid sample).

    Applied at every *terminal* finalize (never on pre-combine partials —
    a shard with no sample for a seed must still contribute the identity to
    the cross-shard extremum). Keeping ±inf here would make any downstream
    use produce ``0·inf = NaN`` under autodiff — the classic silent
    train-step NaN — so identity rows now read 0 on every op, matching
    add/or, and their cotangent is cut at the ``where``.
    """
    if op in ("max", "min"):
        return jnp.where(jnp.isfinite(out), out, 0)
    return out


def _finalize(red, cnt, op: gas.Op):
    """Partial → output rows: mean for add, identity-masked passthrough
    otherwise (terminal positions only — see ``aggregate_sampled``)."""
    if op == "add":
        return red / jnp.maximum(cnt, 1).astype(red.dtype)[..., None]
    return _mask_identity_rows(red, op)


def _combine_shards(parts, cnts, op: gas.Op):
    """(n, B, F) per-source-shard partials (+ (n, B) counts) → (B, F)."""
    if op == "add":
        return parts.sum(0) / jnp.maximum(cnts.sum(0), 1).astype(parts.dtype)[..., None]
    if op in ("max", "or"):
        return _mask_identity_rows(parts.max(0), op)
    return _mask_identity_rows(parts.min(0), op)


def _pad_rows(x, mult, fill):
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1), constant_values=fill)


def scan_request_chunks(body, nbrs2d, mask2d, chunk: int):
    """Stream the (R, K) request block through ``body`` in row chunks.

    The SSD command-queue analogue: requests are issued ``chunk`` rows at a
    time; padded rows are all-masked so they reduce to the op identity and
    are sliced off. Chunking partitions rows (never a row's K entries), so
    the result is bit-exact with one full-block ``body`` call. ``body`` maps
    an (chunk, K) id/mask pair to (chunk, F) output rows. Shared with the
    chunked embedding lookup (``repro.models.embedding``). The scope spans
    the scan itself, so its per-chunk slicing and output writes are named
    with the body's work.
    """
    R = nbrs2d.shape[0]
    chunk = max(1, min(chunk, R))

    def step(_, inp):
        return None, body(*inp)

    with jax.named_scope("cgtrans.chunk"):
        nb = _pad_rows(nbrs2d, chunk, 0)
        mk = _pad_rows(mask2d, chunk, False)
        steps = nb.shape[0] // chunk
        _, outs = lax.scan(step, None, (nb.reshape(steps, chunk, -1),
                                        mk.reshape(steps, chunk, -1)))
        return outs.reshape(steps * chunk, -1)[:R]


class SegmentDescriptor(NamedTuple):
    """Static layout of a coalesced request block (one "SSD command block").

    A coalesced block concatenates S request segments — each a
    ``(rows_i, K_i)`` id/mask pair — into one flat id stream. The
    descriptor records where every segment lives in that stream so the ONE
    combined gather / all_to_all can be split back into per-segment
    results, forward and backward. All fields are static Python ints:
    under ``jit`` the descriptor is baked into the jaxpr (and closed over
    by the custom-VJP residuals of the pallas gather), so the backward
    splits the cotangent block along exactly the same boundaries — no
    runtime bookkeeping crosses the bus.

    ``shapes``       — per-segment (rows_i, K_i);
    ``id_offsets``   — flat-id offset of each segment (length S+1;
                       segment i's ids live at ``[id_offsets[i],
                       id_offsets[i+1])``, so ``id_offsets[-1]`` is the
                       total id count);
    ``row_offsets``  — output-row offset of each segment (length S+1) in
                       the concatenated (rows_tot, F) result block;
    ``tenants``      — per-segment OWNER tags (length S, or None for the
                       single-caller case). A serving command block fuses
                       request segments from several concurrent callers;
                       the tenant tag is what scatters each segment's rows
                       back to the caller that issued them and nobody else
                       (``repro.serving`` is the consumer; the serving tier
                       asserts results never cross callers).
    """
    shapes: Tuple[Tuple[int, int], ...]
    id_offsets: Tuple[int, ...]
    row_offsets: Tuple[int, ...]
    tenants: Optional[Tuple[int, ...]] = None

    @property
    def n_ids(self) -> int:
        return self.id_offsets[-1]

    @property
    def n_rows(self) -> int:
        return self.row_offsets[-1]

    def segments_of(self, tenant: int) -> Tuple[int, ...]:
        """Indices of the segments owned by ``tenant`` (in block order)."""
        if self.tenants is None:
            raise ValueError("descriptor carries no tenant tags")
        return tuple(i for i, t in enumerate(self.tenants) if t == tenant)


def segment_descriptor(shapes: Sequence[Tuple[int, int]],
                       tenants: Optional[Sequence[int]] = None
                       ) -> SegmentDescriptor:
    """Build the descriptor for segments of static (rows_i, K_i) shapes.

    ``tenants`` (optional) tags each segment with the caller that owns it —
    the cross-request serving engine fuses many callers' segments into one
    command block and uses the tags to scatter results back per caller.
    """
    shapes = tuple((int(r), int(k)) for r, k in shapes)
    if not shapes:
        raise ValueError("a request block needs at least one segment")
    if any(r < 1 or k < 1 for r, k in shapes):
        raise ValueError(f"degenerate segment in {shapes}")
    if tenants is not None:
        tenants = tuple(int(t) for t in tenants)
        if len(tenants) != len(shapes):
            raise ValueError(
                f"tenant tags ({len(tenants)}) must match segments "
                f"({len(shapes)})")
    ids, rows = [0], [0]
    for r, k in shapes:
        ids.append(ids[-1] + r * k)
        rows.append(rows[-1] + r)
    return SegmentDescriptor(shapes, tuple(ids), tuple(rows), tenants)


def _encode_requests(blocks):
    """Encode each (nbrs, mask) segment as one id stream with masked
    entries set to -1 — the request broadcast then carries ONE array
    instead of an (ids, mask) pair: a dead id resolves as owned-by-nobody
    on every shard (``rel < 0`` everywhere), which is exactly what the
    mask meant. Returns the (P, N_tot) concatenated stream."""
    flat = [jnp.where(m, nb, -1).reshape(nb.shape[0], -1)
            for nb, m in blocks]
    return flat[0] if len(flat) == 1 else jnp.concatenate(flat, axis=1)


def _multi_find(table, seg_ids, op: gas.Op, impl: str, use_sched: bool,
                sparse_cap: Optional[int] = None):
    """The in-SSD step of a coalesced command block: ONE combined gather
    over every segment's encoded ids, then the per-segment seed reductions.

    ``table``: (rows, F) local feature rows; ``seg_ids``: list of
    (R_i, K_i) encoded id blocks (-1 or out-of-range = dead). Exactly one
    ``gas_gather`` is issued regardless of segment count — under pallas its
    custom VJP therefore scatter-adds the whole block's cotangent through
    the kernel in ONE backward dispatch, split per segment by the same
    static offsets. ``sparse_cap`` swaps the gather for the packed
    compressed-table read (one find either way). Returns a list of
    (red_i (R_i, F), cnt_i (R_i,))."""
    V, F = table.shape
    flat = (seg_ids[0].reshape(-1) if len(seg_ids) == 1 else
            jnp.concatenate([s.reshape(-1) for s in seg_ids]))
    own = (flat >= 0) & (flat < V)
    rows = _find(table, jnp.clip(flat, 0, V - 1), impl=impl,
                 sparse_cap=sparse_cap)
    outs, off = [], 0
    for s in seg_ids:
        R, K = s.shape
        outs.append(_seed_reduce_rows(
            rows[off:off + R * K].reshape(R, K, F),
            own[off:off + R * K].reshape(R, K), op, impl, use_sched))
        off += R * K
    return outs


def aggregate_multi(
    feats: jax.Array,     # (P, part, F) owner-sharded features
    blocks,               # sequence of (nbrs (P, R_i, K_i), mask) segments
    *,
    mesh: Optional[Mesh] = None,
    dataflow: str = "cgtrans",
    op: gas.Op = "add",
    impl: str = "xla",
    request_chunk: Optional[int] = None,
    scheduled: Optional[bool] = None,   # None → on for impl="pallas"
    wire: str = "f32",                  # f32 | bf16 | int8 (cgtrans only)
    features: str = "dense",            # dense | sparse (repro.core.sparse)
    sparse_capacity: Optional[int] = None,
):
    """Coalesced request blocks: aggregate SEVERAL sampled request segments
    in ONE SSD command block. Returns a tuple of (P, R_i, F), one per
    segment, each exactly what ``aggregate_sampled`` would return for that
    segment alone (bit-exact on integer-valued data — the coalesce tier
    asserts it, values and gradients).

    This is the paper's command-queue batching applied across *request
    streams*, not just within one: ``sage_forward``'s self-row lookup (a
    K=1 pure find) and its 2-hop aggregation used to run as two
    ``shard_map`` bodies — two request broadcasts, two kernel gathers, two
    result shipments, two backward scatters per step. Here the segments
    concatenate into one (ids ‖ segment-descriptor) block and the sharded
    body runs ONCE:

    * **one request broadcast** — a single ``all_gather`` of the
      concatenated id stream (masks ride the ``-1`` encoding, so no second
      mask collective);
    * **one kernel gather** — ``_multi_find`` resolves every segment's ids
      against the local rows in one ``gas_gather``; per-segment reductions
      stay separate (a K=1 segment stays the pure find with no kernel
      round-trip, K>1 segments keep their sort-free banded schedules);
    * **one result shipment** — per-segment partials (plus, for
      ``op="add"``, the contribution counts as one extra feature column)
      concatenate into a single ``all_to_all`` payload, split back on
      arrival by the static ``SegmentDescriptor``;
    * **one cotangent scatter** — under ``impl="pallas"`` the combined
      gather's custom VJP scatters the whole block's cotangent through the
      FAST-GAS kernel in one dispatch; the descriptor (closed over as a
      static residual) splits the cotangent block the same way the forward
      split the results.

    ``request_chunk`` streams each segment through the collectives
    ``request_chunk`` rows at a time; chunk boundaries always respect the
    segment descriptor (a chunk never spans two segments — their K differ),
    so chunked mode degenerates to per-segment command queues and stays
    bit-exact with the unchunked block.

    ``wire`` compresses BOTH collectives (``repro.core.wire``): the request
    broadcast ships int16 delta-encoded ids (when the vertex range permits
    — a static gate; the ``-1`` encoding is preserved exactly) and the
    result shipment ships bf16 or per-row-scaled int8 partials, decoded to
    f32 before any accumulation. The backward cotangent block takes the
    same wire. ``wire="f32"`` traces byte-identically to the pre-wire code;
    the unsharded reference path has no interconnect, so wire is a no-op
    there (validated, then ignored).

    ``features="sparse"`` (capacity from ``sparse.table_capacity``) reads
    the local table through the packed compressed-sparse layout on BOTH
    dataflows (the SSD→host gather bytes scale with density), and on the
    baseline dataflow additionally ships the raw row block as
    (packed nonzeros ‖ occupancy bitmap) through the same single
    ``all_to_all`` — composing with a narrow wire, the nonzeros quantize
    while the bitmap rides exact. cgtrans partial shipments stay dense
    (aggregated rows have union support). Bit-exact with dense, values and
    gradients, under the static capacity gate; a capacity that fails
    ``sparse.sparse_fits`` falls back to the unchanged dense path.
    """
    if dataflow not in ("cgtrans", "baseline"):
        raise ValueError(dataflow)
    _check_wire(wire, dataflow, features)
    blocks = tuple(blocks)
    Pn, part, F = feats.shape
    sparse_cap = _resolve_sparse(features, sparse_capacity, F)
    desc = segment_descriptor([nb.shape[-2:] for nb, _ in blocks])
    use_sched = _resolve_scheduled(scheduled, impl)
    enc = _encode_requests(blocks)                       # (P, N_tot)

    def split_ids(flat):
        """Flat (… N_tot) stream → per-segment (…·R_i, K_i) blocks."""
        return [flat[..., desc.id_offsets[i]:desc.id_offsets[i + 1]]
                .reshape(-1, k)
                for i, (r, k) in enumerate(desc.shapes)]

    if not is_sharded(mesh):
        table = feats.reshape(Pn * part, F)
        seg_enc = split_ids(enc)                         # (Pn·R_i, K_i)
        if request_chunk is None:
            outs = [_finalize(red, cnt, op)
                    for red, cnt in _multi_find(table, seg_enc, op, impl,
                                                use_sched, sparse_cap)]
        else:
            def one(nb_c, m_c):
                red, cnt = _multi_find(table, [jnp.where(m_c, nb_c, -1)],
                                       op, impl, use_sched, sparse_cap)[0]
                return _finalize(red, cnt, op)

            outs = [scan_request_chunks(one, e, e >= 0, request_chunk)
                    for e in seg_enc]
        return tuple(o.reshape(Pn, r, F)
                     for o, (r, k) in zip(outs, desc.shapes))

    n = mesh.shape[AXIS]
    assert Pn == n, f"partitions ({Pn}) must equal data-axis size ({n})"

    def shard_fn(f, ids_enc):
        f, ids_enc = f[0], ids_enc[0]                    # (part, F), (N_tot,)
        lo = lax.axis_index(AXIS) * part

        def fetch(seg_enc):
            """ONE command block over local segments [(r_i, k_i) encoded
            ids] → list of (r_i, F) aggregated rows for OUR seeds."""
            shapes = [s.shape for s in seg_enc]
            flat = (seg_enc[0].reshape(-1) if len(seg_enc) == 1 else
                    jnp.concatenate([s.reshape(-1) for s in seg_enc]))
            # the request broadcast: ONE all_gather of the concatenated id
            # stream ("addresses into the SSD" — masks ride the encoding).
            # On a narrow wire the stream ships as int16 first-order deltas
            # (half the bytes) whenever the vertex range statically fits —
            # the cumsum decode restores every id, -1 dead codes included.
            with _exchange():
                if wire != "f32" and wirefmt.delta_ids_fit(n * part):
                    ids = wirefmt.delta_decode_ids(
                        lax.all_gather(wirefmt.delta_encode_ids(flat), AXIS))
                else:
                    ids = lax.all_gather(flat, AXIS)     # (n, N)
            rel = ids - lo                               # dead ids stay < 0

            if dataflow == "cgtrans":
                # one source of truth for the segment layout: the same
                # descriptor arithmetic callers and the VJP split by
                offs = segment_descriptor(shapes).id_offsets
                seg_rel = [rel[:, offs[i]:offs[i + 1]].reshape(n * r, k)
                           for i, (r, k) in enumerate(shapes)]
                # in-SSD aggregation: ONE gather, per-segment reductions
                found = _multi_find(f, seg_rel, op, impl, use_sched,
                                    sparse_cap)
                reds = [red.reshape(n, r, F)
                        for (red, _), (r, k) in zip(found, shapes)]
                payload = reds[0] if len(reds) == 1 else jnp.concatenate(
                    reds, axis=1)                        # (n, R_tot, F)
                if op == "add":
                    cnts = [cnt.reshape(n, r).astype(f.dtype)
                            for (_, cnt), (r, k) in zip(found, shapes)]
                    cnt = (cnts[0] if len(cnts) == 1 else
                           jnp.concatenate(cnts, axis=1))
                    # the counts ride the payload as one extra feature
                    # column — compressed transmission stays ONE collective
                    payload = jnp.concatenate([payload, cnt[..., None]],
                                              axis=-1)
                if wire == "f32":
                    with _exchange():
                        parts = lax.all_to_all(payload, AXIS, split_axis=0,
                                               concat_axis=0, tiled=False)
                else:
                    # quantize the shipment; the add path's count column is
                    # an "exact" column (int8 bitcasts it; bf16 carries
                    # integer counts ≤ 256 exactly) so the mean never
                    # divides by a quantized count
                    parts = _wire_all_to_all(
                        payload, wire, _wire_identity(op),
                        1 if op == "add" else 0)
                outs, roff = [], 0
                for r, k in shapes:
                    seg = parts[:, roff:roff + r]
                    roff += r
                    outs.append(_combine_shards(seg[..., :F], seg[..., F],
                                                op) if op == "add"
                                else _combine_shards(seg, None, op))
                return outs

            # baseline: gather once, ship the raw (n, N, F) rows plus the
            # ownership bits to the seed owners, reduce there ("the
            # accelerator") — also through the GAS engine.
            own = (rel >= 0) & (rel < part)
            rows = _find(f, jnp.clip(rel, 0, part - 1).reshape(-1),
                         impl=impl, sparse_cap=sparse_cap).reshape(n, -1, F)
            rows = jnp.where(own[..., None], rows, 0)
            if sparse_cap is not None and rows.dtype.itemsize == 4:
                # the raw shipment, packed: non-owned rows were just zeroed
                # (popcount 0) and owned rows fit the table's capacity gate,
                # so the SAME static capacity covers every shipped row
                raw = _sparse_all_to_all(rows, wire, sparse_cap)
            else:
                # sub-32-bit tables (bf16 serving) keep the dense ship: an
                # int32 bitmap has no 16-bit bitcast lane to ride in
                with _exchange():
                    raw = lax.all_to_all(rows, AXIS, split_axis=0,
                                         concat_axis=0, tiled=False)
            with _exchange():
                okk = lax.all_to_all(own[..., None], AXIS, split_axis=0,
                                     concat_axis=0, tiled=False)[..., 0]
            outs, off = [], 0
            for r, k in shapes:
                sl = slice(off, off + r * k)
                off += r * k
                # every source shard's k candidates line up per seed row:
                # (r, n·k) — the destination-side reduce is a seed scatter
                seg_rows = raw[:, sl].reshape(n, r, k, F).transpose(
                    1, 0, 2, 3).reshape(r, n * k, F)
                seg_ok = okk[:, sl].reshape(n, r, k).transpose(
                    1, 0, 2).reshape(r, n * k)
                red, cnt = _seed_reduce_rows(seg_rows, seg_ok, op, impl,
                                             use_sched)
                outs.append(_finalize(red, cnt, op))
            return outs

        if request_chunk is None:
            outs = fetch(split_ids(ids_enc))
        else:
            # the chunked command queue respects segment boundaries: each
            # segment streams separately (their K differ, so a chunk can
            # never span two segments)
            def one(nb_c, m_c):
                return fetch([jnp.where(m_c, nb_c, -1)])[0]

            outs = [scan_request_chunks(one, e, e >= 0, request_chunk)
                    for e in split_ids(ids_enc)]
        return tuple(o[None] for o in outs)

    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS)),
        out_specs=tuple(P(AXIS) for _ in blocks), check_vma=_check_vma(impl),
    )(feats, enc)


def aggregate_sampled(
    feats: jax.Array,     # (P, part, F) owner-sharded features
    nbrs: jax.Array,      # (P, B_loc, K) global neighbor ids, seed-sharded
    mask: jax.Array,      # (P, B_loc, K)
    *,
    mesh: Optional[Mesh] = None,
    dataflow: str = "cgtrans",
    op: gas.Op = "add",
    impl: str = "xla",
    request_chunk: Optional[int] = None,
    scheduled: Optional[bool] = None,   # None → on for impl="pallas"
    wire: str = "f32",                  # f32 | bf16 | int8 (cgtrans only)
    features: str = "dense",            # dense | sparse (repro.core.sparse)
    sparse_capacity: Optional[int] = None,
) -> jax.Array:
    """Returns (P, B_loc, F) aggregated neighbor features per seed.

    ``op="add"`` is the masked *mean* (GraphSAGE); max/min/or reduce
    elementwise over the valid samples. Seeds with no valid sample read 0 on
    every op — the ±inf max/min identities are masked at the terminal
    finalize (``_mask_identity_rows``) so autodiff never meets ``0·inf``.
    ``impl`` selects the GAS backend for every per-shard reduction (both
    backends differentiate; under pallas the backward runs through the
    FAST-GAS kernel); ``request_chunk`` streams the seed block through the
    collectives ``request_chunk`` seeds at a time; ``scheduled`` turns the
    per-shard reductions' idle-skip occupancy into the sort-free banded form
    (seed rows are destination-binned by construction).

    This is the single-segment form of ``aggregate_multi`` — one code path,
    so every coalesced mechanism (mask-encoded request broadcast, count
    column riding the payload) serves the plain sampled entry too: one
    ``all_gather`` + one ``all_to_all`` per request burst on the cgtrans
    dataflow.
    """
    out, = aggregate_multi(feats, ((nbrs, mask),), mesh=mesh,
                           dataflow=dataflow, op=op, impl=impl,
                           request_chunk=request_chunk, scheduled=scheduled,
                           wire=wire, features=features,
                           sparse_capacity=sparse_capacity)
    return out
