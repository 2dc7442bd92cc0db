"""GCN / GraphSAGE models on the CGTrans substrate (the paper's workload).

Two entry styles:

* ``gcn_forward_full`` — full-graph GCN layers (aggregation = CGTrans edge
  dataflow, combination = tensor-parallel matmul). Used by correctness tests
  and the full-graph benchmarks.
* ``sage_*`` — minibatch GraphSAGE (fan-out sampling, the paper's deployed
  algorithm §4.2). Vertex features live **owner-sharded on the storage tier**
  (never shipped raw under CGTrans); the training batch carries only ids.
  Layer-1's remote feature aggregation is the distributed step; deeper layers
  compute on the locally-materialized subgraph (standard practice).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.common.schema import ParamDef
from repro.core import cgtrans


@dataclasses.dataclass(frozen=True)
class GCNConfig:
    n_features: int
    hidden: int = 128
    n_classes: int = 16
    fanout: int = 50             # paper: GraphSAGE samples 50 neighbors
    aggregate: str = "add"       # add | max  (paper: sum and max are common)
    dataflow: str = "cgtrans"    # cgtrans | baseline
    n_layers: int = 2
    impl: str = "xla"            # xla | pallas — GAS backend for aggregation
    request_chunk: Optional[int] = None  # SSD command-queue depth (seeds per
                                         # sampled-aggregation request burst)
    scheduled: Optional[bool] = None     # destination-binned edge schedule
                                         # (idle-skip locality pass); None →
                                         # on exactly when impl="pallas"
    coalesce: bool = True                # fuse sage_forward's self-row
                                         # lookup + 2-hop aggregation into
                                         # ONE SSD command block (one
                                         # all_gather/all_to_all/kernel
                                         # gather/backward scatter); False
                                         # = the legacy two-body form
    wire: str = "f32"                    # collective transport format
                                         # (repro.core.wire): f32 | bf16 |
                                         # int8 — quantized partials +
                                         # delta-encoded id streams, f32
                                         # accumulation always (cgtrans
                                         # dataflow only)
    features: str = "dense"              # feature transport format
                                         # (repro.core.sparse): dense |
                                         # sparse — compressed-sparse rows
                                         # (occupancy bitmap + packed
                                         # nonzeros) on the table gather
                                         # and the baseline raw-row
                                         # shipment; requires
                                         # sparse_capacity
    sparse_capacity: Optional[int] = None  # static packed width for
                                         # features="sparse" — measure it
                                         # once per table with
                                         # sparse.table_capacity(feats)
    partition: str = "interval"          # host-side vertex layout
                                         # (repro.graph.partition): interval
                                         # = contiguous-id split | island =
                                         # islandized locality relabeling —
                                         # callers partition via
                                         # partition_graph(method="island")
                                         # and pass the IslandPartition's
                                         # relabel map to sage_forward /
                                         # gcn_forward_full, which translate
                                         # ids in and un-permute full-graph
                                         # outputs back to original vertex
                                         # order (islandized ≡ interval
                                         # bit-exact)


def gcn_schema(cfg: GCNConfig) -> Dict[str, Any]:
    F, H, C = cfg.n_features, cfg.hidden, cfg.n_classes
    s: Dict[str, Any] = {}
    d_in = F
    for i in range(cfg.n_layers):
        d_out = H
        # SAGE concat [self ‖ aggregated] → weight is (2·d_in, d_out)
        s[f"w{i}"] = ParamDef((2 * d_in, d_out), ("embed", "ff"), init="lecun")
        s[f"b{i}"] = ParamDef((d_out,), ("ff",), init="zeros")
        d_in = H
    s["w_out"] = ParamDef((d_in, C), ("embed", None), init="lecun")
    s["b_out"] = ParamDef((C,), (None,), init="zeros")
    return s


# ---------------------------------------------------------------------------
# full-graph GCN
# ---------------------------------------------------------------------------

def _check_partition_knob(cfg: GCNConfig, relabel) -> None:
    """``cfg.partition`` and the relabel map travel together or not at all:
    an islandized partition without the map (or vice versa) would silently
    aggregate the wrong rows, so mismatches fail loudly at trace time."""
    if cfg.partition not in ("interval", "island"):
        raise ValueError(f"unknown cfg.partition {cfg.partition!r} "
                         "(expected 'interval' or 'island')")
    if (cfg.partition == "island") != (relabel is not None):
        raise ValueError(
            "cfg.partition='island' requires the IslandPartition relabel map "
            "(relabel=isl.relabel), and relabel= requires partition='island' "
            f"— got partition={cfg.partition!r}, "
            f"relabel={'set' if relabel is not None else 'None'}")


def gcn_forward_full(params, feats, src_local, dst_global, weights, mask,
                     cfg: GCNConfig, *, mesh: Optional[Mesh] = None,
                     impl: Optional[str] = None, relabel=None):
    """feats: (P, part, F) owner-sharded. Returns (P, part, C) logits.

    ``impl`` overrides ``cfg.impl`` when given (the benchmarks sweep it).
    The destination-binned edge schedule is computed ONCE here and reused by
    every layer's aggregation (and, as a VJP residual, by the backward
    pass) — the paper's idle-skip buffer content is per (partition, batch),
    not per layer.

    With ``cfg.partition="island"`` the inputs live in the islandized id
    space (``partition_graph(..., method="island")``) and ``relabel`` is the
    old→new map; the output is un-permuted back so row ``v`` of the
    flattened result is original vertex ``v``'s logits (pad rows zeroed),
    making islandized ≡ interval bit-exact row-for-row over ``[0, V)``.
    """
    _check_partition_knob(cfg, relabel)
    impl_r = impl or cfg.impl
    use_sched = (impl_r == "pallas") if cfg.scheduled is None else cfg.scheduled
    sched, applied = None, False
    # (the sharded baseline flow bins AFTER raw assembly in its own row
    # space — a precomputed V-space schedule would be dead work there)
    if use_sched and (cfg.dataflow == "cgtrans"
                      or not cgtrans.is_sharded(mesh)):
        sched = cgtrans.build_edge_schedule(
            dst_global, mask, feats.shape[0] * feats.shape[1], mesh=mesh)
        if cgtrans.is_sharded(mesh):
            # pay the edge-list permutation once at partition time too —
            # every layer then consumes the binned list directly
            src_local, dst_global, weights, mask = cgtrans.apply_edge_schedule(
                sched, src_local, dst_global, weights, mask)
            applied = True
    h = feats
    for i in range(cfg.n_layers):
        agg = cgtrans.aggregate_edges(
            h, src_local, dst_global, weights, mask,
            mesh=mesh, dataflow=cfg.dataflow, op=cfg.aggregate,
            impl=impl_r, scheduled=use_sched, schedule=sched,
            schedule_applied=applied, wire=cfg.wire,
            # sparse only where the gather reads the RAW table: layer-0
            # rows are post-ReLU-style sparse inputs, deeper layers' h are
            # dense activations whose measured capacity would be F anyway
            features=cfg.features if i == 0 else "dense",
            sparse_capacity=cfg.sparse_capacity if i == 0 else None)
        with jax.named_scope("gcn.combine"):
            if cfg.aggregate in ("max", "min"):
                # vertices with no in-edges hold the ±inf identity; mask
                # before the combine so neither the forward nor the
                # cotangent meets inf
                agg = jnp.where(jnp.isfinite(agg), agg, 0.0)
            h = jnp.concatenate([h, agg], axis=-1)
            h = jax.nn.relu(jnp.einsum("pvf,fh->pvh", h, params[f"w{i}"])
                            + params[f"b{i}"])
    with jax.named_scope("gcn.combine"):
        out = jnp.einsum("pvh,hc->pvc", h, params["w_out"]) + params["b_out"]
    if relabel is not None:
        # un-permute: islandized row relabel[v] holds original vertex v.
        # Interval mode places vertex v at flat row v exactly (owner = v //
        # part, local = v % part), so after this gather the two layouts
        # agree row-for-row on [0, V); the replicated gather stays off the
        # data axis (host-permutation bookkeeping, not a collective).
        P_, psz, C = out.shape
        flat = out.reshape(P_ * psz, C)
        orig = jnp.take(flat, jnp.asarray(relabel, jnp.int32), axis=0)
        flat = jnp.zeros_like(flat).at[: orig.shape[0]].set(orig)
        out = flat.reshape(P_, psz, C)
    return out


# ---------------------------------------------------------------------------
# minibatch GraphSAGE
# ---------------------------------------------------------------------------

def lookup_rows(feats, ids, *, mesh=None, dataflow="cgtrans", impl="xla",
                request_chunk=None, scheduled=None, wire="f32",
                features="dense", sparse_capacity=None):
    """Distributed row lookup: ids (P, B_loc) → (P, B_loc, F)."""
    nbrs = ids[..., None]
    mask = jnp.ones_like(nbrs, dtype=bool)
    return cgtrans.aggregate_sampled(feats, nbrs, mask, mesh=mesh,
                                     dataflow=dataflow, impl=impl,
                                     request_chunk=request_chunk,
                                     scheduled=scheduled, wire=wire,
                                     features=features,
                                     sparse_capacity=sparse_capacity)


def sage_forward(params, feats, batch, cfg: GCNConfig, *,
                 mesh: Optional[Mesh] = None, relabel=None):
    """2-layer minibatch GraphSAGE.

    batch (all seed-sharded on the data axis, leading dim P):
      seeds (P, B)            seed vertex ids
      nbrs1 (P, B, K1)        1-hop samples
      mask1 (P, B, K1)
      nbrs2 (P, B·(1+K1), K2) 2-hop samples for every layer-1 vertex
      mask2 (P, B·(1+K1), K2)

    Returns (P, B, C) logits.

    With ``cfg.coalesce`` (the default) the distributed step issues ONE
    coalesced SSD command block (``cgtrans.aggregate_multi``): the self-row
    lookups and the 2-hop requests share a single request broadcast, kernel
    gather, result all_to_all and backward cotangent scatter —
    collectives-per-step 2 → 1 vs the two-body form, bit-exact both ways
    (``tests/test_cgtrans_coalesce.py``).

    With ``cfg.partition="island"`` the feature table is islandized
    (``IslandPartition.relabel_rows`` order) and ``relabel`` translates the
    batch's caller-visible vertex ids into that space at entry. Outputs are
    positional per seed — no un-permute needed — so islandized ≡ interval
    bit-exact (identical rows fetched in identical order).
    """
    _check_partition_knob(cfg, relabel)
    if relabel is not None:
        r = jnp.asarray(relabel, jnp.int32)
        batch = dict(batch,
                     seeds=jnp.take(r, batch["seeds"]),
                     nbrs1=jnp.take(r, batch["nbrs1"]),
                     nbrs2=jnp.take(r, batch["nbrs2"]))
    Pn, B = batch["seeds"].shape
    K1 = batch["nbrs1"].shape[-1]

    ids1 = jnp.concatenate([batch["seeds"][..., None], batch["nbrs1"]], axis=-1)
    flat1 = ids1.reshape(Pn, B * (1 + K1))

    # distributed step: fetch self features + aggregate 2-hop neighborhoods.
    if cfg.coalesce:
        # ONE SSD command block: the self-row lookups (a K=1 pure-find
        # segment) and the 2-hop sample requests concatenate into a single
        # (ids ‖ segment-descriptor) block — one request broadcast, one
        # kernel gather, one compressed result shipment, and (under
        # impl="pallas") one backward cotangent scatter, where the
        # two-body form below issues two of each.
        x_self, x_agg = cgtrans.aggregate_multi(
            feats,
            ((flat1[..., None], jnp.ones(flat1.shape + (1,), bool)),
             (batch["nbrs2"], batch["mask2"])),
            mesh=mesh, dataflow=cfg.dataflow, impl=cfg.impl,
            request_chunk=cfg.request_chunk, scheduled=cfg.scheduled,
            wire=cfg.wire, features=cfg.features,
            sparse_capacity=cfg.sparse_capacity)
    else:
        x_self = lookup_rows(feats, flat1, mesh=mesh, dataflow=cfg.dataflow,
                             impl=cfg.impl, request_chunk=cfg.request_chunk,
                             scheduled=cfg.scheduled, wire=cfg.wire,
                             features=cfg.features,
                             sparse_capacity=cfg.sparse_capacity)
        x_agg = cgtrans.aggregate_sampled(
            feats, batch["nbrs2"], batch["mask2"], mesh=mesh,
            dataflow=cfg.dataflow, impl=cfg.impl,
            request_chunk=cfg.request_chunk, scheduled=cfg.scheduled,
            wire=cfg.wire, features=cfg.features,
            sparse_capacity=cfg.sparse_capacity)

    with jax.named_scope("gcn.combine"):
        h1 = jnp.concatenate([x_self, x_agg], axis=-1)
        h1 = jax.nn.relu(jnp.einsum("pbf,fh->pbh", h1, params["w0"])
                         + params["b0"])
        h1 = h1.reshape(Pn, B, 1 + K1, -1)

        # local step: aggregate 1-hop h1 per seed.
        m1 = batch["mask1"][..., None].astype(h1.dtype)
        agg1 = (h1[:, :, 1:] * m1).sum(2) / jnp.maximum(m1.sum(2), 1.0)
        h2 = jnp.concatenate([h1[:, :, 0], agg1], axis=-1)
        h2 = jax.nn.relu(jnp.einsum("pbf,fh->pbh", h2, params["w1"])
                         + params["b1"])
        return jnp.einsum("pbh,hc->pbc", h2, params["w_out"]) + params["b_out"]


def sage_loss(params, feats, batch, cfg: GCNConfig, *,
              mesh: Optional[Mesh] = None, relabel=None):
    logits = sage_forward(params, feats, batch, cfg, mesh=mesh, relabel=relabel)
    labels = batch["labels"]                  # (P, B)
    with jax.named_scope("gcn.combine"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        acc = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
        return nll.mean(), {"loss": nll.mean(), "acc": acc.mean()}
