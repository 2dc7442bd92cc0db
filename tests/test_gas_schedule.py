"""Scheduler tier: the destination-binned edge schedule + fused kernel.

What the tentpole must guarantee (``scripts/ci.sh --tier sched`` runs this
file alone):

1. **Schedule invariants** — ``schedule_edges`` is a stable counting sort
   by destination row block: the permutation is a bijection, bins ascend,
   intra-bin edge order is preserved, dead (masked/out-of-range) edges sort
   last; the banded bounds and the (W, 4) work list cover every live
   (row-block × edge-tile) round exactly once and init every row block.
2. **Fused kernel ≡ oracle** — ``gas_scatter_fused`` (mask via dead-row
   convention, weights via match-line scaling, scheduled banded walk or
   unscheduled dense grid) matches ``gas_scatter_weighted_ref``.
3. **Schedule invariance, bit-exact** — scheduled ≡ unscheduled for values
   AND gradients on integer-valued data (float addition is associative on
   integers, so any dropped/duplicated/misrouted contribution is a hard
   bitwise failure, not tolerance noise): permutation invariance of the
   scatter forward, un-permutation of cotangents through the ``take``
   transpose in the backward.
4. **The idle-skip actually skips** — on a clustered graph the scheduled
   walk executes a fraction of the dense grid's rounds; the K=1 request
   path never dispatches the kernel at all (a single-sample request is a
   pure find).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.core import cgtrans, gas
from repro.kernels.gas_scatter import kernel as K
from repro.kernels.gas_scatter import ops as gas_ops
from repro.kernels.gas_scatter import (gas_scatter_weighted_ref,
                                       schedule_skip_stats)

OPS = ("add", "max", "min", "or")


def _nan2num(a):
    return np.nan_to_num(np.asarray(a, np.float32), posinf=9e9, neginf=-9e9)


# ---------------------------------------------------------------------------
# 1. schedule invariants
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(
    e=st.integers(1, 700),
    r=st.integers(1, 500),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_schedule_is_stable_binned_permutation(e, r, seed):
    rng = np.random.default_rng(seed)
    dst = rng.integers(-4, r + 4, e).astype(np.int32)
    mask = rng.random(e) < 0.8
    sched = gas_ops.schedule_edges(jnp.asarray(dst), jnp.asarray(mask), r)
    perm = np.asarray(sched.perm)
    assert sorted(perm.tolist()) == list(range(e)), "perm must be a bijection"

    n_blocks = -(-r // K.ROW_BLOCK)
    live = mask & (dst >= 0) & (dst < r)
    bins = np.where(live, dst // K.ROW_BLOCK, n_blocks)
    sorted_bins = bins[perm]
    assert (np.diff(sorted_bins) >= 0).all(), "bins must ascend (binned)"
    # stability: edges of one bin keep their original relative order
    for b in np.unique(sorted_bins):
        idx = perm[sorted_bins == b]
        assert (np.diff(idx) > 0).all(), f"bin {b} reordered (unstable sort)"


@settings(max_examples=25, deadline=None)
@given(
    e=st.integers(1, 700),
    r=st.integers(1, 500),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_work_list_covers_live_rounds_exactly(e, r, seed):
    """The banded walk must visit every live (row-block, tile) round at
    least once (a missed round silently drops aggregation work), never
    visit the same round twice (double-counts a scatter-add), and init
    every row block exactly once (uninitialized output rows are garbage)."""
    rng = np.random.default_rng(seed)
    dst = rng.integers(-4, r + 4, e).astype(np.int32)
    mask = rng.random(e) < 0.8
    sched = gas_ops.schedule_edges(jnp.asarray(dst), jnp.asarray(mask), r)
    perm = np.asarray(sched.perm)
    et = K.EDGE_TILE
    n_blocks = -(-r // K.ROW_BLOCK)

    live = mask & (dst >= 0) & (dst < r)
    bins = np.where(live, dst // K.ROW_BLOCK, n_blocks)[perm]
    bins = np.pad(bins, (0, (-e) % et), constant_values=n_blocks)
    tiles = bins.reshape(-1, et)
    needed = {(b, t) for t in range(tiles.shape[0])
              for b in np.unique(tiles[t][tiles[t] < n_blocks])}

    work = np.asarray(sched.work)
    visited = [(int(rb), int(t)) for rb, t, lv, _ in work if lv]
    assert len(visited) == len(set(visited)), "round visited twice"
    assert needed <= set(visited), f"missed rounds: {needed - set(visited)}"
    inits = work[work[:, 3] == 1][:, 0]
    assert sorted(inits.tolist()) == list(range(n_blocks)), (
        "every row block must be initialized exactly once")
    assert (np.diff(work[:, 0]) >= 0).all(), (
        "work must walk row blocks in order (output revisit contract)")


# ---------------------------------------------------------------------------
# 2. fused kernel ≡ oracle (scheduled and unscheduled)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    e=st.integers(1, 400),
    r=st.integers(1, 300),
    op=st.sampled_from(("add", "max", "min")),
    scheduled=st.sampled_from((False, True)),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_fused_matches_weighted_oracle(e, r, op, scheduled, seed):
    rng = np.random.default_rng(seed)
    F = 5
    dst = jnp.asarray(rng.integers(-3, r + 3, e).astype(np.int32))
    vals = jnp.asarray(rng.standard_normal((e, F)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal(e).astype(np.float32))
    m = jnp.asarray(rng.random(e) < 0.7)
    weights = w if op == "add" else None
    want = gas_scatter_weighted_ref(dst, vals, weights, m, r, op=op)
    if scheduled:
        sched = gas_ops.schedule_edges(dst, m, r)
        p = sched.perm
        got = gas_ops.gas_scatter_fused(
            dst[p], vals[p], None if weights is None else weights[p], m[p],
            r, op=op, schedule=sched)
    else:
        got = gas_ops.gas_scatter_fused(dst, vals, weights, m, r, op=op)
    np.testing.assert_allclose(_nan2num(got), _nan2num(want),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# 3. scheduled ≡ unscheduled, bit-exact (values and gradients)
# ---------------------------------------------------------------------------

def _int_edges(rng, P_, part, e, op):
    """Integer-valued inputs: exact arithmetic → bitwise assertions."""
    f = rng.integers(-8, 9, (P_, part, 4)).astype(np.float32)
    if op == "or":
        f = (f > 0).astype(np.int32)
    src = rng.integers(0, part, (P_, e)).astype(np.int32)
    dst = rng.integers(0, P_ * part, (P_, e)).astype(np.int32)
    w = rng.integers(-3, 4, (P_, e)).astype(np.float32)
    m = rng.random((P_, e)) < 0.8
    return tuple(jnp.asarray(x) for x in (f, src, dst, w, m))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("op", OPS)
def test_edges_scheduled_bit_exact_with_unscheduled(rng, impl, op):
    f, src, dst, w, m = _int_edges(rng, 2, 32, 213, op)
    outs = [cgtrans.aggregate_edges(f, src, dst, w, m, mesh=None, op=op,
                                    impl=impl, scheduled=s)
            for s in (False, True)]
    np.testing.assert_array_equal(_nan2num(outs[0]), _nan2num(outs[1]))


@pytest.mark.parametrize("op", ["add", "max"])
def test_edges_scheduled_grads_bit_exact(rng, op):
    """Cotangents must un-permute exactly through the schedule's ``take``
    transpose: d_feats AND d_weights equal scheduled vs not — bitwise for
    ``add`` (integer-valued contributions are order-exact). For ``max`` the
    per-edge cotangent is itself bitwise order-independent, but a tie's
    share g/ties can be a non-dyadic rational (g/3), so the un-permuting
    scatter-SUM of shares into d_feats is compared at float-ulp tolerance
    instead."""
    f, src, dst, w, m = _int_edges(rng, 2, 16, 147, op)
    u = jnp.asarray(rng.integers(-3, 4, (2, 16, 4)).astype(np.float32))

    def loss(feats, wts, scheduled):
        out = cgtrans.aggregate_edges(feats, src, dst, wts, m, mesh=None,
                                      op=op, impl="pallas",
                                      scheduled=scheduled)
        return jnp.sum(jnp.where(jnp.isfinite(out), out, 0.0) * u)

    g_off = jax.grad(lambda a, b: loss(a, b, False), argnums=(0, 1))(f, w)
    g_on = jax.grad(lambda a, b: loss(a, b, True), argnums=(0, 1))(f, w)
    if op == "add":
        np.testing.assert_array_equal(np.asarray(g_off[0]),
                                      np.asarray(g_on[0]))
        np.testing.assert_array_equal(np.asarray(g_off[1]),
                                      np.asarray(g_on[1]))
    else:
        np.testing.assert_allclose(np.asarray(g_off[0]), np.asarray(g_on[0]),
                                   atol=1e-6, rtol=1e-6)
        # weights are not consumed by the compare ops: exact zeros both ways
        np.testing.assert_array_equal(np.asarray(g_off[1]),
                                      np.asarray(g_on[1]))


@settings(max_examples=15, deadline=None)
@given(
    b=st.integers(1, 13),
    k=st.integers(1, 6),
    chunk=st.sampled_from((None, 1, 3)),
    op=st.sampled_from(OPS),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_sampled_scheduled_bit_exact(b, k, chunk, op, seed):
    """scheduled ∈ {on, off} × chunking on the sampled path (its schedule
    is the sort-free assume_sorted band): bit-exact on integer data."""
    rng = np.random.default_rng(seed)
    P_, part = 2, 16
    f = rng.integers(-8, 9, (P_, part, 3)).astype(np.float32)
    if op == "or":
        f = (f > 0).astype(np.int32)
    f = jnp.asarray(f)
    nb = jnp.asarray(rng.integers(0, P_ * part, (P_, b, k)).astype(np.int32))
    mk = jnp.asarray(rng.random((P_, b, k)) < 0.7)
    outs = [cgtrans.aggregate_sampled(f, nb, mk, mesh=None, op=op,
                                      impl="pallas", scheduled=s,
                                      request_chunk=chunk)
            for s in (False, True)]
    np.testing.assert_array_equal(_nan2num(outs[0]), _nan2num(outs[1]))


def test_gcn_forward_full_hoisted_schedule_matches_xla(rng):
    """The multi-layer reuse path: one ``build_edge_schedule`` serves every
    layer of ``gcn_forward_full`` and matches the xla forward."""
    from repro.common.schema import init_params
    from repro.core.gcn import GCNConfig, gcn_forward_full, gcn_schema

    P_, part, F, e = 2, 32, 8, 301
    feats = jnp.asarray(rng.standard_normal((P_, part, F)).astype(np.float32))
    src = jnp.asarray(rng.integers(0, part, (P_, e)).astype(np.int32))
    dst = jnp.asarray(rng.integers(0, P_ * part, (P_, e)).astype(np.int32))
    w = jnp.asarray(rng.standard_normal((P_, e)).astype(np.float32))
    m = jnp.asarray(rng.random((P_, e)) < 0.8)
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = GCNConfig(n_features=F, hidden=16, n_classes=4, impl=impl)
        params = init_params(gcn_schema(cfg), jax.random.PRNGKey(0))
        outs[impl] = gcn_forward_full(params, feats, src, dst, w, m, cfg,
                                      mesh=None)
    np.testing.assert_allclose(np.asarray(outs["pallas"]),
                               np.asarray(outs["xla"]), atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# 4. the idle-skip actually skips
# ---------------------------------------------------------------------------

def test_idle_skip_counter_on_clustered_graph():
    """Paper Fig 11(c): on a clustered graph the scheduled walk executes a
    small fraction of the dense R×T rounds, and strictly fewer than the
    unscheduled occupancy leaves live. Uniform graphs barely skip
    unscheduled — the schedule is what makes the idle-skip buffer fire."""
    from repro.graph import clustered_graph, partition_by_src, uniform_graph
    from repro.kernels.gas_scatter import dense_skip_stats

    V, E, ways = 1024, 16384, 8
    stats = {}
    for kind, g in (("clustered", clustered_graph(
                        V, E, n_clusters=V // K.ROW_BLOCK, p_intra=0.9,
                        seed=7)),
                    ("uniform", uniform_graph(V, E, seed=7))):
        # locality lives in the PARTITIONED per-shard streams (the src-owner
        # layout the dataflows actually aggregate), not generation order
        pg = partition_by_src(g, ways)
        live_s = live_u = total = 0
        for p in range(ways):
            dst = jnp.asarray(pg.dst[p])
            mask = jnp.asarray(pg.mask[p])
            ls, ts = schedule_skip_stats(
                gas_ops.schedule_edges(dst, mask, V))
            live_s += ls
            total += ts
            live_u += dense_skip_stats(dst, mask, V)[0]
        stats[kind] = (live_s, live_u, total)

    for kind, (live_s, live_u, total) in stats.items():
        assert live_s < live_u, (kind, stats)          # schedule skips MORE
        assert total - live_s > 0, (kind, stats)       # …and skips at all
    # scheduled round count is locality-driven: ≤ T + blocks - 1 ≪ total
    live_s, live_u, total = stats["clustered"]
    assert live_s <= total // 4, stats
    # without the schedule, only clustering skips anything much
    assert stats["clustered"][1] < stats["uniform"][1], stats


def test_k1_request_is_a_pure_find(rng, monkeypatch):
    """A K=1 request block (the row-lookup path) must not pay a kernel
    round-trip: the seed scatter is the identity permutation. The gather's
    VJP still scatters through the kernel — that is asserted by
    tests/test_cgtrans_grad.py; here we pin the forward."""
    calls = {"n": 0}
    real = gas_ops.gas_scatter_fused

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(gas_ops, "gas_scatter_fused", counting)
    feats = jnp.asarray(rng.standard_normal((2, 16, 4)).astype(np.float32))
    nb = jnp.asarray(rng.integers(0, 32, (2, 9, 1)).astype(np.int32))
    mk = jnp.asarray(rng.random((2, 9, 1)) < 0.8)
    out_p = cgtrans.aggregate_sampled(feats, nb, mk, mesh=None, impl="pallas")
    assert calls["n"] == 0, "K=1 forward must not dispatch the kernel"
    out_x = cgtrans.aggregate_sampled(feats, nb, mk, mesh=None, impl="xla")
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("op", OPS)
def test_k1_find_matches_k2_duplicate_semantics(rng, op):
    """Regression: the K=1 pure-find shortcut must keep the SCATTER path's
    op semantics — notably op="or"'s int-cast + clamp-at-0 normalization
    (an early version passed raw values through, so a row of -1.0/0.5
    leaked instead of reading 0). Duplicating the single sample to K=2
    forces the scatter path; every op must agree on every impl."""
    P_, part, F, B = 2, 16, 3, 7
    f = rng.standard_normal((P_, part, F)).astype(np.float32)
    if op == "or":
        f = f.round(1)                 # keep fractional + negative values
    f = jnp.asarray(f)
    nb1 = jnp.asarray(rng.integers(0, P_ * part, (P_, B, 1)).astype(np.int32))
    mk1 = jnp.asarray(rng.random((P_, B, 1)) < 0.7)
    nb2 = jnp.concatenate([nb1, nb1], axis=-1)       # same sample, twice
    mk2 = jnp.concatenate([mk1, mk1], axis=-1)
    for impl in ("xla", "pallas"):
        o1 = cgtrans.aggregate_sampled(f, nb1, mk1, mesh=None, op=op,
                                       impl=impl)
        o2 = cgtrans.aggregate_sampled(f, nb2, mk2, mesh=None, op=op,
                                       impl=impl)
        np.testing.assert_allclose(_nan2num(o1), _nan2num(o2),
                                   atol=1e-5, rtol=1e-5, err_msg=(op, impl))
