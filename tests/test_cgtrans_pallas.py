"""Differential tier for FAST-GAS inside the CGTrans dataflows.

Three layers of guarantees:

1. **In-process (1-device) matrix** — for every op × scheduled ∈ {on, off}
   (the destination-binned locality pass), ``impl="pallas"`` ≡
   ``impl="xla"`` on the single-shard reference path of both aggregation
   entry points, including ragged/non-tile-aligned edge counts and
   all-masked inputs. Runs on the plain pytest topology (no mesh needed:
   unsharded, impl/scheduled are the only variables). The scheduler's own
   tier (``tests/test_gas_schedule.py``) additionally asserts scheduled ≡
   unscheduled bit-exactness and the idle-skip round counts.
2. **Property tests** (``_propcheck``) — the chunked request stream is
   *bit-exact* with the unchunked path for arbitrary ``request_chunk``
   (chunking partitions seeds, never a seed's K contributions), and the
   idle-skip ``occupancy_map`` never skips a tile holding a live edge after
   the wrapper's in-shard re-padding.
3. **On-mesh matrix** (``distributed`` marker) — the full
   (dataflow × op × path × impl) grid on a REAL 8-way ``shard_map`` mesh,
   via one shared subprocess run (``case_cgtrans_pallas_parity``); each cell
   is asserted as its own test here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.core import cgtrans

OPS = ("add", "max", "min", "or")
FLOWS = ("cgtrans", "baseline")


def _feats(rng, n, f, op):
    x = rng.standard_normal((n, f)).astype(np.float32)
    if op == "or":
        return (np.abs(x) > 0.5).astype(np.int32)
    return x


def _close(a, b, tol=1e-4):
    a = jnp.nan_to_num(a.astype(jnp.float32), posinf=9e9, neginf=-9e9)
    b = jnp.nan_to_num(b.astype(jnp.float32), posinf=9e9, neginf=-9e9)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# 1. in-process differential matrix (single-shard reference path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheduled", [False, True])
@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("e", [1, 37, 128, 517])   # ragged + tile-aligned
def test_edges_pallas_vs_xla(rng, op, e, scheduled):
    P_, part, F = 2, 32, 8
    feats = jnp.asarray(_feats(rng, P_ * part, F, op)).reshape(P_, part, F)
    src = jnp.asarray(rng.integers(0, part, (P_, e)).astype(np.int32))
    dst = jnp.asarray(rng.integers(0, P_ * part, (P_, e)).astype(np.int32))
    w = jnp.asarray(rng.standard_normal((P_, e)).astype(np.float32))
    m = jnp.asarray(rng.random((P_, e)) < 0.8)
    outs = {impl: cgtrans.aggregate_edges(feats, src, dst, w, m, mesh=None,
                                          op=op, impl=impl,
                                          scheduled=scheduled)
            for impl in ("xla", "pallas")}
    _close(outs["pallas"], outs["xla"])


@pytest.mark.parametrize("op", OPS)
def test_edges_all_masked(rng, op):
    """mask all-False: every row holds the op identity, both backends."""
    P_, part, F, e = 2, 16, 4, 33
    feats = jnp.asarray(_feats(rng, P_ * part, F, op)).reshape(P_, part, F)
    src = jnp.asarray(rng.integers(0, part, (P_, e)).astype(np.int32))
    dst = jnp.asarray(rng.integers(0, P_ * part, (P_, e)).astype(np.int32))
    w = jnp.ones((P_, e), jnp.float32)
    m = jnp.zeros((P_, e), bool)
    outs = {impl: cgtrans.aggregate_edges(feats, src, dst, w, m, mesh=None,
                                          op=op, impl=impl)
            for impl in ("xla", "pallas")}
    _close(outs["pallas"], outs["xla"])


@pytest.mark.parametrize("scheduled", [False, True])
@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("k", [1, 7, 16])
def test_sampled_pallas_vs_xla(rng, op, k, scheduled):
    P_, part, F, B = 2, 32, 8, 13
    feats = jnp.asarray(_feats(rng, P_ * part, F, op)).reshape(P_, part, F)
    nb = jnp.asarray(rng.integers(0, P_ * part, (P_, B, k)).astype(np.int32))
    mk = jnp.asarray(rng.random((P_, B, k)) < 0.8)
    outs = {impl: cgtrans.aggregate_sampled(feats, nb, mk, mesh=None,
                                            op=op, impl=impl,
                                            scheduled=scheduled)
            for impl in ("xla", "pallas")}
    _close(outs["pallas"], outs["xla"])


def test_sampled_all_masked(rng):
    """Seeds with zero valid samples: mean path returns 0 on both backends."""
    P_, part, F, B, k = 2, 16, 4, 5, 3
    feats = jnp.asarray(rng.standard_normal((P_, part, F)).astype(np.float32))
    nb = jnp.asarray(rng.integers(0, P_ * part, (P_, B, k)).astype(np.int32))
    mk = jnp.zeros((P_, B, k), bool)
    for impl in ("xla", "pallas"):
        out = cgtrans.aggregate_sampled(feats, nb, mk, mesh=None, impl=impl)
        np.testing.assert_array_equal(np.asarray(out), 0.0, err_msg=impl)


# ---------------------------------------------------------------------------
# 2. property tests: chunked ≡ unchunked; occupancy never skips live work
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    chunk=st.integers(1, 40),       # covers 1, primes, and ≥ B_loc (=2·13)
    b=st.integers(1, 13),
    k=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_chunked_request_stream_exact(chunk, b, k, seed):
    """The chunked SSD-request stream is BIT-EXACT with the unchunked path:
    chunking partitions the seed block, never a seed's K contributions."""
    rng = np.random.default_rng(seed)
    P_, part, F = 2, 16, 4
    feats = jnp.asarray(rng.standard_normal((P_, part, F)).astype(np.float32))
    nb = jnp.asarray(rng.integers(0, P_ * part, (P_, b, k)).astype(np.int32))
    mk = jnp.asarray(rng.random((P_, b, k)) < 0.7)
    ref = cgtrans.aggregate_sampled(feats, nb, mk, mesh=None)
    out = cgtrans.aggregate_sampled(feats, nb, mk, mesh=None,
                                    request_chunk=chunk)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@settings(max_examples=20, deadline=None)
@given(
    e=st.integers(1, 400),
    r=st.integers(1, 300),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_occupancy_never_skips_live_tile(e, r, seed):
    """Replicate the kernel wrapper's in-shard re-padding (clip-to-dead-row +
    pad-to-tile) and assert the idle-skip map marks every (row-block,
    edge-tile) pair that contains a live edge — a skipped live tile would
    silently drop aggregation work."""
    from repro.kernels.gas_scatter import kernel as K
    from repro.kernels.gas_scatter import occupancy_map

    rng = np.random.default_rng(seed)
    dst = rng.integers(-3, r + 3, e).astype(np.int32)   # incl. out-of-range
    et = K.EDGE_TILE
    R = ((r + K.ROW_BLOCK - 1) // K.ROW_BLOCK) * K.ROW_BLOCK
    ok = (dst >= 0) & (dst < r)
    dstp = np.where(ok, dst, R)
    dstp = np.pad(dstp, (0, (-len(dstp)) % et), constant_values=R)
    occ = np.asarray(occupancy_map(jnp.asarray(dstp), R // K.ROW_BLOCK, et))
    tiles = dstp.reshape(-1, et)
    for t in range(tiles.shape[0]):
        live = tiles[t][tiles[t] < R]          # dead-row padding excluded
        for blk in np.unique(live // K.ROW_BLOCK):
            assert occ[blk, t], (t, blk)


# ---------------------------------------------------------------------------
# 3. the on-mesh matrix: every cell of the shared 8-way subprocess run
# ---------------------------------------------------------------------------

@pytest.mark.distributed
@pytest.mark.parametrize("flow", FLOWS)
@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("path", ["edges", "sampled"])
def test_mesh_parity_cell(pallas_parity_report, path, op, flow):
    line = f"parity path={path} flow={flow} op={op} impl=pallas ok"
    assert line in pallas_parity_report, (
        f"missing/failed matrix cell: {line!r}")


@pytest.mark.distributed
@pytest.mark.parametrize("flow", FLOWS)
@pytest.mark.parametrize("chunk", [1, 3, 64])
def test_mesh_parity_chunked(pallas_parity_report, flow, chunk):
    line = f"parity path=sampled flow={flow} chunk={chunk} ok"
    assert line in pallas_parity_report, (
        f"missing/failed chunked-request cell: {line!r}")


@pytest.mark.distributed
@pytest.mark.parametrize("flow", FLOWS)
@pytest.mark.parametrize("op", ["add", "max"])
@pytest.mark.parametrize("path", ["edges", "sampled"])
def test_mesh_parity_scheduled_off(pallas_parity_report, path, op, flow):
    """pallas defaults to scheduled on the mesh — these cells pin the
    scheduled=off pallas path (dense-occupancy grid) as a separate axis."""
    line = f"parity path={path} flow={flow} op={op} impl=pallas sched=off ok"
    assert line in pallas_parity_report, (
        f"missing/failed scheduled-off cell: {line!r}")


@pytest.mark.distributed
def test_mesh_parity_hoisted_schedule(pallas_parity_report):
    """The deployment path: build_edge_schedule + apply_edge_schedule +
    schedule_applied through shard_map, and gcn_forward_full's sharded
    auto-hoist — locked in on the real 8-way mesh, not just benchmarked."""
    assert "parity path=edges flow=cgtrans hoisted-schedule ok" in \
        pallas_parity_report
    assert "parity gcn-full sharded hoisted-schedule ok" in \
        pallas_parity_report
