"""FAST-GAS scatter kernel vs jnp oracle: shape/dtype sweeps + properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.kernels.gas_scatter import gas_scatter, gas_scatter_ref, occupancy_map
from repro.kernels.gas_scatter import kernel as K


def _cmp(dst, val, rows, op, tol=1e-4):
    got = gas_scatter(dst, val, rows, op=op)
    want = gas_scatter_ref(dst, val, rows, op=op)
    g = jnp.nan_to_num(got, posinf=9e9, neginf=-9e9)
    w = jnp.nan_to_num(want, posinf=9e9, neginf=-9e9)
    np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=tol, rtol=tol)


@pytest.mark.parametrize("op", ["add", "max", "min"])
@pytest.mark.parametrize("shape", [(64, 8, 32), (500, 30, 200), (1000, 7, 50),
                                   (128, 128, 128), (64, 300, 513), (1, 1, 1)])
def test_shape_sweep(rng, op, shape):
    E, F, R = shape
    dst = jnp.asarray(rng.integers(-3, R + 3, E).astype(np.int32))
    val = jnp.asarray(rng.standard_normal((E, F)).astype(np.float32))
    _cmp(dst, val, R, op)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtype_sweep(rng, dtype):
    E, F, R = 256, 64, 128
    dst = jnp.asarray(rng.integers(0, R, E).astype(np.int32))
    val = jnp.asarray(rng.standard_normal((E, F))).astype(dtype)
    got = gas_scatter(dst, val, R, op="add")
    want = gas_scatter_ref(dst, val, R, op="add")
    assert got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=0.2 if dtype == jnp.bfloat16 else 1e-4, rtol=0.05)


def test_1d_values(rng):
    dst = jnp.asarray(rng.integers(0, 40, 200).astype(np.int32))
    val = jnp.asarray(rng.standard_normal(200).astype(np.float32))
    got = gas_scatter(dst, val, 40, op="add")
    assert got.shape == (40,)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(gas_scatter_ref(dst, val[:, None], 40, op="add")[:, 0]),
        atol=1e-4)


def test_occupancy_is_idle_skip_safe(rng):
    """Rounds marked idle by the occupancy map truly have no matches."""
    E = 4 * K.EDGE_TILE
    R = 4 * K.ROW_BLOCK
    dst = rng.integers(0, R, E).astype(np.int32)
    dst[:K.EDGE_TILE] = 0  # first tile only touches row block 0
    occ = np.asarray(occupancy_map(jnp.asarray(dst), R // K.ROW_BLOCK,
                                   K.EDGE_TILE))
    tiles = dst.reshape(-1, K.EDGE_TILE) // K.ROW_BLOCK
    for r in range(occ.shape[0]):
        for t in range(occ.shape[1]):
            if not occ[r, t]:
                assert not np.any(tiles[t] == r)


@settings(max_examples=20, deadline=None)
@given(
    e=st.integers(1, 300),
    f=st.integers(1, 40),
    r=st.integers(1, 200),
    op=st.sampled_from(["add", "max", "min"]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_matches_oracle(e, f, r, op, seed):
    rng = np.random.default_rng(seed)
    dst = jnp.asarray(rng.integers(-2, r + 2, e).astype(np.int32))
    val = jnp.asarray(rng.standard_normal((e, f)).astype(np.float32))
    _cmp(dst, val, r, op)


def test_weighted_or_ignores_weights(rng):
    """Regression: op="or" must not scale by edge weights — a zero or
    negative weight used to zero/flip the contribution before the masked
    segment-max, silently corrupting boolean-or semantics."""
    from repro.core.gas import gas_scatter_weighted

    dst = jnp.asarray(np.array([0, 0, 1, 2, 2, 3], np.int32))
    src = jnp.asarray(np.array([1, 0, 1, 1, 0, 1], np.int32))[:, None]
    w = jnp.asarray(np.array([0.0, 5.0, -2.0, 0.0, 1.0, -1.0], np.float32))
    mask = jnp.asarray(np.array([1, 1, 1, 1, 1, 0], bool))
    # row0: {1,0}→1 even with weight 0; row1: {1}→1 despite negative weight;
    # row2: {1,0}→1 with weight 0 on the set bit; row3: masked out → 0
    for impl in ("xla", "pallas"):
        out = gas_scatter_weighted(dst, src, w, mask, 4, op="or", impl=impl)
        np.testing.assert_array_equal(np.asarray(out)[:, 0], [1, 1, 1, 0],
                                      err_msg=impl)


def test_or_1d_int_values(rng):
    """Regression: op="or" on 1-D int values used to recurse to 2-D with
    op="or" still set, so the float32-max dtype rewrite ran at both
    recursion depths; the rewrite now happens exactly once, before the ndim
    dispatch. Pin the whole contract: result matches the int segment-max
    oracle, dtype is preserved, empty rows hold the or-identity 0, and 1-D
    agrees exactly with the equivalent 2-D call."""
    E, R = 200, 40
    dst = jnp.asarray(rng.integers(-2, R + 2, E).astype(np.int32))
    val = jnp.asarray(rng.integers(0, 2, E).astype(np.int32))
    got = gas_scatter(dst, val, R, op="or")
    assert got.shape == (R,) and got.dtype == jnp.int32
    ok = (np.asarray(dst) >= 0) & (np.asarray(dst) < R)
    want = np.zeros(R, np.int32)
    np.maximum.at(want, np.asarray(dst)[ok], np.asarray(val)[ok])
    np.testing.assert_array_equal(np.asarray(got), want)
    got2d = gas_scatter(dst, val[:, None], R, op="or")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got2d)[:, 0])
    # rows with no incoming edge hold 0 (the or-identity), not -inf/INT_MIN
    untouched = np.setdiff1d(np.arange(R), np.asarray(dst)[ok])
    if untouched.size:
        np.testing.assert_array_equal(np.asarray(got)[untouched], 0)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), e=st.integers(2, 200))
def test_property_permutation_invariance(seed, e):
    """Scatter-add is invariant to edge order (the row-parallel semantics)."""
    rng = np.random.default_rng(seed)
    dst = rng.integers(0, 50, e).astype(np.int32)
    val = rng.standard_normal((e, 6)).astype(np.float32)
    perm = rng.permutation(e)
    a = gas_scatter(jnp.asarray(dst), jnp.asarray(val), 50, op="add")
    b = gas_scatter(jnp.asarray(dst[perm]), jnp.asarray(val[perm]), 50, op="add")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
