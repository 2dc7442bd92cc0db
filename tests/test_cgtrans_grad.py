"""Gradient-parity differential tier for the differentiable FAST-GAS path.

The paper's find-and-compute symmetry is that the backward pass is itself
GAS work — the backward of a scatter-add is a gather, the backward of a
gather is a scatter — so ``impl="pallas"`` must differentiate end-to-end
through the same kernel the forward uses. Four layers of guarantees:

1. **In-process grad matrix** — ``jax.grad`` parity pallas ≡ xla ≡ a
   central-finite-difference reference over dataflow × op × {full-graph,
   sampled} × {chunked, unchunked} on the single-shard reference path,
   including ragged (non-tile-aligned) edge counts and all-masked inputs.
2. **Property tests** (``_propcheck``) — the ``scan_request_chunks`` VJP is
   *exactly* chunked ≡ unchunked (asserted bit-for-bit on integer-valued
   data, where float addition is associative, so any dropped or duplicated
   contribution shows up as a hard mismatch); and the
   ``gas_scatter_weighted`` pallas VJP equals ``jax.grad`` of the jnp
   oracle for random masks/weights on all four ops.
3. **NaN regression** — seeds with no valid sample used to hold the ±inf
   max/min identity, which autodiff turns into ``0·inf = NaN``; identity
   rows are now masked at the terminal finalize and the all-masked-seed
   grad must be finite (and zero) on both backends.
4. **On-mesh matrix** (``distributed`` marker) — the full grad grid on a
   REAL 8-way ``shard_map`` mesh via one shared subprocess run
   (``case_cgtrans_grad_parity``), plus a 3-step ``make_sage_train_step``
   smoke: ``cfg.impl="pallas"`` trains, the loss decreases, and per-step
   params match ``impl="xla"`` to fp32 tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.core import cgtrans, gas

GRAD_OPS = ("add", "max", "min")     # "or" is flat (zero grads) — see below
FLOWS = ("cgtrans", "baseline")


def _grad_close(a, b, tol=1e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=tol, rtol=tol)


def _fd_directional(f, x, v, eps=1e-2):
    """Central-difference directional derivative ⟨∇f, v⟩ at ``x``."""
    return (float(f(x + eps * v)) - float(f(x - eps * v))) / (2 * eps)


def _masked_linear_loss(out, u):
    """⟨mask(out), u⟩ — linear in ``out`` so finite differences are exact up
    to float32 noise; ±inf rows (full-graph vertices with no in-edge) are
    masked exactly the way ``gcn_forward_full`` consumes the aggregation."""
    return jnp.sum(jnp.where(jnp.isfinite(out), out, 0.0) * u)


# ---------------------------------------------------------------------------
# 1. in-process grad matrix (single-shard reference path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheduled", [False, True])
@pytest.mark.parametrize("op", GRAD_OPS)
@pytest.mark.parametrize("e", [37, 128])      # ragged + tile-aligned
def test_edges_grad_pallas_vs_xla_vs_fd(rng, op, e, scheduled):
    P_, part, F = 2, 16, 4
    feats = jnp.asarray(rng.standard_normal((P_, part, F)).astype(np.float32))
    src = jnp.asarray(rng.integers(0, part, (P_, e)).astype(np.int32))
    dst = jnp.asarray(rng.integers(0, P_ * part, (P_, e)).astype(np.int32))
    w = jnp.asarray(rng.standard_normal((P_, e)).astype(np.float32))
    m = jnp.asarray(rng.random((P_, e)) < 0.8)
    u = jnp.asarray(rng.standard_normal(feats.shape).astype(np.float32))

    def loss(f, wts, impl):
        out = cgtrans.aggregate_edges(f, src, dst, wts, m, mesh=None,
                                      op=op, impl=impl, scheduled=scheduled)
        return _masked_linear_loss(out, u)

    grads = {impl: jax.grad(lambda f, wts: loss(f, wts, impl),
                            argnums=(0, 1))(feats, w)
             for impl in ("xla", "pallas")}
    _grad_close(grads["pallas"][0], grads["xla"][0])
    _grad_close(grads["pallas"][1], grads["xla"][1])

    # finite-difference reference, one random direction per argument
    vf = jnp.asarray(rng.standard_normal(feats.shape).astype(np.float32))
    vw = jnp.asarray(rng.standard_normal(w.shape).astype(np.float32))
    fd_f = _fd_directional(lambda f: loss(f, w, "xla"), feats, vf)
    fd_w = _fd_directional(lambda wts: loss(feats, wts, "xla"), w, vw)
    for impl in ("xla", "pallas"):
        np.testing.assert_allclose(
            float(jnp.vdot(grads[impl][0], vf)), fd_f, atol=1e-2, rtol=1e-2,
            err_msg=f"{impl} d_feats vs finite differences")
        np.testing.assert_allclose(
            float(jnp.vdot(grads[impl][1], vw)), fd_w, atol=1e-2, rtol=1e-2,
            err_msg=f"{impl} d_weights vs finite differences")


@pytest.mark.parametrize("op", GRAD_OPS)
@pytest.mark.parametrize("chunk", [None, 1, 5])
def test_sampled_grad_pallas_vs_xla_vs_fd(rng, op, chunk):
    P_, part, F, B, K = 2, 16, 4, 7, 3
    feats = jnp.asarray(rng.standard_normal((P_, part, F)).astype(np.float32))
    nb = jnp.asarray(rng.integers(0, P_ * part, (P_, B, K)).astype(np.int32))
    mk = jnp.asarray(rng.random((P_, B, K)) < 0.8)
    u = jnp.asarray(rng.standard_normal((P_, B, F)).astype(np.float32))

    def loss(f, impl):
        out = cgtrans.aggregate_sampled(f, nb, mk, mesh=None, op=op,
                                        impl=impl, request_chunk=chunk)
        return jnp.sum(out * u)     # identity rows are already masked to 0

    grads = {impl: jax.grad(lambda f: loss(f, impl))(feats)
             for impl in ("xla", "pallas")}
    _grad_close(grads["pallas"], grads["xla"])

    v = jnp.asarray(rng.standard_normal(feats.shape).astype(np.float32))
    fd = _fd_directional(lambda f: loss(f, "xla"), feats, v)
    for impl in ("xla", "pallas"):
        np.testing.assert_allclose(float(jnp.vdot(grads[impl], v)), fd,
                                   atol=1e-2, rtol=1e-2,
                                   err_msg=f"{impl} vs finite differences")


@pytest.mark.parametrize("op", GRAD_OPS)
def test_sampled_grad_chunked_matches_unchunked(rng, op):
    """Chunk boundaries must not change the VJP: same grads for any depth."""
    P_, part, F, B, K = 2, 16, 4, 13, 4
    feats = jnp.asarray(rng.standard_normal((P_, part, F)).astype(np.float32))
    nb = jnp.asarray(rng.integers(0, P_ * part, (P_, B, K)).astype(np.int32))
    mk = jnp.asarray(rng.random((P_, B, K)) < 0.8)
    u = jnp.asarray(rng.standard_normal((P_, B, F)).astype(np.float32))

    def grad_at(impl, chunk):
        return jax.grad(lambda f: jnp.sum(cgtrans.aggregate_sampled(
            f, nb, mk, mesh=None, op=op, impl=impl, request_chunk=chunk) * u)
        )(feats)

    for impl in ("xla", "pallas"):
        ref = grad_at(impl, None)
        for chunk in (1, 3, 64):
            _grad_close(grad_at(impl, chunk), ref)


def test_or_grads_are_zero(rng):
    """op="or" is flat almost everywhere: the oracle differentiates to exact
    zeros through its int cast and the pallas VJP must agree."""
    P_, part, F, B, K = 2, 16, 4, 5, 3
    feats01 = jnp.asarray(
        (rng.random((P_, part, F)) < 0.5).astype(np.float32))
    nb = jnp.asarray(rng.integers(0, P_ * part, (P_, B, K)).astype(np.int32))
    mk = jnp.asarray(rng.random((P_, B, K)) < 0.8)
    for impl in ("xla", "pallas"):
        g = jax.grad(lambda f: jnp.sum(cgtrans.aggregate_sampled(
            f, nb, mk, mesh=None, op="or", impl=impl).astype(jnp.float32))
        )(feats01)
        np.testing.assert_array_equal(np.asarray(g), 0.0, err_msg=impl)


# ---------------------------------------------------------------------------
# 2. property tests: scan VJP exactness; kernel VJP vs the jnp oracle
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    chunk=st.integers(1, 40),
    r=st.integers(1, 13),
    k=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_scan_request_chunks_vjp_exact(chunk, r, k, seed):
    """The VJP of the chunked request stream is BIT-EXACT with the unchunked
    body call. Integer-valued float data keeps every partial sum exactly
    representable, so the assertion is order-independent and any chunk-
    boundary contribution that is dropped, duplicated, or routed to the
    wrong row is a hard bitwise failure — not tolerance noise."""
    rng = np.random.default_rng(seed)
    n_rows, F = 11, 3
    table = jnp.asarray(rng.integers(-8, 9, (n_rows, F)).astype(np.float32))
    nb = jnp.asarray(rng.integers(0, n_rows, (r, k)).astype(np.int32))
    mk = jnp.asarray(rng.random((r, k)) < 0.7)
    u = jnp.asarray(rng.integers(-4, 5, (r, F)).astype(np.float32))

    def body(t, nb_c, m_c):
        rows = jnp.take(t, nb_c.reshape(-1), axis=0).reshape(
            nb_c.shape[0], -1, F)
        return (rows * m_c[..., None]).sum(1)

    def loss(t, chunked):
        if chunked:
            out = cgtrans.scan_request_chunks(
                lambda nb_c, m_c: body(t, nb_c, m_c), nb, mk, chunk)
        else:
            out = body(t, nb, mk)
        return jnp.sum(out * u)

    g_chunked = jax.grad(lambda t: loss(t, True))(table)
    g_full = jax.grad(lambda t: loss(t, False))(table)
    np.testing.assert_array_equal(np.asarray(g_chunked), np.asarray(g_full))


@settings(max_examples=20, deadline=None)
@given(
    e=st.integers(1, 200),
    r=st.integers(1, 40),
    op=st.sampled_from(("add", "max", "min", "or")),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_scatter_weighted_vjp_matches_oracle(e, r, op, seed):
    """The pallas custom VJP of ``gas_scatter_weighted`` ≡ ``jax.grad`` of
    the jnp oracle for random masks/weights on all four ops — including
    duplicated values (max/min gradient ties must split exactly like XLA's
    even-among-ties convention) and for "or" the oracle's exact zeros."""
    rng = np.random.default_rng(seed)
    F = 4
    dst = jnp.asarray(rng.integers(0, r, e).astype(np.int32))
    if op == "or":
        vals = jnp.asarray((rng.random((e, F)) < 0.5).astype(np.float32))
    else:
        vals = jnp.asarray(rng.integers(-5, 6, (e, F)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal(e).astype(np.float32))
    m = jnp.asarray(rng.random(e) < 0.7)
    u = jnp.asarray(rng.standard_normal((r, F)).astype(np.float32))

    def loss(v, wts, impl):
        out = gas.gas_scatter_weighted(dst, v, wts, m, r, op=op, impl=impl)
        return _masked_linear_loss(out.astype(jnp.float32), u)

    gx = jax.grad(lambda v, wts: loss(v, wts, "xla"), argnums=(0, 1))(vals, w)
    gp = jax.grad(lambda v, wts: loss(v, wts, "pallas"), argnums=(0, 1))(vals, w)
    _grad_close(gp[0], gx[0])
    _grad_close(gp[1], gx[1])


def test_backward_scatter_routes_through_kernel(rng, monkeypatch):
    """The acceptance bar: the backward really dispatches the FAST-GAS
    kernel — not a silent XLA fallback. Count kernel-wrapper invocations
    (both the plain and the fused dispatch — the gather VJP and the
    tie-count router now use the fused entry) around ``jax.vjp``: the
    pallas gather's forward is a plain take (zero kernel calls) but pulling
    its cotangent MUST hit the kernel (the backward of a gather is a
    scatter), and the max-scatter's backward must hit it again for the
    tie-count router."""
    from repro.kernels.gas_scatter import ops as gas_ops

    count = {"n": 0}
    real_plain = gas_ops.gas_scatter
    real_fused = gas_ops.gas_scatter_fused

    def counting_plain(*args, **kwargs):
        count["n"] += 1
        return real_plain(*args, **kwargs)

    def counting_fused(*args, **kwargs):
        count["n"] += 1
        return real_fused(*args, **kwargs)

    monkeypatch.setattr(gas_ops, "gas_scatter", counting_plain)
    monkeypatch.setattr(gas_ops, "gas_scatter_fused", counting_fused)

    table = jnp.asarray(rng.standard_normal((16, 4)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 16, 23).astype(np.int32))
    out, pull = jax.vjp(lambda t: gas.gas_gather(t, ids, impl="pallas"), table)
    fwd_calls = count["n"]
    assert fwd_calls == 0, "the pallas gather forward is a plain take"
    pull(jnp.ones_like(out))
    assert count["n"] > fwd_calls, (
        "gather cotangent did not dispatch the FAST-GAS kernel")

    dst = jnp.asarray(rng.integers(0, 8, 23).astype(np.int32))
    vals = jnp.asarray(rng.standard_normal((23, 4)).astype(np.float32))
    w = jnp.ones((23,), jnp.float32)
    m = jnp.ones((23,), bool)
    out, pull = jax.vjp(
        lambda v: gas.gas_scatter_weighted(dst, v, w, m, 8, op="max",
                                           impl="pallas"), vals)
    before = count["n"]
    pull(jnp.ones_like(out))
    assert count["n"] > before, (
        "max-op tie-count router did not dispatch the FAST-GAS kernel")


# ---------------------------------------------------------------------------
# 3. NaN regression: the all-masked-seed gradient
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", GRAD_OPS)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_all_masked_seed_grad_finite_and_zero(rng, op, impl):
    """Seeds with zero valid samples used to hold ±inf for max/min; an
    unmasked downstream consumer then produced 0·inf = NaN gradients. The
    terminal finalize now masks identity rows, so the forward reads 0 and
    the grad is exactly zero — no NaN on either backend, no downstream
    ``isfinite`` guard required."""
    P_, part, F, B, K = 2, 16, 4, 5, 3
    feats = jnp.asarray(rng.standard_normal((P_, part, F)).astype(np.float32))
    nb = jnp.asarray(rng.integers(0, P_ * part, (P_, B, K)).astype(np.int32))
    mk = jnp.zeros((P_, B, K), bool)                  # every seed all-masked

    def loss(f):
        out = cgtrans.aggregate_sampled(f, nb, mk, mesh=None, op=op,
                                        impl=impl)
        return jnp.sum(out ** 2)                      # deliberately unmasked

    val, g = jax.value_and_grad(loss)(feats)
    assert np.isfinite(float(val)), (op, impl, float(val))
    assert bool(jnp.isfinite(g).all()), (op, impl)
    np.testing.assert_array_equal(np.asarray(g), 0.0)


@pytest.mark.parametrize("op", ["max", "min"])
def test_partially_masked_seed_grad_unaffected_by_identity_rows(rng, op):
    """Masking the identity rows must not disturb live seeds' grads: a mixed
    batch (one all-masked seed among live ones) grads identically to the
    same batch with the dead seed's rows simply absent from the loss."""
    P_, part, F, K = 1, 16, 4, 3
    feats = jnp.asarray(rng.standard_normal((P_, part, F)).astype(np.float32))
    nb = jnp.asarray(rng.integers(0, part, (P_, 3, K)).astype(np.int32))
    mk = np.ones((P_, 3, K), bool)
    mk[0, 1] = False                                  # dead seed in the middle
    mk = jnp.asarray(mk)
    u = jnp.asarray(rng.standard_normal((P_, 3, F)).astype(np.float32))
    live = jnp.asarray(np.array([1.0, 0.0, 1.0], np.float32))[None, :, None]

    for impl in ("xla", "pallas"):
        g_mixed = jax.grad(lambda f: jnp.sum(cgtrans.aggregate_sampled(
            f, nb, mk, mesh=None, op=op, impl=impl) * u))(feats)
        g_live = jax.grad(lambda f: jnp.sum(cgtrans.aggregate_sampled(
            f, nb, mk, mesh=None, op=op, impl=impl) * u * live))(feats)
        _grad_close(g_mixed, g_live)


# ---------------------------------------------------------------------------
# 4a. end-to-end: 3 pallas train steps ≡ 3 xla train steps (fp32 tolerance)
# ---------------------------------------------------------------------------

def test_sage_train_step_pallas_three_steps():
    """``make_sage_train_step(cfg.impl="pallas")`` is legal (the assertion is
    gone), the loss decreases over 3 steps, and every step's params match
    ``impl="xla"`` to fp32 tolerance — same data, same init."""
    from repro.common.config import TrainConfig
    from repro.common.schema import init_params
    from repro.core.gcn import GCNConfig, gcn_schema
    from repro.data import GraphBatchStream, synthetic_node_labels
    from repro.graph import partition_by_src, uniform_graph
    from repro.optim import adamw_init
    from repro.train import make_sage_train_step

    g = uniform_graph(64, 512, seed=0, n_features=8)
    labels = synthetic_node_labels(g.features, 4)
    pg = partition_by_src(g, 2)
    feats = jnp.asarray(pg.features)
    tc = TrainConfig(learning_rate=1e-2, warmup_steps=0, total_steps=3,
                     weight_decay=0.0)
    stream = GraphBatchStream(g, labels, n_parts=2, batch_per_part=8,
                              k1=3, k2=3)
    # one repeated batch: descent on it is guaranteed, so "loss decreases"
    # tests the gradient's sign, not the sampling noise
    batch = {k: jnp.asarray(v) for k, v in stream.batch_at(0).items()}
    batches = [batch] * 3

    trajectories = {}
    for impl in ("xla", "pallas"):
        cfg = GCNConfig(n_features=8, hidden=16, n_classes=4, fanout=3,
                        impl=impl)
        params = init_params(gcn_schema(cfg), jax.random.PRNGKey(0))
        state = {"params": params, "opt": adamw_init(params, tc),
                 "step": jnp.zeros((), jnp.int32)}
        step = jax.jit(make_sage_train_step(cfg, tc, mesh=None))
        losses, snaps = [], []
        for b in batches:
            state, m = step(state, b, feats)
            losses.append(float(m["total_loss"]))
            snaps.append(jax.tree.map(np.asarray, state["params"]))
        trajectories[impl] = (losses, snaps)

    xl, xs = trajectories["xla"]
    pl_, ps = trajectories["pallas"]
    assert pl_[-1] < pl_[0], f"pallas loss did not decrease: {pl_}"
    for i in range(3):
        np.testing.assert_allclose(pl_[i], xl[i], atol=1e-4, rtol=1e-4)
        flat_x = jax.tree.leaves(xs[i])
        flat_p = jax.tree.leaves(ps[i])
        for ax, ap in zip(flat_x, flat_p):
            np.testing.assert_allclose(ap, ax, atol=1e-5, rtol=1e-5,
                                       err_msg=f"params diverged at step {i}")


# ---------------------------------------------------------------------------
# 4b. the on-mesh grad matrix: every cell of the shared 8-way subprocess run
# ---------------------------------------------------------------------------

@pytest.mark.distributed
@pytest.mark.parametrize("flow", FLOWS)
@pytest.mark.parametrize("op", GRAD_OPS)
@pytest.mark.parametrize("path", ["edges", "sampled"])
def test_mesh_grad_parity_cell(grad_parity_report, path, op, flow):
    line = f"grad path={path} flow={flow} op={op} impl=pallas ok"
    assert line in grad_parity_report, (
        f"missing/failed grad matrix cell: {line!r}")


@pytest.mark.distributed
@pytest.mark.parametrize("flow", FLOWS)
@pytest.mark.parametrize("chunk", [1, 3, 64])
def test_mesh_grad_parity_chunked(grad_parity_report, flow, chunk):
    line = f"grad path=sampled flow={flow} chunk={chunk} ok"
    assert line in grad_parity_report, (
        f"missing/failed chunked grad cell: {line!r}")


@pytest.mark.distributed
@pytest.mark.parametrize("flow", FLOWS)
@pytest.mark.parametrize("op", ["add", "max"])
def test_mesh_grad_parity_scheduled_off(grad_parity_report, op, flow):
    """pallas grads default to the scheduled path on the mesh — these cells
    pin the scheduled=off (dense-occupancy) backward as its own axis."""
    line = f"grad path=edges flow={flow} op={op} impl=pallas sched=off ok"
    assert line in grad_parity_report, (
        f"missing/failed scheduled-off grad cell: {line!r}")


@pytest.mark.distributed
def test_mesh_grad_hoisted_schedule(grad_parity_report):
    """The hoisted deployment's backward on the real mesh: d_feats matches
    the unpermuted reference, d_weights un-permutes per shard."""
    assert "grad path=edges hoisted-schedule ok" in grad_parity_report


@pytest.mark.distributed
def test_mesh_pallas_train_parity(grad_parity_report):
    assert "train pallas-vs-xla 3-step parity ok" in grad_parity_report
