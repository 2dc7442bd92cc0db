"""Multi-device test payloads, executed in SUBPROCESSES (each sets its own
fake-device count before importing jax — the main pytest process stays at the
real 1-device topology).

Run directly:  python tests/distributed_cases.py <case-name>
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402


def case_cgtrans_equivalence():
    import jax
    import jax.numpy as jnp
    from repro.core import cgtrans
    from repro.graph import partition_by_src, uniform_graph, host_sample
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(8)
    rng = np.random.default_rng(0)
    g = uniform_graph(256, 4096, seed=1, n_features=16, weights=True)
    pg = partition_by_src(g, 8)
    feats = jnp.asarray(pg.features)
    args = (feats, jnp.asarray(pg.src), jnp.asarray(pg.dst),
            jnp.asarray(pg.weights), jnp.asarray(pg.mask))
    ref = cgtrans.aggregate_edges(*args, mesh=None)
    for flow in ("cgtrans", "baseline"):
        out = jax.jit(lambda *a, f=flow: cgtrans.aggregate_edges(
            *a, mesh=mesh, dataflow=f))(*args)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-3, (flow, err)

    seeds = rng.integers(0, 256, 64).astype(np.int32)
    nbrs, mask = host_sample(g, seeds, 10, seed=2)
    nb = jnp.asarray(nbrs.reshape(8, 8, 10))
    mk = jnp.asarray(mask.reshape(8, 8, 10))
    ref_s = cgtrans.aggregate_sampled(feats, nb, mk, mesh=None)
    for flow in ("cgtrans", "baseline"):
        out = jax.jit(lambda f, n, m, fl=flow: cgtrans.aggregate_sampled(
            f, n, m, mesh=mesh, dataflow=fl))(feats, nb, mk)
        err = float(jnp.max(jnp.abs(out - ref_s)))
        assert err < 1e-3, (flow, err)
    print("cgtrans equivalence ok")


def case_cgtrans_pallas_parity():
    """The full differential matrix on a REAL 8-way mesh: for every
    (dataflow, op, path), impl="pallas" ≡ impl="xla" ≡ the single-shard
    reference — with ragged (non-tile-aligned) per-shard edge counts, one
    all-padded shard (mask all-False), int features for op="or", and the
    chunked request stream checked against the unchunked one.

    Prints one ``parity path=… flow=… op=… impl=… ok`` line per cell;
    tests/test_cgtrans_pallas.py parses them into per-cell test results.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import cgtrans
    from repro.graph import partition_by_src, uniform_graph, host_sample
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(8)
    rng = np.random.default_rng(0)
    # E=1000 over 8 src-partitions → ragged live counts, padded to a
    # non-tile-aligned per-shard E (not a multiple of any kernel tile)
    g = uniform_graph(256, 1000, seed=1, n_features=16, weights=True)
    pg = partition_by_src(g, 8)
    feats = jnp.asarray(pg.features)
    feats_int = (jnp.abs(feats) > 0.5).astype(jnp.int32)   # op="or" features
    mask = np.asarray(pg.mask).copy()
    mask[3] = False                                        # all-padded shard
    mask = jnp.asarray(mask)
    eargs = (jnp.asarray(pg.src), jnp.asarray(pg.dst), jnp.asarray(pg.weights),
             mask)

    def close(a, b, tag, tol=1e-3):
        a = jnp.nan_to_num(a.astype(jnp.float32), posinf=9e9, neginf=-9e9)
        b = jnp.nan_to_num(b.astype(jnp.float32), posinf=9e9, neginf=-9e9)
        err = float(jnp.max(jnp.abs(a - b)))
        assert err < tol, (tag, err)

    for op in ("add", "max", "min", "or"):
        f = feats_int if op == "or" else feats
        ref = cgtrans.aggregate_edges(f, *eargs, mesh=None, op=op)
        for flow in ("cgtrans", "baseline"):
            for impl in ("xla", "pallas"):
                out = jax.jit(lambda ff, *a, fl=flow, i=impl, o=op:
                              cgtrans.aggregate_edges(
                                  ff, *a, mesh=mesh, dataflow=fl, op=o,
                                  impl=i))(f, *eargs)
                close(out, ref, ("edges", flow, op, impl))
                print(f"parity path=edges flow={flow} op={op} impl={impl} ok")

    seeds = rng.integers(0, 256, 64).astype(np.int32)
    nbrs, smask = host_sample(g, seeds, 10, seed=2)
    nb = jnp.asarray(nbrs.reshape(8, 8, 10))
    mk = np.asarray(smask.reshape(8, 8, 10)).copy()
    mk[5] = False                                          # all-padded shard
    mk = jnp.asarray(mk)
    for op in ("add", "max", "min", "or"):
        f = feats_int if op == "or" else feats
        ref = cgtrans.aggregate_sampled(f, nb, mk, mesh=None, op=op)
        for flow in ("cgtrans", "baseline"):
            for impl in ("xla", "pallas"):
                out = jax.jit(lambda ff, n_, m_, fl=flow, i=impl, o=op:
                              cgtrans.aggregate_sampled(
                                  ff, n_, m_, mesh=mesh, dataflow=fl, op=o,
                                  impl=i))(f, nb, mk)
                close(out, ref, ("sampled", flow, op, impl))
                print(f"parity path=sampled flow={flow} op={op} impl={impl} ok")

    # chunked request stream ≡ unchunked, on the mesh, both dataflows
    ref = cgtrans.aggregate_sampled(feats, nb, mk, mesh=None)
    for flow in ("cgtrans", "baseline"):
        for chunk in (1, 3, 64):
            out = jax.jit(lambda ff, n_, m_, fl=flow, c=chunk:
                          cgtrans.aggregate_sampled(
                              ff, n_, m_, mesh=mesh, dataflow=fl,
                              request_chunk=c))(feats, nb, mk)
            close(out, ref, ("chunked", flow, chunk))
            print(f"parity path=sampled flow={flow} chunk={chunk} ok")

    # scheduled=off pallas cells: the impl=pallas cells above run the
    # destination-binned schedule (the mesh default); pin the unscheduled
    # dense-occupancy grid as its own matrix axis on a reduced op set
    for op in ("add", "max"):
        f = feats
        ref_e = cgtrans.aggregate_edges(f, *eargs, mesh=None, op=op)
        ref_s = cgtrans.aggregate_sampled(f, nb, mk, mesh=None, op=op)
        for flow in ("cgtrans", "baseline"):
            out = jax.jit(lambda ff, *a, fl=flow, o=op:
                          cgtrans.aggregate_edges(
                              ff, *a, mesh=mesh, dataflow=fl, op=o,
                              impl="pallas", scheduled=False))(f, *eargs)
            close(out, ref_e, ("edges-unsched", flow, op))
            print(f"parity path=edges flow={flow} op={op} impl=pallas "
                  f"sched=off ok")
            out = jax.jit(lambda ff, n_, m_, fl=flow, o=op:
                          cgtrans.aggregate_sampled(
                              ff, n_, m_, mesh=mesh, dataflow=fl, op=o,
                              impl="pallas", scheduled=False))(f, nb, mk)
            close(out, ref_s, ("sampled-unsched", flow, op))
            print(f"parity path=sampled flow={flow} op={op} impl=pallas "
                  f"sched=off ok")

    # the HOISTED deployment (what PALLAS_CONFIG ships): schedule built once
    # per (partition, batch), edge list restructured at partition time, and
    # every aggregation consuming it through shard_map via schedule_applied —
    # plus the sharded gcn_forward_full auto-hoist wrapping the same plumbing
    sched = cgtrans.build_edge_schedule(eargs[1], mask, 256, mesh=mesh)
    p_args = cgtrans.apply_edge_schedule(sched, *eargs)
    ref = cgtrans.aggregate_edges(feats, *eargs, mesh=None, op="add")
    out = jax.jit(lambda ff, sc, *a: cgtrans.aggregate_edges(
        ff, *a, mesh=mesh, dataflow="cgtrans", op="add", impl="pallas",
        schedule=sc, schedule_applied=True))(feats, sched, *p_args)
    close(out, ref, ("edges hoisted",))
    print("parity path=edges flow=cgtrans hoisted-schedule ok")

    from repro.common.schema import init_params
    from repro.core.gcn import GCNConfig, gcn_forward_full, gcn_schema
    params = init_params(
        gcn_schema(GCNConfig(n_features=16, hidden=8, n_classes=4)),
        jax.random.PRNGKey(0))
    gouts = {}
    for impl in ("xla", "pallas"):
        cfg = GCNConfig(n_features=16, hidden=8, n_classes=4, impl=impl)
        gouts[impl] = jax.jit(lambda pp, ff, c=cfg: gcn_forward_full(
            pp, ff, *eargs, c, mesh=mesh))(params, feats)
    close(gouts["pallas"], gouts["xla"], ("gcn-full hoisted",))
    print("parity gcn-full sharded hoisted-schedule ok")
    print("cgtrans pallas parity ok")


def case_cgtrans_coalesce_parity():
    """The coalesced-request matrix on a REAL 8-way mesh: for every
    (dataflow, impl, chunked, scheduled) cell, ``aggregate_multi`` over a
    sage-shaped request pair (a K=1 all-valid lookup segment + a masked
    fan-out segment) ≡ the two separate ``aggregate_sampled`` calls — with
    one all-masked seed shard, gradients, the deterministic
    collectives-per-step 2 → 1 assertion (jaxpr-level, immune to XLA
    combiner passes), and a ``sage_forward`` coalesce-flag parity twin.

    Prints one ``coalesce … ok`` line per cell;
    tests/test_cgtrans_coalesce.py parses them into per-cell test results.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import cgtrans
    from repro.graph import partition_by_src, uniform_graph, host_sample
    from repro.launch.jaxpr_stats import collective_counts
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(8)
    rng = np.random.default_rng(0)
    g = uniform_graph(256, 1000, seed=1, n_features=16, weights=True)
    pg = partition_by_src(g, 8)
    feats = jnp.asarray(pg.features)

    seeds = rng.integers(0, 256, 64).astype(np.int32)
    nbrs, smask = host_sample(g, seeds, 10, seed=2)
    nb2 = jnp.asarray(nbrs.reshape(8, 8, 10))
    mk2 = np.asarray(smask.reshape(8, 8, 10)).copy()
    mk2[5] = False                                         # all-masked shard
    mk2 = jnp.asarray(mk2)
    nb1 = jnp.asarray(rng.integers(0, 256, (8, 6, 1)).astype(np.int32))
    mk1 = jnp.ones((8, 6, 1), bool)
    b1, b2 = (nb1, mk1), (nb2, mk2)

    def close(a, b, tag, tol=1e-3):
        err = float(jnp.max(jnp.abs(a - b)))
        assert err < tol, (tag, err)

    ref1 = cgtrans.aggregate_sampled(feats, nb1, mk1, mesh=None)
    ref2 = cgtrans.aggregate_sampled(feats, nb2, mk2, mesh=None)
    for flow in ("cgtrans", "baseline"):
        for impl in ("xla", "pallas"):
            for chunk in (None, 3):
                o1, o2 = jax.jit(lambda f, fl=flow, i=impl, c=chunk:
                                 cgtrans.aggregate_multi(
                                     f, (b1, b2), mesh=mesh, dataflow=fl,
                                     impl=i, request_chunk=c))(feats)
                close(o1, ref1, ("coalesce seg1", flow, impl, chunk))
                close(o2, ref2, ("coalesce seg2", flow, impl, chunk))
                tag = "on" if chunk else "off"
                print(f"coalesce flow={flow} impl={impl} chunked={tag} ok")
        # the scheduled axis (pallas defaults to scheduled on the mesh —
        # the cells above run it; pin scheduled=off explicitly too)
        for sched in (False, True):
            o1, o2 = jax.jit(lambda f, fl=flow, s=sched:
                             cgtrans.aggregate_multi(
                                 f, (b1, b2), mesh=mesh, dataflow=fl,
                                 impl="pallas", scheduled=s))(feats)
            close(o1, ref1, ("coalesce-sched seg1", flow, sched))
            close(o2, ref2, ("coalesce-sched seg2", flow, sched))
            print(f"coalesce flow={flow} impl=pallas "
                  f"sched={'on' if sched else 'off'} ok")

    # gradients: d_feats through the coalesced block ≡ the separate calls
    u1 = jnp.asarray(rng.standard_normal((8, 6, 16)).astype(np.float32))
    u2 = jnp.asarray(rng.standard_normal((8, 8, 16)).astype(np.float32))
    ref_g = jax.grad(lambda f: jnp.sum(
        cgtrans.aggregate_sampled(f, nb1, mk1, mesh=None) * u1) + jnp.sum(
        cgtrans.aggregate_sampled(f, nb2, mk2, mesh=None) * u2))(feats)
    for flow in ("cgtrans", "baseline"):
        for impl in ("xla", "pallas"):
            gc = jax.jit(jax.grad(
                lambda f, fl=flow, i=impl: (lambda a, b:
                                            jnp.sum(a * u1) + jnp.sum(b * u2))(
                    *cgtrans.aggregate_multi(f, (b1, b2), mesh=mesh,
                                             dataflow=fl, impl=i))))(feats)
            close(gc, ref_g, ("coalesce grad", flow, impl))
        print(f"coalesce grads flow={flow} ok")

    # the headline, counted deterministically at the jaxpr level:
    # collectives-per-step 2 → 1 on the cgtrans dataflow, halved on baseline
    def sep(f, fl):
        return (cgtrans.aggregate_sampled(f, nb1, mk1, mesh=mesh, dataflow=fl),
                cgtrans.aggregate_sampled(f, nb2, mk2, mesh=mesh, dataflow=fl))

    def coa(f, fl):
        return cgtrans.aggregate_multi(f, (b1, b2), mesh=mesh, dataflow=fl)

    # the expected counts come from analysis/contracts.py — the committed
    # budget table is the single source of truth (lint verifies it against
    # the abstract trace; this asserts it on the REAL mesh programs)
    from repro.analysis.contracts import SAGE_FETCH_COLLECTIVES
    cs = collective_counts(lambda f: sep(f, "cgtrans"), feats)
    cc = collective_counts(lambda f: coa(f, "cgtrans"), feats)
    for counts, budget in ((cs, SAGE_FETCH_COLLECTIVES["separate"]),
                           (cc, SAGE_FETCH_COLLECTIVES["coalesced"])):
        for coll, want in budget.items():
            assert counts[coll] == want, (coll, want, dict(counts))
    print("coalesce collectives cgtrans separate=2 coalesced=1 ok")
    bs = collective_counts(lambda f: sep(f, "baseline"), feats)
    bc = collective_counts(lambda f: coa(f, "baseline"), feats)
    assert bc["all_to_all"] * 2 == bs["all_to_all"], (dict(bs), dict(bc))
    assert bc["all_gather"] * 2 == bs["all_gather"], (dict(bs), dict(bc))
    print("coalesce collectives baseline halved ok")

    # sage_forward on the mesh: coalesce=True ≡ coalesce=False end to end
    from repro.common.schema import init_params
    from repro.core.gcn import GCNConfig, gcn_schema, sage_forward
    batch = {
        "seeds": jnp.asarray(rng.integers(0, 256, (8, 4)).astype(np.int32)),
        "nbrs1": jnp.asarray(rng.integers(0, 256, (8, 4, 3)).astype(np.int32)),
        "mask1": jnp.asarray(rng.random((8, 4, 3)) < 0.8),
        "nbrs2": jnp.asarray(rng.integers(0, 256, (8, 16, 5)).astype(np.int32)),
        "mask2": jnp.asarray(rng.random((8, 16, 5)) < 0.8),
    }
    logits = {}
    for coalesce in (True, False):
        cfg = GCNConfig(n_features=16, hidden=8, n_classes=4, fanout=5,
                        coalesce=coalesce)
        params = init_params(gcn_schema(cfg), jax.random.PRNGKey(0))
        logits[coalesce] = jax.jit(lambda p, f, c=cfg: sage_forward(
            p, f, batch, c, mesh=mesh))(params, feats)
    close(logits[True], logits[False], ("sage coalesce parity",), tol=1e-5)
    print("coalesce sage-forward mesh parity ok")
    print("cgtrans coalesce parity ok")


def case_cgtrans_grad_parity():
    """The gradient matrix on a REAL 8-way mesh: for every (dataflow, op,
    path), ``jax.grad`` through impl="pallas" ≡ impl="xla" ≡ the
    single-shard reference — with ragged per-shard edge counts, one
    all-masked shard, weights grads on the edges path, the chunked request
    stream, and a 3-step pallas-vs-xla ``make_sage_train_step`` parity run.

    Prints one ``grad path=… flow=… op=… impl=… ok`` line per cell;
    tests/test_cgtrans_grad.py parses them into per-cell test results.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import cgtrans
    from repro.graph import partition_by_src, uniform_graph, host_sample
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(8)
    rng = np.random.default_rng(0)
    g = uniform_graph(256, 1000, seed=1, n_features=16, weights=True)
    pg = partition_by_src(g, 8)
    feats = jnp.asarray(pg.features)
    mask = np.asarray(pg.mask).copy()
    mask[3] = False                                        # all-padded shard
    mask = jnp.asarray(mask)
    src, dst, wts = (jnp.asarray(pg.src), jnp.asarray(pg.dst),
                     jnp.asarray(pg.weights))
    u_e = jnp.asarray(rng.standard_normal(feats.shape).astype(np.float32))

    def close(a, b, tag, tol=1e-3):
        err = float(jnp.max(jnp.abs(a - b)))
        assert err < tol, (tag, err)

    def eloss(f, w, flow, op, impl, mesh_):
        out = cgtrans.aggregate_edges(f, src, dst, w, mask, mesh=mesh_,
                                      dataflow=flow, op=op, impl=impl)
        # mask the no-in-edge ±inf identities the way gcn_forward_full does
        return jnp.sum(jnp.where(jnp.isfinite(out), out, 0.0) * u_e)

    egrad = jax.jit(jax.grad(eloss, argnums=(0, 1)),
                    static_argnums=(2, 3, 4, 5))
    for op in ("add", "max", "min"):
        ref_f, ref_w = egrad(feats, wts, "cgtrans", op, "xla", None)
        for flow in ("cgtrans", "baseline"):
            for impl in ("xla", "pallas"):
                gf, gw = egrad(feats, wts, flow, op, impl, mesh)
                close(gf, ref_f, ("edges d_feats", flow, op, impl))
                close(gw, ref_w, ("edges d_weights", flow, op, impl))
                print(f"grad path=edges flow={flow} op={op} impl={impl} ok")

    seeds = rng.integers(0, 256, 64).astype(np.int32)
    nbrs, smask = host_sample(g, seeds, 10, seed=2)
    nb = jnp.asarray(nbrs.reshape(8, 8, 10))
    mk = np.asarray(smask.reshape(8, 8, 10)).copy()
    mk[5] = False                                          # all-padded shard
    mk = jnp.asarray(mk)
    u_s = jnp.asarray(rng.standard_normal((8, 8, 16)).astype(np.float32))

    def sloss(f, flow, op, impl, mesh_, chunk):
        out = cgtrans.aggregate_sampled(f, nb, mk, mesh=mesh_, dataflow=flow,
                                        op=op, impl=impl, request_chunk=chunk)
        return jnp.sum(out * u_s)      # identity rows read 0 on every op

    sgrad = jax.jit(jax.grad(sloss), static_argnums=(1, 2, 3, 4, 5))
    for op in ("add", "max", "min"):
        ref = sgrad(feats, "cgtrans", op, "xla", None, None)
        for flow in ("cgtrans", "baseline"):
            for impl in ("xla", "pallas"):
                gf = sgrad(feats, flow, op, impl, mesh, None)
                close(gf, ref, ("sampled d_feats", flow, op, impl))
                print(f"grad path=sampled flow={flow} op={op} impl={impl} ok")

    # chunked request stream: pallas grads, chunked ≡ unchunked, on the mesh
    ref = sgrad(feats, "cgtrans", "add", "xla", None, None)
    for flow in ("cgtrans", "baseline"):
        for chunk in (1, 3, 64):
            gf = sgrad(feats, flow, "add", "pallas", mesh, chunk)
            close(gf, ref, ("chunked grad", flow, chunk))
            print(f"grad path=sampled flow={flow} chunk={chunk} ok")

    # scheduled=off pallas grad cells (the pallas cells above run the mesh
    # default, i.e. scheduled): pin the unscheduled backward too
    def eloss_unsched(f, w, flow, op):
        out = cgtrans.aggregate_edges(f, src, dst, w, mask, mesh=mesh,
                                      dataflow=flow, op=op, impl="pallas",
                                      scheduled=False)
        return jnp.sum(jnp.where(jnp.isfinite(out), out, 0.0) * u_e)

    egrad_u = jax.jit(jax.grad(eloss_unsched, argnums=(0, 1)),
                      static_argnums=(2, 3))
    for op in ("add", "max"):
        ref_f, ref_w = egrad(feats, wts, "cgtrans", op, "xla", None)
        for flow in ("cgtrans", "baseline"):
            gf, gw = egrad_u(feats, wts, flow, op)
            close(gf, ref_f, ("edges d_feats unsched", flow, op))
            close(gw, ref_w, ("edges d_weights unsched", flow, op))
            print(f"grad path=edges flow={flow} op={op} impl=pallas "
                  f"sched=off ok")

    # the HOISTED deployment's backward: schedule built/applied once at
    # partition time, grads pulled through schedule_applied aggregation —
    # d_feats matches the unpermuted reference (edge order never touches
    # the row space), d_weights matches the reference permuted per shard
    sched = cgtrans.build_edge_schedule(dst, mask, 256, mesh=mesh)
    p_src, p_dst, p_wts, p_mask = cgtrans.apply_edge_schedule(
        sched, src, dst, wts, mask)

    def hloss(f, w):
        out = cgtrans.aggregate_edges(f, p_src, p_dst, w, p_mask, mesh=mesh,
                                      dataflow="cgtrans", op="add",
                                      impl="pallas", schedule=sched,
                                      schedule_applied=True)
        return jnp.sum(jnp.where(jnp.isfinite(out), out, 0.0) * u_e)

    ref_f, ref_w = egrad(feats, wts, "cgtrans", "add", "xla", None)
    gf, gw = jax.jit(jax.grad(hloss, argnums=(0, 1)))(feats, p_wts)
    close(gf, ref_f, ("hoisted d_feats",))
    close(gw, jnp.take_along_axis(ref_w, sched.perm, axis=1),
          ("hoisted d_weights",))
    print("grad path=edges hoisted-schedule ok")

    _train_parity_on_mesh(mesh)
    print("cgtrans grad parity ok")


def _train_parity_on_mesh(mesh):
    """3 ``make_sage_train_step`` steps on the 8-way mesh: impl="pallas"
    loss decreases and per-step params track impl="xla" to fp32 tolerance."""
    import jax
    import jax.numpy as jnp
    from repro.common.config import TrainConfig
    from repro.common.schema import init_params
    from repro.core.gcn import GCNConfig, gcn_schema
    from repro.data import GraphBatchStream, synthetic_node_labels
    from repro.graph import partition_by_src, uniform_graph
    from repro.optim import adamw_init
    from repro.train import make_sage_train_step

    g = uniform_graph(128, 1024, seed=0, n_features=8)
    labels = synthetic_node_labels(g.features, 4)
    pg = partition_by_src(g, 8)
    feats = jnp.asarray(pg.features)
    tc = TrainConfig(learning_rate=1e-2, warmup_steps=0, total_steps=3,
                     weight_decay=0.0)
    stream = GraphBatchStream(g, labels, n_parts=8, batch_per_part=4,
                              k1=3, k2=3)
    # one repeated batch: descent on it is guaranteed (see the in-process
    # twin in tests/test_cgtrans_grad.py)
    batch = {k: jnp.asarray(v) for k, v in stream.batch_at(0).items()}
    batches = [batch] * 3

    runs = {}
    for impl in ("xla", "pallas"):
        cfg = GCNConfig(n_features=8, hidden=16, n_classes=4, fanout=3,
                        impl=impl)
        params = init_params(gcn_schema(cfg), jax.random.PRNGKey(0))
        state = {"params": params, "opt": adamw_init(params, tc),
                 "step": jnp.zeros((), jnp.int32)}
        step = jax.jit(make_sage_train_step(cfg, tc, mesh=mesh))
        losses, snaps = [], []
        for b in batches:
            state, m = step(state, b, feats)
            losses.append(float(m["total_loss"]))
            snaps.append(jax.tree.map(np.asarray, state["params"]))
        runs[impl] = (losses, snaps)

    xl, xs = runs["xla"]
    pl_, ps = runs["pallas"]
    assert pl_[-1] < pl_[0], f"pallas loss did not decrease: {pl_}"
    for i in range(3):
        np.testing.assert_allclose(pl_[i], xl[i], atol=1e-4, rtol=1e-4)
        for ax, ap in zip(jax.tree.leaves(xs[i]), jax.tree.leaves(ps[i])):
            np.testing.assert_allclose(ap, ax, atol=1e-4, rtol=1e-4,
                                       err_msg=f"params diverged at step {i}")
    print("train pallas-vs-xla 3-step parity ok")


def case_wire_parity():
    """The compressed-wire matrix on a REAL 8-way mesh (repro.core.wire):

    * bf16 wire ≡ f32 wire BIT-EXACT — values and gradients — on
      integer-valued features (|x| ≤ 5, fan-out sums ≤ 256 fit bf16's 8
      mantissa bits; dyadic counts keep the mean divisions exact), across
      sampled/multi/edges × add/max/min × xla/pallas;
    * int8 wire bounded error on float features (per-row scale/2 per hop);
    * the delta-id gate: V > 32767 falls back to the raw int32 id stream
      and still agrees with the reference;
    * collective counts: the narrow wire changes BYTES, never counts —
      except edges-add's pinned psum_scatter → all_to_all swap;
    * the serving engine on the bf16 wire ≡ the f32 engine bit for bit.

    Prints one ``wire … ok`` line per cell; tests/test_wire.py parses them.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import cgtrans
    from repro.graph import partition_by_src, uniform_graph, host_sample
    from repro.launch.jaxpr_stats import collective_counts
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(8)
    rng = np.random.default_rng(0)
    g = uniform_graph(256, 1000, seed=1, n_features=16, weights=True)
    pg = partition_by_src(g, 8)
    # integer-valued f32 features in [-5, 5]: masked fan-out sums stay
    # ≤ 10·5 ≪ 256, so the bf16 wire is lossless by construction
    feats = jnp.asarray(np.round(np.asarray(pg.features) * 5.0)
                        .astype(np.float32))
    mask = np.asarray(pg.mask).copy()
    mask[3] = False                                        # all-padded shard
    mask = jnp.asarray(mask)
    eargs = (jnp.asarray(pg.src), jnp.asarray(pg.dst),
             jnp.asarray(pg.weights), mask)

    seeds = rng.integers(0, 256, 64).astype(np.int32)
    nbrs, smask = host_sample(g, seeds, 10, seed=2)
    nb = jnp.asarray(nbrs.reshape(8, 8, 10))
    mk = np.asarray(smask.reshape(8, 8, 10)).copy()
    mk[5] = False                                          # all-padded shard
    mk = jnp.asarray(mk)

    def exact(a, b, tag):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(tag))

    # -- bf16 ≡ f32 bit-exact values: sampled × op × impl -------------------
    for op in ("add", "max", "min"):
        for impl in ("xla", "pallas"):
            outs = {}
            for w in ("f32", "bf16"):
                outs[w] = jax.jit(lambda f, o=op, i=impl, ww=w:
                                  cgtrans.aggregate_sampled(
                                      f, nb, mk, mesh=mesh, op=o, impl=i,
                                      wire=ww))(feats)
            exact(outs["bf16"], outs["f32"], ("sampled", op, impl))
            print(f"wire path=sampled op={op} impl={impl} bf16 exact ok")

    # -- bf16 ≡ f32 bit-exact values: edges × op ----------------------------
    # (unit edge weights keep the payload integer-valued; untouched
    # destinations hold the ±inf identity on BOTH wires — inf compares
    # equal to inf, so assert_array_equal pins them too)
    ew = (eargs[0], eargs[1], jnp.ones_like(eargs[2]), eargs[3])
    for op in ("add", "max", "min"):
        outs = {}
        for w in ("f32", "bf16"):
            outs[w] = jax.jit(lambda f, o=op, ww=w: cgtrans.aggregate_edges(
                f, *ew, mesh=mesh, op=o, wire=ww))(feats)
        exact(outs["bf16"], outs["f32"], ("edges", op))
        print(f"wire path=edges op={op} bf16 exact ok")

    # -- bf16 ≡ f32 bit-exact: the coalesced command block ------------------
    nb1 = jnp.asarray(rng.integers(0, 256, (8, 6, 1)).astype(np.int32))
    mk1 = jnp.ones((8, 6, 1), bool)
    b1, b2 = (nb1, mk1), (nb, mk)
    for impl in ("xla", "pallas"):
        outs = {}
        for w in ("f32", "bf16"):
            outs[w] = jax.jit(lambda f, i=impl, ww=w: cgtrans.aggregate_multi(
                f, (b1, b2), mesh=mesh, impl=i, wire=ww))(feats)
        exact(outs["bf16"][0], outs["f32"][0], ("multi seg1", impl))
        exact(outs["bf16"][1], outs["f32"][1], ("multi seg2", impl))
        print(f"wire path=multi impl={impl} bf16 exact ok")

    # -- bf16 ≡ f32 bit-exact GRADIENTS -------------------------------------
    # dyadic setup: all-valid masks + K=4 make every mean division exact in
    # binary; integer cotangents in [-4, 4] stay dyadic through the 1/K —
    # the backward wire (the custom_vjp ships cotangents through the SAME
    # codec) is then lossless too
    nb4 = jnp.asarray(rng.integers(0, 256, (8, 8, 4)).astype(np.int32))
    mk4 = jnp.ones((8, 8, 4), bool)
    u = jnp.asarray(rng.integers(-4, 5, (8, 8, 16)).astype(np.float32))

    def sloss(f, impl, w):
        out = cgtrans.aggregate_sampled(f, nb4, mk4, mesh=mesh, impl=impl,
                                        wire=w)
        return jnp.sum(out * u)

    sgrad = jax.jit(jax.grad(sloss), static_argnums=(1, 2))
    for impl in ("xla", "pallas"):
        exact(sgrad(feats, impl, "bf16"), sgrad(feats, impl, "f32"),
              ("sampled grad", impl))
        print(f"wire grad path=sampled impl={impl} bf16 exact ok")

    u1 = jnp.asarray(rng.integers(-4, 5, (8, 6, 16)).astype(np.float32))

    def mloss(f, w):
        a, b = cgtrans.aggregate_multi(f, ((nb1, mk1), (nb4, mk4)),
                                       mesh=mesh, wire=w)
        return jnp.sum(a * u1) + jnp.sum(b * u)

    mgrad = jax.jit(jax.grad(mloss), static_argnums=(1,))
    exact(mgrad(feats, "bf16"), mgrad(feats, "f32"), ("multi grad",))
    print("wire grad path=multi bf16 exact ok")

    # -- int8 bounded error -------------------------------------------------
    # float features now; the bound is loose (one scale/2 per hop) but the
    # claim that matters — quantization stays a TRANSPORT error, never a
    # corruption — shows as a small fraction of the payload magnitude
    ffeats = jnp.asarray(pg.features)
    for path, fn in (("sampled", lambda f, w: cgtrans.aggregate_sampled(
            f, nb, mk, mesh=mesh, wire=w)),
                     ("edges", lambda f, w: cgtrans.aggregate_edges(
                         f, *eargs, mesh=mesh, op="max", wire=w))):
        a = np.asarray(jax.jit(lambda f, fn=fn: fn(f, "int8"))(ffeats))
        b = np.asarray(jax.jit(lambda f, fn=fn: fn(f, "f32"))(ffeats))
        fin = np.isfinite(a) & np.isfinite(b)
        # identity rows (±inf / untouched) must agree EXACTLY between wires
        assert (np.isfinite(a) == np.isfinite(b)).all(), path
        err = np.abs(a[fin] - b[fin]).max()
        span = np.abs(b[fin]).max()
        assert err <= 0.02 * span + 1e-6, (path, err, span)
        print(f"wire path={path} int8 bounded ok")

    # -- the delta-id range gate: V over the int16 limit falls back ---------
    big_v = 2**16                      # > ID_DELTA_MAX_V → raw int32 ids
    bfeats = jnp.asarray(np.round(rng.standard_normal(
        (8, big_v // 8, 4)) * 5.0).astype(np.float32))
    bnb = jnp.asarray(rng.integers(0, big_v, (8, 4, 4)).astype(np.int32))
    bmk = jnp.ones((8, 4, 4), bool)
    outs = {}
    for w in ("f32", "bf16"):
        outs[w] = jax.jit(lambda f, ww=w: cgtrans.aggregate_sampled(
            f, bnb, bmk, mesh=mesh, wire=ww))(bfeats)
    exact(outs["bf16"], outs["f32"], ("delta fallback",))
    print("wire delta-fallback raw-int32 ids ok")

    # -- counts: bytes change, budgets don't (except edges-add's swap) ------
    for w in ("bf16", "int8"):
        cw = collective_counts(lambda f: cgtrans.aggregate_sampled(
            f, nb, mk, mesh=mesh, wire=w), feats)
        c0 = collective_counts(lambda f: cgtrans.aggregate_sampled(
            f, nb, mk, mesh=mesh, wire="f32"), feats)
        assert dict(cw) == dict(c0), (w, dict(cw), dict(c0))
        ce = collective_counts(lambda f: cgtrans.aggregate_edges(
            f, *eargs, mesh=mesh, op="add", wire=w), feats)
        assert ce["psum_scatter"] == 0 and ce["all_to_all"] == 1, dict(ce)
    print("wire collective counts ok")

    # -- the serving engine on the bf16 wire --------------------------------
    from repro.serving import ServingEngine
    V, F = 256, 16
    sfeats = np.round(rng.standard_normal((V, F)) * 5.0).astype(np.float32)
    indptr, indices, _ = g.to_csr()
    res = {}
    sseeds = rng.integers(0, V, 8)
    for w in ("f32", "bf16"):
        eng = ServingEngine(sfeats, indptr, indices, mesh=mesh, fanout=4,
                            wire=w, max_batch=8)
        rids = [eng.submit([int(s)]) for s in sseeds]
        assert eng.poll() == 8
        res[w] = [eng.result(r) for r in rids]
    for a, b in zip(res["bf16"], res["f32"]):
        exact(a.self_rows, b.self_rows, ("serving self",))
        exact(a.agg_rows, b.agg_rows, ("serving agg",))
    print("wire serving bf16 exact ok")
    print("wire parity ok")


def case_cgtrans_collective_bytes():
    """The paper's mechanism measured: cgtrans moves ≈ K× fewer collective
    bytes than baseline for fan-out K sampled aggregation."""
    import jax
    import jax.numpy as jnp
    from repro.core import cgtrans
    from repro.launch import hlo_analysis as H
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(8)
    P_, part, F = 8, 64, 128
    B_loc, K = 32, 16
    feats = jnp.zeros((P_, part, F))
    nbrs = jnp.zeros((P_, B_loc, K), jnp.int32)
    mask = jnp.ones((P_, B_loc, K), bool)
    bytes_ = {}
    for flow in ("cgtrans", "baseline"):
        comp = jax.jit(lambda f, n, m, fl=flow: cgtrans.aggregate_sampled(
            f, n, m, mesh=mesh, dataflow=fl)).lower(feats, nbrs, mask).compile()
        bytes_[flow] = H.analyze(comp.as_text()).collective_bytes
    ratio = bytes_["baseline"] / bytes_["cgtrans"]
    assert ratio > K / 4, (bytes_, ratio)   # compression ≈ fan-out
    print(f"collective bytes: baseline={bytes_['baseline']:.0f} "
          f"cgtrans={bytes_['cgtrans']:.0f} ratio={ratio:.1f} ok")


def case_embedding_cgtrans():
    import jax
    import jax.numpy as jnp
    from repro.models.embedding import embed_lookup
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh(2, 4)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((64, 16)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 64, (4, 8)).astype(np.int32))
    want = np.asarray(table)[np.asarray(ids)]
    got = jax.jit(lambda t, i: embed_lookup(t, i, mesh=mesh, cgtrans=True,
                                            compute_dtype=jnp.float32))(table, ids)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    # gradient: owner-aggregated scatter equals dense one-hot gradient, on
    # both GAS backends (pallas = the FAST-GAS kernel in the custom VJP) and
    # with the chunked request stream on
    dense = jax.grad(lambda t: jnp.sum(jnp.take(t, ids, 0) ** 2))(table)
    for impl in ("xla", "pallas"):
        for chunk in (None, 5):
            def loss(t, impl=impl, chunk=chunk):
                e = embed_lookup(t, ids, mesh=mesh, cgtrans=True,
                                 compute_dtype=jnp.float32, impl=impl,
                                 request_chunk=chunk)
                return jnp.sum(e * e)
            g = jax.jit(jax.grad(loss))(table)
            np.testing.assert_allclose(np.asarray(g), np.asarray(dense),
                                       atol=1e-4, err_msg=f"{impl}/{chunk}")
    print("embedding cgtrans ok")


def case_elastic_checkpoint():
    """Save on a (4,2) mesh, restore onto (2,4) and 1-device — elastic."""
    import tempfile
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.checkpoint import CheckpointManager
    from repro.common.logical import to_physical
    from repro.launch.mesh import make_test_mesh

    spec_tree = {"w": ("vocab", "embed"), "b": (None,)}
    state = {"w": jnp.arange(64 * 4, dtype=jnp.float32).reshape(64, 4),
             "b": jnp.ones(4)}
    mesh_a = make_test_mesh(4, 2)
    sharded = {
        "w": jax.device_put(state["w"], NamedSharding(mesh_a, to_physical(spec_tree["w"], mesh_a))),
        "b": jax.device_put(state["b"], NamedSharding(mesh_a, to_physical(spec_tree["b"], mesh_a))),
    }
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(sharded, 7)
        mesh_b = make_test_mesh(2, 4)
        restored, step = mgr.restore(state, mesh=mesh_b, spec_tree=spec_tree)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))
        shard_shape = restored["w"].sharding.shard_shape(restored["w"].shape)
        # 2D FSDP×TP: vocab/model(4) × embed/data(2) on the new mesh
        assert shard_shape == (16, 2)
        plain, _ = mgr.restore(state)     # 1-device style restore
        np.testing.assert_array_equal(np.asarray(plain["w"]), np.asarray(state["w"]))
    print("elastic checkpoint ok")


def case_distributed_sage_training():
    """2-layer GraphSAGE + CGTrans trains on an 8-way storage mesh — with
    the chunked request stream on (the SSD command-queue analogue)."""
    import jax
    import jax.numpy as jnp
    from repro.common.config import TrainConfig
    from repro.common.schema import init_params
    from repro.core.gcn import GCNConfig, gcn_schema
    from repro.data import GraphBatchStream, synthetic_node_labels
    from repro.graph import partition_by_src, uniform_graph
    from repro.launch.mesh import make_data_mesh
    from repro.optim import adamw_init
    from repro.train import make_sage_train_step

    mesh = make_data_mesh(8)
    g = uniform_graph(512, 8192, seed=0, n_features=16)
    labels = synthetic_node_labels(g.features, 4)
    pg = partition_by_src(g, 8)
    feats = jnp.asarray(pg.features)
    cfg = GCNConfig(n_features=16, hidden=32, n_classes=4, fanout=8,
                    request_chunk=8)
    tc = TrainConfig(learning_rate=5e-3, warmup_steps=5, total_steps=60,
                     weight_decay=0.0)
    params = init_params(gcn_schema(cfg), jax.random.PRNGKey(0))
    state = {"params": params, "opt": adamw_init(params, tc),
             "step": jnp.zeros((), jnp.int32)}
    stream = GraphBatchStream(g, labels, n_parts=8, batch_per_part=16, k1=4, k2=4)

    step = jax.jit(make_sage_train_step(cfg, tc, mesh=mesh))

    losses = []
    for i, batch in zip(range(60), stream):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        b["mask1"] = b["mask1"].astype(bool)
        b["mask2"] = b["mask2"].astype(bool)
        state, m = step(state, b, feats)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])
    print(f"sage training ok: loss {losses[0]:.3f} -> {losses[-1]:.3f}")


def case_pipeline_parallel():
    """GPipe fill–drain over a 2-stage 'pod' axis == sequential execution."""
    import jax
    import jax.numpy as jnp
    from repro.compat import AxisType, make_mesh
    from repro.train.pipeline import pipelined_apply, split_stages

    assert split_stages(10, 4) == ((0, 3), (3, 6), (6, 8), (8, 10))
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
    rng = np.random.default_rng(0)
    n_blocks, D = 6, 8
    W = jnp.asarray(rng.standard_normal((n_blocks, D, D)).astype(np.float32) * 0.3)
    x = jnp.asarray(rng.standard_normal((4, 2, 5, D)).astype(np.float32))

    def block_fn(x, w):
        return jnp.tanh(x @ w)

    # sequential reference
    ref = x
    for i in range(n_blocks):
        ref = block_fn(ref, W[i])

    with mesh:
        out = jax.jit(lambda w, xx: pipelined_apply(
            block_fn, w, xx, mesh=mesh))(W, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    print("pipeline parallel ok")


def case_islandized_parity():
    """Islandized ≡ interval on a REAL 8-way mesh, plus the counted wins.

    The graph is the adversarial case: a clustered_graph whose vertex ids
    are SCRAMBLED, so the contiguous-interval split gets zero locality while
    ``islandize`` recovers the communities. Edges are deduplicated and the
    integer feature table is per-column injective over vertices, so max/min
    have a unique winner per (destination, column) — the even-split tie
    convention then never mixes non-dyadic fractions and every gradient sum
    is an integer, making bit-exactness hold under any edge reordering.

    Cells (tests/test_partition.py parses the lines):
    * values: aggregate_edges island ≡ interval, un-permuted, across
      dataflow × op × impl;
    * grads: d/d_feats of a masked integer-cotangent loss, same matrix
      (add/max);
    * sage_forward island ≡ interval (and one optimizer step through
      make_sage_train_step(relabel=), bit-exact params);
    * ServingEngine(partition="island") ≡ interval with the hot cache ON;
    * counted locality: remote destination rows and dense occupancy rounds
      both strictly reduced.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import cgtrans
    from repro.core.gcn import GCNConfig, gcn_schema, sage_forward
    from repro.graph import (COOGraph, clustered_graph, partition_by_src,
                             partition_graph, remote_destination_rows)
    from repro.kernels.gas_scatter import ops as gas_ops
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(8)
    rng = np.random.default_rng(0)
    V, E0, F = 256, 2048, 8
    g0 = clustered_graph(V, E0, n_clusters=8, p_intra=0.92, seed=3)
    perm = rng.permutation(V).astype(np.int32)
    src, dst = perm[g0.src], perm[g0.dst]
    # dedupe (src, dst) pairs: duplicate edges are exact max/min ties whose
    # even-split backward would go non-dyadic
    pairs = np.unique(np.stack([src, dst], 1), axis=0)
    # per-column injective integer features: column f holds v - 128 + f with
    # alternating sign, so every destination's max/min winner is unique
    feats = ((np.arange(V)[:, None] - V // 2 + np.arange(F)[None, :])
             * np.where(np.arange(F) % 2 == 0, 1.0, -1.0)).astype(np.float32)
    g = COOGraph(V, pairs[:, 0].astype(np.int32),
                 pairs[:, 1].astype(np.int32), None, feats)

    pg_i, _ = partition_graph(g, 8, method="interval")
    pg_s, isl = partition_graph(g, 8, method="island")
    assert isl is not None and pg_i.part_size == pg_s.part_size
    part = pg_i.part_size

    # -- counted locality: both reductions strict on the 8-way mesh ---------
    # (counted on a graph big enough for several 128-row blocks per shard —
    # the parity graph above keeps the matrix cheap, but its 2-block row
    # grid saturates the dense occupancy in both layouts)
    gl0 = clustered_graph(1024, 8192, n_clusters=8, p_intra=0.95, seed=3)
    permL = np.random.default_rng(1003).permutation(1024).astype(np.int32)
    gl = COOGraph(1024, permL[gl0.src], permL[gl0.dst])
    lpg_i, _ = partition_graph(gl, 8, method="interval")
    lpg_s, _ = partition_graph(gl, 8, method="island")
    rr_i = remote_destination_rows(lpg_i)
    rr_s = remote_destination_rows(lpg_s)
    assert int(rr_s.sum()) < int(rr_i.sum()), (rr_i, rr_s)
    assert int(rr_s.max()) < int(rr_i.max()), (rr_i, rr_s)
    print(f"island locality remote_rows interval={int(rr_i.sum())} "
          f"island={int(rr_s.sum())} ok")

    def dense_live(pg):
        live = 0
        for p in range(8):
            l, _ = gas_ops.dense_skip_stats(
                jnp.asarray(pg.dst[p]), jnp.asarray(pg.mask[p]),
                8 * pg.part_size)
            live += int(l)
        return live

    dl_i, dl_s = dense_live(lpg_i), dense_live(lpg_s)
    assert dl_s < dl_i, (dl_i, dl_s)
    print(f"island locality dense_rounds interval={dl_i} island={dl_s} ok")

    def exact(a, b, tag):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(tag))

    def unpermute(flat_rows):
        """(8·part, F) islandized rows → original vertex order, rows [0, V)."""
        return np.asarray(flat_rows).reshape(8 * part, -1)[isl.relabel]

    args_i = (jnp.asarray(pg_i.features), jnp.asarray(pg_i.src),
              jnp.asarray(pg_i.dst), jnp.asarray(pg_i.weights),
              jnp.asarray(pg_i.mask))
    args_s = (jnp.asarray(pg_s.features), jnp.asarray(pg_s.src),
              jnp.asarray(pg_s.dst), jnp.asarray(pg_s.weights),
              jnp.asarray(pg_s.mask))

    # -- values: dataflow × op × impl ---------------------------------------
    agg = jax.jit(
        lambda a, flow, op, impl: cgtrans.aggregate_edges(
            *a, mesh=mesh, dataflow=flow, op=op, impl=impl),
        static_argnums=(1, 2, 3))
    for flow in ("cgtrans", "baseline"):
        for op in ("add", "max", "min"):
            for impl in ("xla", "pallas"):
                out_i = np.asarray(agg(args_i, flow, op, impl))
                out_s = np.asarray(agg(args_s, flow, op, impl))
                exact(out_i.reshape(8 * part, F)[:V],
                      unpermute(out_s), (flow, op, impl))
                print(f"island parity path=edges flow={flow} op={op} "
                      f"impl={impl} ok")

    # -- grads: d/d_feats of an integer-cotangent loss, add/max -------------
    u = rng.integers(-3, 4, (V, F)).astype(np.float32)
    u_i = np.zeros((8 * part, F), np.float32)
    u_i[:V] = u
    u_s = np.zeros((8 * part, F), np.float32)
    u_s[:V] = u[isl.inverse]                # cotangent follows its vertex
    u_i, u_s = (jnp.asarray(u_i.reshape(8, part, F)),
                jnp.asarray(u_s.reshape(8, part, F)))

    def loss(f, rest, ct, flow, op, impl):
        out = cgtrans.aggregate_edges(f, *rest, mesh=mesh, dataflow=flow,
                                      op=op, impl=impl)
        return jnp.sum(jnp.where(jnp.isfinite(out), out, 0.0) * ct)

    dgrad = jax.jit(jax.grad(loss), static_argnums=(3, 4, 5))
    for flow in ("cgtrans", "baseline"):
        for op in ("add", "max"):
            for impl in ("xla", "pallas"):
                g_i = np.asarray(dgrad(args_i[0], args_i[1:], u_i,
                                       flow, op, impl))
                g_s = np.asarray(dgrad(args_s[0], args_s[1:], u_s,
                                       flow, op, impl))
                exact(g_i.reshape(8 * part, F)[:V],
                      unpermute(g_s.reshape(8 * part, F)),
                      ("grad", flow, op, impl))
                print(f"island parity grad flow={flow} op={op} "
                      f"impl={impl} ok")

    # -- sage_forward + one optimizer step ----------------------------------
    import dataclasses as _dc

    from repro.common.config import TrainConfig
    from repro.common.schema import init_params
    from repro.optim import adamw_init
    from repro.train import make_sage_train_step

    B, K1, K2 = 4, 3, 3
    cfg_i = GCNConfig(n_features=F, hidden=16, n_classes=4, fanout=K1)
    cfg_s = _dc.replace(cfg_i, partition="island")
    batch = {
        "seeds": jnp.asarray(rng.integers(0, V, (8, B)).astype(np.int32)),
        "nbrs1": jnp.asarray(rng.integers(0, V, (8, B, K1)).astype(np.int32)),
        "mask1": jnp.asarray(rng.random((8, B, K1)) < 0.8),
        "nbrs2": jnp.asarray(
            rng.integers(0, V, (8, B * (1 + K1), K2)).astype(np.int32)),
        "mask2": jnp.asarray(rng.random((8, B * (1 + K1), K2)) < 0.8),
        "labels": jnp.asarray(rng.integers(0, 4, (8, B)).astype(np.int32)),
    }
    params = init_params(gcn_schema(cfg_i), jax.random.PRNGKey(0))
    t_i = jnp.asarray(pg_i.features)
    t_s = jnp.asarray(pg_s.features)
    for impl in ("xla", "pallas"):
        ci = _dc.replace(cfg_i, impl=impl)
        cs = _dc.replace(cfg_s, impl=impl)
        o_i = jax.jit(lambda p, f: sage_forward(p, f, batch, ci, mesh=mesh)
                      )(params, t_i)
        o_s = jax.jit(lambda p, f: sage_forward(
            p, f, batch, cs, mesh=mesh, relabel=isl.relabel))(params, t_s)
        exact(o_i, o_s, ("sage", impl))
    tc = TrainConfig(learning_rate=1e-2, warmup_steps=0, total_steps=1,
                     weight_decay=0.0)
    snaps = {}
    for name, cfg, t, rl in (("interval", cfg_i, t_i, None),
                             ("island", cfg_s, t_s, isl.relabel)):
        p0 = init_params(gcn_schema(cfg_i), jax.random.PRNGKey(1))
        st = {"params": p0, "opt": adamw_init(p0, tc),
              "step": jnp.zeros((), jnp.int32)}
        step = jax.jit(make_sage_train_step(cfg, tc, mesh=mesh, relabel=rl))
        st, _m = step(st, batch, t)
        snaps[name] = jax.tree.map(np.asarray, st["params"])
    for k in snaps["interval"]:
        exact(snaps["interval"][k], snaps["island"][k], ("train", k))
    print("island sage parity ok")

    # -- serving: cache ON, fused blocks, tenants — original-id API --------
    from repro.serving import ServingEngine

    indptr, indices, _ = g.to_csr()
    # integer-valued serve table: the fan-out segment's partial sums group
    # by owner shard, which the relabeling changes — integer addition is
    # order-invariant, float addition only to 1 ulp
    sfeats = np.round(rng.standard_normal((V, F)) * 5.0).astype(np.float32)
    kw = dict(fanout=4, mesh=mesh, max_batch=8, max_delay_s=1e9,
              cache_capacity=32)
    eng_i = ServingEngine(sfeats, indptr, indices, **kw)
    eng_s = ServingEngine(sfeats, indptr, indices, partition="island", **kw)
    seeds = [3, 9, 3, 17, 40, 9, 77, 130]
    for _wave in range(2):                     # wave 2 exercises cache hits
        rids = [(eng_i.submit([s]), eng_s.submit([s])) for s in seeds]
        eng_i.flush()
        eng_s.flush()
        for ri, rs in rids:
            a, b = eng_i.result(ri), eng_s.result(rs)
            exact(a.self_rows, b.self_rows, ("serve self", ri))
            exact(a.agg_rows, b.agg_rows, ("serve agg", ri))
            exact(a.from_cache, b.from_cache, ("serve cache", ri))
    assert eng_i.cache.snapshot() == eng_s.cache.snapshot()
    assert eng_s.cache.snapshot()["hits"] > 0
    print("island serving parity cache=on ok")

    print("islandized parity ok")


def case_sparse_parity():
    """The compressed-sparse feature matrix on a REAL 8-way mesh
    (repro.core.sparse):

    * sparse ≡ dense BIT-EXACT — values and gradients — on integer-valued
      ~10%-dense features, across sampled × add/max/min × cgtrans/baseline
      × xla/pallas, plus the multi and edges entrypoints;
    * the capacity gate: a capacity that can't beat dense falls back to the
      unchanged dense path (still bit-exact);
    * sparse composes with the bf16 wire (baseline raw-row shipment packs
      quantized nonzeros + bitmap) — still exact on small integers;
    * collective counts: the format changes BYTES, never counts;
    * the serving engine on sparse features ≡ the dense engine bit for bit.

    Prints one ``sparse … ok`` line per cell; tests/test_sparse.py parses
    them.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import cgtrans
    from repro.core import sparse as sparsefmt
    from repro.graph import partition_by_src, uniform_graph, host_sample
    from repro.launch.jaxpr_stats import collective_counts
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(8)
    rng = np.random.default_rng(0)
    g = uniform_graph(256, 1000, seed=1, n_features=16, weights=True)
    pg = partition_by_src(g, 8)
    # integer-valued features at ~10% density: round to ints (bit-exact
    # addition in any order), then zero most entries so the measured
    # table_capacity clears the sparse_fits gate
    fdense = np.round(np.asarray(pg.features) * 5.0).astype(np.float32)
    keep = rng.random(fdense.shape) < 0.1
    feats = jnp.asarray(np.where(keep, np.where(fdense == 0, 1.0, fdense), 0.0))
    cap = sparsefmt.table_capacity(np.asarray(feats))
    F = feats.shape[-1]
    assert sparsefmt.sparse_fits(cap, F), (cap, F)
    mask = np.asarray(pg.mask).copy()
    mask[3] = False                                        # all-padded shard
    mask = jnp.asarray(mask)
    eargs = (jnp.asarray(pg.src), jnp.asarray(pg.dst),
             jnp.ones_like(jnp.asarray(pg.weights)), mask)

    seeds = rng.integers(0, 256, 64).astype(np.int32)
    nbrs, smask = host_sample(g, seeds, 10, seed=2)
    nb = jnp.asarray(nbrs.reshape(8, 8, 10))
    mk = np.asarray(smask.reshape(8, 8, 10)).copy()
    mk[5] = False                                          # all-padded shard
    mk = jnp.asarray(mk)

    def exact(a, b, tag):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(tag))

    # -- sparse ≡ dense values: sampled × flow × op × impl ------------------
    for flow in ("cgtrans", "baseline"):
        for op in ("add", "max", "min"):
            for impl in ("xla", "pallas"):
                outs = {}
                for feat_mode, c in (("dense", None), ("sparse", cap)):
                    outs[feat_mode] = jax.jit(
                        lambda f, fl=flow, o=op, i=impl, fm=feat_mode, cc=c:
                        cgtrans.aggregate_sampled(
                            f, nb, mk, mesh=mesh, dataflow=fl, op=o, impl=i,
                            features=fm, sparse_capacity=cc))(feats)
                exact(outs["sparse"], outs["dense"],
                      ("sampled", flow, op, impl))
                print(f"sparse path=sampled flow={flow} op={op} impl={impl} "
                      "exact ok")

    # -- sparse ≡ dense values: edges × flow × op ---------------------------
    for flow in ("cgtrans", "baseline"):
        for op in ("add", "max", "min"):
            outs = {}
            for feat_mode, c in (("dense", None), ("sparse", cap)):
                outs[feat_mode] = jax.jit(
                    lambda f, fl=flow, o=op, fm=feat_mode, cc=c:
                    cgtrans.aggregate_edges(
                        f, *eargs, mesh=mesh, dataflow=fl, op=o,
                        features=fm, sparse_capacity=cc))(feats)
            exact(outs["sparse"], outs["dense"], ("edges", flow, op))
            print(f"sparse path=edges flow={flow} op={op} exact ok")

    # -- sparse ≡ dense: the coalesced command block ------------------------
    nb1 = jnp.asarray(rng.integers(0, 256, (8, 6, 1)).astype(np.int32))
    mk1 = jnp.ones((8, 6, 1), bool)
    for flow in ("cgtrans", "baseline"):
        for impl in ("xla", "pallas"):
            outs = {}
            for feat_mode, c in (("dense", None), ("sparse", cap)):
                outs[feat_mode] = jax.jit(
                    lambda f, fl=flow, i=impl, fm=feat_mode, cc=c:
                    cgtrans.aggregate_multi(
                        f, ((nb1, mk1), (nb, mk)), mesh=mesh, dataflow=fl,
                        impl=i, features=fm, sparse_capacity=cc))(feats)
            exact(outs["sparse"][0], outs["dense"][0],
                  ("multi seg1", flow, impl))
            exact(outs["sparse"][1], outs["dense"][1],
                  ("multi seg2", flow, impl))
            print(f"sparse path=multi flow={flow} impl={impl} exact ok")

    # -- sparse ≡ dense GRADIENTS -------------------------------------------
    # dyadic setup (the wire-parity recipe): all-valid masks + K=4 keep the
    # mean divisions exact; integer cotangents keep every sum bit-exact
    nb4 = jnp.asarray(rng.integers(0, 256, (8, 8, 4)).astype(np.int32))
    mk4 = jnp.ones((8, 8, 4), bool)
    u = jnp.asarray(rng.integers(-4, 5, (8, 8, 16)).astype(np.float32))

    def sloss(f, flow, impl, feat_mode, c):
        out = cgtrans.aggregate_sampled(
            f, nb4, mk4, mesh=mesh, dataflow=flow, impl=impl,
            features=feat_mode, sparse_capacity=c)
        return jnp.sum(out * u)

    sgrad = jax.jit(jax.grad(sloss), static_argnums=(1, 2, 3, 4))
    for flow in ("cgtrans", "baseline"):
        for impl in ("xla", "pallas"):
            exact(sgrad(feats, flow, impl, "sparse", cap),
                  sgrad(feats, flow, impl, "dense", None),
                  ("sampled grad", flow, impl))
            print(f"sparse grad path=sampled flow={flow} impl={impl} "
                  "exact ok")

    def eloss(f, feat_mode, c):
        out = cgtrans.aggregate_edges(
            f, *eargs, mesh=mesh, op="add", features=feat_mode,
            sparse_capacity=c)
        return jnp.sum(out * jnp.asarray(
            rng2.integers(-4, 5, out.shape).astype(np.float32)))

    rng2 = np.random.default_rng(9)
    ge_s = jax.jit(jax.grad(eloss), static_argnums=(1, 2))(feats, "sparse", cap)
    rng2 = np.random.default_rng(9)
    ge_d = jax.jit(jax.grad(eloss), static_argnums=(1, 2))(feats, "dense", None)
    exact(ge_s, ge_d, ("edges grad",))
    print("sparse grad path=edges exact ok")

    # -- the capacity gate: no-win capacity ships dense unchanged -----------
    out_gate = jax.jit(lambda f: cgtrans.aggregate_sampled(
        f, nb, mk, mesh=mesh, features="sparse",
        sparse_capacity=F))(feats)   # F + bitmap ≥ F → gate fails
    out_ref = jax.jit(lambda f: cgtrans.aggregate_sampled(
        f, nb, mk, mesh=mesh))(feats)
    exact(out_gate, out_ref, ("gate fallback",))
    print("sparse gate-fallback dense ok")

    # -- sparse × bf16 wire: the baseline raw-row shipment ------------------
    # (baseline + narrow wire is ONLY legal with sparse features — the
    # packed nonzeros quantize like partials; integer values ≤ 5 keep the
    # bf16 leg lossless, so the composition is still exact)
    for flow in ("cgtrans", "baseline"):
        out_w = jax.jit(lambda f, fl=flow: cgtrans.aggregate_sampled(
            f, nb, mk, mesh=mesh, dataflow=fl, wire="bf16",
            features="sparse", sparse_capacity=cap))(feats)
        out_d = jax.jit(lambda f, fl=flow: cgtrans.aggregate_sampled(
            f, nb, mk, mesh=mesh, dataflow=fl))(feats)
        exact(out_w, out_d, ("bf16 wire", flow))
        print(f"sparse wire=bf16 flow={flow} exact ok")

    # -- counts: the format changes bytes, never counts ---------------------
    for flow in ("cgtrans", "baseline"):
        cs = collective_counts(lambda f, fl=flow: cgtrans.aggregate_sampled(
            f, nb, mk, mesh=mesh, dataflow=fl, features="sparse",
            sparse_capacity=cap), feats)
        cd = collective_counts(lambda f, fl=flow: cgtrans.aggregate_sampled(
            f, nb, mk, mesh=mesh, dataflow=fl), feats)
        assert dict(cs) == dict(cd), (flow, dict(cs), dict(cd))
    print("sparse collective counts ok")

    # -- the serving engine on sparse features ------------------------------
    from repro.serving import ServingEngine
    V = 256
    sfeats = np.asarray(feats).reshape(V, F)
    indptr, indices, _ = g.to_csr()
    res = {}
    sseeds = rng.integers(0, V, 8)
    for feat_mode in ("dense", "sparse"):
        eng = ServingEngine(sfeats, indptr, indices, mesh=mesh, fanout=4,
                            features=feat_mode, max_batch=8)
        rids = [eng.submit([int(s)]) for s in sseeds]
        assert eng.poll() == 8
        res[feat_mode] = [eng.result(r) for r in rids]
    assert res_cap_fits(sfeats)
    for a, b in zip(res["sparse"], res["dense"]):
        exact(a.self_rows, b.self_rows, ("serving self",))
        exact(a.agg_rows, b.agg_rows, ("serving agg",))
    print("sparse serving exact ok")
    print("sparse parity ok")


def res_cap_fits(sfeats):
    """The serving cell only demonstrates compression if the measured
    capacity actually clears the gate on this table."""
    from repro.core import sparse as sparsefmt
    return sparsefmt.sparse_fits(sparsefmt.table_capacity(sfeats),
                                 sfeats.shape[-1])


def _plain_sage_loss(params, table, batch):
    """2-layer concat GraphSAGE in plain ``jax.numpy`` on the whole (V, F)
    table: each layer-1 vertex takes its own row and the mean of its valid
    2-hop rows, each seed its own hidden row and the mean of its valid
    1-hop hidden rows; mean NLL over every seed of every chip."""
    import jax
    import jax.numpy as jnp

    def mean(x, mask):                    # (..., K, D), (..., K) -> (..., D)
        m = mask[..., None].astype(x.dtype)
        return (x * m).sum(-2) / jnp.maximum(m.sum(-2), 1)

    def dense(x, w, b):
        return jnp.einsum("...i,io->...o", x, w) + b

    n, B = batch["seeds"].shape
    K1 = batch["nbrs1"].shape[-1]
    ids1 = jnp.concatenate([batch["seeds"][..., None], batch["nbrs1"]], -1)
    x_self = table[ids1]                                  # (n, B, 1+K1, F)
    x_agg = mean(table[batch["nbrs2"]], batch["mask2"]).reshape(
        n, B, 1 + K1, -1)
    h1 = jax.nn.relu(dense(jnp.concatenate([x_self, x_agg], -1),
                           params["w0"], params["b0"]))
    h2 = jax.nn.relu(dense(jnp.concatenate(
        [h1[:, :, 0], mean(h1[:, :, 1:], batch["mask1"])], -1),
        params["w1"], params["b1"]))
    logp = jax.nn.log_softmax(dense(h2, params["w_out"], params["b_out"]))
    return -jnp.take_along_axis(logp, batch["labels"][..., None],
                                -1)[..., 0].mean()


def _sharded_sage_gaps(drop_exchange: bool):
    """One sampled train step of the deployed configuration (PALLAS_CONFIG:
    the FAST-GAS kernel in interpret mode, 4-seed chunks, scheduled) on a
    4-chip owner-sharded table of a tiny R-MAT graph, against the plain
    float32 reference on the same batch and weights. Returns the relative
    gap of the loss and, per parameter, the largest gradient entry gap over
    the leaf's largest entry.

    ``drop_exchange`` plants the fault the comparison must catch: the
    request broadcast hands each chip its own requests only (the others'
    read as dead ids), so each chip aggregates only the rows it owns."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.common.config import TrainConfig
    from repro.common.schema import init_params
    from repro.configs import graphic_gcn
    from repro.core.gcn import gcn_schema
    from repro.data import GraphBatchStream
    from repro.graph import rmat
    from repro.launch.mesh import make_data_mesh
    from repro.optim import adamw_init
    from repro.train import make_sage_train_step

    n, F, H, C, B, K1, K2 = 4, 16, 8, 5, 8, 3, 4
    g = rmat(8, 4, seed=0)                        # 256 vertices, 1,024 edges
    V = g.n_vertices
    mesh = make_data_mesh(n)
    cfg = dataclasses.replace(graphic_gcn.PALLAS_CONFIG, n_features=F,
                              hidden=H, n_classes=C, fanout=K2,
                              request_chunk=4)
    # a clip no gradient reaches: AdamW's first moment after one step is
    # (1 - beta1) times the raw gradient
    tc = TrainConfig(learning_rate=1e-2, warmup_steps=0, grad_clip=1e9)
    table = jax.random.normal(jax.random.PRNGKey(1), (V, F), jnp.float32)
    feats = jax.device_put(table.reshape(n, V // n, F),
                           NamedSharding(mesh, P("data")))
    labels = np.random.default_rng(2).integers(0, C, V).astype(np.int32)
    batch = GraphBatchStream(g, labels, n_parts=n, batch_per_part=B, k1=K1,
                             k2=K2, seed=3).batch_at(0)
    params = init_params(gcn_schema(cfg), jax.random.PRNGKey(0))
    state = {"params": params, "opt": adamw_init(params, tc),
             "step": jnp.zeros((), jnp.int32)}

    gather = lax.all_gather

    def own_requests_only(x, axis_name, **kw):
        got = gather(x, axis_name, **kw)
        mine = jnp.arange(got.shape[0]) == lax.axis_index(axis_name)
        return jnp.where(mine.reshape((-1,) + (1,) * (got.ndim - 1)), got,
                         -1)

    if drop_exchange:
        lax.all_gather = own_requests_only
    try:
        new, metrics = jax.jit(make_sage_train_step(cfg, tc, mesh=mesh))(
            state, batch, feats)
    finally:
        lax.all_gather = gather
    loss = float(metrics["total_loss"])
    grads = {k: np.asarray(m) / (1 - tc.beta1)
             for k, m in new["opt"]["m"].items()}
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(_plain_sage_loss)(
            params, table, {k: jnp.asarray(v) for k, v in batch.items()})
    gaps = {"loss": abs(loss - float(ref_loss)) / abs(float(ref_loss))}
    for k, r in ref_grads.items():
        r = np.asarray(r)
        gaps[k] = float(np.max(np.abs(grads[k] - r)) / np.max(np.abs(r)))
    return gaps


# The program sums each mean in another order than the reference (the
# owner-side partials, then the cross-chip combine) and runs its dense
# layers on the CPU in float32, so loss and gradients differ from the
# float32 reference by rounding alone: about 1e-7 of the value. The limits
# leave two orders of magnitude for that; an aggregation that loses any
# chip's rows moves the loss and every gradient by far more.
SAGE_LOSS_RTOL, SAGE_GRAD_RTOL = 1e-5, 1e-4


def case_sharded_sage_reference():
    """The owner-sharded train step (request all_gather, owner-side find and
    FAST-GAS reduce, partials all_to_all) equals the plain reference."""
    gaps = _sharded_sage_gaps(drop_exchange=False)
    print(gaps)
    assert gaps["loss"] <= SAGE_LOSS_RTOL, gaps
    assert max(v for k, v in gaps.items() if k != "loss") <= (
        SAGE_GRAD_RTOL), gaps
    print("sharded sage reference ok")


def case_sharded_sage_no_exchange():
    """The same comparison catches a step that drops the exchange."""
    gaps = _sharded_sage_gaps(drop_exchange=True)
    print(gaps)
    assert gaps["loss"] > SAGE_LOSS_RTOL, gaps
    assert all(v > SAGE_GRAD_RTOL for k, v in gaps.items()
               if k.startswith("w")), gaps
    print("sharded sage no-exchange caught ok")


def case_exchange_scope():
    """On a 4-chip mesh every cross-chip collective of the sampled path is
    named ``cgtrans.exchange``: the request all_gather and the partials'
    all_to_all of the deployed train step (its backward ships nothing: no
    gradient reaches the table), and the transposed all_to_all of a
    gradient taken through the aggregation into the table."""
    import dataclasses
    import re
    import jax
    import jax.numpy as jnp
    from repro.common.config import TrainConfig
    from repro.common.schema import init_params
    from repro.configs import graphic_gcn
    from repro.core import cgtrans
    from repro.core.gcn import gcn_schema
    from repro.data import GraphBatchStream
    from repro.graph import rmat
    from repro.launch.mesh import make_data_mesh
    from repro.optim import adamw_init
    from repro.train import make_sage_train_step

    n, F = 4, 16
    mesh = make_data_mesh(n)
    g = rmat(8, 4, seed=0)
    feats = jax.random.normal(jax.random.PRNGKey(1),
                              (n, g.n_vertices // n, F))
    cfg = dataclasses.replace(graphic_gcn.PALLAS_CONFIG, n_features=F,
                              hidden=8, n_classes=5, fanout=3,
                              request_chunk=2)
    tc = TrainConfig(learning_rate=1e-2, warmup_steps=0)
    params = init_params(gcn_schema(cfg), jax.random.PRNGKey(0))
    state = {"params": params, "opt": adamw_init(params, tc),
             "step": jnp.zeros((), jnp.int32)}
    batch = GraphBatchStream(g, np.zeros(g.n_vertices, np.int32), n_parts=n,
                             batch_per_part=4, k1=3, k2=3,
                             seed=3).batch_at(0)
    step = jax.jit(make_sage_train_step(cfg, tc, mesh=mesh))
    nb, mk = jnp.asarray(batch["nbrs2"]), jnp.asarray(batch["mask2"])
    into_table = jax.jit(jax.grad(lambda f: cgtrans.aggregate_sampled(
        f, nb, mk, mesh=mesh, impl=cfg.impl, request_chunk=2).sum()))
    coll = re.compile(r" (all-gather|all-to-all|reduce-scatter)(?:-start)?\(")
    for name, compiled in (
            ("train step", step.lower(state, batch, feats).compile()),
            ("table gradient", into_table.lower(feats).compile())):
        ops = []
        for line in compiled.as_text().splitlines():
            m = coll.search(line)
            if m:
                stack = re.search(r'op_name="([^"]*)"', line)
                ops.append((m.group(1), stack.group(1) if stack else ""))
        print(name, ops)
        assert {"all-gather", "all-to-all"} <= {op for op, _ in ops}, ops
        unnamed = [o for o in ops if cgtrans.EXCHANGE_SCOPE
                   not in o[1].split("/")]
        assert not unnamed, (name, unnamed)
        if name == "table gradient":
            assert any(op == "all-to-all" and "transpose(" in stack
                       for op, stack in ops), ops
    print("exchange scope ok")


CASES = {n[len("case_"):]: f for n, f in list(globals().items())
         if n.startswith("case_")}

if __name__ == "__main__":
    if len(sys.argv) != 2 or sys.argv[1] not in CASES:
        sys.exit(f"usage: {sys.argv[0]} <case>\n"
                 f"cases: {', '.join(sorted(CASES))}")
    CASES[sys.argv[1]]()
