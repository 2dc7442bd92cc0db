"""Environment preflight: probe ``repro.compat`` feature detection on the
installed JAX and print a support matrix. Fails fast with ONE actionable
message instead of letting 12 test modules error at collection/runtime.

Exit 0 = the tier-1 suite (including the distributed subprocess cases) can
run here; exit 1 = something required is missing, with the reason printed.

Run:  PYTHONPATH=src python scripts/check_env.py [--json]
(``scripts/ci.sh`` runs this, then tier-1; the CI workflow runs it with
``--json`` and folds the machine-readable matrix into the step summary.)
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

#: the one JAX release ``repro.compat`` is written for
SUPPORTED_JAX = "0.9.0"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="emit the detected matrix as JSON "
                         "({matrix, failures, ok}) instead of the table — "
                         "for the CI step summary")
    args = ap.parse_args(argv)

    failures = []
    rows = []

    # -- python / required third-party ------------------------------------
    rows.append(("python", sys.version.split()[0]))
    for mod, why in [
        ("numpy", "array plumbing everywhere"),
        ("jax", "the whole engine"),
        ("pytest", "tier-1 runner"),
    ]:
        try:
            m = importlib.import_module(mod)
            rows.append((mod, getattr(m, "__version__", "present")))
        except ImportError as e:
            rows.append((mod, "MISSING"))
            failures.append(f"`{mod}` is required ({why}): {e}")

    # -- compat-layer feature detection ------------------------------------
    try:
        from repro import compat
    except ImportError as e:
        print(f"cannot import repro.compat — is PYTHONPATH=src set? ({e})",
              file=sys.stderr)
        return 1
    if compat.FEATURES["jax_version"] != SUPPORTED_JAX:
        failures.append(
            f"repro.compat is written for jax {SUPPORTED_JAX}; "
            f"jax {compat.FEATURES['jax_version']} is installed")

    for key, val in compat.feature_matrix().items():
        rows.append((f"compat.{key}", str(val)))

    # -- smoke: build a mesh + trace a shard_map through compat ------------
    try:
        import jax
        from jax.sharding import PartitionSpec as P

        mesh = compat.make_mesh((1,), ("data",),
                                axis_types=(compat.AxisType.Auto,))
        out = jax.jit(compat.shard_map(lambda x: x * 2, mesh=mesh,
                                       in_specs=P(), out_specs=P()))(
            jax.numpy.ones(4))
        assert float(out.sum()) == 8.0
        rows.append(("compat.smoke", "mesh + shard_map trace ok"))
    except Exception as e:  # noqa: BLE001 — report, don't crash the report
        rows.append(("compat.smoke", "FAILED"))
        failures.append(f"compat smoke test failed on this JAX: {e!r}")

    # -- Pallas interpret mode (the FAST-GAS kernel off-TPU) ---------------
    # the differential tier (tests/test_cgtrans_pallas.py, ci.sh --tier
    # pallas) runs the kernel in interpret mode on CPU; probe it with a tiny
    # scatter so a broken pallas install fails HERE with one message
    try:
        import jax.numpy as jnp
        from repro.kernels.gas_scatter import gas_scatter

        out = gas_scatter(jnp.array([0, 1, 0], jnp.int32),
                          jnp.ones((3, 2), jnp.float32), 2, op="add")
        assert float(out.sum()) == 6.0
        rows.append(("pallas interpret", "functional (gas_scatter probe ok)"))
    except Exception as e:  # noqa: BLE001 — report, don't crash the report
        rows.append(("pallas interpret", "BROKEN"))
        failures.append(
            f"Pallas interpret mode is non-functional on this JAX — the "
            f"impl='pallas' differential tier cannot run: {e!r}")

    # -- Pallas interpret VJP (the differentiable FAST-GAS path) -----------
    # the grad tier (tests/test_cgtrans_grad.py, ci.sh --tier grad) takes
    # jax.grad THROUGH the kernel via the custom VJPs in repro.core.gas;
    # probe that the backward traces and produces the known gradient here
    try:
        import jax
        import jax.numpy as jnp
        from repro.core import gas

        dst = jnp.array([0, 1, 0], jnp.int32)
        vals = jnp.ones((3, 2), jnp.float32)
        w = jnp.array([1.0, 2.0, 3.0])
        m = jnp.array([True, True, True])
        g = jax.grad(lambda v: gas.gas_scatter_weighted(
            dst, v, w, m, 2, op="add", impl="pallas").sum())(vals)
        # d_vals[e] = w[e] (every row's cotangent is 1): sum = 2·(1+2+3)
        assert float(g.sum()) == 12.0, float(g.sum())
        rows.append(("pallas interpret VJP",
                     "functional (grad-through-kernel probe ok)"))
    except Exception as e:  # noqa: BLE001 — report, don't crash the report
        rows.append(("pallas interpret VJP", "BROKEN"))
        failures.append(
            f"the pallas custom VJP does not trace on this JAX — the "
            f"gradient-parity tier (impl='pallas' training) cannot run: {e!r}")

    # -- fused + scheduled kernel (the locality-scheduled fast path) -------
    # the scheduler tier (tests/test_gas_schedule.py, ci.sh --tier sched)
    # runs the fused weighted kernel through the destination-binned banded
    # walk; probe that it traces in interpret mode and produces the known
    # weighted scatter so a broken scalar-prefetch path fails HERE
    try:
        import jax.numpy as jnp
        from repro.kernels.gas_scatter import (gas_scatter_fused,
                                               schedule_edges)

        dst = jnp.array([2, 0, 2, 9], jnp.int32)
        msk = jnp.array([True, True, True, False])
        w = jnp.array([1.0, 2.0, 3.0, 4.0])
        vals = jnp.ones((4, 2), jnp.float32)
        sched = schedule_edges(dst, msk, 10)
        p = sched.perm
        out = gas_scatter_fused(dst[p], vals[p], w[p], msk[p], 10, op="add",
                                schedule=sched)
        # row 2 gets w0+w2 = 4, row 0 gets w1 = 2, the masked edge nothing
        assert float(out[2, 0]) == 4.0 and float(out[0, 0]) == 2.0, out
        assert float(out.sum()) == 12.0, out
        rows.append(("pallas fused+scheduled",
                     "functional (banded-walk probe ok)"))
    except Exception as e:  # noqa: BLE001 — report, don't crash the report
        rows.append(("pallas fused+scheduled", "BROKEN"))
        failures.append(
            f"the fused/scheduled FAST-GAS dispatch does not trace on this "
            f"JAX — the scheduler tier (ci.sh --tier sched) cannot run: "
            f"{e!r}")

    # -- coalesced request blocks (one SSD command block ≡ two calls) ------
    # the coalesce tier (tests/test_cgtrans_coalesce.py, ci.sh --tier
    # coalesce) runs aggregate_multi — the self-lookup + fan-out segments
    # fused into one gather/all_to_all; probe that one combined block
    # reproduces two separate aggregate_sampled calls bit-for-bit HERE
    try:
        import jax.numpy as jnp
        from repro.core import cgtrans

        feats = jnp.arange(2 * 8 * 4, dtype=jnp.float32).reshape(2, 8, 4)
        nb1 = jnp.array([[[3], [9]], [[0], [15]]], jnp.int32)
        mk1 = jnp.ones((2, 2, 1), bool)
        nb2 = jnp.array([[[1, 2, 8]], [[4, 5, 11]]], jnp.int32)
        mk2 = jnp.array([[[True, True, False]], [[True, False, True]]])
        o1, o2 = cgtrans.aggregate_multi(feats, ((nb1, mk1), (nb2, mk2)),
                                         mesh=None)
        s1 = cgtrans.aggregate_sampled(feats, nb1, mk1, mesh=None)
        s2 = cgtrans.aggregate_sampled(feats, nb2, mk2, mesh=None)
        assert bool((o1 == s1).all()) and bool((o2 == s2).all()), (o1, o2)
        rows.append(("coalesced requests",
                     "functional (one command block ≡ two calls)"))
    except Exception as e:  # noqa: BLE001 — report, don't crash the report
        rows.append(("coalesced requests", "BROKEN"))
        failures.append(
            f"aggregate_multi does not reproduce the separate request "
            f"streams — the coalesce tier (ci.sh --tier coalesce) cannot "
            f"run: {e!r}")

    # -- wire codecs (the compressed transport layer) ----------------------
    # the wire tier (tests/test_wire.py, ci.sh --tier wire) ships quantized
    # partials and delta-encoded id streams through the collectives; probe
    # the pure codecs HERE (they need bitcast_convert_type over int8/int16,
    # which a stripped backend can lack) so a broken codec fails with one
    # message instead of a parity-matrix explosion
    try:
        import numpy as np
        import jax.numpy as jnp
        from repro.core import wire

        ids = jnp.array([[0, 5, -1, 3]], jnp.int32)
        dec = wire.delta_decode_ids(wire.delta_encode_ids(ids))
        assert bool((dec == ids).all()), dec
        x = jnp.array([[1.0, -3.0, 256.0, float("inf")]], jnp.float32)
        bf = wire.decode_payload(wire.encode_payload(x, "bf16"), "bf16")
        assert bool((bf == x).all()), bf           # ints ≤ 256 + inf: exact
        q = wire.decode_payload(wire.encode_payload(x, "int8"), "int8")
        scale = np.asarray(wire.int8_row_scale(x))[..., None]
        fin = np.isfinite(np.asarray(x))
        err = np.abs(np.asarray(q) - np.asarray(x))[fin]
        assert (err <= scale / 2 + 1e-6).all(), err.max()
        rows.append(("wire codecs",
                     "functional (delta ids exact, bf16 exact, int8 bounded)"))
    except Exception as e:  # noqa: BLE001 — report, don't crash the report
        rows.append(("wire codecs", "BROKEN"))
        failures.append(
            f"the compressed-wire codecs do not round-trip on this JAX — "
            f"the wire tier (ci.sh --tier wire) cannot run: {e!r}")

    # -- sparse feature codec (the compressed-sparse tier) -----------------
    # the sparse tier (tests/test_sparse.py, ci.sh --tier sparse) ships
    # bitmap+packed feature rows through the gather and the baseline
    # all_to_all; probe the pure codec HERE (cumsum-positional decode plus
    # the static capacity gate) so a broken round-trip fails with one
    # message instead of a parity-matrix explosion
    try:
        import numpy as np
        import jax.numpy as jnp
        from repro.core import sparse

        x = jnp.array([[0.0, 2.0, 0.0, 0.0, 5.0, 0.0, 0.0, 1.0],
                       [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0]],
                      jnp.float32)
        cap = sparse.table_capacity(np.asarray(x))
        packed, bitmap = sparse.encode_rows(x, cap)
        dec = sparse.decode_rows(packed, bitmap, x.shape[1])
        assert bool((dec == x).all()), dec              # round-trip is exact
        pc = np.asarray(sparse.popcount(bitmap))
        assert (pc == [3, 0]).all(), pc                 # bitmap ≡ packed len
        assert sparse.sparse_fits(cap, 64)              # small cap wins at F=64
        assert not sparse.sparse_fits(8, 8)             # dense table: gate off
        rows.append(("sparse codec",
                     "functional (bitmap+packed round-trip exact, capacity "
                     "gate static)"))
    except Exception as e:  # noqa: BLE001 — report, don't crash the report
        rows.append(("sparse codec", "BROKEN"))
        failures.append(
            f"the compressed-sparse feature codec does not round-trip on "
            f"this JAX — the sparse tier (ci.sh --tier sparse) cannot "
            f"run: {e!r}")

    # -- islandized locality partitioner (the partitioning tier) -----------
    # the part tier (tests/test_partition.py, ci.sh --tier part) rests on
    # islandize emitting a true permutation whose packing beats the interval
    # split on community graphs; probe the host-side pipeline end to end on
    # a tiny shuffled clustered graph so a numpy/BFS regression fails with
    # one message instead of a tier-wide explosion
    try:
        import numpy as np
        from repro.graph import (COOGraph, clustered_graph, islandize,
                                 partition_by_src, partition_graph,
                                 remote_destination_rows)

        gk = clustered_graph(64, 512, n_clusters=8, p_intra=0.95, seed=0)
        pm = np.random.default_rng(1).permutation(64).astype(np.int32)
        gk = COOGraph(64, pm[gk.src], pm[gk.dst])
        isl = islandize(gk, 4)
        assert np.array_equal(np.sort(isl.relabel), np.arange(64)), "not a permutation"
        assert np.array_equal(isl.inverse[isl.relabel], np.arange(64))
        rr_i = remote_destination_rows(partition_by_src(gk, 4)).sum()
        rr_s = remote_destination_rows(
            partition_graph(gk, 4, method="island")[0]).sum()
        assert int(rr_s) < int(rr_i), (rr_i, rr_s)
        rows.append(("islandize",
                     "functional (relabel is a permutation, locality win "
                     f"{int(rr_i)}->{int(rr_s)} remote rows)"))
    except Exception as e:  # noqa: BLE001 — report, don't crash the report
        rows.append(("islandize", "BROKEN"))
        failures.append(
            f"the islandized locality partitioner failed its probe — the "
            f"partitioning tier (ci.sh --tier part) cannot run: {e!r}")

    # -- abstract tracing through shard_map (the lint/contract layer) ------
    # scripts/lint.py verifies every DataflowContract by jax.make_jaxpr /
    # eval_shape over ShapeDtypeStruct args — traced through shard_map with
    # NOTHING executed, which is exactly what a headless CI box must
    # support; probe it here so a JAX that can't trace abstractly fails
    # with one message instead of 39 contract errors
    try:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        mesh = compat.make_mesh((1,), ("data",),
                                axis_types=(compat.AxisType.Auto,))
        fn = compat.shard_map(lambda x: jax.lax.psum(x, "data"),
                              mesh=mesh, in_specs=P(), out_specs=P())
        out = jax.eval_shape(fn, jax.ShapeDtypeStruct((4, 2), jnp.float32))
        assert out.shape == (4, 2) and out.dtype == jnp.float32, out
        jx = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((4, 2), jnp.float32))
        assert jx.jaxpr.eqns, "empty jaxpr from an abstract shard_map trace"
        rows.append(("abstract trace",
                     "functional (eval_shape/make_jaxpr through shard_map)"))
    except Exception as e:  # noqa: BLE001 — report, don't crash the report
        rows.append(("abstract trace", "BROKEN"))
        failures.append(
            f"abstract tracing through shard_map failed — the lint tier "
            f"(scripts/lint.py dataflow contracts) cannot run: {e!r}")

    # -- fake-device topology for the distributed cases --------------------
    flag = "--xla_force_host_platform_device_count=8"
    rows.append(("distributed tests",
                 f"subprocesses set XLA_FLAGS={flag} themselves"))

    # -- offline property-testing story ------------------------------------
    try:
        importlib.import_module("hypothesis")
        rows.append(("hypothesis", "installed (property tests use it)"))
    except ImportError:
        rows.append(("hypothesis",
                     "absent — tests/_propcheck.py deterministic fallback"))

    if args.json:
        print(json.dumps({"matrix": dict(rows), "failures": failures,
                          "ok": not failures}, indent=2))
        return 1 if failures else 0

    width = max(len(k) for k, _ in rows)
    print("repro environment support matrix")
    print("-" * (width + 40))
    for k, v in rows:
        print(f"{k:<{width}}  {v}")
    print("-" * (width + 40))

    if failures:
        print("\nNOT RUNNABLE:", file=sys.stderr)
        for f in failures:
            print(f"  * {f}", file=sys.stderr)
        return 1
    print("ok: tier-1 suite is runnable here "
          "(PYTHONPATH=src python -m pytest -x -q)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
