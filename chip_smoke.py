"""Chip smoke test: the repo's main path on a TPU, checked against its oracle.

    PYTHONPATH=src python chip_smoke.py            # one chip
    PYTHONPATH=src python chip_smoke.py --chips 4  # the sharded path only

One chip (the default): a seeded R-MAT graph at Reddit's widths (scale 18 =
262,144 vertices, edge factor 16, F=602) lives on the chip as the feature
table. Sampled 2-hop GraphSAGE trains for a few steps under
``configs.graphic_gcn.PALLAS_CONFIG`` (every aggregation in the FAST-GAS
Pallas kernel, compiled — the step must contain a ``tpu_custom_call``) and
under ``CONFIG`` (``impl="xla"``, the oracle) on the same batches; losses
and parameters must agree at the fp32 tolerance of
``tests/test_cgtrans_grad.py``. Then a ``ServingEngine(impl="pallas")``
answers zipf-skewed requests and must agree with an ``impl="xla"`` engine.

``--chips 4``: the table owner-sharded V/4 rows per chip over a 4-way
``data`` mesh; the cgtrans dataflow on the kernel is compared with the
baseline dataflow on XLA, and each chip's memory must show its own shard.

Times printed are host-clock times around ``block_until_ready`` from this
one run. The last line of stdout is the JSON verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed phase
raises and exits non-zero before it. The script refuses to run anywhere but
on a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.common.config import TrainConfig  # noqa: E402
from repro.common.schema import init_params  # noqa: E402
from repro.configs.graphic_gcn import CONFIG, PALLAS_CONFIG  # noqa: E402
from repro.core.gcn import gcn_schema  # noqa: E402
from repro.data import GraphBatchStream, synthetic_node_labels  # noqa: E402
from repro.graph import partition_by_src, rmat  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.optim import adamw_init  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402
from repro.train import make_sage_train_step  # noqa: E402

SCALE = 18               # R-MAT 2^18 = 262,144 vertices (Reddit: 232,965)
EDGE_FACTOR = 16
SEEDS_PER_SHARD = 64     # as examples/train_graphsage.py
STEPS = 5
SERVE_REQUESTS = 16
LOSS_TOL = 1e-4          # tests/test_cgtrans_grad.py, 3-step train parity
PARAM_TOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def make_graph(scale: int, n_features: int, n_classes: int):
    """The seeded R-MAT graph with integer-valued features (rounded
    normals, |x| ≲ 20) and learnable labels (host arrays). Integer values
    make every aggregation sum exact in f32 whatever its order, so the
    kernel and the XLA oracle hand the dense layers identical inputs — a
    summation-order ulp would otherwise reach the parameters amplified by
    Adam's normalization of near-zero gradients."""
    g = rmat(scale, EDGE_FACTOR, seed=0)
    g.features = np.round(4.0 * np.random.default_rng(1).standard_normal(
        (g.n_vertices, n_features), dtype=np.float32))
    return g, synthetic_node_labels(g.features, n_classes)


def place_table(g, mesh):
    """The owner-sharded (P, V/P, F) feature table, one shard per device of
    the ``data`` axis."""
    pg = partition_by_src(g, mesh.shape["data"])
    return jax.device_put(pg.features, NamedSharding(mesh, P("data")))


def make_batches(g, labels, mesh, steps: int, seeds_per_shard: int,
                 fanout: int):
    stream = GraphBatchStream(g, labels, n_parts=mesh.shape["data"],
                              batch_per_part=seeds_per_shard,
                              k1=fanout, k2=fanout)
    sharding = NamedSharding(mesh, P("data"))
    return [jax.device_put(stream.batch_at(i), sharding)
            for i in range(steps)]


def train(cfg, mesh, feats, batches, name: str):
    """Run the jitted train step over ``batches``; returns the per-step
    losses, the per-step parameter snapshots and the compiled step."""
    tc = TrainConfig(learning_rate=3e-3, warmup_steps=0,
                     total_steps=len(batches), weight_decay=0.01)
    params = init_params(gcn_schema(cfg), jax.random.PRNGKey(0))
    state = {"params": params, "opt": adamw_init(params, tc),
             "step": jnp.zeros((), jnp.int32)}
    t0 = time.perf_counter()
    step = jax.jit(make_sage_train_step(cfg, tc, mesh=mesh)).lower(
        state, batches[0], feats).compile()
    t_compile = time.perf_counter() - t0
    losses, snaps, times = [], [], []
    for b in batches:
        t0 = time.perf_counter()
        state, m = step(state, b, feats)
        jax.block_until_ready(state)
        times.append(time.perf_counter() - t0)
        losses.append(float(m["total_loss"]))
        snaps.append(jax.tree.map(np.asarray, state["params"]))
    log(f"train[{name}] compile {t_compile:.3f} s; device step times "
        f"(s, host clock around block_until_ready, this one run): "
        f"{[round(t, 6) for t in times]}; losses {losses}")
    return losses, snaps, step


def check_match(ref, test, what: str) -> None:
    """Per-step losses and parameters of ``test`` ≡ ``ref`` at fp32
    tolerance; every loss finite."""
    (lr, sr), (lt, st) = ref, test
    assert np.isfinite(lr).all() and np.isfinite(lt).all(), (lr, lt)
    for i in range(len(lr)):
        np.testing.assert_allclose(lt[i], lr[i], atol=LOSS_TOL, rtol=LOSS_TOL,
                                   err_msg=f"{what}: loss at step {i}")
        for k in sr[i]:
            np.testing.assert_allclose(
                st[i][k], sr[i][k], atol=PARAM_TOL, rtol=PARAM_TOL,
                err_msg=f"{what}: param {k} after step {i}")
    log(f"{what}: {len(lr)} steps match (losses {LOSS_TOL}, params "
        f"{PARAM_TOL})")


def check_compiled(step, table_bytes: int) -> None:
    """The kernel runs compiled (a Mosaic custom call, not the interpreter)
    and the table is an argument of the program, not a constant in it."""
    assert "tpu_custom_call" in step.as_text(), "no FAST-GAS kernel compiled"
    mem = step.memory_analysis()
    assert mem.argument_size_in_bytes >= table_bytes, (
        mem.argument_size_in_bytes, table_bytes)
    log(f"compiled pallas step: tpu_custom_call present; arguments "
        f"{mem.argument_size_in_bytes} B (table {table_bytes} B), temp "
        f"{mem.temp_size_in_bytes} B")


def serve_requests(g, n_requests: int):
    """``n_requests`` zipf-skewed seed sets, as ``launch/serve.py --workload
    graph`` generates them."""
    rng = np.random.default_rng(0)
    V = g.n_vertices
    p = np.empty(V)
    p[rng.permutation(V)] = 1.0 / (np.arange(V) + 1.0)
    p /= p.sum()
    return [rng.choice(V, int(rng.integers(1, 4)), p=p)
            for _ in range(n_requests)]


def serve(g, requests, fanout: int) -> None:
    """The same requests through a pallas and an xla engine on the same
    table; every request's rows must agree."""
    indptr, indices, _ = g.to_csr()
    results = {}
    for impl in ("pallas", "xla"):
        eng = ServingEngine(g.features, indptr, indices, fanout=fanout,
                            impl=impl, max_batch=8, max_delay_s=1e9,
                            cache_capacity=32)
        t0 = time.perf_counter()
        rids, served = [], 0
        for i, seeds in enumerate(requests):
            rids.append(eng.submit(seeds, tenant=i % 4))
            served += eng.poll()       # dispatches when a batch is full
        served += eng.flush()
        dt = time.perf_counter() - t0
        assert served == len(requests), (served, len(requests))
        results[impl] = [eng.result(r) for r in rids]
        log(f"serve[{impl}] {served} requests in "
            f"{eng.stats['command_blocks']} command blocks, {dt:.3f} s "
            f"(host clock, this one run, compiles included)")
    for rp, rx in zip(results["pallas"], results["xla"]):
        np.testing.assert_array_equal(rp.self_rows, rx.self_rows)
        np.testing.assert_allclose(rp.agg_rows, rx.agg_rows,
                                   atol=PARAM_TOL, rtol=PARAM_TOL)
        assert np.isfinite(rp.agg_rows).all()
    log(f"serve: pallas == xla on all {len(requests)} requests")


def peak_bytes(devices) -> list:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def bytes_in_use(devices) -> list:
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def one_chip(scale: int = SCALE, steps: int = STEPS,
             seeds_per_shard: int = SEEDS_PER_SHARD,
             n_requests: int = SERVE_REQUESTS) -> None:
    cfg_p, cfg_x = PALLAS_CONFIG, CONFIG
    t0 = time.perf_counter()
    g, labels = make_graph(scale, cfg_p.n_features, cfg_p.n_classes)
    mesh = make_data_mesh(1)
    feats = place_table(g, mesh)
    batches = make_batches(g, labels, mesh, steps, seeds_per_shard,
                           cfg_p.fanout)
    log(f"graph: {g.n_vertices} vertices, {g.n_edges} edges, table "
        f"{feats.shape} {feats.dtype} ({feats.nbytes} B) on the chip; "
        f"set-up {time.perf_counter() - t0:.3f} s")

    ref = train(cfg_x, mesh, feats, batches, "xla")
    lp, sp, step = train(cfg_p, mesh, feats, batches, "pallas")
    check_compiled(step, feats.nbytes)
    check_match(ref[:2], (lp, sp), "pallas vs xla")
    serve(g, serve_requests(g, n_requests), cfg_p.fanout)
    log(f"peak device memory (B, this one run): "
        f"{peak_bytes(jax.devices()[:1])}")


def four_chips(scale: int = SCALE, steps: int = STEPS,
               seeds_per_shard: int = SEEDS_PER_SHARD) -> None:
    cfg_p = PALLAS_CONFIG                                  # cgtrans + pallas
    cfg_b = dataclasses.replace(CONFIG, dataflow="baseline")   # + xla
    g, labels = make_graph(scale, cfg_p.n_features, cfg_p.n_classes)
    mesh = make_data_mesh(4)
    devices = list(mesh.devices.flat)
    before = bytes_in_use(devices)
    feats = place_table(g, mesh)
    jax.block_until_ready(feats)
    held = [a - b for a, b in zip(bytes_in_use(devices), before)]
    shard_bytes = feats.nbytes // 4
    log(f"table {feats.shape} ({feats.nbytes} B) over {len(devices)} "
        f"devices; bytes each device took on: {held}")
    owners = {s.device for s in feats.addressable_shards}
    assert owners == set(devices), (owners, devices)
    assert all(s.data.shape[0] == 1 for s in feats.addressable_shards)
    # each device holds one shard — and not the whole table
    assert all(shard_bytes <= h < 2 * shard_bytes for h in held), (
        held, shard_bytes)

    batches = make_batches(g, labels, mesh, steps, seeds_per_shard,
                           cfg_p.fanout)
    ref = train(cfg_b, mesh, feats, batches, "baseline+xla")
    lp, sp, step = train(cfg_p, mesh, feats, batches, "cgtrans+pallas")
    check_compiled(step, shard_bytes)
    check_match(ref[:2], (lp, sp), "cgtrans+pallas vs baseline+xla")
    log(f"peak device memory (B, this one run): {peak_bytes(devices)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded cgtrans path and its "
                         "baseline comparison")
    args = ap.parse_args(argv)

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's backend here is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    enable_compile_cache()
    # the checks compare two fp32 programs at fp32 tolerance; at default
    # precision a TPU f32 matmul rounds its inputs to bf16
    jax.config.update("jax_default_matmul_precision", "highest")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    if args.chips == 4:
        if len(devs) < 4:
            print(f"chip_smoke --chips 4: only {len(devs)} devices",
                  file=sys.stderr)
            return 1
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
