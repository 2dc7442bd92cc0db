"""Benchmark driver — one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]``

Prints ``name,us_per_call,derived`` CSV rows (derived = the figure's headline
number). Wall-times are CPU-host times for the jitted artifact (the TPU
numbers are the §Roofline terms from the dry-run); derived columns are the
paper-claim reproductions.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def _timeit(fn, *args, n=3, warmup=1):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6, out


def bench_fig14_area(fast=False):
    """Fig 14: area to sustain equal aggregation throughput."""
    from repro.core import cost_model as cm
    a = cm.fig14_area()
    print(f"fig14_area_gas,0.0,{a['gas_mm2']:.2f}mm2")
    print(f"fig14_area_insider,0.0,{a['insider_mm2']:.2f}mm2")
    print(f"fig14_area_digital,0.0,{a['digital_mm2']:.2f}mm2")
    print(f"fig14_area_eff_vs_insider,0.0,{a['area_eff_vs_insider']:.1f}x")


def bench_fig15_cgtrans(fast=False):
    """Fig 15: per-dataset latency of GCNAX vs CGTrans(Insider) vs GRAPHIC."""
    from repro.core import cost_model as cm
    rows = cm.fig15_table()
    for r in rows:
        print(f"fig15_{r['dataset']},0.0,load_red={r['load_reduction']:.0f}x;"
              f"vs_gcnax={r['speedup_vs_gcnax']:.2f}x;"
              f"vs_insider={r['speedup_vs_insider']:.2f}x")
    print(f"fig15_avg,0.0,load_red={np.mean([r['load_reduction'] for r in rows]):.0f}x;"
          f"vs_gcnax={np.mean([r['speedup_vs_gcnax'] for r in rows]):.2f}x;"
          f"vs_insider={np.mean([r['speedup_vs_insider'] for r in rows]):.2f}x")


def _bfs_levels(indptr, indices, n, src=0):
    lev = np.full(n, -1, np.int64)
    lev[src] = 0
    frontier = [src]
    d = 0
    while frontier:
        nxt = []
        for v in frontier:
            for u in indices[indptr[v]:indptr[v + 1]]:
                if lev[u] < 0:
                    lev[u] = d + 1
                    nxt.append(u)
        frontier = nxt
        d += 1
    return lev


def bench_fig16a_algorithms(fast=False):
    """Fig 16(a): FE/BFS/SSSP/CC on the GAS engine — measured wall time of the
    jitted algorithm + trace-model speedups (idle-skip vs typical cache)."""
    import jax.numpy as jnp
    from repro.core import algorithms as alg
    from repro.core import cost_model as cm
    from repro.graph import rmat

    scale = 10 if fast else 12
    g = rmat(scale, 16, seed=3, weights=True)
    indptr, indices, _ = g.to_csr()
    lev = _bfs_levels(indptr, indices, g.n_vertices)
    sim = cm.simulate_gas_traversal(indptr, lev, cache_mb=1.0)

    src = jnp.asarray(g.src)
    dst = jnp.asarray(g.dst)
    w = jnp.asarray(g.weights)
    feats = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((g.n_vertices, 32)).astype(np.float32))

    us, _ = _timeit(lambda: alg.feature_embedding(src, dst, w, feats), n=3)
    print(f"fig16a_feature_embedding,{us:.0f},edges={g.n_edges}")
    us, _ = _timeit(lambda: alg.bfs(src, dst, g.n_vertices, 0, max_iters=64), n=3)
    print(f"fig16a_bfs,{us:.0f},idle_skip={sim['speedup_idle_skip']:.1f}x;"
          f"no_skip={sim['speedup_no_skip']:.2f}x")
    us, _ = _timeit(lambda: alg.sssp(src, dst, w, g.n_vertices, 0, max_iters=64), n=3)
    print(f"fig16a_sssp,{us:.0f},")
    us, _ = _timeit(lambda: alg.connected_components(src, dst, g.n_vertices,
                                                     max_iters=64), n=3)
    print(f"fig16a_cc,{us:.0f},")


def bench_fig16b_scale(fast=False):
    """Fig 16(b): BFS on G500 scales × GAS cache sizes."""
    from repro.core import cost_model as cm
    from repro.graph import rmat

    scales = (10, 12) if fast else (12, 14, 16)
    for scale in scales:
        g = rmat(scale, 16, seed=3)
        indptr, indices, _ = g.to_csr()
        lev = _bfs_levels(indptr, indices, g.n_vertices)
        for mb in (0.5, 1.0, 2.0, 4.0):
            r = cm.simulate_gas_traversal(indptr, lev, cache_mb=mb)
            print(f"fig16b_s{scale}_c{mb},0.0,"
                  f"idle_skip={r['speedup_idle_skip']:.2f}x;passes={r['passes']:.1f}")


def bench_fig16c_breakdown(fast=False):
    """Fig 16(c): Reddit GCN end-to-end latency breakdown."""
    from repro.core import cost_model as cm
    bd = cm.fig16c_breakdown()
    for sysname, d in bd.items():
        parts = ";".join(f"{k}={v * 1e3:.2f}ms" for k, v in d.items() if k != "total")
        print(f"fig16c_{sysname},0.0,total={d['total'] * 1e3:.2f}ms;{parts}")
    cut = 1 - bd["graphic"]["total"] / bd["gcnax"]["total"]
    print(f"fig16c_latency_cut,0.0,{cut * 100:.1f}%")


def bench_collective_bytes(fast=False):
    """The mechanism on real lowered HLO, folded in from
    benchmarks/collective_bytes.py (run on 8 fake devices in a subprocess to
    keep this process single-device; it writes BENCH_collective_bytes.json).
    Emits one CSV row per sampled byte-ratio point — including the paper's
    K≈50 operating point of the ≈50× claim — plus the per-shard
    aggregation-time and full train-step-time columns: the FAST-GAS pallas
    kernel vs the XLA oracle inside the sharded cgtrans dataflow, forward
    (agg_time) and forward+backward+AdamW (train_step, the differentiable
    pallas path)."""
    import json
    import os
    import subprocess
    import tempfile
    here = os.path.dirname(__file__)
    # fast mode skips the K/F sweeps — keep the committed full-sweep
    # trajectory artifact intact and write the reduced set to a temp path
    # (per-invocation, so concurrent users on one host don't collide)
    if fast:
        fd, out_path = tempfile.mkstemp(prefix="BENCH_collective_bytes.",
                                        suffix=".json")
        os.close(fd)
    else:
        out_path = os.path.join(here, "..", "BENCH_collective_bytes.json")
    cmd = [sys.executable, os.path.join(here, "collective_bytes.py"),
           "--out", out_path] + (["--fast"] if fast else [])
    # the child counts HLO bytes on 8 virtual CPU devices; it never needs
    # (and must not claim) the accelerator this process may already hold
    proc = subprocess.run(
        cmd, capture_output=True, text=True,
        env={**os.environ,
             "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "PYTHONPATH": os.path.join(here, "..", "src")})
    try:
        if proc.returncode != 0 or not os.path.exists(out_path):
            tail = (proc.stderr.strip().splitlines() or ["?"])[-1]
            raise RuntimeError(f"collective_bytes.py exit="
                               f"{proc.returncode}: {tail}")
        with open(out_path) as f:
            data = json.load(f)
    finally:
        if fast and os.path.exists(out_path):
            os.unlink(out_path)
    for r in data["rows"]:
        if r["mode"] == "sampled" and r["ways"] == 8:
            tag = "paper_fig_" if r.get("paper_figure") else ""
            print(f"collective_bytes_{tag}K{r['K']}_F{r['F']},0.0,"
                  f"ratio={r['ratio']:.1f}x;baseline={r['baseline']:.0f}B;"
                  f"cgtrans={r['cgtrans']:.0f}B")
        elif r["mode"] == "agg_time":
            tag = "_sched" if r.get("scheduled") else ""
            print(f"agg_time_{r['impl']}{tag},{r['us']:.0f},"
                  f"per_shard_us={r['us_per_shard']:.0f};ways={r['ways']}")
        elif r["mode"] == "skip_rate":
            tag = "sched" if r["scheduled"] else "unsched"
            print(f"skip_rate_{r['graph']}_{tag},0.0,"
                  f"live={r['live_rounds']}/{r['total_rounds']};"
                  f"skip_rate={r['skip_rate']:.2f}")
        elif r["mode"] == "partition":
            print(f"partition_{r['method']},0.0,"
                  f"remote_rows={r['remote_rows']}"
                  f"(max{r['remote_rows_max_shard']});"
                  f"dense_live={r['live_rounds']}/{r['total_rounds']};"
                  f"vs_interval={r['remote_rows_vs_interval']:.2f}")
        elif r["mode"] == "train_step_time":
            tag = "_sched" if r.get("scheduled") else ""
            print(f"train_step_{r['impl']}{tag},{r['us']:.0f},"
                  f"loss={r['loss']:.3f};ways={r['ways']}")
        elif r["mode"] == "coalesce":
            print(f"coalesce_{r['flow']}_{r['form']},0.0,"
                  f"all_gather={r['all_gather']};all_to_all={r['all_to_all']};"
                  f"finds={r['finds']};bytes={r['bytes']:.0f}")
        elif r["mode"] == "coalesce_grad":
            print(f"coalesce_grad_{r['form']},0.0,"
                  f"finds={r['finds']};kernel_scatters={r['kernel_scatters']}")
        elif r["mode"] == "serving":
            print(f"serving_{r['form']},0.0,"
                  f"N={r['N']};blocks={r['command_blocks']};"
                  f"finds_per_query={r['finds_per_query']:.3f};"
                  f"collectives_per_query={r['collectives_per_query']:.3f};"
                  f"bitexact={r['bitexact_vs_naive']}")
        elif r["mode"] == "serving_cache":
            print(f"serving_cache,0.0,"
                  f"hits={r['hits']}/{r['hits'] + r['misses']};"
                  f"hit_rate={r['hit_rate']:.2f};"
                  f"finds_per_query={r['finds_per_query']:.3f}")
    s = data["summary"]
    print(f"collective_bytes_summary,0.0,"
          f"{s['checked'] - s['failed']}/{s['checked']}_rows_pass;"
          f"paper_fig_ratio={s.get('paper_figure_ratio', 0.0):.1f}x;"
          f"agg_sched_vs_xla={s.get('agg_pallas_sched_vs_xla', 0.0):.2f};"
          f"coalesce_collectives="
          f"{s.get('coalesce_collectives_separate', '?')}to"
          f"{s.get('coalesce_collectives_coalesced', '?')};"
          f"serving_finds_per_query="
          f"{s.get('serving_finds_per_query', {}).get('fused', '?')};"
          f"serving_cache_hit_rate="
          f"{s.get('serving_cache_hit_rate', '?')};"
          f"partition_remote_rows="
          f"{s.get('partition_remote_rows', {}).get('interval', '?')}to"
          f"{s.get('partition_remote_rows', {}).get('island', '?')}")


def bench_kernels(fast=False):
    """Pallas kernels (interpret mode, correctness-path timing) vs jnp refs."""
    import jax.numpy as jnp
    from repro.kernels.gas_scatter import gas_scatter, gas_scatter_ref
    from repro.kernels.flash_attention import flash_attention, flash_attention_ref

    rng = np.random.default_rng(0)
    E, F, R = (2048, 64, 512) if fast else (8192, 128, 1024)
    dst = jnp.asarray(rng.integers(0, R, E).astype(np.int32))
    val = jnp.asarray(rng.standard_normal((E, F)).astype(np.float32))
    us_k, _ = _timeit(lambda: gas_scatter(dst, val, R), n=2)
    us_r, _ = _timeit(lambda: gas_scatter_ref(dst, val, R), n=2)
    print(f"kernel_gas_scatter_interpret,{us_k:.0f},ref_us={us_r:.0f}")

    B, S, H, hd = 1, 256, 4, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)).astype(np.float32))
    us_k, _ = _timeit(lambda: flash_attention(q, k, v, causal=True), n=2)
    us_r, _ = _timeit(lambda: flash_attention_ref(q, k, v, causal=True), n=2)
    print(f"kernel_flash_attention_interpret,{us_k:.0f},ref_us={us_r:.0f}")


def bench_sage_step(fast=False):
    """Wall time of one jitted GraphSAGE+CGTrans train step (CPU host)."""
    import jax
    import jax.numpy as jnp
    from repro.common.config import TrainConfig
    from repro.common.schema import init_params
    from repro.core.gcn import GCNConfig, gcn_schema, sage_loss
    from repro.data import GraphBatchStream, synthetic_node_labels
    from repro.graph import partition_by_src, uniform_graph
    from repro.optim import adamw_init, adamw_update

    g = uniform_graph(1024, 16384, seed=0, n_features=32)
    labels = synthetic_node_labels(g.features, 8)
    pg = partition_by_src(g, 4)
    feats = jnp.asarray(pg.features)
    cfg = GCNConfig(n_features=32, hidden=64, n_classes=8, fanout=10)
    tc = TrainConfig(learning_rate=1e-3)
    params = init_params(gcn_schema(cfg), jax.random.PRNGKey(0))
    opt = adamw_init(params, tc)
    stream = GraphBatchStream(g, labels, n_parts=4, batch_per_part=32, k1=10, k2=10)
    batch = {k: jnp.asarray(v) for k, v in stream.batch_at(0).items()}

    @jax.jit
    def step(params, opt, batch):
        (_, m), grads = jax.value_and_grad(
            lambda p: sage_loss(p, feats, batch, cfg), has_aux=True)(params)
        params, opt, _ = adamw_update(params, grads, opt, tc)
        return params, opt, m

    us, (_, _, m) = _timeit(lambda: step(params, opt, batch), n=3)
    print(f"sage_train_step,{us:.0f},loss={float(m['loss']):.3f}")


BENCHES = {
    "fig14_area": bench_fig14_area,
    "fig15_cgtrans": bench_fig15_cgtrans,
    "fig16a_algorithms": bench_fig16a_algorithms,
    "fig16b_scale": bench_fig16b_scale,
    "fig16c_breakdown": bench_fig16c_breakdown,
    "collective_bytes": bench_collective_bytes,
    "kernels": bench_kernels,
    "sage_step": bench_sage_step,
}


def main() -> int:
    """Runs every bench (or ``--only`` one); exit 1 if any of them raised,
    after the rest have run and each failure has printed its ERROR row."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failed = []
    for name, fn in BENCHES.items():
        if args.only and args.only != name:
            continue
        try:
            fn(fast=args.fast)
        except Exception as e:  # noqa: BLE001 — reported, and fails the run
            print(f"{name},ERROR,{type(e).__name__}:{e}")
            failed.append(name)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
