"""Collective-bytes benchmark — the paper's CGTrans mechanism, measured.

Lowers BOTH dataflows of ``repro.core.cgtrans`` (full-graph edge COO and
sampled GraphSAGE) on 1/2/4/8-way data meshes, extracts the interconnect
bytes from the compiled HLO via ``repro.launch.hlo_analysis``, sweeps the
sampling fan-out K and feature width F, and writes the trajectory to
``BENCH_collective_bytes.json``.

The headline: baseline (GCNAX-style raw transmission) ships O(B·K·F) bytes,
CGTrans ships O(B·F) — the ratio tracks the fan-out K, reproducing the
paper's fan-in compression at the paper's own operating point (K≈50, the
``paper_figure`` row, asserted ≥ 30×).

Measurements per run:

* byte rows — compile-time only (HLO diffing), seconds on the 8-way
  fake-device CPU topology. The 1-way points are skipped: a single shard
  has zero collective bytes by construction, so their ``ratio=0`` rows were
  degenerate noise in the JSON.
* ``agg_time`` rows — the per-shard aggregation wall time of the sharded
  cgtrans dataflow: ``impl="xla"`` vs ``impl="pallas"`` unscheduled vs
  ``impl="pallas"`` with the destination-binned edge schedule
  (``build_edge_schedule`` hoisted, the multi-layer deployment — the
  counting sort is paid once per (partition, batch), which is what
  ``gcn_forward_full`` does). Timings are interleaved best-of-N: this box
  shares 2 cores across 8 fake devices and run-to-run noise exceeds the
  effect, so the minimum is the only stable estimator.
* ``sched_build`` row — the one-time cost of building that schedule.
* ``skip_rate`` rows — the idle-skip mechanism, counted not timed: live vs
  total (row-block × edge-tile) rounds on a clustered graph, scheduled
  (banded walk) vs unscheduled (dense occupancy). Paper Fig 11(c).
* ``train_step_time`` rows — one full jitted GraphSAGE **train step**
  (forward + backward + AdamW) on the 8-way mesh, ``impl="xla"`` vs
  ``impl="pallas"`` scheduled/unscheduled — the kernel carries custom VJPs,
  so the backward runs through FAST-GAS too.
* ``partition`` rows — islandized locality partitioning, counted: the same
  scrambled-id clustered graph split by the plain interval cut vs
  ``partition_graph(method="island")`` (``repro.graph.islandize``), with the
  remote all_to_all destination rows (``remote_destination_rows``, summed
  and worst-shard) and the dense (row-block × edge-tile) live rounds per
  layout. Asserted by the exit code via ``check_partition_rows``: the
  islandized layout must STRICTLY beat the interval cut on both counters,
  with total rounds unchanged (the relabeling is a pure permutation).
* ``coalesce``/``coalesce_grad`` rows — request coalescing, counted: the
  sage-shaped two-stream fetch (self-row lookup + 2-hop block) issued as
  ONE ``aggregate_multi`` command block vs two ``aggregate_sampled`` calls.
  Collectives-per-step (jaxpr-level all_gather/all_to_all counts,
  deterministic) go 2 → 1 on cgtrans and halve on baseline; kernel gathers
  go 2 → 1; pallas fwd+bwd kernel scatters go 3 → 2 (one backward cotangent
  scatter instead of two). Asserted by the exit code via
  ``check_coalesce_rows``.
* ``wire`` rows — the compressed wire format (``repro.core.wire``): the
  same cgtrans sampled dataflow lowered under ``wire="f32"/"bf16"/"int8"``
  at the paper's K=50, with per-collective bytes split out of the compiled
  HLO. The all_gather ships int16 delta-encoded ids (2×), the all_to_all
  ships bf16 (2×) or int8+bitcast scales (≈3.9×) partials. Asserted by the
  exit code via ``check_wire_rows``: per-collective floors at F=128 (the
  id stream's int16 floor caps the combined int8 total there — recorded,
  not hidden), total floors ≥1.9× (bf16) / ≥3.5× (int8) at F=512, and
  collective COUNTS identical to the f32 wire in every row.
* ``serving``/``serving_cache`` rows — the online serving engine, counted:
  a queue of N concurrent single-seed callers drains as ONE fused command
  block (finds-per-query 1/N, mesh collectives-per-query 2/N, bit-exact
  with the one-query-one-dispatch baseline) and the hot-vertex cache hit
  rate on a deterministic hot-set replay. Asserted by the exit code via
  ``check_serving_rows`` against the ``SERVE_FETCH_*`` contract tables.

Interpret-mode caveat: off-TPU the kernel runs in the Pallas interpreter,
which pays a fixed emulation cost per grid round and per dispatch; treat
absolute pallas-vs-xla times as a correctness-path comparison biased
AGAINST the kernel (native XLA scatters pay none of that), and read the
``skip_rate`` rows for the mechanism the schedule buys on hardware.

``benchmarks/run.py`` runs this script and folds the rows into its CSV.

Run:  PYTHONPATH=src python benchmarks/collective_bytes.py [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import cgtrans  # noqa: E402
from repro.core import sparse as sparsefmt  # noqa: E402
from repro.graph import partition_by_src, uniform_graph  # noqa: E402
from repro.launch import hlo_analysis as H  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402

FLOWS = ("baseline", "cgtrans")
PAPER_K = 50          # paper §4.2: GraphSAGE samples 50 neighbors
PAPER_MIN_RATIO = 30  # the ≈50× claim, with slack for collective overheads


def _collective_bytes(fn, *args) -> float:
    comp = jax.jit(fn).lower(*args).compile()
    return H.analyze(comp.as_text()).collective_bytes


def bench_sampled(ways: int, K: int, F: int, B_loc: int = 32,
                  part: int = 64) -> dict:
    """Sampled GraphSAGE aggregation: B_loc seeds/shard, fan-out K, width F."""
    mesh = make_data_mesh(ways) if ways > 1 else None
    feats = jnp.zeros((max(ways, 1), part, F))
    nbrs = jnp.zeros((max(ways, 1), B_loc, K), jnp.int32)
    mask = jnp.ones((max(ways, 1), B_loc, K), bool)
    row = {"mode": "sampled", "ways": ways, "K": K, "F": F,
           "B_loc": B_loc, "part": part}
    for flow in FLOWS:
        row[flow] = _collective_bytes(
            lambda f, n, m, fl=flow: cgtrans.aggregate_sampled(
                f, n, m, mesh=mesh, dataflow=fl), feats, nbrs, mask)
    row["ratio"] = row["baseline"] / row["cgtrans"] if row["cgtrans"] else 0.0
    return row


def bench_full_graph(ways: int, F: int, V: int = 256, E: int = 4096) -> dict:
    """Full-graph edge COO aggregation on a partitioned uniform graph."""
    mesh = make_data_mesh(ways) if ways > 1 else None
    g = uniform_graph(V, E, seed=1, n_features=F, weights=True)
    pg = partition_by_src(g, max(ways, 1))
    args = (jnp.asarray(pg.features), jnp.asarray(pg.src), jnp.asarray(pg.dst),
            jnp.asarray(pg.weights), jnp.asarray(pg.mask))
    row = {"mode": "full", "ways": ways, "V": V, "E": E, "F": F,
           "avg_fanin": E / V}
    for flow in FLOWS:
        row[flow] = _collective_bytes(
            lambda *a, fl=flow: cgtrans.aggregate_edges(
                *a, mesh=mesh, dataflow=fl), *args)
    row["ratio"] = row["baseline"] / row["cgtrans"] if row["cgtrans"] else 0.0
    return row


def _interleaved_min_us(fns: dict, run_one, trials: int = 9,
                        reps: int = 3) -> dict:
    """Best-of-N wall time per labelled fn, trials interleaved so machine
    drift (this box: 2 cores under 8 fake devices) hits every candidate
    equally. Returns label → best mean-of-reps in µs."""
    best = {k: float("inf") for k in fns}
    for _ in range(trials):
        for k, fn in fns.items():
            t0 = time.perf_counter()
            for _ in range(reps):
                run_one(fn)
            best[k] = min(best[k], (time.perf_counter() - t0) / reps * 1e6)
    return best


def bench_agg_time(ways: int = 8, V: int = 256, E: int = 4096,
                   F: int = 16) -> list:
    """Per-shard aggregation wall time of the sharded cgtrans dataflow:
    impl="xla" vs impl="pallas" unscheduled vs scheduled (hoisted
    destination-binned schedule — the multi-layer deployment). Actually
    executed, not just lowered; interleaved best-of-N timing."""
    mesh = make_data_mesh(ways)
    g = uniform_graph(V, E, seed=1, n_features=F, weights=True)
    pg = partition_by_src(g, ways)
    args = (jnp.asarray(pg.features), jnp.asarray(pg.src), jnp.asarray(pg.dst),
            jnp.asarray(pg.weights), jnp.asarray(pg.mask))

    build = jax.jit(lambda d, m: cgtrans.build_edge_schedule(
        d, m, V, mesh=mesh))
    sched = jax.block_until_ready(build(args[2], args[4]))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(build(args[2], args[4]))
    sched_us = (time.perf_counter() - t0) / 5 * 1e6
    # the schedule is paid once per (partition, batch): the edge list is
    # restructured here (SGCN-style) and every timed call consumes it
    s_args = (args[0],) + cgtrans.apply_edge_schedule(sched, *args[1:])

    f_xla = jax.jit(lambda *a: cgtrans.aggregate_edges(
        *a, mesh=mesh, dataflow="cgtrans", impl="xla"))
    f_uns = jax.jit(lambda *a: cgtrans.aggregate_edges(
        *a, mesh=mesh, dataflow="cgtrans", impl="pallas", scheduled=False))
    f_sch = jax.jit(lambda *a: cgtrans.aggregate_edges(
        *a, mesh=mesh, dataflow="cgtrans", impl="pallas",
        schedule=sched, schedule_applied=True))
    fns = {
        ("xla", False): lambda: jax.block_until_ready(f_xla(*args)),
        ("pallas", False): lambda: jax.block_until_ready(f_uns(*args)),
        ("pallas", True): lambda: jax.block_until_ready(f_sch(*s_args)),
    }
    for fn in fns.values():
        fn()                                         # compile + warm
    best = _interleaved_min_us(fns, lambda fn: fn())
    rows = [{"mode": "agg_time", "ways": ways, "V": V, "E": E, "F": F,
             "impl": impl, "scheduled": scheduled, "us": us,
             "us_per_shard": us / ways}
            for (impl, scheduled), us in best.items()]
    rows.append({"mode": "sched_build", "ways": ways, "V": V, "E": E,
                 "us": sched_us})
    return rows


def bench_skip_rate(ways: int = 8, V: int = 1024, E: int = 16384) -> list:
    """The idle-skip mechanism, counted: live vs total (row-block ×
    edge-tile) rounds per shard on a CLUSTERED graph (paper Fig 11(c)'s
    favorable case), scheduled (banded walk) vs unscheduled (dense
    occupancy bitmap). Uniform graphs are the adversary — shown alongside."""
    from repro.graph import clustered_graph
    from repro.kernels.gas_scatter import kernel as K
    from repro.kernels.gas_scatter import (dense_skip_stats, schedule_edges,
                                           schedule_skip_stats)

    rows = []
    for graph_kind, g in (
            ("clustered", clustered_graph(V, E, n_clusters=V // K.ROW_BLOCK,
                                          p_intra=0.9, seed=3)),
            ("uniform", uniform_graph(V, E, seed=3))):
        pg = partition_by_src(g, ways)
        live_s = total_s = live_u = total_u = 0
        for p in range(ways):
            dst = jnp.asarray(pg.dst[p])
            mask = jnp.asarray(pg.mask[p])
            ls, ts = schedule_skip_stats(schedule_edges(dst, mask, V))
            live_s += ls
            total_s += ts
            lu, tu = dense_skip_stats(dst, mask, V)
            live_u += lu
            total_u += tu
        for scheduled, live, total in ((True, live_s, total_s),
                                       (False, live_u, total_u)):
            rows.append({
                "mode": "skip_rate", "ways": ways, "V": V, "E": E,
                "graph": graph_kind, "scheduled": scheduled,
                "live_rounds": live, "total_rounds": total,
                "skipped_rounds": total - live,
                "skip_rate": 1.0 - live / total,
            })
    return rows


def bench_partition(ways: int = 8, V: int = 1024, E: int = 8192,
                    n_clusters: int = 8, p_intra: float = 0.95) -> list:
    """Islandized locality partitioning, counted: the same scrambled-id
    clustered graph split two ways — the plain contiguous-id interval cut vs
    ``partition_graph(method="island")`` (BFS island growing + boundary
    refinement + aligned packing, host-side, once per graph). Two counters
    per layout, both deterministic:

    * ``remote_rows`` — distinct live destination rows each shard must ship
      through the all_to_all because another shard owns them (summed, plus
      the max shard for the tail), from ``remote_destination_rows``;
    * ``live_rounds`` — dense (row-block × edge-tile) occupancy of the raw
      per-shard edge streams (``dense_skip_stats``): the relabeling packs
      communities into contiguous row blocks, so occupancy goes near
      block-diagonal even before the destination-binned schedule runs.

    The ids are scrambled through a fixed permutation first — on id-ordered
    clusters the interval cut is already island-aligned and there is nothing
    to win; scrambled ids are the honest (and realistic) adversary.
    """
    from repro.graph import (COOGraph, clustered_graph, partition_graph,
                             remote_destination_rows)
    from repro.kernels.gas_scatter import dense_skip_stats

    g0 = clustered_graph(V, E, n_clusters=n_clusters, p_intra=p_intra, seed=3)
    perm = np.random.default_rng(1003).permutation(V).astype(np.int32)
    g = COOGraph(V, perm[g0.src], perm[g0.dst], g0.weights, None)

    rows = []
    for method in ("interval", "island"):
        pg, _ = partition_graph(g, ways, method=method)
        rr = remote_destination_rows(pg)
        live = total = 0
        for p in range(ways):
            lv, tt = dense_skip_stats(jnp.asarray(pg.dst[p]),
                                      jnp.asarray(pg.mask[p]), V)
            live += lv
            total += tt
        rows.append({
            "mode": "partition", "ways": ways, "V": V, "E": E,
            "n_clusters": n_clusters, "p_intra": p_intra, "method": method,
            "remote_rows": int(rr.sum()),
            "remote_rows_max_shard": int(rr.max()),
            "live_rounds": int(live), "total_rounds": int(total),
        })
    by = {r["method"]: r for r in rows}
    for r in rows:
        r["remote_rows_vs_interval"] = (
            r["remote_rows"] / max(by["interval"]["remote_rows"], 1))
        r["live_rounds_vs_interval"] = (
            r["live_rounds"] / max(by["interval"]["live_rounds"], 1))
    return rows


def check_partition_rows(rows) -> list:
    """The islandization mechanism, asserted deterministically: on the
    scrambled-id clustered graph the islandized layout must STRICTLY beat
    the interval cut on both counters — fewer remote destination rows
    (summed and on the worst shard) and fewer dense live rounds. Returns
    failure strings (empty = the claim holds)."""
    by = {r["method"]: r for r in rows if r["mode"] == "partition"}
    iv, isl = by["interval"], by["island"]
    failures = []
    if isl["remote_rows"] >= iv["remote_rows"]:
        failures.append(
            f"islandized remote destination rows ({isl['remote_rows']}) not "
            f"below the interval cut ({iv['remote_rows']})")
    if isl["remote_rows_max_shard"] >= iv["remote_rows_max_shard"]:
        failures.append(
            f"islandized worst-shard remote rows "
            f"({isl['remote_rows_max_shard']}) not below the interval cut "
            f"({iv['remote_rows_max_shard']})")
    if isl["live_rounds"] >= iv["live_rounds"]:
        failures.append(
            f"islandized dense live rounds ({isl['live_rounds']}) not below "
            f"the interval cut ({iv['live_rounds']})")
    if isl["total_rounds"] != iv["total_rounds"]:
        failures.append(
            f"total rounds changed under relabeling "
            f"({isl['total_rounds']} vs {iv['total_rounds']}) — the "
            f"relabeling must be a pure permutation")
    return failures


def bench_coalesce(ways: int = 8, B: int = 8, K1: int = 3, K2: int = 10,
                   F: int = 64, part: int = 32) -> list:
    """Request coalescing, measured the way it is claimed: DETERMINISTIC
    counters, not wall clock. For a sage-shaped request pair (the K=1
    self-row lookup + the fan-out-K2 2-hop block), count what the separate
    two-stream form issues vs the coalesced ``aggregate_multi`` command
    block:

    * collectives per step (jaxpr-level, immune to XLA combiner passes):
      all_gather (the request broadcast) and all_to_all (the result
      shipment) — cgtrans: 2 → 1 each;
    * GAS engine dispatches (trace-time counters): finds 2 → 1, and under
      pallas the fwd+bwd kernel scatters 3 → 2 (ONE backward cotangent
      scatter where the separate form pays two);
    * collective bytes from the compiled HLO, for the record (coalescing
      is about round-trips; bytes stay ≈ equal by construction).
    """
    from repro.core import gas
    from repro.launch.jaxpr_stats import collective_counts

    mesh = make_data_mesh(ways)
    R1 = B * (1 + K1)
    feats = jnp.zeros((ways, part, F))
    b1 = (jnp.zeros((ways, R1, 1), jnp.int32), jnp.ones((ways, R1, 1), bool))
    b2 = (jnp.zeros((ways, R1, K2), jnp.int32),
          jnp.ones((ways, R1, K2), bool))

    def sep(f, flow, impl="xla"):
        a = cgtrans.aggregate_sampled(f, *b1, mesh=mesh, dataflow=flow,
                                      impl=impl)
        b = cgtrans.aggregate_sampled(f, *b2, mesh=mesh, dataflow=flow,
                                      impl=impl)
        return a, b

    def coa(f, flow, impl="xla"):
        return cgtrans.aggregate_multi(f, (b1, b2), mesh=mesh, dataflow=flow,
                                       impl=impl)

    rows = []
    for flow in FLOWS:
        for form, fn in (("separate", sep), ("coalesced", coa)):
            with gas.count_dispatches() as disp:
                colls = collective_counts(lambda f: fn(f, flow), feats)
            rows.append({
                "mode": "coalesce", "ways": ways, "flow": flow, "form": form,
                "B": B, "K1": K1, "K2": K2, "F": F,
                "all_gather": int(colls["all_gather"]),
                "all_to_all": int(colls["all_to_all"]),
                "finds": int(disp["find"]), "reduces": int(disp["reduce"]),
                "bytes": _collective_bytes(lambda f: fn(f, flow), feats),
            })

    # the backward, counted on the pallas path: grad-of-sum traces the
    # custom VJPs, so the kernel_scatter count covers fwd + bwd dispatches
    for form, fn in (("separate", sep), ("coalesced", coa)):
        with gas.count_dispatches() as disp:
            jax.make_jaxpr(jax.grad(
                lambda f: sum(jnp.sum(o) for o in
                              fn(f, "cgtrans", "pallas"))))(feats)
        rows.append({
            "mode": "coalesce_grad", "ways": ways, "flow": "cgtrans",
            "form": form, "impl": "pallas",
            "finds": int(disp["find"]),
            "kernel_scatters": int(disp["kernel_scatter"]),
        })
    return rows


def check_coalesce_rows(rows) -> list:
    """The coalescing mechanism, asserted deterministically. Returns a list
    of failure strings (empty = the claim holds). Every expected count is
    imported from ``repro.analysis.contracts`` — the committed budget table
    the lint tier verifies against the abstract traces — so this bench, the
    coalesce test tier and the contracts can never disagree."""
    from repro.analysis.contracts import (SAGE_FETCH_COLLECTIVES,
                                          SAGE_FETCH_DISPATCH,
                                          SAGE_FETCH_KERNEL_SCATTERS_FWD_BWD)

    by = {(r["flow"], r["form"]): r for r in rows if r["mode"] == "coalesce"}
    gby = {r["form"]: r for r in rows if r["mode"] == "coalesce_grad"}
    failures = []

    for form in ("separate", "coalesced"):
        r = by[("cgtrans", form)]
        budget = SAGE_FETCH_COLLECTIVES[form]
        if not all(r[c] == n for c, n in budget.items()):
            failures.append(f"{form} cgtrans must issue exactly {budget} "
                            f"collectives per step, saw {r}")
    bs, bc = by[("baseline", "separate")], by[("baseline", "coalesced")]
    if not (bc["all_gather"] * 2 == bs["all_gather"]
            and bc["all_to_all"] * 2 == bs["all_to_all"]):
        failures.append(f"coalescing must halve baseline collectives, saw "
                        f"sep={bs} coa={bc}")
    finds = {form: SAGE_FETCH_DISPATCH[form]["find"]
             for form in ("separate", "coalesced")}
    for flow in FLOWS:
        s, c = by[(flow, "separate")], by[(flow, "coalesced")]
        if not (s["finds"] == finds["separate"]
                and c["finds"] == finds["coalesced"]):
            failures.append(f"{flow}: kernel gathers must go "
                            f"{finds['separate']} → {finds['coalesced']}, "
                            f"saw sep={s['finds']} coa={c['finds']}")
    gs, gc = gby["separate"], gby["coalesced"]
    ks = SAGE_FETCH_KERNEL_SCATTERS_FWD_BWD
    if not (gs["kernel_scatters"] == ks["separate"]
            and gc["kernel_scatters"] == ks["coalesced"]):
        failures.append(
            f"pallas fwd+bwd kernel scatters must go {ks['separate']} → "
            f"{ks['coalesced']} (one backward cotangent scatter instead of "
            f"two), saw sep={gs['kernel_scatters']} "
            f"coa={gc['kernel_scatters']}")
    return failures


def _collective_detail(fn, *args):
    """(total collective bytes, per-kind {count, bytes}) of the lowered HLO."""
    comp = jax.jit(fn).lower(*args).compile()
    s = H.analyze(comp.as_text())
    return s.collective_bytes, s.collectives


def bench_wire(ways: int = 8, B_loc: int = 32, part: int = 64) -> list:
    """The compressed wire format (``repro.core.wire``), measured at the
    paper's K=50 operating point: the SAME cgtrans dataflow lowered under
    ``wire="f32"/"bf16"/"int8"``, per-collective bytes split out of the
    compiled HLO.

    What moves: the all_gather ships int16 delta-encoded ids (2× under any
    narrow wire), the all_to_all ships bf16 (2×) or int8+scales (≈3.9×)
    partials. What the TOTAL shows depends on F — at F=128 the id stream's
    int16 floor caps the combined int8 win near 3×, so the per-collective
    ratios carry the claim there; at F=512 the payload dominates and the
    totals themselves clear 1.9×/3.5×. Both operating points are emitted so
    the JSON records the floor instead of hiding it.
    """
    mesh = make_data_mesh(ways)
    rows = []
    for K, F in ((PAPER_K, 128), (PAPER_K, 512)):
        feats = jnp.zeros((ways, part, F))
        nbrs = jnp.zeros((ways, B_loc, K), jnp.int32)
        mask = jnp.ones((ways, B_loc, K), bool)
        for w in ("f32", "bf16", "int8"):
            total, colls = _collective_detail(
                lambda f, n, m, ww=w: cgtrans.aggregate_sampled(
                    f, n, m, mesh=mesh, dataflow="cgtrans", wire=ww),
                feats, nbrs, mask)
            rows.append({
                "mode": "wire", "ways": ways, "K": K, "F": F,
                "B_loc": B_loc, "part": part, "wire": w, "bytes": total,
                "all_gather_bytes": colls["all-gather"]["bytes"],
                "all_to_all_bytes": colls["all-to-all"]["bytes"],
                "all_gather_count": colls["all-gather"]["count"],
                "all_to_all_count": colls["all-to-all"]["count"],
            })
    return rows


#: byte-ratio floors the wire rows must clear (vs the f32 wire, K=50):
#: nominal 2× (bf16/int16) and 4× (int8) minus slack for the scale columns
#: and lowering noise
WIRE_MIN_BF16 = 1.9
WIRE_MIN_INT8 = 3.5


def check_wire_rows(rows) -> list:
    """The wire-format mechanism, asserted deterministically (compiled-HLO
    bytes, never clocks). Returns failure strings (empty = the claims
    hold).

    * every narrow wire must keep the COLLECTIVE COUNTS of the f32 wire
      (compression that added a round-trip would be a regression);
    * F=128 (the paper-figure row): per-collective ratios — bf16 total
      ≥ 1.9×, int8 all_to_all ≥ 3.5×, int8 all_gather ≥ 1.9× (the id
      stream's int16 floor is declared, not asserted away);
    * F=512: the TOTALS clear the same floors — bf16 ≥ 1.9×, int8 ≥ 3.5×.
    """
    by = {(r["K"], r["F"], r["wire"]): r for r in rows
          if r["mode"] == "wire"}
    failures = []
    for (K, F) in sorted({(k, f) for k, f, _ in by}):
        f32, bf16, int8 = (by[(K, F, w)] for w in ("f32", "bf16", "int8"))
        for narrow in (bf16, int8):
            for c in ("all_gather_count", "all_to_all_count"):
                if narrow[c] != f32[c]:
                    failures.append(
                        f"wire={narrow['wire']} K={K} F={F} changed {c}: "
                        f"{f32[c]:.0f} → {narrow[c]:.0f} (bytes may shrink, "
                        f"counts must not)")
        bf16_total = f32["bytes"] / bf16["bytes"]
        int8_a2a = f32["all_to_all_bytes"] / int8["all_to_all_bytes"]
        int8_gather = f32["all_gather_bytes"] / int8["all_gather_bytes"]
        int8_total = f32["bytes"] / int8["bytes"]
        if bf16_total < WIRE_MIN_BF16:
            failures.append(f"bf16 wire K={K} F={F}: total ratio "
                            f"{bf16_total:.2f} < {WIRE_MIN_BF16}")
        if F >= 512:
            if int8_total < WIRE_MIN_INT8:
                failures.append(f"int8 wire K={K} F={F}: total ratio "
                                f"{int8_total:.2f} < {WIRE_MIN_INT8} (payload-"
                                f"dominated row must clear the full floor)")
        else:
            if int8_a2a < WIRE_MIN_INT8:
                failures.append(f"int8 wire K={K} F={F}: all_to_all ratio "
                                f"{int8_a2a:.2f} < {WIRE_MIN_INT8}")
            if int8_gather < WIRE_MIN_BF16:
                failures.append(f"int8 wire K={K} F={F}: all_gather ratio "
                                f"{int8_gather:.2f} < {WIRE_MIN_BF16} (int16 "
                                f"delta ids must halve the request bytes)")
    return failures


def bench_sparse(ways: int = 8, B_loc: int = 32, part: int = 64,
                 K: int = 10, F: int = 512) -> list:
    """Compressed-sparse features (``repro.core.sparse``): the baseline
    raw-row shipment lowered per measured density — synthetic tables at
    density 0.1 / 0.3 / 1.0, capacity MEASURED from each table
    (``table_capacity``, the entrypoints' own gate input), per-collective
    bytes split from the compiled HLO plus the analytic SSD→host bytes per
    gathered row (capacity + bitmap words vs F dense lanes — the codec is
    deterministic, so the per-row arithmetic IS the claim).

    Density 1.0 is the control: ``table_capacity`` returns F, the
    ``sparse_fits`` gate fails, and the path must ship the EXACT dense
    bytes — compression that couldn't win must cost nothing.
    """
    mesh = make_data_mesh(ways)
    rng = np.random.default_rng(0)
    rows = []
    nbrs = jnp.zeros((ways, B_loc, K), jnp.int32)
    mask = jnp.ones((ways, B_loc, K), bool)

    def lower(features, cap):
        return _collective_detail(
            lambda f, n, m: cgtrans.aggregate_sampled(
                f, n, m, mesh=mesh, dataflow="baseline", features=features,
                sparse_capacity=cap),
            jnp.zeros((ways, part, F)), nbrs, mask)

    dense_total, dense_colls = lower("dense", None)
    wpr = sparsefmt.bitmap_words(F)
    for density in (0.1, 0.3, 1.0):
        vals = np.round(rng.standard_normal((ways, part, F)) * 5.0)
        feats = np.where(rng.random(vals.shape) < density,
                         np.where(vals == 0, 1.0, vals), 0.0)
        cap = sparsefmt.table_capacity(feats)
        fits = sparsefmt.sparse_fits(cap, F)
        total, colls = lower("sparse", cap)
        ssd_dense = F * 4
        ssd_sparse = (cap + wpr) * 4 if fits else ssd_dense
        rows.append({
            "mode": "sparse", "ways": ways, "K": K, "F": F, "B_loc": B_loc,
            "part": part, "density": sparsefmt.density_stats(feats)["density"],
            "target_density": density, "capacity": cap, "fits": fits,
            "bytes": total, "dense_bytes": dense_total,
            "all_to_all_bytes": colls["all-to-all"]["bytes"],
            "dense_all_to_all_bytes": dense_colls["all-to-all"]["bytes"],
            "all_gather_count": colls["all-gather"]["count"],
            "all_to_all_count": colls["all-to-all"]["count"],
            "dense_all_gather_count": dense_colls["all-gather"]["count"],
            "dense_all_to_all_count": dense_colls["all-to-all"]["count"],
            "ssd_bytes_per_row": ssd_sparse,
            "dense_ssd_bytes_per_row": ssd_dense,
        })
    return rows


#: all_to_all byte-ratio floors the sparse rows must clear vs the dense
#: shipment: ≈3.5× nominal at density 0.1 (capacity 128 + 16 bitmap words
#: vs 512 lanes) asserted at 2×; ≈1.9× nominal at 0.3 asserted at 1.5×
SPARSE_MIN_D01 = 2.0
SPARSE_MIN_D03 = 1.5


def check_sparse_rows(rows) -> list:
    """The sparse-feature mechanism, asserted deterministically
    (compiled-HLO bytes + codec arithmetic, never clocks). Returns failure
    strings (empty = the claims hold).

    * collective COUNTS equal the dense twin's at every density;
    * density 0.1: all_to_all bytes ≥ 2× smaller AND SSD→host bytes per
      gathered row ≥ 2× smaller;
    * density 0.3: both ratios ≥ 1.5×;
    * density 1.0: the gate falls back — bytes EXACTLY the dense bytes.
    """
    failures = []
    floors = {0.1: SPARSE_MIN_D01, 0.3: SPARSE_MIN_D03}
    for r in (r for r in rows if r["mode"] == "sparse"):
        d = r["target_density"]
        for c in ("all_gather_count", "all_to_all_count"):
            if r[c] != r[f"dense_{c}"]:
                failures.append(
                    f"sparse density={d} changed {c}: {r[f'dense_{c}']:.0f} "
                    f"→ {r[c]:.0f} (bytes may shrink, counts must not)")
        if d in floors:
            a2a = r["dense_all_to_all_bytes"] / r["all_to_all_bytes"]
            ssd = r["dense_ssd_bytes_per_row"] / r["ssd_bytes_per_row"]
            if a2a < floors[d]:
                failures.append(f"sparse density={d}: all_to_all ratio "
                                f"{a2a:.2f} < {floors[d]}")
            if ssd < floors[d]:
                failures.append(f"sparse density={d}: SSD row ratio "
                                f"{ssd:.2f} < {floors[d]}")
        else:                    # density 1.0 — the gate-fallback control
            if r["fits"]:
                failures.append("sparse density=1.0 capacity cleared the "
                                "gate — table_capacity is broken")
            if r["bytes"] != r["dense_bytes"]:
                failures.append(
                    f"sparse density=1.0 gate fallback moved "
                    f"{r['bytes']:.0f}B ≠ dense {r['dense_bytes']:.0f}B — "
                    f"a losing compression must cost nothing")
    return failures


def bench_serving(ways: int = 8, V: int = 64, F: int = 16,
                  fanout: int = 10) -> list:
    """Online serving, counted the way it is claimed: a queue of N
    concurrent single-seed callers drains as ONE fused ``aggregate_multi``
    command block vs the one-query-one-dispatch baseline (same requests,
    same neighbor samples). Rows record

    * finds-per-query (``gas.count_dispatches`` on the executed drain):
      fused 1/N vs naive 1;
    * collectives-per-query (jaxpr-level all_gather/all_to_all on the
      8-way mesh trace of the exact same blocks): fused 2/N vs naive 2;
    * bit-exactness of the fused scatter-back against the baseline;
    * the hot-vertex cache hit rate on a deterministic hot-set replay
      (4 waves over the same seeds — wave 1 fills, waves 2–4 hit).

    Asserted by the exit code via ``check_serving_rows`` against the
    ``SERVE_FETCH_*`` budget tables in ``repro.analysis.contracts``.
    """
    from repro.analysis.contracts import SERVE_CONTRACT_N
    from repro.launch.jaxpr_stats import collective_counts
    from repro.serving import ServingEngine

    n = SERVE_CONTRACT_N
    g = uniform_graph(V, 6 * V, seed=5)
    indptr, indices, _ = g.to_csr()
    rng = np.random.default_rng(7)
    feats = rng.integers(-5, 6, (V, F)).astype(np.float32)
    seeds = [int(s) for s in rng.integers(0, V, n)]

    # the executed drains run un-sharded (the find counters and the
    # bit-exactness claim are mesh-independent); the collective counts come
    # from the ABSTRACT mesh trace of the identical blocks below
    rows, results = [], {}
    engines = {}
    for form, fuse in (("fused", True), ("naive_per_query", False)):
        eng = ServingEngine(feats, indptr, indices, fanout=fanout,
                            max_batch=n, fuse=fuse)
        rids = [eng.submit([s], tenant=j) for j, s in enumerate(seeds)]
        eng.flush()
        results[form] = [eng.result(r) for r in rids]
        engines[form] = eng

    mesh = make_data_mesh(ways)
    trace_eng = ServingEngine(feats, indptr, indices, fanout=fanout,
                              max_batch=n, mesh=mesh)
    for j, s in enumerate(seeds):
        trace_eng.submit([s], tenant=j)
    fn, fargs = trace_eng.fetch_callable()
    fused_colls = collective_counts(fn, *fargs)
    blocks = fargs[1]

    def naive_trace(f, blocks_):
        outs = []
        for j in range(n):
            outs.extend(cgtrans.aggregate_multi(
                f, blocks_[2 * j:2 * j + 2], mesh=mesh, dataflow="cgtrans"))
        return tuple(outs)

    naive_colls = collective_counts(naive_trace, fargs[0], blocks)

    bitexact = all(
        np.array_equal(a.self_rows, b.self_rows)
        and np.array_equal(a.agg_rows, b.agg_rows)
        for a, b in zip(results["fused"], results["naive_per_query"]))
    for form, colls in (("fused", fused_colls),
                        ("naive_per_query", naive_colls)):
        eng = engines[form]
        rows.append({
            "mode": "serving", "ways": ways, "form": form, "N": n,
            "V": V, "F": F, "fanout": fanout,
            "command_blocks": eng.stats["command_blocks"],
            "finds": eng.stats["find"],
            "finds_per_query": eng.finds_per_query(),
            "all_gather": int(colls["all_gather"]),
            "all_to_all": int(colls["all_to_all"]),
            "collectives_per_query":
                (colls["all_gather"] + colls["all_to_all"]) / n,
            "bitexact_vs_naive": bool(bitexact),
        })

    # the hot-vertex cache: 4 waves over one hot seed set — wave 1 is all
    # misses (and fills), waves 2–4 are all hits → hit_rate 0.75, counted
    hot = [int(h) for h in rng.choice(V, n, replace=False)]
    ceng = ServingEngine(feats, indptr, indices, fanout=fanout,
                         max_batch=n, cache_capacity=2 * n)
    waves = 4
    for _ in range(waves):
        for j, s in enumerate(hot):
            ceng.submit([s], tenant=j)
        ceng.flush()
    snap = ceng.cache.snapshot()
    rows.append({
        "mode": "serving_cache", "ways": 1, "N": n, "waves": waves,
        "V": V, "F": F, "capacity": ceng.cache.capacity,
        "hits": snap["hits"], "misses": snap["misses"],
        "hit_rate": snap["hit_rate"],
        "finds_per_query": ceng.finds_per_query(),
    })
    return rows


def check_serving_rows(rows) -> list:
    """The serving mechanism, asserted deterministically (counters, never
    clocks). Returns failure strings (empty = the claims hold). Budgets
    come from the ``SERVE_FETCH_*`` tables in ``repro.analysis.contracts``
    — the same single source the serve test tier and the lint contracts
    pin — so the bench can never drift from them."""
    from repro.analysis.contracts import (SERVE_CONTRACT_N,
                                          SERVE_FETCH_COLLECTIVES,
                                          SERVE_FETCH_FINDS)

    by = {r["form"]: r for r in rows if r["mode"] == "serving"}
    cache_rows = [r for r in rows if r["mode"] == "serving_cache"]
    failures = []
    f, nv = by["fused"], by["naive_per_query"]
    n = f["N"]
    if n < SERVE_CONTRACT_N:
        failures.append(f"serving rows must batch N >= {SERVE_CONTRACT_N} "
                        f"concurrent requests, saw N={n}")
    if f["command_blocks"] != 1:
        failures.append(f"a fused drain of {n} requests must dispatch ONE "
                        f"command block, saw {f['command_blocks']}")
    if f["finds"] != SERVE_FETCH_FINDS["fused"]:
        failures.append(f"fused drain must issue "
                        f"{SERVE_FETCH_FINDS['fused']} find, saw "
                        f"{f['finds']}")
    if nv["finds"] != SERVE_FETCH_FINDS["naive_per_query"] * n:
        failures.append(f"naive baseline must issue one find per query "
                        f"({n}), saw {nv['finds']}")
    for coll, want in SERVE_FETCH_COLLECTIVES["fused"].items():
        if f[coll] != want:
            failures.append(f"fused drain must trace {want} {coll}, saw "
                            f"{f[coll]}")
    for coll, per_q in SERVE_FETCH_COLLECTIVES["naive_per_query"].items():
        if nv[coll] != per_q * n:
            failures.append(f"naive baseline must trace {per_q} {coll} per "
                            f"query ({per_q * n} total), saw {nv[coll]}")
    for key in ("finds_per_query", "collectives_per_query"):
        if not f[key] < nv[key]:
            failures.append(f"fused {key} ({f[key]:.3f}) not strictly below "
                            f"the naive baseline ({nv[key]:.3f})")
    if not f["bitexact_vs_naive"]:
        failures.append("fused scatter-back diverged from the sequential "
                        "per-request baseline (must be bit-exact)")
    if not cache_rows or cache_rows[0]["hits"] <= 0:
        failures.append("hot-vertex cache replay recorded zero hits")
    return failures


def bench_train_step_time(ways: int = 8) -> list:
    """Wall time of one jitted GraphSAGE+CGTrans TRAIN step on the sharded
    mesh, impl="xla" vs impl="pallas" scheduled/unscheduled — the
    differentiable-kernel path (forward and backward through FAST-GAS),
    actually executed; interleaved best-of-N timing."""
    import jax.random
    from repro.common.config import TrainConfig
    from repro.common.schema import init_params
    from repro.core.gcn import GCNConfig, gcn_schema
    from repro.data import GraphBatchStream, synthetic_node_labels
    from repro.graph import partition_by_src, uniform_graph
    from repro.optim import adamw_init
    from repro.train import make_sage_train_step

    mesh = make_data_mesh(ways)
    g = uniform_graph(128, 1024, seed=0, n_features=8)
    labels = synthetic_node_labels(g.features, 4)
    pg = partition_by_src(g, ways)
    feats = jnp.asarray(pg.features)
    tc = TrainConfig(learning_rate=1e-3)
    stream = GraphBatchStream(g, labels, n_parts=ways, batch_per_part=4,
                              k1=4, k2=4)
    batch = {k: jnp.asarray(v) for k, v in stream.batch_at(0).items()}

    runs = {}
    for key in (("xla", False), ("pallas", True), ("pallas", False)):
        impl, scheduled = key
        cfg = GCNConfig(n_features=8, hidden=16, n_classes=4, fanout=4,
                        impl=impl, scheduled=scheduled)
        params = init_params(gcn_schema(cfg), jax.random.PRNGKey(0))
        state = {"params": params, "opt": adamw_init(params, tc),
                 "step": jnp.zeros((), jnp.int32)}
        step = jax.jit(make_sage_train_step(cfg, tc, mesh=mesh))
        state, m = step(state, batch, feats)            # compile + warm
        jax.block_until_ready(state)
        runs[key] = {"step": step, "state": state,
                     "loss": float(m["total_loss"])}

    def run_one(r):
        r["state"], _ = r["step"](r["state"], batch, feats)
        jax.block_until_ready(r["state"])

    best = _interleaved_min_us(runs, run_one, trials=7, reps=3)
    return [{"mode": "train_step_time", "ways": ways, "impl": impl,
             "scheduled": scheduled, "us": best[(impl, scheduled)],
             "loss": runs[(impl, scheduled)]["loss"]}
            for impl, scheduled in runs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_collective_bytes.json")
    ap.add_argument("--fast", action="store_true",
                    help="skip the K/F sweeps; mesh-scaling rows only")
    args = ap.parse_args(argv)

    n_dev = jax.device_count()
    if n_dev < 8:
        print(f"need 8 (fake) devices, have {n_dev} — set XLA_FLAGS="
              f"--xla_force_host_platform_device_count=8 before importing jax",
              file=sys.stderr)
        return 2

    rows = []

    def emit(row):
        rows.append(row)
        tag = f"{row['mode']}/{row['ways']}-way K={row.get('K', '-')} F={row['F']}"
        print(f"{tag:34s} baseline={row['baseline']:>12.0f}B "
              f"cgtrans={row['cgtrans']:>12.0f}B ratio={row['ratio']:.1f}")

    # mesh scaling at the reference point (K=16, F=128). The 1-way point is
    # intentionally absent: one shard moves zero collective bytes, so its
    # baseline=0/ratio=0 row carried no information (and polluted ratio
    # consumers downstream).
    for ways in (2, 4, 8):
        emit(bench_sampled(ways, K=16, F=128))
        emit(bench_full_graph(ways, F=16))

    # the paper figure: the operating point of the ≈50× claim (K≈50) —
    # always measured, even under --fast (benchmarks/run.py keys on it)
    paper_row = bench_sampled(8, K=PAPER_K, F=128)
    paper_row["paper_figure"] = f"50x_claim_at_K{PAPER_K}"
    emit(paper_row)

    if not args.fast:
        # fan-out sweep: the compression ratio should track K
        for K in (4, 16, 64):
            emit(bench_sampled(8, K=K, F=128))
        # feature-width sweep: the ratio is width-independent (both scale ∝ F)
        for F in (32, 128, 512):
            emit(bench_sampled(8, K=16, F=F))

    # per-shard aggregation time: the FAST-GAS kernel inside the sharded
    # dataflow vs the XLA oracle (executed on the 8-way fake mesh),
    # scheduled (banded walk, hoisted schedule) vs unscheduled
    agg_rows = bench_agg_time(8)
    for r in agg_rows:
        rows.append(r)
        if r["mode"] == "sched_build":
            print(f"sched_build/{r['ways']}-way "
                  f"{r['us']:>10.0f}us (once per partition+batch)")
        else:
            tag = "sched" if r["scheduled"] else "unsched"
            print(f"agg_time/{r['ways']}-way impl={r['impl']:<6s} {tag:<7s} "
                  f"{r['us']:>10.0f}us total  {r['us_per_shard']:>9.0f}us/shard")

    # the idle-skip mechanism, counted: scheduled vs dense rounds on a
    # clustered graph (the paper's Fig 11(c) case) and its uniform adversary
    for r in bench_skip_rate(8):
        rows.append(r)
        tag = "sched" if r["scheduled"] else "unsched"
        print(f"skip_rate/{r['graph']:<9s} {tag:<7s} "
              f"{r['live_rounds']:>5d}/{r['total_rounds']:<5d} rounds live  "
              f"skip_rate={r['skip_rate']:.2f}")

    # islandized locality partitioning, counted: on the scrambled-id
    # clustered graph the island relabeling must shrink both the remote
    # all_to_all destination rows and the dense round occupancy
    partition_rows = bench_partition(8)
    for r in partition_rows:
        rows.append(r)
        print(f"partition/{r['method']:<9s} "
              f"remote_rows={r['remote_rows']:>5d} "
              f"(max/shard {r['remote_rows_max_shard']:>4d})  "
              f"dense {r['live_rounds']:>5d}/{r['total_rounds']:<5d} rounds "
              f"live  vs_interval={r['remote_rows_vs_interval']:.2f}")

    # request coalescing, counted: the sage-shaped two-stream fetch as one
    # SSD command block — collectives-per-step 2 → 1 (cgtrans), finds
    # 2 → 1, pallas fwd+bwd kernel scatters 3 → 2; bytes for the record
    coalesce_rows = bench_coalesce(8)
    for r in coalesce_rows:
        rows.append(r)
        if r["mode"] == "coalesce":
            print(f"coalesce/{r['flow']:<8s} {r['form']:<9s} "
                  f"all_gather={r['all_gather']} all_to_all={r['all_to_all']} "
                  f"finds={r['finds']}  {r['bytes']:>10.0f}B")
        else:
            print(f"coalesce_grad/pallas {r['form']:<9s} "
                  f"finds={r['finds']} kernel_scatters={r['kernel_scatters']}")

    # the compressed wire: the same cgtrans dataflow lowered per wire
    # format, per-collective bytes split out — the id stream's int16 floor
    # shows at F=128, the payload-dominated totals at F=512
    wire_rows = bench_wire(8)
    for r in wire_rows:
        rows.append(r)
        print(f"wire/K={r['K']} F={r['F']:<4d} {r['wire']:<5s} "
              f"total={r['bytes']:>9.0f}B  "
              f"gather={r['all_gather_bytes']:>7.0f}B  "
              f"a2a={r['all_to_all_bytes']:>9.0f}B")

    # compressed-sparse features: the baseline raw-row shipment per
    # measured density — bytes scale with density, the density-1.0 control
    # must fall back to the exact dense bytes
    sparse_rows = bench_sparse(8)
    for r in sparse_rows:
        rows.append(r)
        print(f"sparse/d={r['target_density']:<4} cap={r['capacity']:<4d} "
              f"{'fit' if r['fits'] else 'dense'} "
              f"a2a={r['all_to_all_bytes']:>9.0f}B "
              f"(dense {r['dense_all_to_all_bytes']:>9.0f}B)  "
              f"ssd/row={r['ssd_bytes_per_row']:>5d}B "
              f"(dense {r['dense_ssd_bytes_per_row']}B)")

    # online serving, counted: N concurrent callers drain as ONE fused
    # command block — finds-per-query 1/N, collectives-per-query 2/N,
    # bit-exact with the per-request baseline; plus the hot-cache replay
    serving_rows = bench_serving(8)
    for r in serving_rows:
        rows.append(r)
        if r["mode"] == "serving":
            print(f"serving/{r['form']:<15s} N={r['N']} "
                  f"blocks={r['command_blocks']} "
                  f"finds/q={r['finds_per_query']:.3f} "
                  f"colls/q={r['collectives_per_query']:.3f} "
                  f"bitexact={r['bitexact_vs_naive']}")
        else:
            print(f"serving_cache N={r['N']}x{r['waves']}waves "
                  f"hits={r['hits']}/{r['hits'] + r['misses']} "
                  f"hit_rate={r['hit_rate']:.2f} "
                  f"finds/q={r['finds_per_query']:.3f}")

    # one full train step (fwd + bwd + AdamW): the differentiable pallas
    # path vs the xla oracle — the backward also runs through the kernel
    for r in bench_train_step_time(8):
        rows.append(r)
        tag = "sched" if r["scheduled"] else "unsched"
        print(f"train_step/{r['ways']}-way impl={r['impl']:<6s} {tag:<7s} "
              f"{r['us']:>10.0f}us/step  loss={r['loss']:.3f}")

    # the paper's claim, asserted: sampled compression ≈ fan-out (same
    # threshold as tests/distributed_cases.py::case_cgtrans_collective_bytes),
    # plus the headline ≥30× at the paper's K≈50 operating point
    checked = [r for r in rows if r["mode"] == "sampled" and r["ways"] == 8]
    failures = []            # (row, threshold-it-missed) — one entry per row
    for r in checked:
        thresh = max(r["K"] / 4,
                     PAPER_MIN_RATIO if r.get("paper_figure") else 0.0)
        if r["ratio"] <= thresh:
            failures.append((r, thresh))
    agg = {(r["impl"], r.get("scheduled")): r["us"] for r in rows
           if r["mode"] == "agg_time"}
    sk = [r for r in rows if r["mode"] == "skip_rate"
          and r["graph"] == "clustered" and r["scheduled"]]
    co = {(r["flow"], r["form"]): r for r in rows if r["mode"] == "coalesce"}
    summary = {
        "claim": "baseline/cgtrans collective bytes > K/4 on the 8-way mesh; "
                 f">= {PAPER_MIN_RATIO}x at the paper's K={PAPER_K}",
        "checked": len(checked),
        "failed": len(failures),
        "max_ratio": max((r["ratio"] for r in checked), default=0.0),
        "paper_figure_ratio": paper_row["ratio"],
        # the scheduler headline: scheduled pallas vs xla vs unscheduled
        # pallas aggregation time (interleaved best-of-N; see the module
        # docstring for the interpret-mode caveat) + clustered skip rate
        "agg_pallas_sched_vs_xla":
            agg[("pallas", True)] / agg[("xla", False)],
        "agg_sched_vs_unsched_pallas":
            agg[("pallas", True)] / agg[("pallas", False)],
        "clustered_skipped_rounds": sk[0]["skipped_rounds"] if sk else 0,
        # the partitioning headline: what the islandized relabeling removes
        # on the scrambled-id clustered graph, per counter (island/interval,
        # lower is better — asserted strict by check_partition_rows)
        "partition_remote_rows": {
            r["method"]: r["remote_rows"] for r in partition_rows},
        "partition_dense_live_rounds": {
            r["method"]: r["live_rounds"] for r in partition_rows},
        # the coalescing headline: collectives-per-step on the cgtrans
        # sampled path, separate two-stream form vs the coalesced command
        # block (each = all_gather + all_to_all counts, deterministic)
        "coalesce_collectives_separate":
            co[("cgtrans", "separate")]["all_gather"]
            + co[("cgtrans", "separate")]["all_to_all"],
        "coalesce_collectives_coalesced":
            co[("cgtrans", "coalesced")]["all_gather"]
            + co[("cgtrans", "coalesced")]["all_to_all"],
        # the serving headline: per-query amortization at N concurrent
        # callers, plus what the hot cache removes on the skewed replay
        "serving_finds_per_query": {
            r["form"]: r["finds_per_query"] for r in serving_rows
            if r["mode"] == "serving"},
        "serving_collectives_per_query": {
            r["form"]: r["collectives_per_query"] for r in serving_rows
            if r["mode"] == "serving"},
        "serving_cache_hit_rate": next(
            r["hit_rate"] for r in serving_rows
            if r["mode"] == "serving_cache"),
        # the wire headline: bytes vs the f32 wire at the paper's K=50 —
        # total ratio per format and the per-collective split at F=128
        # (where the id stream's int16 floor caps the int8 total; the
        # F=512 rows in the JSON show the payload-dominated totals)
        "wire_ratios_K50_F128": {
            w: next(r2["bytes"] for r2 in wire_rows
                    if r2["F"] == 128 and r2["wire"] == "f32")
            / next(r2["bytes"] for r2 in wire_rows
                   if r2["F"] == 128 and r2["wire"] == w)
            for w in ("bf16", "int8")},
        # the sparse-feature headline: baseline all_to_all bytes vs the
        # dense shipment per density (F=512; 1.0 is the gate-fallback
        # control and must read exactly 1.0)
        "sparse_a2a_ratios": {
            str(r2["target_density"]):
                r2["dense_all_to_all_bytes"] / r2["all_to_all_bytes"]
            for r2 in sparse_rows},
    }
    # the scheduler mechanism, asserted DETERMINISTICALLY (round counts,
    # not wall times — timing on this topology is an estimator, the counts
    # are the claim): the scheduled walk on the clustered graph must skip
    # rounds, and execute strictly fewer than the unscheduled occupancy
    # leaves live
    sk_rows = {(r["graph"], r["scheduled"]): r for r in rows
               if r["mode"] == "skip_rate"}
    cs = sk_rows[("clustered", True)]
    cu = sk_rows[("clustered", False)]
    mech_failures = []
    if cs["skipped_rounds"] <= 0:
        mech_failures.append("scheduled walk skipped zero rounds on the "
                             "clustered graph")
    if cs["live_rounds"] >= cu["live_rounds"]:
        mech_failures.append(
            f"scheduled live rounds ({cs['live_rounds']}) not below the "
            f"unscheduled occupancy ({cu['live_rounds']})")
    # the islandization mechanism, asserted the same way (counters, not
    # clocks): strictly fewer remote rows and dense live rounds than the
    # interval cut on the scrambled-id clustered graph
    mech_failures += check_partition_rows(partition_rows)
    # the coalescing mechanism, asserted the same way (counters, not clocks)
    mech_failures += check_coalesce_rows(coalesce_rows)
    # and the serving mechanism: fused command blocks + hot cache
    mech_failures += check_serving_rows(serving_rows)
    # and the wire mechanism: byte ratios per format, counts unchanged
    mech_failures += check_wire_rows(wire_rows)
    # and the sparse-feature mechanism: bytes scale with density, the
    # density-1.0 gate fallback costs exactly nothing
    mech_failures += check_sparse_rows(sparse_rows)

    out = {"jax_version": jax.__version__, "devices": n_dev,
           "rows": rows, "summary": summary}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {args.out}: {len(rows)} rows; "
          f"{summary['checked'] - summary['failed']}/{summary['checked']} "
          f"sampled rows beat their threshold "
          f"(max ratio {summary['max_ratio']:.1f}×); clustered idle-skip "
          f"{cs['skipped_rounds']}/{cs['total_rounds']} rounds skipped")
    if failures or mech_failures:
        for r, thresh in failures:
            print(f"FAIL: K={r['K']} F={r['F']} ratio={r['ratio']:.2f} "
                  f"≤ {thresh:.1f}", file=sys.stderr)
        for msg in mech_failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
