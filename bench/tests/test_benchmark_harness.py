"""CPU tests of the benchmark's harness and yardstick.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

They check the trace reduction on a recorded chip trace, the required-work
counts against hand counts, the determinism of what a seed draws, the
refusals (an unknown device kind, a CPU backend, a checkout without the
program), and that a whole run at a tiny size comes out correct, while the
control and each planted fault of the timed path come out not correct.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
from yard import graph, peaks, reference, tracing, work  # noqa: E402

TRAIN, INFER = "sage-reddit.train-b1024", "sage-reddit.infer-full"
TRAIN4 = tiny.FOUR_CHIP
RECORDED = BENCH / "testdata" / "trace_rows.json.gz"


def _cpu_env(devices: int = 1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def _last_json(stdout: str):
    return json.loads(stdout.strip().splitlines()[-1])


# -- the trace reduction ----------------------------------------------------

def test_trace_reduction_hand_counts():
    dev = "/device:TPU:0"
    rows = [
        ("/host:CPU", "t", "bench.window", 0, 100),
        ("/host:CPU", "t", "bench.sample", 10, 30),
        (dev, "XLA Ops", "while while (s32[])", 0, 100),   # a loop: busy,
        (dev, "XLA Ops", "fusion fusion f32[8]", 0, 10),  # hides nothing
        (dev, "XLA Ops",
         "gas_scatter_banded custom-call f32[8] tpu_custom_call", 40, 20),
        (dev, "XLA Ops", "all-to-all all-to-all f32[8]", 55, 15),
        (dev, "XLA Ops", "fusion fusion f32[4]", 90, 20),  # clipped at 100
    ]
    s = tracing.summarize(rows)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(100e-9)
    assert s["kernel_s"] == pytest.approx(20e-9)
    assert s["collective_exposed_s"] == pytest.approx(10e-9)
    assert [n for n, _ in s["device_ops"]][0].startswith("gas_scatter")
    s = tracing.summarize(rows[:2] + rows[3:])   # without the loop
    assert s["busy_s"] == pytest.approx((10 + 30 + 10) * 1e-9)
    assert s["idle_gaps"][0] == ["bench.sample", pytest.approx(30e-9)]


def test_op_names_from_hlo_text():
    text = ('%gas_scatter_banded.7 = f32[128,640]{1,0:T(8,128)S(1)} '
            'custom-call(s32[81]{0:T(128)S(1)} %reshape.468), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints'
            '={s32[81]{0}}')
    name = tracing.op_name(text)
    assert name == ("gas_scatter_banded custom-call "
                    "f32[128,640]{1,0:T(8,128)S(1)} tpu_custom_call")
    assert tracing.is_kernel(name) and not tracing.is_collective(name)
    a2a = tracing.op_name("%all-to-all.3 = f32[4,8]{1,0} all-to-all(f32[4,8]"
                          "{1,0} %fusion.2), replica_groups={{0,1,2,3}}")
    assert tracing.is_collective(a2a) and not tracing.is_kernel(a2a)
    loop = tracing.op_name(
        "%while.86 = (s32[]{:T(128)}, bf16[3264,16,602]{2,1,0:T(8,128)(2,1)S"
        "(1)}, f32[]{:T(128)}) while((s32[]{:T(128)}, bf16[3264,16,602]{2,1,0"
        ":T(8,128)(2,1)S(1)}, f32[]{:T(128)}) %tuple.141), condition=%c, "
        "body=%b")
    assert loop.split(" ")[:2] == ["while", "while"]
    assert tracing.is_container(loop)


def _sweep_busy(rows, lo, hi):
    """Busy time by an independent sweep over interval endpoints."""
    pts = []
    for p, l, n, s, d in rows:
        if p.startswith("/device:TPU:0"):
            s, e = max(s, lo), min(s + d, hi)
            if e > s:
                pts += [(s, 1), (e, -1)]
    busy, depth, last = 0, 0, None
    for t, k in sorted(pts):
        if depth > 0:
            busy += t - last
        depth += k
        last = t
    return busy


def test_trace_reduction_recorded_trace():
    rows = tracing.load_rows(RECORDED)
    s = tracing.summarize(rows)
    win = [(r[3], r[3] + r[4]) for r in rows if r[2] == tracing.WINDOW_SPAN]
    lo, hi = min(a for a, _ in win), max(b for _, b in win)
    assert s["busy_s"] == pytest.approx(_sweep_busy(rows, lo, hi) / 1e9)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert 0 < s["kernel_s"] <= s["busy_s"]
    assert s["device_ops"] and len(s["device_ops"]) <= 10
    assert all(g[0].startswith("bench.") or g[0] == "other"
               for g in s["idle_gaps"])


# -- required work ----------------------------------------------------------

def test_required_work_hand_counts():
    # 2 seeds, fan-out 1 then 2, F=4, H=3, C=2, 10 parameters
    w = work.sage_train_step(2, 1, 2, 4, 3, 2, 10)
    rows1 = 2 * 2
    assert w["gas_bytes"] == (rows1 * 2 * 4 + rows1 * 4) * 4
    assert w["bytes"] == w["gas_bytes"] + rows1 * 4 * 4 + 7 * 10 * 4
    fwd = 2 * 4 * 8 * 3 + 2 * 2 * 6 * 3 + 2 * 2 * 3 * 2
    bwd = 2 * 4 * 8 * 3 + 2 * (2 * 2 * 6 * 3) + 2 * (2 * 2 * 3 * 2)
    assert w["flops"] == fwd + bwd + rows1 * 2 * 4 + 2 * 1 * 3
    # 8 vertices, 16 edges, F=4, H=3, C=2
    g = work.gcn_full_pass(8, 16, 4, 3, 2)
    assert g["gas_bytes"] == ((16 * 4 + 8 * 4) + (16 * 3 + 8 * 3)) * 4
    assert g["flops"] == (2 * 16 * 4 + 2 * 8 * 8 * 3 + 2 * 16 * 3
                          + 2 * 8 * 6 * 3 + 2 * 8 * 3 * 2)
    assert g["bytes"] == (12 * 16 + g["gas_bytes"] + (8 * 4 + 8 * 3) * 4
                          + (8 * 3 + 8 * 3) * 4 + 8 * 2 * 4)
    t, bound = work.least_time(g, peaks.PEAKS["TPU v5 lite"])
    assert bound == "bytes" and t == g["bytes"] / 819e9


# -- what a seed draws ------------------------------------------------------

def test_graph_is_deterministic_and_sorted(tmp_path):
    a = graph.load_graph("g", 8, 4, 0, tmp_path)
    b = graph.load_graph("g", 8, 4, 0, tmp_path / "other")
    c = graph.load_graph("g", 8, 4, 0, tmp_path)        # from the cache
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    assert np.array_equal(a.src, c.src) and np.array_equal(a.dst, c.dst)
    key = a.src.astype(np.int64) * a.n_vertices + a.dst
    assert np.all(np.diff(key) >= 0) and a.n_edges == 4 << 8
    d = graph.rmat_edges(8, 4, 1)
    assert not np.array_equal(graph.sort_edges(*d, 256)[1], a.dst)


def _invalid_samples(batch, g, labels) -> int:
    """Entries of a minibatch that break the sampler's contract: a seed out
    of range or with the wrong label, a sample that is not a neighbour of
    its vertex, or a mask bit that is not "the vertex has a neighbour" (a
    vertex with none repeats itself, masked off)."""
    V, indptr = g.n_vertices, g.indptr()
    keys = g.src.astype(np.int64) * V + g.dst        # ascending
    seeds = batch["seeds"].astype(np.int64)
    bad = int(((seeds < 0) | (seeds >= V)).sum())
    seeds = np.clip(seeds, 0, V - 1)
    bad += int((batch["labels"] != labels[seeds]).sum())
    lay1 = np.concatenate([seeds[..., None], batch["nbrs1"]], -1)
    for own, nb, mask in ((seeds, batch["nbrs1"], batch["mask1"]),
                          (lay1, batch["nbrs2"], batch["mask2"])):
        own = np.clip(own.reshape(-1), 0, V - 1)
        nb = nb.reshape(own.shape[0], -1).astype(np.int64)
        mask = mask.reshape(nb.shape)
        has = (indptr[own + 1] - indptr[own] > 0)[:, None]
        q = own[:, None] * V + nb
        pos = np.minimum(np.searchsorted(keys, q), keys.shape[0] - 1)
        ok = np.where(has, keys[pos] == q, nb == own[:, None]) & (mask == has)
        bad += int((~ok).sum())
    return bad


def test_minibatch_seeds_and_draws_are_deterministic():
    from repro.data import GraphBatchStream
    from repro.graph import COOGraph
    from repro.launch.mesh import make_data_mesh
    from yard import draws
    g = graph.Graph(256, *graph.sort_edges(*graph.rmat_edges(8, 4, 0), 256))
    seed = 2**31 + 11
    labels = draws.make_labels(seed, 256, 5)
    assert np.array_equal(labels, draws.make_labels(seed, 256, 5))

    def batch(s, step=2):
        return draws.minibatch(int(draws.seed_words(s, 3)[2]), step,
                               g.indptr(), g.dst, labels, 2, 8, 3, 3)
    b1, b2, b3 = batch(seed), batch(seed), batch(seed + 1)
    assert all(np.array_equal(b1[k], b2[k]) for k in b1)
    assert not np.array_equal(b1["seeds"], b3["seeds"])
    assert not np.array_equal(b1["seeds"], batch(seed, 3)["seeds"])
    assert _invalid_samples(b1, g, labels) == 0
    # the benchmark's draw is the program's sampler's, entry by entry
    prog = GraphBatchStream(
        COOGraph(256, g.src, g.dst), labels, n_parts=2, batch_per_part=8,
        k1=3, k2=3, seed=int(draws.seed_words(seed, 3)[2])).batch_at(2)
    assert sorted(prog) == sorted(b1)
    assert all(np.array_equal(prog[k], b1[k]) for k in b1)
    mesh = make_data_mesh(1)
    shapes = draws.param_shapes(6, 4, 3)
    p1, p2 = (draws.make_params(seed, shapes, mesh) for _ in range(2))
    assert all(np.array_equal(p1[k], p2[k]) for k in shapes)
    t1 = draws.make_table(seed, 64, 6, mesh)
    t2 = draws.make_table(seed + 1, 64, 6, mesh)
    assert t1.shape == (1, 64, 6) and not np.array_equal(t1, t2)


def test_reference_aggregates_in_bounded_blocks():
    """Shard by shard and block by block, the reference's sampled means are
    the plain means of the rows, with -1 read as masked off."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    table = rng.standard_normal((1, 64, 5)).astype(np.float32)
    ids = rng.integers(-1, 64, (1, 10, 4))
    got = reference.sampled_means([jax.device_put(table)], ids, jnp.float32,
                                  block=3, table_block=16)
    rows = np.where((ids >= 0)[..., None], table[0][np.maximum(ids, 0)], 0)
    want = rows.sum(2) / np.maximum((ids >= 0).sum(2), 1)[..., None]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


# -- refusals ---------------------------------------------------------------

def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v4")


def test_cpu_backend_is_refused():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", TRAIN,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_cpu_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_checkout_without_the_program_is_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", TRAIN, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(_cpu_env(), PYTHONPATH=""),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


# -- whole runs at a tiny size ----------------------------------------------

@pytest.mark.parametrize("cell", [TRAIN, INFER])
def test_tiny_run_is_correct(cell):
    res = tiny.run_tiny(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"]


def test_tiny_four_chip_run_is_correct():
    p = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "tiny.py"), TRAIN4],
        env=_cpu_env(4), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = _last_json(p.stdout)
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4


@pytest.mark.parametrize("cell,fault", [
    (TRAIN, "unchanged"), (TRAIN, "half_batch"), (TRAIN, "answer_altered"),
    (TRAIN, "draw_moved"), (INFER, "answer_altered")])
def test_planted_fault_is_not_correct(cell, fault):
    res = tiny.run_tiny(cell, fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", tiny.FAULTS)
def test_four_chip_planted_fault_is_not_correct(fault):
    p = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "tiny.py"), TRAIN4, fault],
        env=_cpu_env(4), capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert not _last_json(p.stdout)["correct"]


@pytest.mark.parametrize("cell", [TRAIN, INFER])
def test_control_is_not_correct(cell):
    """The reference one precision lower (bfloat16), put in the program's
    place, reads over a limit of the cell; the program reads under all."""
    limits = json.loads((BENCH / "limits" / f"{cell}.json").read_text())
    s = tiny.calibrate_tiny(cell)
    assert all(s[k]["program_max"] <= limits[k] for k in limits)
    assert any(s[k]["control_min"] > limits[k] for k in limits
               if "control_min" in s[k])
