"""Run the harness on the CPU at a tiny size, optionally with a fault planted
in the program.

    JAX_PLATFORMS=cpu python3 bench/tests/tiny.py <cell> [fault] [--calibrate]

Used by the tests in this directory, in-process or (for the four-chip cell,
on four virtual CPU devices) as a subprocess; the last line of standard
output is the run's result object (or, with ``--calibrate``, the
calibration summary).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402

FAULTS = ("unchanged", "half_batch", "no_exchange", "answer_altered")
# The train cell's mix on four chips (the owner-sharded table and the
# cgtrans exchange), which the benchmark has no cell of yet.
FOUR_CHIP = "sage-reddit.train-b1024@4"


def overrides(cell: str):
    """The cell's own configuration, traffic and limits at a tiny size: the
    same program path, a 1-2K-vertex graph and narrow widths."""
    base = cell.split("@")[0]
    wl = dict(run.cell_spec(run.load_json(run.ROOT / "BENCHMARK.json"), base))
    if cell == FOUR_CHIP:
        wl.update(name=cell, chips=4)
    cfg = run.load_json(run.BENCH / "configs" / f"{wl['config']}.json")
    tr = run.load_json(run.BENCH / "traffic" / f"{wl['traffic']}.json")
    limits = run.load_json(run.BENCH / "limits" / f"{base}.json")
    cfg["model"].update(n_features=16, hidden=8, n_classes=5)
    cfg["graph"].update(scale=10 if wl["chips"] == 1 else 11, edge_factor=4)
    if tr["entry"] == "train":
        tr.update(seeds_per_chip=8, fanout=[3, 3])
    tr["trace_seconds"] = 0.5
    return {"workload": wl, "config": cfg, "traffic": tr, "limits": limits}


@contextlib.contextmanager
def planted(fault: str):
    """Break the timed path underneath the harness."""
    import jax
    import jax.numpy as jnp
    from repro import train
    from repro.core import cgtrans, gcn
    from repro.data import pipeline
    saved = []

    def patch(obj, name, value):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if fault == "unchanged":
        make = train.make_sage_train_step

        def make_unchanged(*a, **k):
            step = make(*a, **k)
            return lambda state, batch, feats: (state,
                                                step(state, batch, feats)[1])
        patch(train, "make_sage_train_step", make_unchanged)
    elif fault == "half_batch":
        def half_loss(params, feats, batch, cfg, *, mesh=None, relabel=None):
            logits = gcn.sage_forward(params, feats, batch, cfg, mesh=mesh)
            B = batch["labels"].shape[1] // 2
            logp = jax.nn.log_softmax(logits[:, :B], axis=-1)
            nll = -jnp.take_along_axis(
                logp, batch["labels"][:, :B, None], axis=-1)[..., 0]
            return nll.mean(), {"loss": nll.mean(), "acc": nll.mean()}
        patch(gcn, "sage_loss", half_loss)
    elif fault == "no_exchange":
        patch(cgtrans.lax, "all_to_all", lambda x, *a, **k: x)
    elif fault == "answer_altered":
        batch_at = pipeline.GraphBatchStream.batch_at

        def altered(self, step):
            b = batch_at(self, step)
            b["nbrs2"] = b["nbrs2"].copy()
            b["nbrs2"][0, 0, 0] = (b["nbrs2"][0, 0, 0] + 1) % (
                self.graph.n_vertices)
            return b
        patch(pipeline.GraphBatchStream, "batch_at", altered)
        full = gcn.gcn_forward_full

        def altered_full(*a, **k):
            return full(*a, **k).at[0, 5, 0].add(1.0)
        patch(gcn, "gcn_forward_full", altered_full)
    elif fault == "draw_moved":
        # from the window on, a valid draw of another step: every id is a
        # real neighbour, but not the traffic the benchmark fixes
        batch_at = pipeline.GraphBatchStream.batch_at
        patch(pipeline.GraphBatchStream, "batch_at",
              lambda self, step: batch_at(self, step + (step >= 3)))
    elif fault is not None:
        raise ValueError(fault)
    try:
        yield
    finally:
        for obj, name, value in reversed(saved):
            setattr(obj, name, value)


def cpu_peaks():
    from yard import peaks
    peaks.PEAKS.setdefault("cpu", dict(peaks.PEAKS["TPU v5 lite"]))


def run_tiny(cell: str, fault=None, trace: int = 0, seed: int = 2**31 + 7,
             graph_cache=None):
    cpu_peaks()
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.5,
                              trace=trace)
    with tempfile.TemporaryDirectory() as tmp, planted(fault):
        return run.run(args, require_tpu=False, overrides=overrides(cell),
                       graph_cache=Path(graph_cache or tmp),
                       log=lambda s: None)


def calibrate_tiny(cell: str, seeds=(1, 2, 3), graph_cache=None):
    import calibrate
    cpu_peaks()
    with tempfile.TemporaryDirectory() as tmp:
        rows = calibrate.main(
            ["--workload", cell, "--seeds", ",".join(map(str, seeds)),
             "--window", "0.3"], require_tpu=False,
            overrides=overrides(cell), graph_cache=Path(graph_cache or tmp))
    return calibrate.summarize(rows)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("fault", nargs="?", default=None)
    ap.add_argument("--calibrate", action="store_true")
    a = ap.parse_args()
    if a.calibrate:
        print(json.dumps(calibrate_tiny(a.cell)))
    else:
        print(json.dumps(run_tiny(a.cell, a.fault)))
