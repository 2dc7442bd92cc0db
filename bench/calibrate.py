"""Readings that the correctness limits are set from (not part of a run).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12

For each seed, in one process: the cell's set-up from that seed (the
program's first steps for a training cell, a short window of passes for a
full-graph cell), then the numbers the run compares, three ways:

* ``program``: the program against the float32 reference (the lower
  reading of each limit is the largest of these over the seeds);
* ``control``: the reference computed in bfloat16, put in the program's
  place, against the float32 reference (the upper reading);
* training cells only, planted faults in the reference put in the
  program's place: ``half_batch`` (the loss over half of each chip's
  seeds), ``no_exchange`` (several chips: each chip aggregates only the
  rows it holds) and ``unchanged`` (a step that returns its state: its
  update reads 1 by construction).

One JSON line per seed and reading goes to standard output, then a summary
line with, per number, the largest program reading and the smallest
control and fault readings.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def readings(cell, seed: int, window_s: float, controls: bool = True
             ) -> Dict[str, Dict[str, float]]:
    import jax.numpy as jnp
    cell.prepare(seed)
    if hasattr(cell, "reference_readings"):          # a training cell
        prog = cell.program_readings()
        cell.free()
        ref = cell.reference_readings(jnp.float32)
        out = {"program": dict(cell.compare(prog, ref),
                               sample_mismatch=float(cell.sample_mismatch())),
               "unchanged": cell.compare(
                   dict(prog, params=cell.params0), ref)}
        if controls:
            out["control"] = cell.compare(
                cell.reference_readings(jnp.bfloat16), ref)
            out["half_batch"] = cell.compare(
                cell.reference_readings(jnp.float32, half=True), ref)
            if cell.n_chips > 1:
                out["no_exchange"] = cell.compare(
                    cell.reference_readings(jnp.float32, exchange=False),
                    ref)
        return out
    cell.window(window_s)
    cell.free()
    ref = cell.reference_logits(jnp.float32)
    out = {"program": cell.compare((cell.last, cell.kept), ref)}
    if controls:
        out["control"] = cell.compare(
            (cell.reference_logits(jnp.bfloat16),), ref)
    return out


def summarize(rows: List[Dict]) -> Dict[str, Dict[str, float]]:
    """Per number: the largest program reading, the smallest reading of
    each control or fault."""
    out: Dict[str, Dict[str, float]] = defaultdict(dict)
    for r in rows:
        for kind, nums in r["readings"].items():
            for k, v in nums.items():
                agg = max if kind == "program" else min
                key = f"{kind}_{'max' if kind == 'program' else 'min'}"
                out[k][key] = agg(out[k].get(key, v), v)
    return dict(out)


def main(argv=None, **build_kw) -> List[Dict]:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--window", type=float, default=2.0,
                    help="seconds of passes per seed (full-graph cells)")
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the control and the faults on the first N "
                         "seeds only (default: every seed)")
    args = ap.parse_args(argv)
    c = run.build_cell(args.workload, **build_kw)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        controls = args.control_seeds is None or i < args.control_seeds
        row = {"seed": seed,
               "readings": readings(c.entry, seed, args.window, controls)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"summary": summarize(rows), "limits": c.limits}),
          flush=True)
    return rows


if __name__ == "__main__":
    try:
        main()
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        sys.exit(2)
