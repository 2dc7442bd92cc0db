"""The on-chip benchmark: one cell, one seed, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name. ``BENCHMARK.json`` (at the root
of the checkout) gives the cell's configuration, traffic and chip count,
and the metrics it reports. ``bench/configs/<config>.json`` holds the
configuration, ``bench/traffic/<traffic>.json`` the mix and the entry that
drives it (``bench/entries/<entry>.py``), ``bench/limits/<cell>.json`` the
correctness limits, and ``bench/metrics/<metric>.py`` one reader per
per-layer metric.

A run: set-up (graph, table and weights from the seed, compilation, the
entry's first steps), then a window of ``--seconds`` with the profiler off
(``--trace 0``: the end-to-end metrics) or a short traced window
(``--trace 1``: the per-layer metrics), then the device's peak memory, then
the comparison with the plain reference. The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), and last ``checks``, each
compared number beside its limit. The run exits non-zero, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    """What an entry is built from."""
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    mesh: Any
    graph_cache: Path
    setup_spans: Dict[str, float] = dataclasses.field(default_factory=dict)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.setup_spans[name] = (self.setup_spans.get(name, 0.0)
                                      + time.perf_counter() - t0)


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of ``bench/`` by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: Dict[str, Any], cell: str) -> Dict[str, List[dict]]:
    """The end-to-end and per-layer metrics this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return {"end_to_end": e2e, "per_layer": layer}


def device_info(chips: int, require_tpu: bool = True) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX's devices are {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX finds "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peak_memory(devices) -> int:
    """The fullest chip's peak: its peak of live buffers plus its peak
    reserved for the loaded programs' temporaries (a separate region of
    HBM, which ``peak_bytes_in_use`` leaves out)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


def traced_window(cell, seconds: float):
    """Run the window under the profiler; returns (window result, trace
    summary)."""
    import jax
    from yard import tracing
    tmp = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp), profiler_options=opts)
        try:
            w = cell.window(seconds)
        finally:
            jax.profiler.stop_trace()
        path = next(tmp.rglob("*.xplane.pb"))
        summary = tracing.summarize(tracing.load_xplane(str(path)))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return w, summary


@dataclasses.dataclass
class Cell:
    """A cell built and ready for ``prepare(seed)``."""
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    metrics: Dict[str, List[dict]]
    device: Dict[str, Any]
    peaks: Dict[str, float]
    ctx: Context
    entry: Any


def build_cell(name: str, *, require_tpu: bool = True,
               overrides: Optional[Dict[str, Dict[str, Any]]] = None,
               graph_cache: Optional[Path] = None) -> Cell:
    """Look the cell up by name, check the chips, and build its entry.
    ``overrides`` replaces the cell's workload entry, configuration,
    traffic or limits (tests run the harness at small sizes with it)."""
    bench = load_json(ROOT / "BENCHMARK.json")
    overrides = overrides or {}
    wl = overrides.get("workload") or cell_spec(bench, name)
    config = overrides.get("config") or load_json(
        BENCH / "configs" / f"{wl['config']}.json")
    traffic = overrides.get("traffic") or load_json(
        BENCH / "traffic" / f"{wl['traffic']}.json")
    limits = overrides.get("limits") or load_json(
        BENCH / "limits" / f"{wl['name']}.json")

    import jax
    from yard import peaks as peaks_mod
    device = device_info(wl["chips"], require_tpu)
    peaks = peaks_mod.peaks_for(device["kind"])
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_data_mesh
    enable_compile_cache()
    # every program, small ones too, comes from the cache after a first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    ctx = Context(wl, config, traffic, make_data_mesh(wl["chips"]),
                  graph_cache or (BENCH / "graph_cache"))
    # process start, imports and the TPU backend's start-up
    ctx.setup_spans["start_and_backend"] = time.perf_counter() - T_START
    entry = load_module(BENCH / "entries" / f"{traffic['entry']}.py")
    return Cell(traffic, limits, metrics_of(bench, wl["name"]), device,
                peaks, ctx, entry.build(ctx))


def run(args, *, log: Callable[[str], None] = lambda s: print(
        s, file=sys.stderr, flush=True), **build_kw) -> Dict[str, Any]:
    """One run of one cell; returns the result object."""
    c = build_cell(args.workload, **build_kw)
    cell, ctx, traffic, device = c.entry, c.ctx, c.traffic, c.device
    mets, limits, peaks, mesh = c.metrics, c.limits, c.peaks, ctx.mesh
    cell.prepare(args.seed)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in ctx.setup_spans.items()))

    if args.trace:
        w, summary = traced_window(cell, min(args.seconds,
                                             traffic["trace_seconds"]))
    else:
        w, summary = cell.window(args.seconds), None
    sample_s = getattr(cell, "sample_s", [])
    log(f"window: {w['steps']} steps in {w['elapsed_s']:.3f} s, "
        f"{w['compiles_in_window']} compiles inside it" + (
            f"; host sampler per batch: min {min(sample_s):.4f} s, "
            f"max {max(sample_s):.4f} s, total {sum(sample_s):.3f} s"
            if sample_s else ""))
    devices = list(mesh.devices.flat)
    device["memory_peak_bytes"] = peak_memory(devices)
    log(f"device 0 memory stats: {devices[0].memory_stats()}")

    metrics: Dict[str, Dict[str, Any]] = {}
    if args.trace:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        rctx = {"summary": summary, "window": w, "cell": cell,
                "peaks": peaks,
                "sample_s": sample_s}
        for m in mets["per_layer"]:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(rctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(cell.e2e(w), setup_s=setup_s)
        for m in mets["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    cell.free()
    got = cell.checks()
    checks = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    result = {"correct": correct, "attempted": w["attempted"],
              "failed": w["failed"], "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for c in result["checks"].values():
        if not math.isfinite(c["value"]):
            c["value"] = None              # JSON has no NaN; None fails
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
