"""The cross-chip exchange: its device time, read from a trace, and the bytes
it requires, counted from the cell's shapes.

The program runs every collective of a sharded body inside
``jax.named_scope("cgtrans.exchange")`` (``repro.core.cgtrans``): the request
``all_gather``, the partials' ``all_to_all`` and the full-graph
``psum_scatter``, with the transposes that carry their names. The name is
not one of ``scopes.SCOPES``, so the per-scope readers still put these ops
under the scope around them (``cgtrans.chunk``); this module reads the one
name on its own:

* ``exchange_s``: per chip, the union of the intervals of the device ops
  whose name stack holds ``cgtrans.exchange`` as a whole component (loops
  left out: their events span their bodies), mean over the chips.

As ``scopes.reading`` does, ``reading`` traces a short window of its own on
the cell the run built, once a run, kept in the readers' shared context. An
op's name stack is its HLO instruction's ``metadata.op_name`` in the module
whose event encloses it (``scopes.hlo_protos`` and ``scopes.name_stacks``
read them from the trace's metadata plane). Where no op carries the name (a
one-chip program, or a program from before the name was given) the readings
are ``None``.

The reduction is plain code over ``(plane, line, name, start_ns, dur_ns,
scope)`` rows, ``scope`` being ``EXCHANGE`` or "", so hand-built rows check
the arithmetic without a chip.
"""

from __future__ import annotations

import bisect
import re
import shutil
import tempfile
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from yard import scopes, tracing

EXCHANGE = "cgtrans.exchange"
# the name as a whole component of a name stack such as
# "jit(train_step)/jvp()/shard_map/cgtrans.chunk/while/body/closed_call/
#  cgtrans.exchange/all_to_all"
EXCHANGE_RE = re.compile(r"(?<![\w.])" + re.escape(EXCHANGE) + r"(?![\w.])")
I32 = F32 = 4   # bytes


def in_exchange(name_stack: str) -> bool:
    return EXCHANGE_RE.search(name_stack) is not None


def load_xplane(path: str) -> List[scopes.ScopedRow]:
    """Device op events (named by ``tracing.op_name``, scope ``EXCHANGE``
    where the op's name stack holds it) and the ``bench.window`` span of one
    ``.xplane.pb``; each distinct (module, op) is looked up once."""
    import jax
    raw = Path(path).read_bytes()
    stacks = {mod: scopes.name_stacks(proto)
              for mod, proto in scopes.hlo_protos(raw).items()}
    rows: List[scopes.ScopedRow] = []
    seen: Dict[tuple, tuple] = {}
    for plane in jax.profiler.ProfileData.from_serialized_xspace(raw).planes:
        is_dev = tracing.DEVICE_PLANE.match(plane.name) is not None
        starts, mods = scopes._modules(plane) if is_dev else ([], [])
        for line in plane.lines:
            if is_dev and line.name != tracing.OP_LINE:
                continue
            for ev in line.events:
                text, s = ev.name, int(ev.start_ns)
                if is_dev:
                    i = bisect.bisect_right(starts, s) - 1
                    mod = mods[i][1] if i >= 0 and s < mods[i][0] else ""
                    got = seen.get((mod, text))
                    if got is None:
                        m = scopes.INSTRUCTION.match(text)
                        stack = stacks.get(mod, {}).get(
                            m.group(1) if m else "", "")
                        got = seen[(mod, text)] = (
                            tracing.op_name(text),
                            EXCHANGE if in_exchange(stack) else "")
                    name, scope = got
                elif text == tracing.WINDOW_SPAN:
                    name, scope = text, ""
                else:
                    continue
                rows.append((plane.name, line.name, name, s,
                             int(ev.duration_ns), scope))
    return rows


def summarize(rows: Sequence[scopes.ScopedRow]) -> Dict[str, Any]:
    """Exchange seconds inside the ``bench.window`` span, mean over the chips
    that ran any op in it, and the number of exchange op events counted."""
    win = [(s, s + d) for _, _, n, s, d, _ in rows
           if n == tracing.WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {tracing.WINDOW_SPAN!r} span")
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    chips = set()
    iv: Dict[str, List[tuple]] = defaultdict(list)
    for plane, _, name, s, d, scope in rows:
        e = s + d
        if not tracing.DEVICE_PLANE.match(plane) or min(e, hi) <= max(s, lo):
            continue
        chips.add(plane)
        if scope == EXCHANGE and not tracing.is_container(name):
            iv[plane].append((max(s, lo), min(e, hi)))
    total_ns = 0
    for evs in iv.values():
        us, ue = scopes.union(np.array([s for s, _ in evs], np.int64),
                              np.array([e for _, e in evs], np.int64))
        total_ns += int((ue - us).sum())
    return {"n_devices": len(chips), "window_s": (hi - lo) / 1e9,
            "exchange_s": total_ns / max(len(chips), 1) / 1e9,
            "exchange_ops": sum(len(v) for v in iv.values())}


# -- the traced window, shared by this module's readers ---------------------

# On a four-chip v5e host a train step takes ~0.7 s (the host sampler's
# pull), so this window holds the step in flight when it ends: one step.
WINDOW_S = 0.25


def reading(ctx: Dict[str, Any]):
    """(window, summary) of a traced window of ``WINDOW_S`` on the cell the
    run built, taken once per run and kept in the readers' context."""
    if "exchange" not in ctx:
        ctx["exchange"] = _trace(ctx["cell"])
    return ctx["exchange"]


def _trace(cell):
    import jax
    tmp = Path(tempfile.mkdtemp(prefix="bench_exchange_"))
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp), profiler_options=opts)
        try:
            w = cell.window(WINDOW_S)
        finally:
            jax.profiler.stop_trace()
        return w, summarize(load_xplane(str(next(tmp.rglob("*.xplane.pb")))))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# -- the bytes the exchange requires ----------------------------------------

def sampled_train_bytes(seeds: int, k1: int, k2: int, n_features: int,
                        n_chips: int) -> float:
    """Bytes one chip's links carry in the exchange of one sampled 2-layer
    GraphSAGE train step whose table is owner-sharded over ``n_chips``.

    A chip's requests are its layer-1 vertices (``rows1``: the seeds and
    their k1 samples), each asking for its own row (one id) and for the mean
    of its k2 samples' rows (k2 ids). The chip receives the request ids of
    every other chip (int32), and sends each of them, per requested row, the
    partial sum of the rows it owns with their count (F + 1 float32). The
    backward sends nothing: no gradient reaches the table, which is data.
    Like ``work.py`` this counts what the dataflow requires, not what the
    program pads.
    """
    rows1 = seeds * (1 + k1)
    others = n_chips - 1
    ids = others * rows1 * (1 + k2) * I32
    partials = others * 2 * rows1 * (n_features + 1) * F32
    return float(ids + partials)


# -- the readers ------------------------------------------------------------

def exchange_ms(ctx: Dict[str, Any]) -> Optional[float]:
    """Device milliseconds per step of the exchange's ops, or ``None`` where
    no op carries the name."""
    w, t = reading(ctx)
    if not t["exchange_ops"] or not w["steps"]:
        return None
    return 1e3 * t["exchange_s"] / w["steps"]


def exchange_ici_share(ctx: Dict[str, Any]) -> Optional[float]:
    """Least time of the exchange's required bytes a step at the chip's peak
    interconnect bandwidth, over the measured ``exchange_ms``, in per cent."""
    ms = exchange_ms(ctx)
    if ms is None:
        return None
    c = ctx["cell"]
    need = sampled_train_bytes(c.B, c.k1, c.k2,
                               c.ctx.config["model"]["n_features"],
                               c.n_chips)
    return 100.0 * need / (ctx["peaks"]["ici_bits_per_s"] / 8) / (ms / 1e3)


def collective_exposed_ms(ctx: Dict[str, Any]) -> Optional[float]:
    """Device milliseconds per step of the harness's window in which a
    collective runs and no other op does, mean over the chips."""
    w = ctx["window"]
    if not w["steps"]:
        return None
    return 1e3 * ctx["summary"]["collective_exposed_s"] / w["steps"]
