"""Device time by program scope, and idle device time by program host span.

The program names its device work with ``jax.named_scope`` (``gas.find``,
``gas.schedule``, ``gas.reduce``, ``cgtrans.chunk``, ``gcn.combine``; the
name lands in each HLO op's ``metadata.op_name``) and its host work with
``jax.profiler.TraceAnnotation`` (``repro.data.sample``,
``repro.train.step``). This module reduces a profiler trace to

* ``scope_s``: per scope, the union of the intervals of the device ops
  whose innermost scope it is (loops left out: their events span their
  bodies), mean over the chips; an op takes the innermost of the scope
  names in its name stack, so ``cgtrans.chunk`` reads the chunk scan's own
  work, exclusive of the find, schedule and reduce inside it;
* ``unscoped_s``: busy time that no scoped op covers;
* ``idle_by_span``: idle device time put down to the innermost ``repro.``
  host span open at that moment, or ``none``, mean over the chips.

The harness's own traced window is reduced by ``tracing.py`` before any
reader runs, and its trace is gone by then; so ``reading`` traces a second,
short window on the same built cell, once per run, and the per-scope
readers share it. Only device time is read from it: the host sampler's
speed shifts within a process (on one v5e host, after the first window's
reduction, four train steps fit a 0.5 s window where a fresh process runs
three, with 18.6 % of it idle under ``repro.data.sample`` against 44 %), so
its idle split is not the benchmark window's, and ``idle_by_span`` has no
reader until the harness's own window is reduced here too. On a program
without the scope names every reading is ``None``.

As in ``tracing.py``, the reduction is plain code over rows, here
``(plane, line, name, start_ns, dur_ns, scope)`` with ``scope`` the op's
innermost scope ("" for none, and for host spans), so a recorded table
(``tracing.load_rows`` reads one) checks the arithmetic without a chip.
"""

from __future__ import annotations

import re
import shutil
import tempfile
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from yard import tracing

SCOPES = ("gas.find", "gas.schedule", "gas.reduce", "cgtrans.chunk",
          "gcn.combine")
SPAN_PREFIX = "repro."
NO_SPAN = "none"
# a scope name as a whole component of a name stack such as
# "jit(train_step)/jvp(cgtrans.chunk)/while/body/gas.find/gather"
SCOPE_RE = re.compile(r"(?<![\w.])(" + "|".join(map(re.escape, SCOPES))
                      + r")(?![\w.])")

ScopedRow = Tuple[str, str, str, int, int, str]


def innermost_scope(name_stack: str) -> str:
    """The last of ``SCOPES`` in an op's name stack, or ""."""
    found = SCOPE_RE.findall(name_stack)
    return found[-1] if found else ""


# -- the name stacks, from the HLO protos in the trace's metadata plane ----

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
MODULE_LINE = "XLA Modules"
INSTRUCTION = re.compile(r"^%([^\s=]+) = ")


def _varint(buf, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf):
    """(field number, value) of one serialized protobuf message: an int for
    a varint, bytes (a memoryview) for the other wire types."""
    buf = memoryview(buf)
    pos = 0
    while pos < len(buf):
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, pos = buf[pos:pos + n], pos + n
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, val


def _first(buf, number: int, default=b""):
    return next((v for n, v in _fields(buf) if n == number), default)


def hlo_protos(xspace: bytes) -> Dict[str, bytes]:
    """Serialized ``HloProto`` of each module in an ``XSpace``'s metadata
    plane, by the module's name as its ``XLA Modules`` events give it
    (``name(program id)``). Fields: XSpace.planes 1; XPlane.name 2,
    event_metadata 4 (map entries: key 1, value 2), stat_metadata 5;
    XEventMetadata.name 2, stats 5; XStat.metadata_id 1, bytes_value 6;
    XStatMetadata.name 2."""
    out: Dict[str, bytes] = {}
    for n, plane in _fields(xspace):
        if n != 1 or bytes(_first(plane, 2)).decode() != METADATA_PLANE:
            continue
        events, stat_names = [], {}
        for f, entry in _fields(plane):
            if f == 4:
                events.append(_first(entry, 2))
            elif f == 5:
                meta = _first(entry, 2)
                stat_names[_first(meta, 1, 0)] = bytes(
                    _first(meta, 2)).decode()
        for ev in events:
            name = bytes(_first(ev, 2)).decode()
            for f, stat in _fields(ev):
                if f == 5 and stat_names.get(_first(stat, 1, 0)) == (
                        HLO_PROTO_STAT):
                    out[name] = bytes(_first(stat, 6))
    return out


def name_stacks(hlo_proto: bytes) -> Dict[str, str]:
    """Instruction name → ``metadata.op_name`` of one module. Fields:
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    metadata 7; OpMetadata.op_name 2."""
    out: Dict[str, str] = {}
    for comp in (v for n, v in _fields(_first(hlo_proto, 1)) if n == 3):
        for instr in (v for n, v in _fields(comp) if n == 2):
            name = stack = b""
            for n, v in _fields(instr):
                if n == 1:
                    name = v
                elif n == 7:
                    stack = _first(v, 2)
            out[bytes(name).decode()] = bytes(stack).decode()
    return out


def _modules(plane) -> Tuple[List[int], List[Tuple[int, str]]]:
    """Start times and (end, name) of the plane's module events."""
    evs = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                  ev.name) for line in plane.lines
                 if line.name == MODULE_LINE for ev in line.events)
    return [s for s, _, _ in evs], [(e, n) for _, e, n in evs]


def load_xplane(path: str) -> List[ScopedRow]:
    """Device op events (named by ``tracing.op_name``, with their innermost
    scope) and the ``bench.window`` and ``repro.`` host spans of one
    ``.xplane.pb``. An op's name stack is its HLO instruction's
    ``metadata.op_name`` in the module whose event encloses it; each
    distinct (module, op) is looked up once."""
    import bisect

    import jax
    raw = Path(path).read_bytes()
    stacks = {mod: name_stacks(proto)
              for mod, proto in hlo_protos(raw).items()}
    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    rows: List[ScopedRow] = []
    seen: Dict[Tuple[str, str], Tuple[str, str]] = {}
    for plane in pd.planes:
        is_dev = tracing.DEVICE_PLANE.match(plane.name) is not None
        starts, mods = _modules(plane) if is_dev else ([], [])
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
                        m = INSTRUCTION.match(text)
                        stack = stacks.get(mod, {}).get(
                            m.group(1) if m else "", "")
                        got = seen[(mod, text)] = (tracing.op_name(text),
                                                   innermost_scope(stack))
                    name, scope = got
                elif text == tracing.WINDOW_SPAN or text.startswith(
                        SPAN_PREFIX):
                    name, scope = text, ""
                else:
                    continue
                rows.append((plane.name, line.name, name, s,
                             int(ev.duration_ns), scope))
    return rows


# -- interval arithmetic on numpy arrays ------------------------------------

def union(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray,
                                                          np.ndarray]:
    """Merged, sorted intervals of (starts, ends)."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:], s.size) - 1
    return s[first], reach[last]


def covered(starts: np.ndarray, ends: np.ndarray, cum: np.ndarray,
            a: int, b: int) -> int:
    """Length of [a, b) covered by merged intervals; ``cum`` is the running
    sum of their lengths with a leading 0."""
    i = int(np.searchsorted(ends, a, side="right"))
    j = int(np.searchsorted(starts, b, side="left"))
    if i >= j:
        return 0
    out = int(cum[j] - cum[i])
    out -= max(0, a - int(starts[i]))
    out -= max(0, int(ends[j - 1]) - b)
    return out


def _spans_by_innermost(spans: Sequence[Tuple[str, int, int]], lo: int,
                        hi: int) -> List[Tuple[int, int, str]]:
    """[lo, hi) cut into pieces, each labelled with the innermost (latest
    started) host span open over it, or ``NO_SPAN``."""
    # at one instant, ends come before starts
    bounds = sorted([(s, 1, i) for i, (_, s, _) in enumerate(spans)]
                    + [(e, 0, i) for i, (_, _, e) in enumerate(spans)]
                    + [(hi, 0, -1)])
    out, open_, t = [], {}, lo
    for at, starts, i in bounds:
        if at > t:
            label = (spans[max(open_, key=open_.get)][0] if open_
                     else NO_SPAN)
            out.append((t, at, label))
            t = at
        if i < 0:
            break
        if starts:
            open_[i] = spans[i][1]
        else:
            open_.pop(i, None)
    return out


def summarize(rows: Sequence[ScopedRow]) -> Dict[str, Any]:
    """Per-scope device seconds, unscoped busy seconds and idle seconds by
    host span inside the ``bench.window`` span, averaged over the chips.
    ``scope_s`` holds the scopes that name some op in the window,
    ``idle_by_span`` the spans open in it (with ``none``)."""
    win = [(s, s + d) for _, _, n, s, d, _ in rows
           if n == tracing.WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {tracing.WINDOW_SPAN!r} span")
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    dev: Dict[str, List[Tuple[int, int, str, bool]]] = defaultdict(list)
    spans: List[Tuple[str, int, int]] = []
    for plane, _, name, s, d, scope in rows:
        e = s + d
        if tracing.DEVICE_PLANE.match(plane):
            if min(e, hi) > max(s, lo):
                dev[plane].append((max(s, lo), min(e, hi), scope,
                                   tracing.is_container(name)))
        elif name.startswith(SPAN_PREFIX) and min(e, hi) > max(s, lo):
            spans.append((name, max(s, lo), min(e, hi)))
    if not dev:
        raise ValueError("the trace holds no device operation in the window")
    pieces = _spans_by_innermost(spans, lo, hi)
    scope_ns: Dict[str, int] = defaultdict(int)
    idle_ns: Dict[str, int] = {n: 0 for n, _, _ in spans}
    idle_ns[NO_SPAN] = 0
    busy_ns = scoped_ns = 0
    code = {name: i for i, name in enumerate(SCOPES)}
    for evs in dev.values():
        s = np.array([r[0] for r in evs], np.int64)
        e = np.array([r[1] for r in evs], np.int64)
        # the op's scope index, or -1: unscoped, or a loop
        c = np.array([-1 if r[3] else code.get(r[2], -1) for r in evs])
        bs, be = union(s, e)
        busy_ns += int((be - bs).sum())
        for name, i in code.items():
            if (c == i).any():
                us, ue = union(s[c == i], e[c == i])
                scope_ns[name] += int((ue - us).sum())
        # scoped ops lie inside the busy union
        us, ue = union(s[c >= 0], e[c >= 0])
        scoped_ns += int((ue - us).sum())
        # idle: the window less the busy union
        gs, ge = np.append(lo, be), np.append(bs, hi)
        gs, ge = gs[ge > gs], ge[ge > gs]
        cum = np.concatenate([[0], np.cumsum(ge - gs)])
        for a, b, label in pieces:
            idle_ns[label] += covered(gs, ge, cum, a, b)
    n = len(dev)
    return {
        "n_devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "scope_s": {k: v / n / 1e9 for k, v in scope_ns.items()},
        "unscoped_s": (busy_ns - scoped_ns) / n / 1e9,
        "idle_by_span": {k: v / n / 1e9 for k, v in idle_ns.items()},
    }


# -- the second traced window, shared by this reduction's readers ----------

# A train step with its sample takes ~0.2 s and a full-graph pass ~0.16 s on
# one v5e chip, so this window holds two whole steps (passes). Its trace
# costs ~10 s a train step to collect when the profiler stops (~31 us a
# device event, ~0.3M events a step).
WINDOW_S = 0.25


def reading(ctx: Dict[str, Any]):
    """(window, summary) of a traced window of ``WINDOW_S`` on the cell the
    run built, taken once per run and kept in the readers' shared
    context."""
    if "scopes" not in ctx:
        ctx["scopes"] = _trace(ctx["cell"])
    return ctx["scopes"]


def _trace(cell):
    import jax
    tmp = Path(tempfile.mkdtemp(prefix="bench_scopes_"))
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


def scope_ms(ctx: Dict[str, Any], scope: str) -> Optional[float]:
    """Device milliseconds per step of the ops whose innermost scope is
    ``scope``, or ``None`` where no op carries it."""
    w, t = reading(ctx)
    if scope not in t["scope_s"] or not w["steps"]:
        return None
    return 1e3 * t["scope_s"][scope] / w["steps"]


def find_ms(ctx):
    return scope_ms(ctx, "gas.find")


def schedule_ms(ctx):
    return scope_ms(ctx, "gas.schedule")


def reduce_ms(ctx):
    return scope_ms(ctx, "gas.reduce")


def chunk_overhead_ms(ctx):
    return scope_ms(ctx, "cgtrans.chunk")


def combine_ms(ctx):
    return scope_ms(ctx, "gcn.combine")
