"""From a profiler trace to the numbers the per-layer metrics read.

A trace is reduced in two stages. ``load_xplane`` turns the profiler's
``.xplane.pb`` into a small event table: every event of the device planes'
operation line (``XLA Ops``, named by op name, opcode and result type), and
the benchmark's own host spans (names starting with ``bench.``).
``summarize`` then computes, per device, the union of busy intervals inside
the benchmark's ``bench.window`` span, the time of the FAST-GAS kernel's
events, the collectives' time not covered by other work, the device
operations that took most time (loops left out: their events span their
bodies), and the longest idle gaps with the host span that covers most of
each. Both stages are plain code over
(plane, line, name, start, duration) rows, so a recorded table checks the
arithmetic without a chip.
"""

from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# An op event is named by its HLO text: "%name.N = type opcode(...), ...".
HLO = re.compile(r"^%([\w.\-]+?)(?:\.\d+)? = (\(.*?\)|\S+) ([\w\-]+)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
# The FAST-GAS kernel's Mosaic calls: the HLO op takes the name of the jitted
# wrapper around each pallas_call (kernels/gas_scatter/kernel.py).
KERNEL_OPS = ("gas_scatter_banded", "gas_scatter_pallas")
COLLECTIVES = ("all-gather", "all-to-all", "reduce-scatter", "all-reduce",
               "collective-permute")
CONTAINERS = ("while", "conditional", "call")

Row = Tuple[str, str, str, int, int]   # plane, line, name, start_ns, dur_ns


def op_name(text: str) -> str:
    """"<name> <opcode> <type>[ <custom-call target>]" from an op event's
    HLO text, or the text itself where it is not HLO."""
    m = HLO.match(text)
    if not m:
        return text[:120]
    t = TARGET.search(text)
    out = f"{m.group(1)} {m.group(3)} {m.group(2)[:60]}"
    return f"{out} {t.group(1)}" if t else out


def load_xplane(path: str) -> List[Row]:
    """The device planes' operation events (named by ``op_name``) and the
    ``bench.`` host spans of one ``.xplane.pb`` file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    rows: List[Row] = []
    for plane in pd.planes:
        is_dev = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if is_dev and line.name != OP_LINE:
                continue
            for ev in line.events:
                name = ev.name
                if is_dev:
                    name = op_name(name)
                elif not name.startswith(HOST_PREFIX):
                    continue
                rows.append((plane.name, line.name, name, int(ev.start_ns),
                             int(ev.duration_ns)))
    return rows


def _parts(name: str) -> Tuple[str, str]:
    """(name, opcode) of a row written by ``op_name``."""
    p = name.split(" ")
    return p[0], (p[1] if len(p) > 1 else "")


def is_kernel(name: str) -> bool:
    base, opcode = _parts(name)
    return opcode == "custom-call" and base in KERNEL_OPS


def is_collective(name: str) -> bool:
    return _parts(name)[1].startswith(COLLECTIVES)


def is_container(name: str) -> bool:
    return _parts(name)[1] in CONTAINERS


def save_rows(rows: Sequence[Row], path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(r) for r in rows], f)


def load_rows(path: Path) -> List[Row]:
    with gzip.open(path, "rt") as f:
        return [tuple(r) for r in json.load(f)]


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Tuple[int, int]], b: Sequence[Tuple[int, int]]
             ) -> List[Tuple[int, int]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def summarize(rows: Sequence[Row], top: int = 10) -> Dict[str, object]:
    """Per-device busy, kernel and exposed-collective seconds inside the
    ``bench.window`` span, averaged over the devices; the top device ops
    and the longest idle gaps (device 0's, labelled by host span)."""
    win = [(s, s + d) for p, l, n, s, d in rows if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    ops: Dict[str, List[Tuple[str, int, int]]] = defaultdict(list)
    host: List[Tuple[str, int, int]] = []
    for plane, line, name, s, d in rows:
        if DEVICE_PLANE.match(plane):
            if min(s + d, hi) > max(s, lo):
                ops[plane].append((name, max(s, lo), min(s + d, hi)))
        elif name != WINDOW_SPAN:
            host.append((name, s, s + d))
    if not ops:
        raise ValueError("the trace holds no device operation in the window")
    busy, kern, coll_exposed, by_name = [], [], [], defaultdict(int)
    gaps0: List[Tuple[int, int]] = []
    for i, plane in enumerate(sorted(ops, key=_device_index)):
        evs = ops[plane]
        busy_iv = union((s, e) for _, s, e in evs)
        busy.append(total(busy_iv))
        kern.append(total(union((s, e) for n, s, e in evs if is_kernel(n))))
        coll = union((s, e) for n, s, e in evs if is_collective(n))
        # a loop's event spans its whole body: not compute that hides a
        # collective
        other = union((s, e) for n, s, e in evs
                      if not is_collective(n) and not is_container(n))
        coll_exposed.append(total(subtract(coll, other)))
        for n, s, e in evs:
            if not is_container(n):
                by_name[n] += e - s
        if i == 0:
            gaps0 = subtract([(lo, hi)], busy_iv)
    n_dev = len(ops)
    window_ns = hi - lo
    gaps = sorted(gaps0, key=lambda g: g[1] - g[0], reverse=True)[:top]
    return {
        "n_devices": n_dev,
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "kernel_s": sum(kern) / n_dev / 1e9,
        "collective_exposed_s": sum(coll_exposed) / n_dev / 1e9,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(g, host), (g[1] - g[0]) / 1e9] for g in gaps],
    }


def _device_index(plane: str) -> int:
    return int(DEVICE_PLANE.match(plane).group(1))


def _label(gap: Tuple[int, int], host: Sequence[Tuple[str, int, int]]) -> str:
    """The host span that overlaps the gap most, or ``other``."""
    best, best_ov = "other", 0
    for n, s, e in host:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ov:
            best, best_ov = n, ov
    return best
