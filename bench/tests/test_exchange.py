"""CPU tests of the exchange's reduction and byte count
(``bench/yard/exchange.py``) and of its readers on the four-chip cell.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_exchange.py
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from yard import exchange, peaks  # noqa: E402

CELL = "sage-reddit-t2.train-b1024x4"
DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
X = exchange.EXCHANGE


def _op(name, s, d, scope="", plane=DEV0):
    return (plane, "XLA Ops", name, s, d, scope)


def _window(s, d):
    return (HOST, "python", "bench.window", s, d, "")


def test_exchange_in_name_stacks():
    f = exchange.in_exchange
    assert f("jit(train_step)/jvp()/shard_map/cgtrans.chunk/while/body/"
             "closed_call/cgtrans.exchange/all_to_all")
    assert f("jit(f)/transpose(jvp())/shard_map/cgtrans.exchange/"
             "all_to_all")
    # nested inside itself (a wire codec inside a wrapped call site)
    assert f("jit(f)/cgtrans.exchange/cgtrans.exchange/convert")
    assert not f("jit(step)/jvp(cgtrans.chunk)/while/body/gas.find/gather")
    assert not f("jit(f)/cgtrans.exchanges/x") and not f("")


def test_exchange_attribution_hand_counts():
    rows = [
        _window(0, 100),
        # a loop whose body is the exchange: its event spans the body, so
        # it counts for nothing
        _op("while while (s32[])", 0, 90, X),
        _op("all-gather all-gather s32[4,1,800]", 0, 10, X),
        _op("fusion fusion s32[4,800]", 5, 10, X),        # union: 0-15
        _op("fusion fusion f32[3200,602]", 15, 30, ""),   # the find: not it
        _op("all-to-all all-to-all f32[4,16,603]", 45, 10, X),
        _op("all-to-all all-to-all f32[4,16,603]", 95, 20, X),  # to 100
        _op("all-gather all-gather s32[4,1,800]", 120, 5, X),   # outside
        # the second chip
        _op("all-gather all-gather s32[4,1,800]", 0, 20, X, plane=DEV1),
        _op("fusion fusion f32[3200,602]", 20, 60, "", plane=DEV1),
    ]
    s = exchange.summarize(rows)
    assert s["n_devices"] == 2
    assert s["window_s"] == pytest.approx(100e-9)
    # chip 0: 0-15, 45-55, 95-100 = 30; chip 1: 0-20 = 20; mean 25
    assert s["exchange_s"] == pytest.approx(25e-9)
    assert s["exchange_ops"] == 5


def _scopes_test_helpers():
    """``test_scopes.py``'s protobuf helpers, loaded under another name."""
    spec = importlib.util.spec_from_file_location(
        "bench_test_scopes_helpers", Path(__file__).with_name(
            "test_scopes.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exchange_ops_come_from_the_metadata_plane(tmp_path):
    """An op is the exchange's where its instruction's name stack, in the
    module whose event encloses it, holds the name; the same instruction
    name in another module is another op."""
    h = _scopes_test_helpers()
    protos = {
        "jit_step(11)": h._hlo_proto({
            "all-to-all.3": "jit(step)/jvp()/shard_map/cgtrans.chunk/while/"
                            "body/closed_call/cgtrans.exchange/all_to_all",
            "fusion.4": "jit(step)/jvp()/shard_map/cgtrans.chunk/while/body/"
                        "closed_call/gas.find/gather"}),
        "jit_other(12)": h._hlo_proto({"all-to-all.3": "jit(other)/x"}),
    }

    def esc(b):
        return "".join("\\%03o" % c for c in b)

    ops = {2: "%all-to-all.3 = f32[4,16,603]{2,1,0} all-to-all(f32[4,16,603]"
              "{2,1,0} %p)",
           3: "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop"}
    meta = "".join(f'event_metadata {{ key: {k} value {{ id: {k} name: '
                   f'"{v}" }} }}\n' for k, v in ops.items())
    modules = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{name}" '
        f'stats {{ metadata_id: 1 bytes_value: "{esc(proto)}" }} }} }}\n'
        for k, (name, proto) in enumerate(protos.items(), 1))
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 10 offset_ps: 0 duration_ps: 50000 }}
    events {{ metadata_id: 11 offset_ps: 60000 duration_ps: 30000 }} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 2 offset_ps: 0 duration_ps: 10000 }}
    events {{ metadata_id: 3 offset_ps: 10000 duration_ps: 5000 }}
    events {{ metadata_id: 2 offset_ps: 60000 duration_ps: 10000 }} }}
  event_metadata {{ key: 10 value {{ id: 10 name: "jit_step(11)" }} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "jit_other(12)" }} }}
  {meta} }}
planes {{ id: 2 name: "/host:metadata"
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
  {modules} }}
planes {{ id: 3 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 900
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 200000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }} }}
"""
    import jax
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        text))
    rows = exchange.load_xplane(str(path))
    dev = [(r[2].split(" ")[0], r[3], r[5]) for r in rows if r[0] == DEV0]
    assert dev == [("all-to-all", 1000, X), ("fusion", 1010, ""),
                   ("all-to-all", 1060, "")]
    s = exchange.summarize(rows)
    assert s["exchange_s"] == pytest.approx(10e-9)
    assert s["exchange_ops"] == 1


def _ctx(rows, steps=2):
    cell = SimpleNamespace(B=2, k1=1, k2=2, n_chips=2,
                           ctx=SimpleNamespace(config={"model": {
                               "n_features": 3}}))
    return {"exchange": ({"steps": steps}, exchange.summarize(rows)),
            "cell": cell, "peaks": peaks.PEAKS["TPU v5 lite"]}


def test_readers_of_hand_rows():
    ctx = _ctx([_window(0, 100),
                _op("all-to-all all-to-all f32[2,2,4]", 0, 40, X)])
    assert exchange.exchange_ms(ctx) == pytest.approx(40e-9 * 1e3 / 2)
    # 176 bytes (below) at 200 GB/s take 0.88 ns, against 20 ns a step
    assert exchange.exchange_ici_share(ctx) == pytest.approx(
        100 * 176 / 200e9 / 20e-9)


def test_no_exchange_op_reads_none():
    """A program without the name (the parent of the change that gave it,
    or any one-chip program): both exchange readers read ``None``."""
    ctx = _ctx([_window(0, 100),
                _op("all-to-all all-to-all f32[2,2,4]", 0, 40, ""),
                _op("while while (s32[])", 0, 90, X)])
    assert exchange.exchange_ms(ctx) is None
    assert exchange.exchange_ici_share(ctx) is None


def test_collective_exposed_ms():
    ctx = {"window": {"steps": 4}, "summary": {"collective_exposed_s": 0.2}}
    assert exchange.collective_exposed_ms(ctx) == pytest.approx(50.0)
    assert exchange.collective_exposed_ms(
        {"window": {"steps": 0}, "summary": {}}) is None


def test_required_bytes_hand_counts():
    # 2 seeds, k1=1, k2=2, F=3, 2 chips: 4 layer-1 vertices ask for 1 + 2
    # ids each (12 int32 = 48 B from the other chip) and get 2 partial rows
    # each of 3 + 1 float32 (32 B per vertex, 128 B to the other chip)
    assert exchange.sampled_train_bytes(2, 1, 2, 3, 2) == 48 + 128
    assert exchange.sampled_train_bytes(2, 1, 2, 3, 1) == 0


def test_required_bytes_of_the_cell_match_its_chunks():
    """At the cell's sizes the count equals what the program's per-chunk
    collectives carry: every 16-row chunk of each segment gathers the other
    three chips' s32[16, K] ids and sends them f32[16, 603] partials."""
    B, k1, k2, F, n, chunk = 1024, 50, 50, 602, 4, 16
    rows1 = B * (1 + k1)
    assert rows1 % chunk == 0
    per_chunk = sum((n - 1) * chunk * (k * 4 + (F + 1) * 4) for k in (1, k2))
    assert exchange.sampled_train_bytes(B, k1, k2, F, n) == (
        rows1 // chunk * per_chunk) == 787_746_816


FOUR_CHIP_READING = """
import json, sys, tempfile
from pathlib import Path
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
import run, tiny
from yard import exchange
tiny.cpu_peaks()
with tempfile.TemporaryDirectory() as tmp:
    c = run.build_cell({cell!r}, require_tpu=False,
                       overrides=tiny.overrides({cell!r}),
                       graph_cache=Path(tmp))
    c.entry.prepare(5)
    e = c.entry
    text = e.step.lower(e.state, e.stream.batch_at(0), e.table).compile(
        ).as_text()
    w, s = exchange.reading({{"cell": e}})
    print(json.dumps({{"n_devices": s["n_devices"], "steps": w["steps"],
                      "exchange_ms": exchange.exchange_ms({{"cell": e,
                          "exchange": (w, s)}}),
                      "named": [l.split("op_name=")[1][:160]
                                for l in text.splitlines()
                                if " all-to-all(" in l
                                and "op_name=" in l]}}))
"""


def test_tiny_four_chip_cell_reads_the_exchange_where_the_trace_allows():
    """The four-chip cell at a tiny size on four CPU devices: its compiled
    step names its all-to-alls ``cgtrans.exchange``, and the exchange
    reading is taken without raising. It reads a number where the trace has
    device planes (a TPU's); a CPU trace has none, so there it reads
    ``None``."""
    code = FOUR_CHIP_READING.format(bench=str(BENCH),
                                    tests=str(BENCH / "tests"),
                                    src=str(BENCH.parent / "src"), cell=CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["named"] and all(X in n for n in got["named"]), got["named"]
    assert got["steps"] >= 1
    assert (got["exchange_ms"] is None) == (got["n_devices"] == 0), got
