"""CPU tests of the per-scope reduction (``bench/yard/scopes.py``) and of the
harness's existing trace reduction, which it leaves as it was.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from yard import scopes, tracing  # noqa: E402

RECORDED = BENCH / "testdata" / "trace_rows.json.gz"
SCOPED = BENCH / "testdata" / "scoped_rows.json.gz"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _op(name, s, d, scope="", plane=DEV):
    return (plane, "XLA Ops", name, s, d, scope)


def _span(name, s, d):
    return (HOST, "python", name, s, d, "")


def test_innermost_scope_of_name_stacks():
    f = scopes.innermost_scope
    assert f("jit(step)/jvp(cgtrans.chunk)/while/body/closed_call/"
             "gas.find/gather") == "gas.find"
    assert f("jit(step)/transpose(jvp(gas.reduce))/mul") == "gas.reduce"
    assert f("jit(step)/jvp(cgtrans.chunk)/while/body/dynamic_slice") == (
        "cgtrans.chunk")
    assert f("jit(f)/vmap(gas.schedule)/sort") == "gas.schedule"
    assert f("jit(step)/add") == "" and f("") == ""
    # a name that merely starts like a scope is not one
    assert f("jit(f)/gas.finder/x") == ""


def test_scope_attribution_hand_counts():
    rows = [
        _span("bench.window", 0, 100),
        # a loop: its event spans its body, so it counts for no scope
        _op("while while (s32[])", 0, 60, "cgtrans.chunk"),
        _op("fusion fusion f32[8]", 0, 10, "gas.find"),
        _op("fusion fusion f32[4]", 5, 10, "gas.find"),     # union: 0-15
        _op("dynamic-slice dynamic-slice s32[1]", 15, 5, "cgtrans.chunk"),
        _op("gas_scatter_banded custom-call f32[8] tpu_custom_call", 20, 20,
            "gas.reduce"),
        _op("sort sort s32[8]", 30, 15, "gas.schedule"),   # overlaps reduce
        _op("fusion fusion f32[2]", 70, 10, "gcn.combine"),
        _op("copy copy f32[9]", 80, 10),                   # unscoped
        _op("fusion fusion f32[3]", 95, 20, "gcn.combine"),  # clipped at 100
    ]
    s = scopes.summarize(rows)
    assert s["scope_s"] == pytest.approx({
        "gas.find": 15e-9, "cgtrans.chunk": 5e-9, "gas.reduce": 20e-9,
        "gas.schedule": 15e-9, "gcn.combine": 15e-9})
    # busy 0-60 (the loop), 70-90, 95-100; the scoped leaves cover 0-45,
    # 70-80 and 95-100, leaving the loop's 45-60 and the copy unscoped
    assert s["busy_s"] == pytest.approx(85e-9)
    assert s["unscoped_s"] == pytest.approx(25e-9)
    assert s["idle_by_span"] == {"none": pytest.approx(15e-9)}


def test_idle_by_innermost_span_hand_counts():
    rows = [
        _span("bench.window", 0, 100),
        _span("bench.sample", 0, 40),            # not a program span
        _span("repro.data.sample", 5, 30),       # 5-35
        _span("repro.train.step", 40, 50),       # 40-90
        _span("repro.data.sample", 60, 10),      # nested in the step: 60-70
        _op("fusion fusion f32[8]", 20, 5, "gas.find"),
        _op("fusion fusion f32[8]", 45, 10, "gas.find"),
        _op("fusion fusion f32[8]", 75, 5, "gcn.combine"),
    ]
    s = scopes.summarize(rows)
    idle = {k: v * 1e9 for k, v in s["idle_by_span"].items()}
    # idle: 0-20, 25-45, 55-75, 80-100
    assert idle == pytest.approx({
        "repro.data.sample": (35 - 5 - 5) + 10,  # 5-20, 25-35; 60-70
        "repro.train.step": 5 + 5 + 5 + 10,      # 40-45, 55-60, 70-75,
                                                 # 80-90
        "none": 5 + 5 + 10})                     # 0-5, 35-40, 90-100
    assert sum(s["idle_by_span"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])


def test_chips_are_averaged():
    rows = [_span("bench.window", 0, 100),
            _op("fusion fusion f32[8]", 0, 40, "gas.find"),
            _op("fusion fusion f32[8]", 0, 20, "gas.find",
                plane="/device:TPU:1")]
    s = scopes.summarize(rows)
    assert s["n_devices"] == 2
    assert s["scope_s"]["gas.find"] == pytest.approx(30e-9)
    assert s["idle_by_span"]["none"] == pytest.approx(70e-9)


def _pb(*fields) -> bytes:
    """A protobuf message of (field number, bytes or str) fields."""
    out = b""
    for num, val in fields:
        val = val.encode() if isinstance(val, str) else val
        out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def _varint(n: int) -> bytes:
    out = b""
    while n >= 0x80:
        out += bytes([n & 0x7F | 0x80])
        n >>= 7
    return out + bytes([n])


def _hlo_proto(stacks):
    """HloProto{hlo_module{computations{instructions{name, metadata{
    op_name}}}}} of instruction name -> name stack."""
    instrs = [_pb((1, name), (7, _pb((2, stack))))
              for name, stack in stacks.items()]
    return _pb((1, _pb((3, _pb(*[(2, i) for i in instrs])))))


def _xspace(tmp_path) -> str:
    """A trace as a TPU writes it: op events named by their HLO text, the
    name stacks only in the metadata plane's HLO protos, per module."""
    protos = {
        "jit_step(11)": _hlo_proto({
            "fusion.3": "jit(step)/jvp(cgtrans.chunk)/while/body/gas.find/"
                        "gather",
            "copy.4": "",
            "gas_scatter_banded.7": "jit(step)/transpose(jvp(gas.reduce))/"
                                    "pallas_call"}),
        "jit_other(12)": _hlo_proto({"fusion.3": "jit(other)/gcn.combine/"
                                                 "dot_general"}),
    }

    def esc(b):
        return "".join("\\%03o" % c for c in b)

    ops = {2: "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
           3: "%copy.4 = f32[8]{0} copy(f32[8]{0} %fusion.3)",
           4: "%gas_scatter_banded.7 = f32[8]{0} custom-call(f32[8]{0} "
              "%copy.4)"}
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
    events {{ metadata_id: 4 offset_ps: 20000 duration_ps: 20000 }}
    events {{ metadata_id: 2 offset_ps: 60000 duration_ps: 10000 }} }}
  event_metadata {{ key: 10 value {{ id: 10 name: "jit_step(11)" }} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "jit_other(12)" }} }}
  {meta} }}
planes {{ id: 2 name: "/host:metadata"
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
  {modules} }}
planes {{ id: 3 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 900
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 200000 }}
    events {{ metadata_id: 2 offset_ps: 50000 duration_ps: 20000 }}
    events {{ metadata_id: 3 offset_ps: 60000 duration_ps: 10000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "repro.train.step" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "PjitFunction(step)" }} }} }}
"""
    import jax
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        text))
    return str(path)


def test_scopes_come_from_the_metadata_plane(tmp_path):
    """Each op takes the name stack of its instruction in the module whose
    event encloses it; the same instruction name in another module is
    another op."""
    rows = scopes.load_xplane(_xspace(tmp_path))
    dev = [r for r in rows if r[0] == DEV]
    assert [(r[2].split(" ")[0], r[3], r[5]) for r in dev] == [
        ("fusion", 1000, "gas.find"), ("copy", 1010, ""),
        ("gas_scatter_banded", 1020, "gas.reduce"),
        ("fusion", 1060, "gcn.combine")]
    assert tracing.is_kernel(dev[2][2])
    host = sorted((r[2], r[3], r[4]) for r in rows if r[0] != DEV)
    assert host == [("bench.window", 900, 200), ("repro.train.step", 950, 20)]


def test_recorded_scoped_trace():
    """A 104 ms slice of a traced train-cell window on one v5e chip (the
    end of one step, the sampling gap, the start of the next; its window
    span cut to the slice): the scoped time and the idle split add up."""
    rows = tracing.load_rows(SCOPED)
    s = scopes.summarize(rows)
    assert set(s["scope_s"]) == set(scopes.SCOPES)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["unscoped_s"] >= 0
    assert sum(s["scope_s"].values()) + s["unscoped_s"] >= s["busy_s"] * (
        1 - 1e-9)
    assert sum(s["idle_by_span"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    assert {"repro.data.sample", "repro.train.step"} <= set(s["idle_by_span"])
    # the harness's reduction of the same rows finds the same busy time
    plain = tracing.summarize([r[:5] for r in rows if r[2] == "bench.window"
                               or r[0] == DEV])
    assert plain["busy_s"] == pytest.approx(s["busy_s"])


def test_existing_summary_of_recorded_trace_is_unchanged():
    """Every key of the harness's summary of its recorded table is what it
    was when this reduction was added beside it, to the last digit."""
    want = json.loads((BENCH / "testdata" / "trace_rows.summary.json")
                      .read_text())
    assert tracing.summarize(tracing.load_rows(RECORDED)) == want
