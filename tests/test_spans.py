"""The program's trace names: device scopes and host spans.

Device work is named with ``jax.named_scope`` (it lands in each HLO op's
``metadata.op_name``): ``gas.find``, ``gas.schedule``, ``gas.reduce``,
``cgtrans.chunk`` and ``gcn.combine``, and on a sharded mesh
``cgtrans.exchange`` around every cross-chip collective. Host work is named with
``jax.profiler.TraceAnnotation``: ``repro.data.sample`` around a minibatch
draw, ``repro.data.sample.block`` around each row block of its neighbour
draws (on whichever thread runs it), ``repro.train.step`` around a training step, the next batch's draw and
the wait, and ``repro.train.wait`` around the wait alone. A trace
reduction attributes each op to the innermost of the scopes in its name
stack, so the three GAS scopes must never nest inside one another.
"""

from __future__ import annotations

import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import TrainConfig
from repro.common.schema import init_params
from repro.configs import graphic_gcn
from repro.core import cgtrans
from repro.core.gcn import gcn_forward_full, gcn_schema
from repro.data import GraphBatchStream, pipeline
from repro.graph import COOGraph, partition_by_src
from repro.launch.mesh import make_data_mesh
from repro.optim import adamw_init
from repro.train import make_sage_train_step, train_loop

from _dist import run_distributed_case

SCOPES = ("gas.find", "gas.schedule", "gas.reduce", "cgtrans.chunk",
          "gcn.combine")
GAS = ("gas.find", "gas.schedule", "gas.reduce")
SCOPE_RE = re.compile(r"(?<![\w.])(" + "|".join(map(re.escape, SCOPES))
                      + r")(?![\w.])")
V, F, H, C, B, K = 64, 16, 8, 5, 4, 3


def _graph():
    rng = np.random.default_rng(0)
    src = rng.integers(0, V, 4 * V).astype(np.int32)
    dst = rng.integers(0, V, 4 * V).astype(np.int32)
    return COOGraph(V, src, dst, np.full(src.shape, 0.25, np.float32))


def _params(key, cfg):
    return init_params(gcn_schema(cfg), key)


@pytest.fixture(scope="module")
def train():
    """The tiny deployed train step (pallas, chunked, scheduled), its state,
    table and batch stream."""
    cfg = dataclasses.replace(graphic_gcn.PALLAS_CONFIG, n_features=F,
                              hidden=H, n_classes=C, fanout=K,
                              request_chunk=2)
    tc = TrainConfig(learning_rate=1e-2, warmup_steps=0)
    mesh = make_data_mesh(1)
    step = jax.jit(make_sage_train_step(cfg, tc, mesh=mesh))
    params = _params(jax.random.PRNGKey(0), cfg)
    state = {"params": params, "opt": adamw_init(params, tc),
             "step": jnp.zeros((), jnp.int32)}
    feats = jax.random.normal(jax.random.PRNGKey(1), (1, V, F))
    labels = np.arange(V, dtype=np.int32) % C
    stream = GraphBatchStream(_graph(), labels, n_parts=1, batch_per_part=B,
                              k1=K, k2=K, seed=3)
    return step, state, feats, stream


def _op_names(compiled_text: str):
    return re.findall(r'op_name="([^"]*)"', compiled_text)


def _train_ops(train):
    step, state, feats, stream = train
    return _op_names(step.lower(state, stream.batch_at(0), feats)
                     .compile().as_text())


def _infer_ops():
    cfg = dataclasses.replace(graphic_gcn.PALLAS_CONFIG, n_features=F,
                              hidden=H, n_classes=C)
    pg = partition_by_src(_graph(), 1)
    feats = jax.random.normal(jax.random.PRNGKey(1), (1, V, F))
    fwd = jax.jit(lambda p, x, *e: gcn_forward_full(p, x, *e, cfg=cfg,
                                                    mesh=make_data_mesh(1)))
    args = (_params(jax.random.PRNGKey(0), cfg), feats, pg.src, pg.dst,
            pg.weights, pg.mask)
    return _op_names(fwd.lower(*args).compile().as_text())


@pytest.fixture(scope="module")
def programs(train):
    return {"train": _train_ops(train), "infer": _infer_ops()}


@pytest.mark.parametrize("program,expected", [
    ("train", SCOPES),
    # full-graph inference runs no chunk scan
    ("infer", ("gas.find", "gas.schedule", "gas.reduce", "gcn.combine")),
])
def test_each_scope_names_an_op(programs, program, expected):
    named = {m for n in programs[program] for m in SCOPE_RE.findall(n)}
    assert set(expected) <= named, sorted(named)


@pytest.mark.parametrize("program", ["train", "infer"])
def test_gas_scopes_never_nest(programs, program):
    nested = [n for n in programs[program]
              if len({m for m in SCOPE_RE.findall(n) if m in GAS}) > 1]
    assert not nested, nested[:5]


@pytest.mark.parametrize("program", ["train", "infer"])
def test_one_chip_programs_carry_no_exchange(programs, program):
    named = [n for n in programs[program]
             if cgtrans.EXCHANGE_SCOPE in n.split("/")]
    assert not named, named[:5]


def test_exchange_scope_is_never_entered_on_one_chip(train, monkeypatch):
    """On a one-device mesh no collective is traced, so the exchange scope
    is never entered: the one-chip programs are what they are without it."""
    step, state, feats, stream = train
    entered = []

    def counted():
        entered.append(1)
        return contextlib.nullcontext()

    monkeypatch.setattr(cgtrans, "_exchange", counted)
    jax.jit(step.__wrapped__).lower(state, stream.batch_at(0),
                                    feats).compile()
    _infer_ops()
    assert not entered


@pytest.mark.distributed
def test_exchange_scope_names_every_collective_on_four_chips():
    """Each all-gather and all-to-all of the sharded train step, and the
    transposed all-to-all of a gradient into the table, carries the scope
    (``tests/distributed_cases.py exchange_scope``, four CPU devices)."""
    assert "exchange scope ok" in run_distributed_case("exchange_scope",
                                                       devices=4)


def _host_events(path: str, names):
    """(name, start_ns, end_ns) of each event named in ``names``, by start."""
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted(((ev.name, ev.start_ns, ev.end_ns) for plane in pd.planes
                   for line in plane.lines for ev in line.events
                   if ev.name in names), key=lambda e: e[1])


def test_host_spans_one_per_step(train, tmp_path):
    step, state, feats, stream = train
    step(state, stream.batch_at(0), feats)          # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, done = train_loop(step_fn=lambda s, b: step(s, b, feats),
                             state=state, batches=iter(stream),
                             total_steps=2, log_every=0)
    finally:
        jax.profiler.stop_trace()
    assert done == 2
    path = next(tmp_path.rglob("*.xplane.pb"))
    events = _host_events(str(path), {"repro.data.sample", "repro.train.step",
                                      "repro.train.wait"})
    assert sorted(name for name, _, _ in events) == (
        ["repro.data.sample"] * 2 + ["repro.train.step"] * 2
        + ["repro.train.wait"] * 2)
    samples, steps, waits = ([e[1:] for e in events if e[0] == name]
                             for name in ("repro.data.sample",
                                          "repro.train.step",
                                          "repro.train.wait"))
    # the second batch is pulled while the first step runs, before its wait
    assert steps[0][0] <= samples[1][0] <= samples[1][1] <= waits[0][0]
    assert waits[0][1] <= steps[0][1]
    # the first batch is pulled before any step
    assert samples[0][1] <= steps[0][0]


@pytest.mark.parametrize("workers", [1, 2])
def test_sample_blocks_per_hop(train, tmp_path, monkeypatch, workers):
    """One draw holds ceil(rows / BLOCK_ROWS) block spans per hop, inside
    its ``repro.data.sample``; hop 2's blocks start after hop 1's end."""
    _, _, _, stream = train
    rows = 3
    monkeypatch.setattr(pipeline, "BLOCK_ROWS", rows)
    monkeypatch.setattr(pipeline, "MAX_WORKERS", workers)
    hop1 = stream.n_parts * stream.batch_per_part
    hop2 = hop1 * (1 + stream.k1)
    n1, n2 = -(-hop1 // rows), -(-hop2 // rows)
    jax.profiler.start_trace(str(tmp_path))
    try:
        stream.batch_at(0)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    events = _host_events(str(path), {"repro.data.sample",
                                      "repro.data.sample.block"})
    (sample,) = [e[1:] for e in events if e[0] == "repro.data.sample"]
    blocks = [e[1:] for e in events if e[0] == "repro.data.sample.block"]
    assert len(blocks) == n1 + n2 == 8
    assert all(sample[0] <= s <= e <= sample[1] for s, e in blocks)
    assert max(e for _, e in blocks[:n1]) <= min(s for s, _ in blocks[n1:])
