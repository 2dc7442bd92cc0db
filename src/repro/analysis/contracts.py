"""Declarative dataflow contracts: the communication/dispatch budget of every
public entrypoint, committed as data and verified against an abstract trace.

A ``DataflowContract`` pins, for one entrypoint configuration
(dataflow × impl × coalesce × scheduled):

* the exact **collective counts** its trace issues — canonical primitive
  names via ``repro.compat`` (``psum_scatter`` whatever the installed JAX
  spells it, ``psum`` even when the shard_map checker rewrites it), counted
  by ``launch/jaxpr_stats`` so combiner/DCE passes can't blur them;
* the exact **GAS dispatch budget** — ``find`` (table gathers), ``reduce``
  (seed reductions), ``kernel_scatter`` (pallas dispatches), via the
  trace-time ``gas.count_dispatches`` counters;
* the **forward vs. forward+backward split** — ``forward`` budgets the
  plain trace, ``fwd_bwd`` budgets ``jax.grad`` through it (the backward of
  the in-SSD dataflow is also in-SSD work: its scatters and collectives are
  part of the claim);
* the **dtype waivers** — which ``analysis.dtype_flow`` rules this
  entrypoint intentionally relaxes, with the justification in ``note``
  (e.g. ``embed_lookup``'s bf16 transport).

Verification is ABSTRACT: ``build()`` returns the function plus
``jax.ShapeDtypeStruct`` arguments, and ``verify_contract`` runs
``jax.make_jaxpr`` — no FLOP executes, no mesh hardware is needed beyond
the fake-device topology (``XLA_FLAGS=--xla_force_host_platform_device_count
=8``, which ``scripts/lint.py`` sets before importing jax). Budgets are
EXACT including implicit zeros: a collective the budget doesn't name must
not appear at all.

The ``SAGE_FETCH_*`` tables double as the single source of truth for the
request-coalescing claim — ``tests/test_cgtrans_coalesce.py``,
``tests/distributed_cases.py`` and
``benchmarks/collective_bytes.py::check_coalesce_rows`` import them instead
of repeating the numbers. Amending a budget is a one-line diff here, seen
by every consumer at once (see README "Static contracts" for when that's
legitimate).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

from repro.analysis.dtype_flow import check_dtype_flow

#: trace-time GAS dispatch counters (see ``repro.core.gas``)
DISPATCH_KEYS = ("find", "reduce", "kernel_scatter")

# ---------------------------------------------------------------------------
# the coalescing headline budgets (imported by tests + benches)
# ---------------------------------------------------------------------------

#: collectives per step of the sage-shaped fetch (K=1 self-lookup + 2-hop
#: block) on the sharded cgtrans dataflow: the separate two-stream form vs
#: the coalesced ``aggregate_multi`` command block — the "one SSD command
#: block" claim, 2 → 1 of each kind
SAGE_FETCH_COLLECTIVES: Dict[str, Dict[str, int]] = {
    "separate": {"all_gather": 2, "all_to_all": 2},
    "coalesced": {"all_gather": 1, "all_to_all": 1},
}

#: forward GAS dispatches of the same pair: finds 2 → 1 (one combined table
#: gather); the K=1 segment stays a pure find either way, so exactly one
#: seed reduction runs in both forms
SAGE_FETCH_DISPATCH: Dict[str, Dict[str, int]] = {
    "separate": {"find": 2, "reduce": 1},
    "coalesced": {"find": 1, "reduce": 1},
}

#: pallas forward+backward kernel dispatches: the separate form pays one
#: fused forward scatter + TWO backward cotangent scatters (one per
#: gather); coalesced pays one forward + ONE backward
SAGE_FETCH_KERNEL_SCATTERS_FWD_BWD: Dict[str, int] = {
    "separate": 3, "coalesced": 2,
}

#: the SERVING headline (``repro.serving.ServingEngine``): one drained batch
#: of N concurrent requests — each a K=1 self-row lookup segment + a fan-out
#: aggregation segment, tenant-tagged — fuses into ONE command block whose
#: collective count is INDEPENDENT of N, where the one-query-one-dispatch
#: baseline pays the same pair PER QUERY. Collectives-per-query: 1/N vs 1.
SERVE_FETCH_COLLECTIVES: Dict[str, Dict[str, int]] = {
    "fused": {"all_gather": 1, "all_to_all": 1},          # per DRAIN, any N
    "naive_per_query": {"all_gather": 1, "all_to_all": 1},  # per QUERY
}

#: GAS finds of the same pair: the fused drain issues ONE combined table
#: gather for every segment of every caller; the naive baseline issues one
#: per query. (Each caller's fan-out segment still reduces separately —
#: reduces scale with N in BOTH forms, finds do not.)
SERVE_FETCH_FINDS: Dict[str, int] = {
    "fused": 1,                 # per drain, any N
    "naive_per_query": 1,       # per query
}

#: concurrency of the committed serving contract fixtures (the bench and the
#: serving tier assert the same N so the three surfaces can't drift)
SERVE_CONTRACT_N = 8


@dataclasses.dataclass(frozen=True)
class DataflowContract:
    """One entrypoint configuration's committed budget.

    ``build`` is lazy (imports the dataflow modules, constructs the mesh and
    the abstract arguments) and returns ``(fn, args)``; gradients for
    ``fwd_bwd`` are taken with respect to ``args[0]`` through the summed
    float outputs. ``forward``/``fwd_bwd`` map canonical collective names
    and ``DISPATCH_KEYS`` to exact counts — unnamed keys mean ZERO.
    """
    name: str
    build: Callable[[], Tuple[Callable, tuple]]
    forward: Mapping[str, int]
    fwd_bwd: Optional[Mapping[str, int]] = None
    dtype_waivers: Tuple[str, ...] = ()
    note: str = ""

    def __post_init__(self):
        from repro.launch.jaxpr_stats import COLLECTIVE_PRIMITIVES
        legal = set(COLLECTIVE_PRIMITIVES) | set(DISPATCH_KEYS)
        for tag, budget in (("forward", self.forward),
                            ("fwd_bwd", self.fwd_bwd)):
            for k in (budget or {}):
                if k not in legal:
                    raise ValueError(
                        f"{self.name}: unknown budget key {k!r} in {tag} "
                        f"(canonical collectives: "
                        f"{sorted(COLLECTIVE_PRIMITIVES)}; dispatches: "
                        f"{DISPATCH_KEYS})")


def _scalarize(fn):
    """Sum every inexact output leaf to a f32 scalar so ``jax.grad`` can
    differentiate an arbitrary entrypoint with respect to ``args[0]``."""
    import jax
    import jax.numpy as jnp

    def loss(*args):
        leaves = jax.tree_util.tree_leaves(fn(*args))
        return sum(jnp.sum(leaf.astype(jnp.float32)) for leaf in leaves
                   if jnp.issubdtype(leaf.dtype, jnp.inexact))
    return loss


def verify_contract(contract: DataflowContract) -> List[str]:
    """Trace the entrypoint abstractly and check it against its budget.

    Returns failure strings (empty = the contract holds). Each failure names
    the contract, the pass (forward / fwd+bwd), and the key with
    expected-vs-observed — that exact line is what a refactor that adds a
    collective will see in CI.
    """
    import jax

    from repro.core import gas
    from repro.launch.jaxpr_stats import (COLLECTIVE_PRIMITIVES,
                                          canonicalize_collectives,
                                          count_primitives)

    fn, args = contract.build()
    failures: List[str] = []
    for tag, budget in (("forward", contract.forward),
                        ("fwd+bwd", contract.fwd_bwd)):
        if budget is None:
            continue
        target = fn if tag == "forward" else jax.grad(_scalarize(fn))
        try:
            with gas.count_dispatches() as disp:
                jaxpr = jax.make_jaxpr(target)(*args)
        except Exception as e:  # noqa: BLE001 — a non-tracing entrypoint is
            failures.append(f"{contract.name} [{tag}] failed to trace: {e!r}")
            continue            # itself a contract violation, not a crash
        observed = canonicalize_collectives(count_primitives(jaxpr))
        for key in COLLECTIVE_PRIMITIVES:
            want, got = int(budget.get(key, 0)), int(observed[key])
            if want != got:
                failures.append(
                    f"{contract.name} [{tag}] collective {key}: "
                    f"budget {want}, traced {got}")
        for key in DISPATCH_KEYS:
            want, got = int(budget.get(key, 0)), int(disp[key])
            if want != got:
                failures.append(
                    f"{contract.name} [{tag}] dispatch {key}: "
                    f"budget {want}, counted {got}")
        for issue in check_dtype_flow(jaxpr, waive=contract.dtype_waivers):
            failures.append(f"{contract.name} [{tag}] dtype {issue}")
    return failures


def verify_all(names: Optional[Sequence[str]] = None
               ) -> Dict[str, List[str]]:
    """Verify every registered contract (or the named subset); returns
    name → failures for the ones that failed."""
    out: Dict[str, List[str]] = {}
    for name in (names if names is not None else CONTRACTS):
        fails = verify_contract(CONTRACTS[name])
        if fails:
            out[name] = fails
    return out


# ---------------------------------------------------------------------------
# abstract argument builders (shared shapes; ShapeDtypeStructs are passed as
# ARGUMENTS of the traced function, never closed over — closing over an
# abstract value breaks tracing inside jnp.where et al.)
# ---------------------------------------------------------------------------

_WAYS = 8                 # the fake-device data mesh every sharded budget
_PART, _F = 32, 64        # uses (scripts/lint.py forces the topology)
_B, _K1, _K2 = 8, 3, 10
_R1 = _B * (1 + _K1)      # rows of the sage-shaped 2-hop block


def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype)


def _fetch_blocks():
    """The sage-shaped request pair: K=1 all-valid self-lookup + fan-out
    2-hop block (the exact pair ``sage_forward`` coalesces)."""
    import jax.numpy as jnp
    feats = _sds((_WAYS, _PART, _F), jnp.float32)
    b1 = (_sds((_WAYS, _R1, 1), jnp.int32), _sds((_WAYS, _R1, 1), jnp.bool_))
    b2 = (_sds((_WAYS, _R1, _K2), jnp.int32),
          _sds((_WAYS, _R1, _K2), jnp.bool_))
    return feats, b1, b2


#: static packed width of the sparse contract fixtures: 16 + 2 bitmap words
#: < _F=64, so the ``sparse_fits`` gate passes and the sparse path traces
_SPARSE_CAP = 16


def _build_sampled(flow: str, impl: str, scheduled: bool, wire: str = "f32",
                   features: str = "dense"):
    def build():
        from repro.core import cgtrans
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(_WAYS)
        feats, _, (nb2, mk2) = _fetch_blocks()

        def fn(f, nb, mk):
            return cgtrans.aggregate_sampled(
                f, nb, mk, mesh=mesh, dataflow=flow, impl=impl,
                scheduled=scheduled, wire=wire, features=features,
                sparse_capacity=_SPARSE_CAP if features == "sparse" else None)
        return fn, (feats, nb2, mk2)
    return build


def _build_multi(flow: str, impl: str, scheduled: bool, wire: str = "f32",
                 features: str = "dense"):
    def build():
        from repro.core import cgtrans
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(_WAYS)
        feats, b1, b2 = _fetch_blocks()

        def fn(f, blocks):
            return cgtrans.aggregate_multi(
                f, blocks, mesh=mesh, dataflow=flow, impl=impl,
                scheduled=scheduled, wire=wire, features=features,
                sparse_capacity=_SPARSE_CAP if features == "sparse" else None)
        return fn, (feats, (b1, b2))
    return build


def _build_separate_fetch(flow: str, impl: str):
    """The UN-coalesced twin of ``_build_multi``: the same request pair
    issued as two ``aggregate_sampled`` streams — the baseline side of the
    2 → 1 claim, contracted so the *pair* of budgets is pinned."""
    def build():
        from repro.core import cgtrans
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(_WAYS)
        feats, b1, b2 = _fetch_blocks()

        def fn(f, blocks):
            (nb1, mk1), (nb2, mk2) = blocks
            return (cgtrans.aggregate_sampled(f, nb1, mk1, mesh=mesh,
                                              dataflow=flow, impl=impl),
                    cgtrans.aggregate_sampled(f, nb2, mk2, mesh=mesh,
                                              dataflow=flow, impl=impl))
        return fn, (feats, (b1, b2))
    return build


def _serve_blocks(n_requests: int):
    """The serving-engine drain fixture: ``n_requests`` concurrent
    single-seed callers, each contributing a K=1 self-row lookup segment +
    a fan-out aggregation segment (the exact block layout
    ``ServingEngine._build_blocks`` emits, one row per shard after its
    pad-to-shard-multiple step)."""
    import jax.numpy as jnp
    feats = _sds((_WAYS, _PART, _F), jnp.float32)
    blocks = []
    for _ in range(n_requests):
        blocks.append((_sds((_WAYS, 1, 1), jnp.int32),
                       _sds((_WAYS, 1, 1), jnp.bool_)))
        blocks.append((_sds((_WAYS, 1, _K2), jnp.int32),
                       _sds((_WAYS, 1, _K2), jnp.bool_)))
    return feats, tuple(blocks)


def _build_serving_fused(impl: str, n_requests: int, wire: str = "f32"):
    def build():
        from repro.core import cgtrans
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(_WAYS)
        feats, blocks = _serve_blocks(n_requests)

        def fn(f, blocks_):
            return cgtrans.aggregate_multi(f, blocks_, mesh=mesh,
                                           dataflow="cgtrans", impl=impl,
                                           wire=wire)
        return fn, (feats, blocks)
    return build


def _build_serving_naive(impl: str, n_requests: int):
    """The one-query-one-dispatch twin: the SAME segment pairs issued as
    one command block per caller."""
    def build():
        from repro.core import cgtrans
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(_WAYS)
        feats, blocks = _serve_blocks(n_requests)

        def fn(f, blocks_):
            outs = []
            for j in range(n_requests):
                outs.extend(cgtrans.aggregate_multi(
                    f, blocks_[2 * j:2 * j + 2], mesh=mesh,
                    dataflow="cgtrans", impl=impl))
            return tuple(outs)
        return fn, (feats, blocks)
    return build


def _sage_cfg_batch(impl: str, coalesce: bool, scheduled: bool):
    import jax
    import jax.numpy as jnp
    from repro.common.schema import init_params
    from repro.core.gcn import GCNConfig, gcn_schema
    B, K1, K2, F = 4, 3, 5, 16
    cfg = GCNConfig(n_features=F, hidden=8, n_classes=4, fanout=K2,
                    impl=impl, coalesce=coalesce, scheduled=scheduled)
    params = jax.tree_util.tree_map(
        lambda a: _sds(jnp.shape(a), a.dtype),
        init_params(gcn_schema(cfg), jax.random.PRNGKey(0)))
    batch = {
        "seeds": _sds((_WAYS, B), jnp.int32),
        "nbrs1": _sds((_WAYS, B, K1), jnp.int32),
        "mask1": _sds((_WAYS, B, K1), jnp.bool_),
        "nbrs2": _sds((_WAYS, B * (1 + K1), K2), jnp.int32),
        "mask2": _sds((_WAYS, B * (1 + K1), K2), jnp.bool_),
    }
    feats = _sds((_WAYS, _PART, F), jnp.float32)
    return cfg, params, feats, batch


def _build_sage(impl: str, coalesce: bool, scheduled: bool):
    def build():
        from repro.core.gcn import sage_forward
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(_WAYS)
        cfg, params, feats, batch = _sage_cfg_batch(impl, coalesce, scheduled)

        def fn(p, f, b):
            return sage_forward(p, f, b, cfg, mesh=mesh)
        return fn, (params, feats, batch)
    return build


def _build_train_step(impl: str, coalesce: bool, scheduled: bool):
    def build():
        import jax
        import jax.numpy as jnp
        from repro.common.config import TrainConfig
        from repro.common.schema import init_params
        from repro.core.gcn import GCNConfig, gcn_schema
        from repro.launch.mesh import make_data_mesh
        from repro.optim import adamw_init
        from repro.train import make_sage_train_step
        mesh = make_data_mesh(_WAYS)
        cfg, _, _, batch = _sage_cfg_batch(impl, coalesce, scheduled)
        batch = dict(batch, labels=_sds((_WAYS, 4), jnp.int32))
        tc = TrainConfig(learning_rate=1e-3)
        params = init_params(gcn_schema(cfg), jax.random.PRNGKey(0))
        state = jax.tree_util.tree_map(
            lambda a: _sds(jnp.shape(a), jnp.result_type(a)),
            {"params": params, "opt": adamw_init(params, tc),
             "step": jnp.zeros((), jnp.int32)})
        step = make_sage_train_step(cfg, tc, mesh=mesh)
        feats = _sds((_WAYS, _PART, cfg.n_features), jnp.float32)
        return step, (state, batch, feats)
    return build


def _build_embed(cgtrans: bool, impl: str):
    def build():
        import jax.numpy as jnp
        from repro.launch.mesh import make_test_mesh
        from repro.models.embedding import embed_lookup
        mesh = make_test_mesh(2, 4)          # data=2 × model=4 storage tier
        table = _sds((64, 16), jnp.float32)  # vocab 64 → 16/model-shard
        ids = _sds((4, 8), jnp.int32)

        def fn(tab, ids_):
            return embed_lookup(tab, ids_, mesh=mesh, cgtrans=cgtrans,
                                impl=impl)
        return fn, (table, ids)
    return build


def _build_edges(flow: str, impl: str, op: str, wire: str = "f32",
                 features: str = "dense"):
    def build():
        import jax.numpy as jnp
        from repro.core import cgtrans
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(_WAYS)
        E = 512
        args = (_sds((_WAYS, _PART, _F), jnp.float32),
                _sds((_WAYS, E), jnp.int32), _sds((_WAYS, E), jnp.int32),
                _sds((_WAYS, E), jnp.float32), _sds((_WAYS, E), jnp.bool_))

        def fn(f, src, dst, w, m):
            return cgtrans.aggregate_edges(
                f, src, dst, w, m, mesh=mesh, dataflow=flow, impl=impl,
                op=op, wire=wire, features=features,
                sparse_capacity=_SPARSE_CAP if features == "sparse" else None)
        return fn, args
    return build


# ---------------------------------------------------------------------------
# the registry: dataflow × impl × coalesce × scheduled
# ---------------------------------------------------------------------------

def _merge(*parts: Mapping[str, int]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for p in parts:
        for k, v in p.items():
            out[k] = out.get(k, 0) + v
    return out


CONTRACTS: Dict[str, DataflowContract] = {}


def _register(c: DataflowContract):
    if c.name in CONTRACTS:
        raise ValueError(f"duplicate contract {c.name}")
    CONTRACTS[c.name] = c


# -- aggregate_sampled: one fan-out-K request stream -------------------------
# cgtrans: ONE all_gather (request broadcast) + ONE all_to_all (compressed
# result shipment). baseline ships raw rows: one extra all_to_all. The
# backward retraces the forward collectives and adds the cotangent
# shipment; pallas adds the kernel-scatter dispatches (fwd fused scatter +
# bwd cotangent scatter) and the tie-count psums of the max/min-capable VJP.
_SAMPLED_FWD = {
    "cgtrans": {"all_gather": 1, "all_to_all": 1, "find": 1, "reduce": 1},
    "baseline": {"all_gather": 1, "all_to_all": 2, "find": 1, "reduce": 1},
}
_SAMPLED_BWD = {       # fwd+bwd budgets, xla backend
    "cgtrans": {"all_gather": 1, "all_to_all": 2, "find": 1, "reduce": 1},
    "baseline": {"all_gather": 1, "all_to_all": 3, "find": 1, "reduce": 1},
}
_SAMPLED_BWD_PALLAS = {
    "cgtrans": {"all_gather": 1, "all_to_all": 2, "psum": 2,
                "find": 1, "reduce": 2, "kernel_scatter": 2},
    "baseline": {"all_gather": 1, "all_to_all": 3, "psum": 2,
                 "find": 1, "reduce": 2, "kernel_scatter": 2},
}

for _flow in ("cgtrans", "baseline"):
    for _impl in ("xla", "pallas"):
        _ks = {"kernel_scatter": 1} if _impl == "pallas" else {}
        for _sched in ((False, True) if _impl == "pallas" else (False,)):
            _register(DataflowContract(
                name=(f"aggregate_sampled/{_flow}/{_impl}"
                      + ("/sched" if _sched else "")),
                build=_build_sampled(_flow, _impl, _sched),
                forward=_merge(_SAMPLED_FWD[_flow], _ks),
                fwd_bwd=(None if _sched else
                         _SAMPLED_BWD_PALLAS[_flow] if _impl == "pallas"
                         else _SAMPLED_BWD[_flow]),
                note="scheduled is collective- and dispatch-neutral: the "
                     "banded walk reorders kernel rounds, never traffic"
                     if _sched else ""))

# -- aggregate_multi: the coalesced SSD command block ------------------------
# budgets COMPOSED from the exported SAGE_FETCH tables so the registry and
# the external consumers can never disagree
_MULTI_BWD = {          # fwd+bwd, xla: forward collectives + cotangent a2a
    "cgtrans": {"all_gather": 1, "all_to_all": 2, "find": 1, "reduce": 1},
    "baseline": {"all_gather": 1, "all_to_all": 3, "find": 1, "reduce": 2},
}
_MULTI_BWD_PALLAS = {
    "cgtrans": _merge({"all_gather": 1, "all_to_all": 2, "psum": 2},
                      {"find": 1, "reduce": 2},
                      {"kernel_scatter":
                       SAGE_FETCH_KERNEL_SCATTERS_FWD_BWD["coalesced"]}),
    "baseline": {"all_gather": 1, "all_to_all": 3, "psum": 3,
                 "find": 1, "reduce": 3, "kernel_scatter": 3},
}
_MULTI_FWD = {
    "cgtrans": _merge(SAGE_FETCH_COLLECTIVES["coalesced"],
                      SAGE_FETCH_DISPATCH["coalesced"]),
    "baseline": {"all_gather": 1, "all_to_all": 2, "find": 1, "reduce": 2},
}
_SEP_FWD = {
    "cgtrans": _merge(SAGE_FETCH_COLLECTIVES["separate"],
                      SAGE_FETCH_DISPATCH["separate"]),
    "baseline": {"all_gather": 2, "all_to_all": 4, "find": 2, "reduce": 2},
}

for _flow in ("cgtrans", "baseline"):
    for _impl in ("xla", "pallas"):
        _ks1 = {"kernel_scatter": 1 if _flow == "cgtrans" else 2} \
            if _impl == "pallas" else {}
        for _sched in ((False, True) if _impl == "pallas" else (False,)):
            _register(DataflowContract(
                name=(f"aggregate_multi/{_flow}/{_impl}"
                      + ("/sched" if _sched else "")),
                build=_build_multi(_flow, _impl, _sched),
                forward=_merge(_MULTI_FWD[_flow], _ks1),
                fwd_bwd=(None if _sched else
                         _MULTI_BWD_PALLAS[_flow] if _impl == "pallas"
                         else _MULTI_BWD[_flow])))
        _register(DataflowContract(
            name=f"separate_fetch/{_flow}/{_impl}",
            build=_build_separate_fetch(_flow, _impl),
            forward=_merge(_SEP_FWD[_flow],
                           {"kernel_scatter": 1 if _flow == "cgtrans" else 2}
                           if _impl == "pallas" else {}),
            fwd_bwd=None,
            note="the UN-coalesced twin of aggregate_multi — the pair pins "
                 "the 2 → 1 coalescing claim as two committed budgets"))

# -- serving_fetch: the cross-request fused drain ----------------------------
# the online engine's headline as a lint-time budget: a drain of
# SERVE_CONTRACT_N concurrent callers traces ONE all_gather + ONE
# all_to_all + ONE find — collectives- and finds-per-query 1/N — while the
# one-query-one-dispatch twin pays the full pair N times. Reduces (and
# pallas kernel scatters) are per fan-out segment in BOTH forms: batching
# amortizes the *transmission*, never the per-caller aggregation math.
# Forward-only: serving is inference (no training family differentiates it).
for _impl in ("xla", "pallas"):
    _ksN = ({"kernel_scatter": SERVE_CONTRACT_N}
            if _impl == "pallas" else {})
    _register(DataflowContract(
        name=f"serving_fetch/fused/{_impl}",
        build=_build_serving_fused(_impl, SERVE_CONTRACT_N),
        forward=_merge(SERVE_FETCH_COLLECTIVES["fused"],
                       {"find": SERVE_FETCH_FINDS["fused"],
                        "reduce": SERVE_CONTRACT_N}, _ksN),
        note=f"one drain of N={SERVE_CONTRACT_N} tenant-tagged request "
             f"pairs — the collective pair is N-independent"))
    _register(DataflowContract(
        name=f"serving_fetch/naive/{_impl}",
        build=_build_serving_naive(_impl, SERVE_CONTRACT_N),
        forward=_merge(
            {k: v * SERVE_CONTRACT_N
             for k, v in SERVE_FETCH_COLLECTIVES["naive_per_query"].items()},
            {"find": SERVE_FETCH_FINDS["naive_per_query"] * SERVE_CONTRACT_N,
             "reduce": SERVE_CONTRACT_N}, _ksN),
        note="the one-query-one-dispatch twin: every caller pays the full "
             "collective pair — the fused/naive budgets pin the serving "
             "ratio as committed data"))

# -- sage_forward: the deployed 2-layer fetch --------------------------------
_SAGE_FWD = {
    True: _merge(SAGE_FETCH_COLLECTIVES["coalesced"],
                 SAGE_FETCH_DISPATCH["coalesced"]),
    False: _merge(SAGE_FETCH_COLLECTIVES["separate"],
                  SAGE_FETCH_DISPATCH["separate"]),
}
for _coal in (True, False):
    _form = "coalesced" if _coal else "separate"
    for _impl in ("xla", "pallas"):
        # only the fan-out segment scatters forward (the K=1 self-lookup
        # stays a pure find), so BOTH forms pay exactly one fwd dispatch
        _ks = {"kernel_scatter": 1} if _impl == "pallas" else {}
        for _sched in ((False, True) if _impl == "pallas" else (False,)):
            _register(DataflowContract(
                name=(f"sage_forward/{_form}/{_impl}"
                      + ("/sched" if _sched else "")),
                build=_build_sage(_impl, _coal, _sched),
                forward=_merge(_SAGE_FWD[_coal], _ks),
                # grad w.r.t. PARAMS (args[0]) — the feature cotangent is
                # never requested, so the backward re-ships nothing and the
                # fwd+bwd budget equals the forward one (same invariant the
                # train-step contracts pin)
                fwd_bwd=None if _sched else _merge(_SAGE_FWD[_coal], _ks)))

# -- make_sage_train_step: the full step (grad + AdamW inside) ---------------
# the step differentiates with respect to PARAMS only — feats is a closed
# constant — so the backward adds no fetch collectives: the forward fetch
# budget IS the step budget (plus the pallas forward kernel scatter)
_TRAIN = {
    (True, "xla"): _SAGE_FWD[True],
    (False, "xla"): _SAGE_FWD[False],
    (True, "pallas"): _merge(_SAGE_FWD[True], {"kernel_scatter": 1}),
    (False, "pallas"): _merge(_SAGE_FWD[False], {"kernel_scatter": 1}),
}
for _coal in (True, False):
    _form = "coalesced" if _coal else "separate"
    for _impl in ("xla", "pallas"):
        for _sched in ((False, True) if _impl == "pallas" else (False,)):
            _register(DataflowContract(
                name=(f"train_step/{_form}/{_impl}"
                      + ("/sched" if _sched else "")),
                build=_build_train_step(_impl, _coal, _sched),
                forward=_TRAIN[(_coal, _impl)],
                note="grad w.r.t. params only — feats is a closed-over "
                     "constant, so the backward re-ships nothing"))

# -- embed_lookup: the model-axis storage tier -------------------------------
_register(DataflowContract(
    name="embed_lookup/cgtrans/xla",
    build=_build_embed(True, "xla"),
    forward={"psum": 1},
    fwd_bwd={"psum": 2},
    dtype_waivers=("accum", "narrow-wire"),
    note="bf16 transport by design (compute_dtype=bfloat16): the psum of "
         "bf16 partials is the compressed-wire precursor the ROADMAP "
         "tracks — transport narrow, accumulate-at-owner; waiver documents "
         "it instead of hiding it"))
_register(DataflowContract(
    name="embed_lookup/cgtrans/pallas",
    build=_build_embed(True, "pallas"),
    forward={"psum": 1},
    fwd_bwd={"psum": 2, "reduce": 1, "kernel_scatter": 1},
    dtype_waivers=("accum", "narrow-wire"),
    note="same bf16-transport waiver; the VJP GAS-scatters the cotangent "
         "at the owner shard through the FAST-GAS kernel"))
_register(DataflowContract(
    name="embed_lookup/baseline/xla",
    build=_build_embed(False, "xla"),
    forward={},
    dtype_waivers=("accum",),
    note="plain sharded take — GSPMD materializes table shards at compile "
         "time, so the jaxpr carries zero explicit collectives (the bytes "
         "show up in the HLO benches instead)"))

# -- aggregate_edges: the full-graph COO dataflow ----------------------------
# cgtrans add rides the fused reduce-scatter (canonical name psum_scatter
# WHATEVER the installed JAX calls the primitive); compare ops ship
# per-destination partials over all_to_all; baseline ships all three edge
# streams raw (3 all_gathers)
_EDGES_FWD = {
    ("cgtrans", "add"): {"psum_scatter": 1, "find": 1, "reduce": 1},
    ("cgtrans", "max"): {"all_to_all": 1, "find": 1, "reduce": 1},
    ("baseline", "add"): {"all_gather": 3, "find": 1, "reduce": 1},
    ("baseline", "max"): {"all_gather": 3, "find": 1, "reduce": 1},
}
for _flow in ("cgtrans", "baseline"):
    for _op in ("add", "max"):
        for _impl in ("xla", "pallas"):
            _ks = {"kernel_scatter": 1} if _impl == "pallas" else {}
            _register(DataflowContract(
                name=f"aggregate_edges/{_flow}/{_op}/{_impl}",
                build=_build_edges(_flow, _impl, _op),
                forward=_merge(_EDGES_FWD[(_flow, _op)], _ks)))

# -- compressed wire variants (repro.core.wire) ------------------------------
# the narrow wire changes BYTES, never budgets: each variant's collective
# and dispatch counts equal its f32 twin's (the codec wraps the same one
# all_to_all, forward and backward — custom_vjp, cotangents take the same
# wire; the delta-encoded id stream rides the same one all_gather). The ONE
# exception is aggregate_edges op="add": quantized codes cannot sum on a
# psum_scatter wire (int8 codes carry per-row scales), so the narrow wire
# ships over all_to_all and accumulates in f32 locally — psum_scatter 1→0,
# all_to_all 0→1, pinned here as its own budget. Every variant declares its
# narrowness via the narrow-wire waiver — extend the waiver, never the rule.
_WIRE_NOTE = ("narrow transport by design (repro.core.wire): int16 delta "
              "ids on the all_gather, {w} partials on the all_to_all, f32 "
              "accumulation on arrival — same budget as the f32 twin")
for _w in ("bf16", "int8"):
    _register(DataflowContract(
        name=f"aggregate_sampled/cgtrans/xla/{_w}",
        build=_build_sampled("cgtrans", "xla", False, wire=_w),
        forward=_SAMPLED_FWD["cgtrans"],
        fwd_bwd=_SAMPLED_BWD["cgtrans"],
        dtype_waivers=("narrow-wire",),
        note=_WIRE_NOTE.format(w=_w)))
    _register(DataflowContract(
        name=f"aggregate_multi/cgtrans/xla/{_w}",
        build=_build_multi("cgtrans", "xla", False, wire=_w),
        forward=_MULTI_FWD["cgtrans"],
        fwd_bwd=_MULTI_BWD["cgtrans"],
        dtype_waivers=("narrow-wire",),
        note=_WIRE_NOTE.format(w=_w)))
    _register(DataflowContract(
        name=f"aggregate_edges/cgtrans/add/xla/{_w}",
        build=_build_edges("cgtrans", "xla", "add", wire=_w),
        forward={"all_to_all": 1, "find": 1, "reduce": 1},
        dtype_waivers=("narrow-wire",),
        note="the one budget a narrow wire changes: quantized partials "
             "cannot sum ON the wire, so psum_scatter 1→0 / all_to_all "
             "0→1 with local f32 accumulation — same bytes shape, ÷2 or "
             "÷4 the width"))
_register(DataflowContract(
    name="aggregate_multi/cgtrans/pallas/bf16",
    build=_build_multi("cgtrans", "pallas", False, wire="bf16"),
    forward=_merge(_MULTI_FWD["cgtrans"], {"kernel_scatter": 1}),
    fwd_bwd=_MULTI_BWD_PALLAS["cgtrans"],
    dtype_waivers=("narrow-wire",),
    note="the kernel path under the narrow wire: codec wraps the "
         "collective only, so the FAST-GAS dispatch budget (fwd scatter + "
         "bwd cotangent scatter) is untouched"))
_register(DataflowContract(
    name="serving_fetch/fused/xla/bf16",
    build=_build_serving_fused("xla", SERVE_CONTRACT_N, wire="bf16"),
    forward=_merge(SERVE_FETCH_COLLECTIVES["fused"],
                   {"find": SERVE_FETCH_FINDS["fused"],
                    "reduce": SERVE_CONTRACT_N}),
    dtype_waivers=("narrow-wire",),
    note=f"the serving drain on the bf16 wire (ServingEngine(wire=)): "
         f"N={SERVE_CONTRACT_N} fused callers, collective pair still "
         f"N-independent, bytes halved"))

# -- compressed-sparse feature variants (repro.core.sparse) ------------------
# like the narrow wire, the format changes BYTES, never budgets: the sparse
# gather is two takes instead of one (both inside the SAME ticked find) and
# the baseline raw-row shipment packs (nonzeros ‖ bitmap) through the SAME
# one all_to_all — so every sparse variant's collective and dispatch counts
# equal its dense twin's, forward AND backward (the sparse-gather VJP
# scatters the dense cotangent with the identical reduce/kernel_scatter
# pattern, and _sparse_all_to_all's VJP ships the dense cotangent over one
# all_to_all exactly like the dense transpose).
_SPARSE_NOTE = ("compressed-sparse features by design (repro.core.sparse): "
                "packed nonzeros + int32 occupancy bitmap on the {leg}, "
                "static capacity {cap} of F={f} — same budget as the dense "
                "twin")
_register(DataflowContract(
    name="aggregate_sampled/cgtrans/xla/sparse",
    build=_build_sampled("cgtrans", "xla", False, features="sparse"),
    forward=_SAMPLED_FWD["cgtrans"],
    fwd_bwd=_SAMPLED_BWD["cgtrans"],
    note=_SPARSE_NOTE.format(leg="table gather", cap=_SPARSE_CAP, f=_F)))
_register(DataflowContract(
    name="aggregate_sampled/cgtrans/pallas/sparse",
    build=_build_sampled("cgtrans", "pallas", False, features="sparse"),
    forward=_merge(_SAMPLED_FWD["cgtrans"], {"kernel_scatter": 1}),
    fwd_bwd=_SAMPLED_BWD_PALLAS["cgtrans"],
    note=_SPARSE_NOTE.format(leg="table gather", cap=_SPARSE_CAP, f=_F)
         + "; the sparse-gather VJP scatters through the FAST-GAS kernel "
           "like the dense pallas gather"))
_register(DataflowContract(
    name="aggregate_sampled/baseline/xla/sparse",
    build=_build_sampled("baseline", "xla", False, features="sparse"),
    forward=_SAMPLED_FWD["baseline"],
    fwd_bwd=_SAMPLED_BWD["baseline"],
    note=_SPARSE_NOTE.format(leg="table gather AND the raw-row all_to_all",
                             cap=_SPARSE_CAP, f=_F)))
_register(DataflowContract(
    name="aggregate_multi/cgtrans/xla/sparse",
    build=_build_multi("cgtrans", "xla", False, features="sparse"),
    forward=_MULTI_FWD["cgtrans"],
    fwd_bwd=_MULTI_BWD["cgtrans"],
    note=_SPARSE_NOTE.format(leg="combined table gather", cap=_SPARSE_CAP,
                             f=_F)))
_register(DataflowContract(
    name="aggregate_edges/cgtrans/add/xla/sparse",
    build=_build_edges("cgtrans", "xla", "add", features="sparse"),
    forward=_EDGES_FWD[("cgtrans", "add")],
    note=_SPARSE_NOTE.format(leg="edge-source gather", cap=_SPARSE_CAP, f=_F)
         + "; partials have UNION support so the psum_scatter shipment "
           "stays dense — unlike the narrow wire, add keeps its budget"))
_register(DataflowContract(
    name="aggregate_sampled/baseline/xla/sparse-bf16",
    build=_build_sampled("baseline", "xla", False, wire="bf16",
                         features="sparse"),
    forward=_SAMPLED_FWD["baseline"],
    fwd_bwd=_SAMPLED_BWD["baseline"],
    dtype_waivers=("narrow-wire",),
    note="the composition the formats were built for: baseline + narrow "
         "wire is ONLY legal with sparse features (packed nonzeros "
         "quantize like partials — bf16 codes + bitcast bitmap lanes on "
         "the raw-row all_to_all), still the dense twin's budget"))


#: every (entrypoint, dataflow-or-form, impl) the meta-test asserts coverage
#: for — adding a config to a dataflow without registering its contract
#: fails tests/test_analysis.py, not code review
def covered_configurations() -> List[str]:
    return sorted(CONTRACTS)
