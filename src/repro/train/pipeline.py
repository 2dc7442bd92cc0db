"""Training pipelines: GPipe stage parallelism + the graph-workload step.

Two entry points:

* ``pipelined_apply`` — pipeline parallelism over the ``pod`` axis (GPipe
  fill–drain schedule). At 512 chips none of the assigned configs *needs* PP
  (FSDP×TP fits them — see EXPERIMENTS §Dry-run), so this stage-parallel
  runner is off by default and exercised by tests. Stages = contiguous block
  ranges of the pattern-scan; the boundary transfer is a ``ppermute`` along
  ``pod``; microbatches stream through with a lax.scan (fill–drain = GPipe;
  jax autodiff differentiates through the ppermute, giving the reverse
  schedule for backward automatically). Composes with the data/model axes
  untouched: within a stage, everything keeps its FSDP×TP sharding.

* ``make_sage_train_step`` — the paper's workload as a jit-able pipeline
  stage: GraphSAGE + CGTrans loss/grad/AdamW against an owner-sharded
  feature table. This is where the FAST-GAS deployment knobs surface into
  training: ``cfg.impl`` (GAS backend for every per-shard aggregation),
  ``cfg.request_chunk`` (SSD command-queue depth for the sampled request
  stream), ``cfg.scheduled`` (the destination-binned locality pass that
  turns the kernel's idle-skip occupancy into a thin band; defaults on
  exactly when ``impl="pallas"``) and ``cfg.coalesce`` (the self-lookup +
  2-hop requests fused into ONE SSD command block — one all_to_all, one
  kernel gather, one backward cotangent scatter per step; on by default)
  ride in on the ``GCNConfig`` — all
  callers (``examples/train_graphsage.py``, the distributed test cases)
  build their step through here instead of hand-rolling the grad/update
  composition. The schedule serves forward AND backward: it is carried as a
  custom-VJP residual, so the reverse pass skips the same idle tiles.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.common.config import ModelConfig, TrainConfig
from repro.compat import shard_map


def make_sage_train_step(cfg, tc: TrainConfig, *,
                         mesh: Optional[Mesh] = None,
                         relabel=None) -> Callable:
    """(state, batch, feats) → (state, metrics) for GraphSAGE + CGTrans
    training.

    ``cfg`` is a ``repro.core.gcn.GCNConfig`` — its ``dataflow``, ``impl``,
    ``request_chunk`` and ``scheduled`` fields select the transmission
    dataflow, the GAS backend, the request-stream chunking and the
    idle-skip locality scheduling for every aggregation in the step.
    ``feats`` is the owner-sharded (P, part, F) feature table (the storage
    tier) and ``state`` is ``{"params", "opt", "step"}``. The table is an
    ARGUMENT of the step, never closed over: ``jax.jit`` embeds a
    closed-over array in the program as a constant, which at a real table
    size is a program of hundreds of MB.

    ``impl="pallas"`` trains end-to-end: the FAST-GAS kernel carries custom
    VJPs (``repro.core.gas``) whose backward is itself in-SSD GAS work — a
    backward scatter through the kernel for the gathers, a masked weighted
    gather for the scatter — so the reverse pass never leaves the regime
    the forward models. Per-step gradient parity with ``impl="xla"`` is
    locked in by ``tests/test_cgtrans_grad.py``.

    With ``cfg.partition="island"``, ``feats`` must be the islandized table
    (``IslandPartition.relabel_rows`` order) and ``relabel`` the old→new id
    map; every batch's caller-visible ids are translated at the
    ``sage_loss`` entry (islandized ≡ interval bit-exact, grads included).
    """
    from repro.core.gcn import sage_loss
    from repro.optim import adamw_update

    def train_step(state, batch, feats):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: sage_loss(p, feats, batch, cfg, mesh=mesh,
                                relabel=relabel),
            has_aux=True)(state["params"])
        new_p, new_opt, om = adamw_update(state["params"], grads,
                                          state["opt"], tc)
        return ({"params": new_p, "opt": new_opt, "step": state["step"] + 1},
                {**metrics, **om, "total_loss": loss})

    return train_step


def split_stages(n_blocks: int, n_stages: int) -> Tuple[Tuple[int, int], ...]:
    """Contiguous block ranges per stage, balanced to ±1."""
    base, extra = divmod(n_blocks, n_stages)
    out = []
    start = 0
    for s in range(n_stages):
        size = base + (1 if s < extra else 0)
        out.append((start, start + size))
        start += size
    return tuple(out)


def pipelined_apply(
    block_fn: Callable,      # (x, block_params) -> x
    params_stacked,          # pytree, leading dim = n_blocks
    x: jax.Array,            # (n_micro, mB, S, D) microbatched activations
    *,
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run the stacked blocks as a pipeline over ``axis``.

    Every pod holds ALL stacked params (they are already FSDP-sharded over
    data; the pod axis replicates them) but only *executes* its own stage's
    slice, selected by ``lax.axis_index``. Schedule: n_micro + n_stages - 1
    ticks; at each tick a pod processes the microbatch it holds (if valid)
    and ppermutes its output to the next pod.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    n_blocks = jax.tree.leaves(params_stacked)[0].shape[0]
    ranges = split_stages(n_blocks, n_stages)
    max_len = max(e - s for s, e in ranges)

    def stage_fn(xi, stage_idx):
        """Run this pod's block range on one microbatch."""
        def body(x, i):
            bp = jax.tree.map(lambda a: a[i], params_stacked)
            return block_fn(x, bp), None

        start = jnp.asarray([r[0] for r in ranges])[stage_idx]
        length = jnp.asarray([r[1] - r[0] for r in ranges])[stage_idx]

        def step(carry, j):
            x = carry
            i = start + jnp.minimum(j, length - 1)
            bp = jax.tree.map(lambda a: a[i], params_stacked)
            y = block_fn(x, bp)
            x = jnp.where(j < length, y, x)
            return x, None

        xi, _ = lax.scan(step, xi, jnp.arange(max_len))
        return xi

    def shard_fn(params_stacked, x):
        stage = lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(x[0])
        outs = jnp.zeros_like(x)

        def tick(carry, t):
            buf, outs = carry
            mb_in = t                     # microbatch entering stage 0 at tick t
            take = (stage == 0) & (mb_in < n_micro)
            inp = jnp.where(take, x[jnp.minimum(mb_in, n_micro - 1)], buf)
            # valid iff this pod currently holds microbatch (t - stage)
            holds = (t >= stage) & (t - stage < n_micro)
            y = stage_fn(inp, stage)
            y = jnp.where(holds, y, inp)
            # last stage writes its finished microbatch
            done_mb = t - stage
            write = holds & (stage == n_stages - 1)
            outs = lax.cond(
                write,
                lambda o: lax.dynamic_update_slice(
                    o, y[None], (jnp.maximum(done_mb, 0),) + (0,) * y.ndim),
                lambda o: o, outs)
            # pass forward along the pipeline
            nxt = lax.ppermute(y, axis,
                               [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outs), None

        (_, outs), _ = lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # only the last stage wrote results; psum broadcasts them to all pods
        outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return lax.psum(outs, axis)

    other = tuple(a for a in mesh.axis_names if a != axis)
    return shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P()),   # params + activations replicated over pod
        out_specs=P(),
        check_vma=False,
    )(params_stacked, x)
