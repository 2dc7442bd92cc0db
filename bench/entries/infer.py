"""Entry ``infer``: full-graph 2-layer inference through the program's
``gcn_forward_full``, pass after pass, over every edge of the graph.

Each pass is one call of the jitted ``gcn_forward_full`` as the program
offers it: it builds the destination-binned edge schedule, gathers every
edge's source row and reduces through the FAST-GAS kernel, layer by layer.
Set-up compiles and runs two passes; the window runs passes back to back,
waiting for each, until the deadline. The check compares the logits of
every vertex from two of the window's passes (the last one and one drawn
from the seed) with the plain reference, by their relative L2 gap.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import graphic_gcn
from repro.core.gcn import gcn_forward_full
from repro.graph import COOGraph, partition_by_src

from yard import draws, graph, reference, work


class InferCell:
    def __init__(self, ctx):
        cfg = ctx.config
        self.ctx, self.mesh = ctx, ctx.mesh
        m, gr = cfg["model"], cfg["graph"]
        assert self.mesh.shape["data"] == 1, "full-graph cells run on 1 chip"
        with ctx.span("graph"):
            self.g = graph.load_graph(cfg["name"], gr["scale"],
                                      gr["edge_factor"], gr["seed"],
                                      ctx.graph_cache)
            self.w = graph.in_degree_weights(self.g)
            pg = partition_by_src(COOGraph(self.g.n_vertices, self.g.src,
                                           self.g.dst, self.w), 1)
            dev = NamedSharding(self.mesh, P())
            self.edges = jax.device_put(
                (pg.src, pg.dst, pg.weights, pg.mask), dev)
        gcfg = dataclasses.replace(
            getattr(graphic_gcn, cfg["program"]["config"]),
            n_features=m["n_features"], hidden=m["hidden"],
            n_classes=m["n_classes"])
        self.fwd = jax.jit(functools.partial(gcn_forward_full, cfg=gcfg,
                                             mesh=self.mesh))
        self.shapes = draws.param_shapes(m["n_features"], m["hidden"],
                                         m["n_classes"])
        self.work = work.gcn_full_pass(self.g.n_vertices, self.g.n_edges,
                                       m["n_features"], m["hidden"],
                                       m["n_classes"])
        self.units_per_step = 2 * self.g.n_edges
        self.table = None

    def prepare(self, seed: int) -> None:
        ctx, m = self.ctx, self.ctx.config["model"]
        self.table = self.kept = self.last = None
        with ctx.span("table"):
            self.table = draws.make_table(seed, self.g.n_vertices,
                                          m["n_features"], self.mesh)
            self.params = draws.make_params(seed, self.shapes, self.mesh)
            jax.block_until_ready((self.table, self.params))
        # which window pass the check keeps besides the last one
        self.keep_pass = int(draws.seed_words(seed, 5)[4] % 3)
        with ctx.span("compile_and_warm"):
            for _ in range(2):
                jax.block_until_ready(self._pass())

    def _pass(self):
        with TraceAnnotation("bench.dispatch"):
            return self.fwd(self.params, self.table, *self.edges)

    def window(self, seconds: float) -> Dict[str, float]:
        passes, compiles = 0, self.fwd._cache_size()
        with TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            while True:
                out = self._pass()
                out.block_until_ready()
                if passes == self.keep_pass:
                    self.kept = out
                passes += 1
                if time.perf_counter() >= deadline:
                    break
            elapsed = time.perf_counter() - t0
        self.last = out
        if self.kept is None:
            self.kept = out
        finite = bool(jnp.isfinite(out).all())
        return {"units": passes * self.units_per_step, "steps": passes,
                "elapsed_s": elapsed, "attempted": passes,
                "failed": 0 if finite else passes,
                "compiles_in_window": self.fwd._cache_size() - compiles}

    def e2e(self, w: Dict[str, float]) -> Dict[str, float]:
        return {"infer_edges_per_s": w["units"] / w["elapsed_s"]}

    def free(self) -> None:
        """Keep the two checked outputs on the host; drop the rest."""
        self.kept = np.asarray(self.kept[0])
        self.last = np.asarray(self.last[0])

    def reference_logits(self, dtype=jnp.float32) -> np.ndarray:
        return np.asarray(reference.gcn_logits(
            self.params, self.table[0], self.g.src, self.g.dst, self.w,
            dtype), np.float32)

    def compare(self, outs, ref) -> Dict[str, float]:
        """The largest relative L2 gap of all logits over ``outs``."""
        return {"logit_l2_gap": max(reference.rel_l2_gap(o, ref)
                                    for o in outs)}

    def checks(self) -> Dict[str, float]:
        return self.compare((self.last, self.kept), self.reference_logits())


def build(ctx) -> InferCell:
    return InferCell(ctx)
