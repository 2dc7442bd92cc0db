"""Entry ``train``: sampled 2-layer GraphSAGE training through the program's
own loop.

Set-up builds one object: the jitted ``make_sage_train_step`` with its
state, the owner-sharded table and the ``GraphBatchStream``. It drives that
object through the traffic's first ``check_steps`` steps with
``train_loop`` (these compile and warm every shape) and keeps what the
correctness check compares. The window hands the same state and stream to
``train_loop`` again, from the next batch on, until a timer ends it through
the program's ``PreemptionGuard.trigger()``.

The program's sampler draws the batches; the check holds them, entry by
entry, to the benchmark's own draw of the same steps (``draws.minibatch``),
and the reference trains on that draw.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import threading
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.config import TrainConfig
from repro.configs import graphic_gcn
from repro.data import GraphBatchStream
from repro.graph import COOGraph
from repro.optim import adamw_init
from repro.runtime import PreemptionGuard
from repro.train import make_sage_train_step, train_loop

from yard import draws, graph, reference, work


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class TrainCell:
    def __init__(self, ctx):
        cfg, tr = ctx.config, ctx.traffic
        self.ctx, self.mesh = ctx, ctx.mesh
        self.n_chips = self.mesh.shape["data"]
        m, gr = cfg["model"], cfg["graph"]
        self.B, self.k1, self.k2 = (tr["seeds_per_chip"], tr["fanout"][0],
                                    tr["fanout"][1])
        self.check_steps = tr["check_steps"]
        with ctx.span("graph"):
            self.g = graph.load_graph(cfg["name"], gr["scale"],
                                      gr["edge_factor"], gr["seed"],
                                      ctx.graph_cache)
            self.coo = COOGraph(self.g.n_vertices, self.g.src, self.g.dst)
        self.gcfg = dataclasses.replace(
            getattr(graphic_gcn, cfg["program"]["config"]),
            n_features=m["n_features"], hidden=m["hidden"],
            n_classes=m["n_classes"], fanout=self.k2)
        self.opt = cfg["optimizer"]
        self.tc = TrainConfig(**self.opt)
        self.step = jax.jit(make_sage_train_step(self.gcfg, self.tc,
                                                 mesh=self.mesh))
        self.shapes = draws.param_shapes(m["n_features"], m["hidden"],
                                         m["n_classes"])
        self.batch_sharding = NamedSharding(self.mesh, P("data"))
        n_params = sum(int(np.prod(s)) for s in self.shapes.values())
        self.work = work.sage_train_step(self.B, self.k1, self.k2,
                                         m["n_features"], m["hidden"],
                                         m["n_classes"], n_params)
        self.units_per_step = self.B * self.n_chips
        self.table = self.state = None

    # -- set-up: draws from the seed, then the first steps -----------------

    def prepare(self, seed: int) -> None:
        ctx, m = self.ctx, self.ctx.config["model"]
        self.table = self.state = None
        with ctx.span("table"):
            self.table = draws.make_table(seed, self.g.n_vertices,
                                          m["n_features"], self.mesh)
            self.labels = draws.make_labels(seed, self.g.n_vertices,
                                            m["n_classes"])
            params = draws.make_params(seed, self.shapes, self.mesh)
            jax.block_until_ready((self.table, params))
        self.params0 = {k: np.asarray(v) for k, v in params.items()}
        self.stream_seed = int(draws.seed_words(seed, 3)[2])
        with ctx.span("sampler"):
            self.stream = GraphBatchStream(
                self.coo, self.labels, n_parts=self.n_chips,
                batch_per_part=self.B, k1=self.k1, k2=self.k2,
                seed=self.stream_seed)
        self.sample_s: List[float] = []
        self.losses: List[jax.Array] = []
        # (step, host batch) of the program's draws that the check compares:
        # the check steps', then the window's last
        self.pulled: List[Tuple[int, Dict[str, np.ndarray]]] = []
        self.last_pulled = None
        self.check_states = []
        state = {"params": params, "opt": adamw_init(params, self.tc),
                 "step": jnp.zeros((), jnp.int32)}
        with ctx.span("compile_and_check_steps"):
            state, _ = train_loop(
                step_fn=self._check_call, state=state,
                batches=self._batches(0, keep=True),
                total_steps=self.check_steps, ckpt=None, log_every=0,
                log_fn=_log)
            jax.block_until_ready(state)
        self.state, self.check_losses = state, list(self.losses)
        self.next_batch = self.check_steps

    def _batches(self, start: int, keep: bool = False):
        i = start
        while True:
            t0 = time.perf_counter()
            with TraceAnnotation("bench.sample"):
                b = self.stream.batch_at(i)
            self.last_pulled = (i, b)
            if keep:
                self.pulled.append((i, b))
            with TraceAnnotation("bench.put"):
                b = jax.device_put(b, self.batch_sharding)
            self.sample_s.append(time.perf_counter() - t0)
            yield b
            i += 1

    def _call(self, state, batch):
        with TraceAnnotation("bench.dispatch"):
            state, metrics = self.step(state, batch, self.table)
        self.losses.append(metrics["total_loss"])
        return state, metrics

    def _check_call(self, state, batch):
        state, metrics = self._call(state, batch)
        self.check_states.append(state)
        return state, metrics

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float) -> Dict[str, float]:
        """Train until ``seconds`` have passed; the step in flight at the
        deadline completes and counts, with all of its time."""
        guard = PreemptionGuard(install=False)
        self.sample_s, self.losses = [], []
        compiles = self.step._cache_size()
        timer = threading.Timer(seconds, guard.trigger)
        with TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            timer.start()
            try:
                state, done = train_loop(
                    step_fn=self._call, state=self.state,
                    batches=self._batches(self.next_batch), total_steps=1 << 40,
                    ckpt=None, log_every=0, guard=guard, log_fn=_log)
                jax.block_until_ready(state)
            finally:
                timer.cancel()
            elapsed = time.perf_counter() - t0
        steps = len(self.losses)
        self.state, self.next_batch = state, self.next_batch + steps
        self.pulled.append(self.last_pulled)
        losses = np.asarray(jax.device_get(self.losses))
        return {"units": steps * self.units_per_step, "steps": steps,
                "elapsed_s": elapsed, "attempted": steps,
                "failed": int((~np.isfinite(losses)).sum()),
                "compiles_in_window": self.step._cache_size() - compiles}

    def e2e(self, w: Dict[str, float]) -> Dict[str, float]:
        return {"train_seeds_per_s": w["units"] / w["elapsed_s"]}

    # -- the comparison -----------------------------------------------------

    def program_readings(self):
        """The program's first-step losses, its first clipped gradient (from
        AdamW's first moment after step 1) and its parameters after the
        check steps, as float32 numpy."""
        b1 = self.opt["beta1"]
        losses = np.asarray(jax.device_get(self.check_losses), np.float64)
        m1 = self.check_states[0]["opt"]["m"]
        g1 = {k: np.asarray(v, np.float32) / (1 - b1) for k, v in m1.items()}
        p3 = {k: np.asarray(v, np.float32)
              for k, v in self.check_states[-1]["params"].items()}
        return {"losses": losses, "g1": g1, "params": p3}

    def free(self) -> None:
        """Drop the program's state and its loaded programs (a loaded step
        holds its temporaries' reservation on every chip), and keep on the
        host what the check compares; the table stays for the reference."""
        self.state = None
        self.check_states = [
            {"params": jax.device_get(s["params"]),
             "opt": {"m": jax.device_get(s["opt"]["m"])}}
            for s in self.check_states]
        jax.clear_caches()
        gc.collect()

    def draw(self, step: int, indptr: np.ndarray) -> Dict[str, np.ndarray]:
        """The benchmark's own minibatch of ``step``."""
        return draws.minibatch(self.stream_seed, step, indptr, self.g.dst,
                               self.labels, self.n_chips, self.B, self.k1,
                               self.k2)

    def reference_readings(self, dtype=jnp.float32, half=False,
                           exchange=True):
        shards = [s.data for s in sorted(
            self.table.addressable_shards, key=lambda s: s.index[0].start)]
        indptr = self.g.indptr()
        batches = [self.draw(i, indptr) for i in range(self.check_steps)]
        losses, g1, params = reference.sage_train(
            self.params0, shards, batches, self.labels, self.k1,
            self.opt, dtype, half=half, exchange=exchange)
        return {"losses": losses, "g1": g1, "params": params}

    def sample_mismatch(self) -> int:
        """Entries of the program's minibatches (the check steps' and the
        window's last) that differ from the benchmark's draw of the same
        step; a key missing or of another shape counts all its entries."""
        indptr, bad = self.g.indptr(), 0
        for step, got in self.pulled:
            for k, want in self.draw(step, indptr).items():
                g = np.asarray(got.get(k, ()))
                bad += (int(want.size) if g.shape != want.shape
                        else int((g != want).sum()))
        return bad

    def compare(self, got, ref) -> Dict[str, float]:
        keep = reference.moving_leaves(ref["g1"])
        d_got = {k: got["params"][k] - self.params0[k] for k in keep}
        d_ref = {k: ref["params"][k] - self.params0[k] for k in keep}
        return {
            "loss_gap": float(np.max(np.abs(got["losses"] - ref["losses"])
                                     / np.abs(ref["losses"]))),
            "grad1_gap": reference.worst_leaf_norm_gap(got["g1"], ref["g1"],
                                                       keep),
            "update_gap": reference.worst_leaf_norm_gap(d_got, d_ref, keep),
        }

    def checks(self) -> Dict[str, float]:
        out = self.compare(self.program_readings(), self.reference_readings())
        out["sample_mismatch"] = float(self.sample_mismatch())
        return out


def build(ctx) -> TrainCell:
    return TrainCell(ctx)
