"""Deterministic data pipelines.

* ``TokenStream``    — synthetic LM token batches, deterministic in
  (seed, step, host), resumable from any step (stateless indexing — the
  fault-tolerance property: a restarted trainer regenerates the exact batch).
* ``ShardedTokenFiles`` — file-backed token shards + manifest (the production
  path): writer + resumable reader with per-host sharding.
* ``GraphBatchStream`` — GraphSAGE minibatches (seed ids + sampled 1/2-hop
  neighborhoods + labels) from a COO graph; ships only ids (CGTrans keeps raw
  features on the storage tier).
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Iterator, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.graph.structure import COOGraph


@dataclasses.dataclass
class TokenStream:
    vocab: int
    batch: int
    seq_len: int
    seed: int = 0
    host: int = 0
    n_hosts: int = 1
    with_frames: int = 0      # whisper: frame-embedding stub (enc_seq)
    with_vision: int = 0      # vlm: patch-embedding stub (vision_seq)
    d_model: int = 0

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, step, self.host]))
        toks = rng.integers(0, self.vocab, (self.batch, self.seq_len + 1),
                            dtype=np.int32)
        out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if self.with_frames:
            out["frames"] = rng.standard_normal(
                (self.batch, self.with_frames, self.d_model)).astype(np.float32)
        if self.with_vision:
            out["vision"] = rng.standard_normal(
                (self.batch, self.with_vision, self.d_model)).astype(np.float32)
        return out

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1


class ShardedTokenFiles:
    """npy token shards + JSON manifest; deterministic resumable reads."""

    def __init__(self, root: str):
        self.root = root
        self.manifest_path = os.path.join(root, "manifest.json")

    @staticmethod
    def write(root: str, tokens: np.ndarray, shard_size: int = 1 << 16) -> None:
        os.makedirs(root, exist_ok=True)
        shards = []
        for i in range(0, len(tokens), shard_size):
            name = f"shard_{i // shard_size:05d}.npy"
            np.save(os.path.join(root, name), tokens[i:i + shard_size])
            shards.append(name)
        tmp = os.path.join(root, ".manifest.tmp")
        with open(tmp, "w") as f:
            json.dump({"shards": shards, "total": len(tokens)}, f)
        os.replace(tmp, os.path.join(root, "manifest.json"))

    def reader(self, batch: int, seq_len: int, *, start_step: int = 0,
               host: int = 0, n_hosts: int = 1) -> Iterator[Dict[str, np.ndarray]]:
        with open(self.manifest_path) as f:
            manifest = json.load(f)
        data = np.concatenate(
            [np.load(os.path.join(self.root, s)) for s in manifest["shards"]])
        data = data.reshape(-1)
        span = batch * (seq_len + 1)
        step = start_step
        while True:
            off = ((step * n_hosts + host) * span) % max(len(data) - span, 1)
            chunk = data[off:off + span].reshape(batch, seq_len + 1).astype(np.int32)
            yield {"tokens": chunk[:, :-1], "labels": chunk[:, 1:]}
            step += 1


# Rows of one block of a hop's neighbour draw. At fan-out 50 a block's
# float64 and int64 temporaries are 3.3 MB each: small enough for the heap
# to hand back from one block to the next, where a whole hop's (84 MB each
# at 208,896 rows) are mapped and page-faulted in anew for every batch.
BLOCK_ROWS = 8192
# The most threads that draw one hop's blocks at once; the host's other
# cores stay with the JAX runtime.
MAX_WORKERS = 8

_pool: Optional[ThreadPoolExecutor] = None
_pool_lock = threading.Lock()


def _executor() -> ThreadPoolExecutor:
    """The process's sampler pool, made on first use (its threads start as
    work is handed to them)."""
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(os.cpu_count() or 1,
                                       thread_name_prefix="repro-sample")
        return _pool


def _each_block(fn: Callable[[int], None], starts: range) -> None:
    """``fn(start)`` for every block start, on ``min(blocks, cores,
    MAX_WORKERS)`` threads; one worker runs them on the calling thread.
    numpy releases the GIL in the random fill, the ufuncs and ``take``."""
    workers = min(len(starts), os.cpu_count() or 1, MAX_WORKERS)
    if workers <= 1:
        for start in starts:
            fn(start)
        return

    def share(w: int) -> None:
        for start in starts[w::workers]:
            fn(start)

    for f in [_executor().submit(share, w) for w in range(workers)]:
        f.result()


@dataclasses.dataclass
class GraphBatchStream:
    """Minibatch sampler for 2-layer GraphSAGE (ids only on the wire)."""

    graph: COOGraph
    labels: np.ndarray            # (V,) int32 class labels
    n_parts: int                  # data-axis shards (seed sharding)
    batch_per_part: int
    k1: int = 10
    k2: int = 10
    seed: int = 0

    def __post_init__(self):
        self.indptr, self.indices, _ = self.graph.to_csr()

    def _sample(self, rng, seeds: np.ndarray, k: int):
        """k neighbours of each vertex of ``seeds``, uniform over its
        out-edges with replacement; a vertex with none repeats itself,
        masked off. Drawn in row blocks of ``BLOCK_ROWS``, each inside the
        host span ``repro.data.sample.block``: the block that starts at row
        ``a`` takes ``rng``'s uniforms from the ``a * k``-th on (one PCG64
        output per float64), so the result is the one serial draw of all
        ``n * k`` uniforms, after which ``rng`` is moved past them."""
        n, k, rows = seeds.shape[0], int(k), BLOCK_ROWS
        nbrs = np.empty((n, k), np.int32)
        mask = np.empty((n, k), np.bool_)
        state = rng.bit_generator.state

        def block(start: int) -> None:
            with TraceAnnotation("repro.data.sample.block"):
                end = min(start + rows, n)
                own = seeds[start:end]
                lo = self.indptr[own]
                deg = self.indptr[own + 1] - lo
                bits = np.random.PCG64()
                bits.state = state
                bits.advance(start * k)
                offs = np.random.Generator(bits).random((end - start, k))
                offs *= np.maximum(deg, 1)[:, None]
                idx = offs.astype(np.int64)
                idx += lo[:, None]
                # mode="clip" is the clamp at E - 1 (idx is never negative)
                out = nbrs[start:end]
                np.take(self.indices, idx, out=out, mode="clip")
                has = deg > 0
                mask[start:end] = has[:, None]
                none = np.flatnonzero(~has)
                out[none] = own[none, None]

        _each_block(block, range(0, n, rows))
        rng.bit_generator.advance(n * k)
        return nbrs, mask

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        """The minibatch of ``step``, drawn inside the host span
        ``repro.data.sample``."""
        with TraceAnnotation("repro.data.sample"):
            rng = np.random.default_rng(
                np.random.SeedSequence([self.seed, step]))
            P, B = self.n_parts, self.batch_per_part
            seeds = rng.integers(0, self.graph.n_vertices,
                                 (P, B)).astype(np.int32)
            flat = seeds.reshape(-1)
            n1, m1 = self._sample(rng, flat, self.k1)
            lay1 = np.concatenate([flat[:, None], n1], axis=1).reshape(-1)
            n2, m2 = self._sample(rng, lay1, self.k2)
            return {
                "seeds": seeds,
                "nbrs1": n1.reshape(P, B, self.k1),
                "mask1": m1.reshape(P, B, self.k1),
                "nbrs2": n2.reshape(P, B * (1 + self.k1), self.k2),
                "mask2": m2.reshape(P, B * (1 + self.k1), self.k2),
                "labels": self.labels[seeds].astype(np.int32),
            }

    def __iter__(self):
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1


def synthetic_node_labels(feats: np.ndarray, n_classes: int, seed: int = 0) -> np.ndarray:
    """Learnable labels: argmax of a fixed random projection of features."""
    rng = np.random.default_rng(seed)
    proj = rng.standard_normal((feats.shape[1], n_classes)).astype(np.float32)
    return np.argmax(feats @ proj, axis=1).astype(np.int32)
