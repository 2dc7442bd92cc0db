"""The benchmark's graphs: Graph500 R-MAT, drawn from a configuration's fixed
seed and cached in the checkout.

The generator is the Graph500 Kronecker/R-MAT recursion (a=0.57, b=0.19,
c=0.19, d=0.05): for each of ``scale`` bits every edge picks one quadrant of
the adjacency matrix. The graph is the dataset: it depends on the
configuration alone, never on ``--seed``. It is stored with its edges sorted
by (source, destination), so a CSR view is a cumulative count away and every
vertex's neighbour list is sorted (the reference checks sampled neighbours
with one binary search).
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import NamedTuple

import numpy as np

A, B, C = 0.57, 0.19, 0.19
CACHE_DIR = Path(__file__).resolve().parents[1] / "graph_cache"


class Graph(NamedTuple):
    n_vertices: int
    src: np.ndarray      # (E,) int32, ascending
    dst: np.ndarray      # (E,) int32, ascending within each source

    @property
    def n_edges(self) -> int:
        return int(self.src.shape[0])

    def indptr(self) -> np.ndarray:
        """(V + 1,) int64 CSR offsets of the source-sorted edge list."""
        out = np.zeros(self.n_vertices + 1, np.int64)
        np.cumsum(np.bincount(self.src, minlength=self.n_vertices),
                  out=out[1:])
        return out


def rmat_edges(scale: int, edge_factor: int, seed: int):
    """(src, dst) int32 edge arrays of an R-MAT graph with 2**scale vertices
    and edge_factor * 2**scale edges, in generation order."""
    rng = np.random.default_rng(seed)
    m = edge_factor << scale
    src = np.zeros(m, np.int32)
    dst = np.zeros(m, np.int32)
    for bit in range(scale):
        r = rng.random(m, dtype=np.float32)
        down = r >= A + B                      # C or D quadrant: src bit
        right = ((r >= A) & (r < A + B)) | (r >= A + B + C)   # B or D: dst
        src |= down.astype(np.int32) << bit
        dst |= right.astype(np.int32) << bit
    return src, dst


def sort_edges(src: np.ndarray, dst: np.ndarray, n_vertices: int):
    """Edges sorted by (src, dst)."""
    key = np.sort(src.astype(np.int64) * n_vertices + dst)
    return ((key // n_vertices).astype(np.int32),
            (key % n_vertices).astype(np.int32))


def load_graph(name: str, scale: int, edge_factor: int, seed: int,
               cache_dir: Path = CACHE_DIR) -> Graph:
    """The configuration's graph, generated on the first call in a checkout
    and read back from ``cache_dir`` after that."""
    path = Path(cache_dir) / f"{name}.s{scale}.e{edge_factor}.g{seed}.npz"
    if path.exists():
        with np.load(path) as z:
            return Graph(1 << scale, z["src"], z["dst"])
    n = 1 << scale
    src, dst = sort_edges(*rmat_edges(scale, edge_factor, seed), n)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + f".tmp{os.getpid()}.npz")
    np.savez(tmp, src=src, dst=dst)
    os.replace(tmp, path)
    return Graph(n, src, dst)


def in_degree_weights(g: Graph) -> np.ndarray:
    """(E,) float32 edge weights 1 / in-degree(dst): the mean aggregation of
    full-graph GCN/SAGE layers."""
    deg = np.bincount(g.dst, minlength=g.n_vertices).astype(np.float32)
    return (1.0 / deg[g.dst]).astype(np.float32)
