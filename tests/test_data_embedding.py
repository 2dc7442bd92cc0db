"""Data pipelines + embedding/CE: determinism, resume, vocab-pad masking."""

import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import (GraphBatchStream, ShardedTokenFiles, TokenStream,
                        pipeline, synthetic_node_labels)
from repro.graph import COOGraph, uniform_graph
from repro.models.embedding import chunked_softmax_xent, logits_matmul


def test_token_stream_determinism_and_resume():
    s1 = TokenStream(vocab=100, batch=4, seq_len=16, seed=7)
    s2 = TokenStream(vocab=100, batch=4, seq_len=16, seed=7)
    b5a = s1.batch_at(5)
    b5b = s2.batch_at(5)
    np.testing.assert_array_equal(b5a["tokens"], b5b["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(s1.batch_at(0)["labels"][:, :-1],
                                  s1.batch_at(0)["tokens"][:, 1:])
    # host disjointness
    h0 = TokenStream(vocab=100, batch=4, seq_len=16, seed=7, host=0).batch_at(3)
    h1 = TokenStream(vocab=100, batch=4, seq_len=16, seed=7, host=1).batch_at(3)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_sharded_token_files_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 1000, 10000).astype(np.int32)
    ShardedTokenFiles.write(str(tmp_path), tokens, shard_size=2048)
    r = ShardedTokenFiles(str(tmp_path))
    it = r.reader(batch=2, seq_len=32)
    b0 = next(it)
    assert b0["tokens"].shape == (2, 32)
    np.testing.assert_array_equal(b0["tokens"][0], tokens[:32])
    # resume from step 3 == reading 4th batch fresh
    it2 = r.reader(batch=2, seq_len=32, start_step=3)
    fresh = ShardedTokenFiles(str(tmp_path)).reader(batch=2, seq_len=32)
    for _ in range(3):
        next(fresh)
    np.testing.assert_array_equal(next(it2)["tokens"], next(fresh)["tokens"])


def test_graph_batch_stream_shapes_and_determinism():
    g = uniform_graph(200, 2000, seed=1, n_features=8)
    labels = synthetic_node_labels(g.features, 5)
    st = GraphBatchStream(g, labels, n_parts=4, batch_per_part=8, k1=3, k2=2)
    b = st.batch_at(4)
    assert b["seeds"].shape == (4, 8)
    assert b["nbrs1"].shape == (4, 8, 3)
    assert b["nbrs2"].shape == (4, 8 * 4, 2)
    assert b["labels"].shape == (4, 8)
    np.testing.assert_array_equal(b["seeds"], st.batch_at(4)["seeds"])
    assert not np.array_equal(b["seeds"], st.batch_at(5)["seeds"])


def _serial_sample(indptr, indices, rng, seeds, k):
    """The one serial neighbour draw of a hop, as ``_sample`` made it
    before it drew in row blocks: the reference the blocked draw must
    reproduce bit for bit."""
    lo = indptr[seeds]
    hi = indptr[seeds + 1]
    deg = (hi - lo).astype(np.int64)
    offs = (rng.random((len(seeds), k)) * np.maximum(deg, 1)[:, None]).astype(np.int64)
    idx = np.minimum(lo[:, None] + offs, len(indices) - 1)
    nbrs = indices[idx].astype(np.int32)
    mask = np.broadcast_to(deg[:, None] > 0, nbrs.shape)
    nbrs = np.where(mask, nbrs, seeds[:, None].astype(np.int32))
    return nbrs, mask


def _serial_batch(st, step):
    rng = np.random.default_rng(np.random.SeedSequence([st.seed, step]))
    P, B = st.n_parts, st.batch_per_part
    seeds = rng.integers(0, st.graph.n_vertices, (P, B)).astype(np.int32)
    flat = seeds.reshape(-1)
    n1, m1 = _serial_sample(st.indptr, st.indices, rng, flat, st.k1)
    lay1 = np.concatenate([flat[:, None], n1], axis=1).reshape(-1)
    n2, m2 = _serial_sample(st.indptr, st.indices, rng, lay1, st.k2)
    return {
        "seeds": seeds,
        "nbrs1": n1.reshape(P, B, st.k1),
        "mask1": m1.reshape(P, B, st.k1),
        "nbrs2": n2.reshape(P, B * (1 + st.k1), st.k2),
        "mask2": m2.reshape(P, B * (1 + st.k1), st.k2),
        "labels": st.labels[seeds].astype(np.int32),
    }


def _isolated_graph(n_vertices, n_edges, seed):
    """Edges only out of the lower half of the vertices: the upper half,
    the last vertex among them, has degree 0."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_vertices // 2, n_edges, dtype=np.int32)
    dst = rng.integers(0, n_vertices, n_edges, dtype=np.int32)
    return COOGraph(n_vertices, src, dst)


def _assert_same_batch(got, want):
    assert got.keys() == want.keys()
    for key, w in want.items():
        g = got[key]
        assert g.shape == w.shape and g.dtype == w.dtype, key
        np.testing.assert_array_equal(g, w, err_msg=key)


# (graph, n_parts, batch_per_part, k1, k2, BLOCK_ROWS): hop rows P·B and
# P·B·(1 + k1) against the block size
_BLOCK_CASES = {
    # 30 and 150 rows: 3 and 12 blocks of 13, each hop's last one partial
    "partial-last-block": (lambda: uniform_graph(300, 3000, seed=2), 3, 10,
                           4, 4, 13),
    # 24 and 96 rows in one block each: drawn on the calling thread
    "single-block": (lambda: uniform_graph(300, 3000, seed=3), 4, 6, 3, 3,
                     pipeline.BLOCK_ROWS),
    # half the vertices, the last among them, have no out-edge
    "degree-0": (lambda: _isolated_graph(200, 800, seed=4), 2, 16, 5, 5, 8),
    # hop 1 draws 7 per row, hop 2 draws 2
    "k1-ne-k2": (lambda: uniform_graph(300, 3000, seed=5), 2, 9, 7, 2, 10),
}


@pytest.mark.parametrize("workers", [1, 2, pipeline.MAX_WORKERS])
@pytest.mark.parametrize("case", sorted(_BLOCK_CASES))
def test_graph_batch_stream_blocks_are_exact(case, workers, monkeypatch):
    """Every block size and worker count draws the serial draw's batch, entry
    by entry, on several steps."""
    make, P, B, k1, k2, rows = _BLOCK_CASES[case]
    monkeypatch.setattr(pipeline, "BLOCK_ROWS", rows)
    monkeypatch.setattr(pipeline, "MAX_WORKERS", workers)
    g = make()
    labels = np.arange(g.n_vertices, dtype=np.int32) % 7
    st = GraphBatchStream(g, labels, n_parts=P, batch_per_part=B, k1=k1,
                          k2=k2, seed=2 ** 40 + 11)
    for step in (0, 1, 9):
        _assert_same_batch(st.batch_at(step), _serial_batch(st, step))
    if case == "degree-0":
        isolated = st.batch_at(0)["seeds"] >= g.n_vertices // 2
        assert isolated.any() and (~isolated).any()


def test_graph_batch_stream_concurrent_callers(monkeypatch):
    """Callers on more threads than cores share the pool and each gets its
    own exact batch."""
    monkeypatch.setattr(pipeline, "BLOCK_ROWS", 7)
    g = uniform_graph(300, 3000, seed=6)
    labels = np.zeros(g.n_vertices, np.int32)
    st = GraphBatchStream(g, labels, n_parts=2, batch_per_part=12, k1=4,
                          k2=3, seed=5)
    n_threads = 2 * (pipeline.os.cpu_count() or 1) + 1
    got, errors = {}, []

    def pull(step):
        try:
            got[step] = st.batch_at(step)
        except Exception as e:      # surfaced by the assertion below
            errors.append(e)

    threads = [threading.Thread(target=pull, args=(i,))
               for i in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert sorted(got) == list(range(n_threads))
    for step, batch in got.items():
        _assert_same_batch(batch, _serial_batch(st, step))


def test_chunked_ce_matches_naive(rng):
    B, S, D, V = 2, 24, 8, 40
    x = jnp.asarray(rng.standard_normal((B, S, D)).astype(np.float32))
    table = jnp.asarray(rng.standard_normal((V, D)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, V, (B, S)).astype(np.int32))
    labels = labels.at[0, :3].set(-1)   # padding
    loss_sum, cnt = chunked_softmax_xent(x, table, labels, max_chunk=5)
    logits = jnp.einsum("bsd,vd->bsv", x, table)
    logp = jax.nn.log_softmax(logits, -1)
    mask = np.asarray(labels) >= 0
    want = -np.asarray(logp)[np.arange(B)[:, None], np.arange(S)[None], np.maximum(np.asarray(labels), 0)]
    np.testing.assert_allclose(float(loss_sum), want[mask].sum(), rtol=1e-5)
    assert float(cnt) == mask.sum()


def test_vocab_pad_masked(rng):
    D, V, Vpad = 8, 37, 64
    x = jnp.asarray(rng.standard_normal((2, D)).astype(np.float32))
    table = jnp.asarray(rng.standard_normal((Vpad, D)).astype(np.float32))
    logits = logits_matmul(x, table, valid_vocab=V)
    assert np.all(np.asarray(logits)[:, V:] < -1e29)
    assert np.all(np.isfinite(np.asarray(logits)[:, :V]))
