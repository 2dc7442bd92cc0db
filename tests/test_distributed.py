"""Multi-device behaviour via subprocesses (8 fake CPU devices each).

Each case lives in tests/distributed_cases.py and sets XLA_FLAGS before
importing jax — keeping this pytest process on the real 1-device topology.

All cases carry the ``distributed`` marker; deselect the ~4-minute subprocess
suite with ``-m "not distributed"``.
"""

import pytest

# the runner lives in tests/_dist.py (shared with conftest.py's session
# fixture for test_cgtrans_pallas.py)
from _dist import run_distributed_case as _run

pytestmark = pytest.mark.distributed


def test_cgtrans_equivalence():
    assert "ok" in _run("cgtrans_equivalence")


def test_cgtrans_pallas_parity(pallas_parity_report):
    """impl="pallas" ≡ impl="xla" ≡ single-shard reference across the full
    (dataflow × op × path) matrix on the real 8-way mesh — see
    tests/test_cgtrans_pallas.py for the per-cell breakdown."""
    assert "cgtrans pallas parity ok" in pallas_parity_report


def test_cgtrans_grad_parity(grad_parity_report):
    """jax.grad through impl="pallas" ≡ impl="xla" ≡ single-shard reference
    across (dataflow × op × path × chunking) on the real 8-way mesh, plus
    the 3-step pallas-vs-xla train parity — see tests/test_cgtrans_grad.py
    for the per-cell breakdown."""
    assert "cgtrans grad parity ok" in grad_parity_report


def test_cgtrans_collective_bytes_compression():
    out = _run("cgtrans_collective_bytes")
    assert "ratio" in out


def test_embedding_cgtrans():
    assert "ok" in _run("embedding_cgtrans")


def test_elastic_checkpoint():
    assert "ok" in _run("elastic_checkpoint")


def test_distributed_sage_training():
    assert "ok" in _run("distributed_sage_training")


def test_pipeline_parallel():
    assert "ok" in _run("pipeline_parallel")


def test_sharded_sage_matches_plain_reference():
    """One deployed train step on a 4-chip owner-sharded table equals a plain
    float32 jax.numpy GraphSAGE on the same batch, loss and gradients."""
    assert "ok" in _run("sharded_sage_reference", devices=4)


def test_sharded_sage_without_exchange_fails_the_comparison():
    """The same comparison refuses a step in which each chip aggregates only
    the rows it owns."""
    assert "caught ok" in _run("sharded_sage_no_exchange", devices=4)
