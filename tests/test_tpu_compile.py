"""The FAST-GAS kernels compile for TPU v5e — without the chip.

The TPU compiler is installed with JAX and compiles for a described,
unattached topology, so Mosaic's refusals (block shapes off the (8, 128)
tiling, operand layouts, unsupported vector casts, SMEM/VMEM overruns)
fail here instead of on the chip. Each case compiles one dispatch of the
main path, NOT interpreted, at the widths ``chip_smoke.py`` runs: Reddit's
F=602 (padded to 640 by the wrapper) and the narrow F=32 (padded to 128),
at the edge count of one ``request_chunk=16`` burst of fan-out 50 and of a
whole unchunked 64-seed step.

The topology is described inside a module fixture (never at import time):
only one process at a time may load the TPU library, and only the test
worker that runs this file should try.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.gas_scatter import kernel as K
from repro.kernels.gas_scatter import ops

HBM_BYTES = 16 * 1024 ** 3          # one v5e chip

#: (edges, destination rows) per dispatch: one chunk of 16 seeds × fan-out
#: 50, and a whole 64-seed step's 2-hop block (64·51 seeds × 50) unchunked
DISPATCHES = {"chunk": (16 * 50, 16), "step": (64 * 51 * 50, 64 * 51)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies  # lint: allow(compat-door): describes the chip to compile for; no version drift to absorb
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache  # lint: allow(compat-door): the cache switch the compile rehearsal needs
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _schedule_shapes(E: int, n_rows: int, sds):
    T = -(-E // K.EDGE_TILE)
    n_blocks = -(-n_rows // K.ROW_BLOCK)
    return ops.EdgeSchedule(perm=sds((E,), jnp.int32),
                            blk_min=sds((T,), jnp.int32),
                            blk_max=sds((T,), jnp.int32),
                            work=sds((T + 2 * n_blocks, 4), jnp.int32))


def _dispatch(path: str, op: str, n_rows: int):
    """The wrapper call a dispatch of ``path`` makes, interpret off."""
    if path == "banded":
        def fn(dst, vals, w, mask, sched):
            return ops.gas_scatter_fused(dst, vals, w if op == "add" else None,
                                         mask, n_rows, op=op, schedule=sched,
                                         interpret=False)
    elif op == "add":       # the unscheduled fused fallback, weighted
        def fn(dst, vals, w, mask, sched):
            return ops.gas_scatter_fused(dst, vals, w, mask, n_rows, op=op,
                                         interpret=False)
    else:                   # the plain dense dispatch (or/max rewrites)
        def fn(dst, vals, w, mask, sched):
            return ops.gas_scatter(dst, vals, n_rows, op=op, interpret=False)
    return fn


@pytest.mark.parametrize("dispatch", sorted(DISPATCHES))
@pytest.mark.parametrize("F", [602, 32])
@pytest.mark.parametrize("path,op", [("banded", "add"), ("banded", "max"),
                                     ("dense", "add"), ("dense", "max")])
def test_kernel_compiles_for_v5e(one_chip, path, op, F, dispatch):
    E, n_rows = DISPATCHES[dispatch]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((E,), jnp.int32), sds((E, F), jnp.float32),
            sds((E,), jnp.float32), sds((E,), jnp.bool_),
            _schedule_shapes(E, n_rows, sds))
    compiled = jax.jit(_dispatch(path, op, n_rows)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES, mem


def test_prefetch_over_smem_budget_is_refused():
    """A scalar-prefetch list past the SMEM budget fails at trace time with
    its shape named, before Mosaic would fail to allocate it: here a dense
    grid of 256 row blocks × 513 edge tiles (131,328 occupancy words)."""
    n_rows, E = 256 * K.ROW_BLOCK, 513 * K.EDGE_TILE
    assert 256 * 513 * 4 > K.SMEM_PREFETCH_BYTES
    with pytest.raises(ValueError, match="SMEM"):
        ops.gas_scatter(jnp.zeros((E,), jnp.int32),
                        jnp.zeros((E, 1), jnp.float32), n_rows)


def test_scoped_kernel_keeps_its_wrapper_name(one_chip, monkeypatch):
    """Under the program's ``gas.reduce`` scope the kernel's HLO op is still
    named after its jitted wrapper, the name a trace reduction finds the
    kernel by, and carries the scope in its ``metadata.op_name``."""
    import re

    from repro.core import gas
    # the wrappers choose interpret mode off the TPU; compile the chip's path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    E, n_rows = DISPATCHES["chunk"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(dst, vals, w, mask, sched):
        return gas.gas_scatter_weighted(dst, vals, w, mask, n_rows,
                                        impl="pallas", schedule=sched)

    args = (sds((E,), jnp.int32), sds((E, 602), jnp.float32),
            sds((E,), jnp.float32), sds((E,), jnp.bool_),
            _schedule_shapes(E, n_rows, sds))
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"'
                       r'[^\n]*op_name="([^"]*)"', text)
    assert calls
    for name, stack in calls:
        assert name.startswith("gas_scatter_banded"), name
        assert "gas.reduce" in stack.split("/"), stack
