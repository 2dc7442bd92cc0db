"""End-to-end driver (the paper's workload): distributed GraphSAGE training
with the CGTrans dataflow on a storage mesh of one shard per device.

Features live owner-sharded on the mesh (never shipped raw); batches carry
only vertex ids; layer-1 aggregation happens at the owner shards and only the
compressed partials cross the interconnect. Full production loop: AdamW +
cosine, checkpointing + resume, straggler monitor, preemption guard.

    PYTHONPATH=src python examples/train_graphsage.py --steps 300

On the CPU (``JAX_PLATFORMS=cpu``, the rehearsal) the script gives itself 8
virtual devices; on accelerators it shards over the chips JAX finds.
"""

import os
if os.environ.get("JAX_PLATFORMS") == "cpu":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import argparse
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.common.config import TrainConfig
from repro.common.schema import count_params, init_params
from repro.core.gcn import GCNConfig, gcn_schema, sage_loss
from repro.data import GraphBatchStream, synthetic_node_labels
from repro.graph import partition_by_src, rmat
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_data_mesh
from repro.optim import adamw_init
from repro.runtime import PreemptionGuard, StepMonitor
from repro.train import make_sage_train_step, train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--scale", type=int, default=14,
                    help="R-MAT scale (2^scale vertices)")
    ap.add_argument("--features", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--fanout", type=int, default=10)
    ap.add_argument("--batch-per-part", type=int, default=64)
    ap.add_argument("--dataflow", choices=["cgtrans", "baseline"],
                    default="cgtrans")
    ap.add_argument("--impl", choices=["xla", "pallas"], default="xla",
                    help="GAS backend for every aggregation — pallas runs "
                         "the FAST-GAS kernel forward AND backward (custom "
                         "VJPs; interpret-mode off-TPU, so expect it slow "
                         "on CPU hosts)")
    ap.add_argument("--request-chunk", type=int, default=None,
                    help="SSD command-queue depth: seeds per sampled-"
                         "aggregation request burst (None = unchunked)")
    ap.add_argument("--no-coalesce", action="store_true",
                    help="issue the self-row lookup and the 2-hop "
                         "aggregation as two separate request streams "
                         "(the legacy two-body form) instead of ONE "
                         "coalesced SSD command block")
    ap.add_argument("--ckpt-dir", default=os.path.join(tempfile.gettempdir(),
                                                       "graphsage_ckpt"))
    args = ap.parse_args()
    enable_compile_cache()

    n_shards = jax.device_count()
    mesh = make_data_mesh(n_shards)
    print(f"mesh: {mesh.shape} (storage tier = 'data' axis)")

    g = rmat(args.scale, 16, seed=0)
    rng = np.random.default_rng(1)
    g.features = rng.standard_normal(
        (g.n_vertices, args.features)).astype(np.float32)
    labels = synthetic_node_labels(g.features, 16)
    pg = partition_by_src(g, n_shards)
    feats = jax.device_put(
        jnp.asarray(pg.features),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data")))
    print(f"graph: {g.n_vertices} vertices, {g.n_edges} edges; "
          f"features owner-sharded {pg.features.shape} over {n_shards} shards")

    cfg = GCNConfig(n_features=args.features, hidden=args.hidden, n_classes=16,
                    fanout=args.fanout, dataflow=args.dataflow,
                    impl=args.impl, request_chunk=args.request_chunk,
                    coalesce=not args.no_coalesce)
    tc = TrainConfig(learning_rate=3e-3, warmup_steps=20,
                     total_steps=args.steps, weight_decay=0.01)
    params = init_params(gcn_schema(cfg), jax.random.PRNGKey(0))
    print(f"model: {count_params(gcn_schema(cfg)) / 1e6:.2f}M params "
          f"(+{feats.size / 1e6:.1f}M feature table on the storage tier), "
          f"dataflow={args.dataflow} impl={args.impl}")

    stream = GraphBatchStream(g, labels, n_parts=n_shards,
                              batch_per_part=args.batch_per_part,
                              k1=args.fanout, k2=args.fanout)

    step = jax.jit(make_sage_train_step(cfg, tc, mesh=mesh))

    state = {"params": params, "opt": adamw_init(params, tc),
             "step": jnp.zeros((), jnp.int32)}

    def batches():
        for b in stream:
            yield {k: jnp.asarray(v) for k, v in b.items()}

    state, n = train_loop(
        step_fn=lambda st, b: step(st, b, feats), state=state,
        batches=batches(),
        total_steps=args.steps,
        ckpt=CheckpointManager(args.ckpt_dir, keep=2), ckpt_every=100,
        monitor=StepMonitor(), guard=PreemptionGuard(), log_every=20)

    # final eval on a fresh batch
    b = {k: jnp.asarray(v) for k, v in stream.batch_at(10_000).items()}
    _, m = sage_loss(state["params"], feats, b, cfg, mesh=mesh)
    print(f"done at step {n}: eval loss {float(m['loss']):.4f} "
          f"acc {float(m['acc']):.3f}")


if __name__ == "__main__":
    main()
