"""The work a step or a pass requires, counted from the cell's shapes.

These counts follow the algorithm, not the program: a gathered row is read
once from the table (F float32 values), a reduced row is written once, and a
dense layer costs 2·rows·d_in·d_out operations. A later change to the
implementation (fusing the find into the kernel, chunking differently,
caching) leaves them unchanged, so the shares computed from them compare
two programs on one yardstick.

Every function returns per-chip counts: sampled cells give each chip
``seeds`` seeds per step, and a full-graph pass runs on one chip.
"""

from __future__ import annotations

from typing import Dict

F32 = 4  # bytes


def sage_train_step(seeds: int, k1: int, k2: int, n_features: int,
                    hidden: int, n_classes: int, n_params: int
                    ) -> Dict[str, float]:
    """One sampled 2-layer GraphSAGE train step on one chip.

    Layer-1 vertices are the seeds and their k1 samples (``rows1``). The
    aggregation finds each layer-1 vertex's own row and its k2 samples'
    rows in the table, and reduces the k2 rows to one mean row per
    layer-1 vertex (the FAST-GAS reduce). The dense part is layer 0 on
    ``rows1`` rows, layer 1 and the head on the seeds, forward and backward
    (layer 0's input is data, so its backward needs the weight gradient
    only); AdamW reads and writes parameters and both moments.
    """
    rows1 = seeds * (1 + k1)
    F, H, C = n_features, hidden, n_classes
    gas_read = rows1 * k2 * F * F32          # the k2 sampled rows
    gas_write = rows1 * F * F32              # their means
    self_read = rows1 * F * F32              # each layer-1 vertex's own row
    fwd = (2 * rows1 * (2 * F) * H + 2 * seeds * (2 * H) * H
           + 2 * seeds * H * C)
    bwd = (2 * rows1 * (2 * F) * H           # dW0 only
           + 2 * (2 * seeds * (2 * H) * H)   # dW1 and dh1
           + 2 * (2 * seeds * H * C))        # dW_out and dh2
    agg_flops = rows1 * k2 * F + seeds * k1 * H
    opt_bytes = 7 * n_params * F32           # p, m, v read + written, g read
    return {
        "flops": float(fwd + bwd + agg_flops),
        "bytes": float(gas_read + gas_write + self_read + opt_bytes),
        "gas_bytes": float(gas_read + gas_write),
    }


def gcn_full_pass(n_vertices: int, n_edges: int, n_features: int,
                  hidden: int, n_classes: int, n_layers: int = 2
                  ) -> Dict[str, float]:
    """One full-graph pass of the 2-layer concat GCN over every edge.

    Per layer: every edge reads its source row (d_in floats) and adds it,
    weighted, into its destination (the FAST-GAS reduce writes one row per
    vertex); each vertex reads its own row for the concat; the dense layer
    maps 2·d_in to ``hidden`` and writes the hidden rows. The head writes the
    logits. The edge list (source, destination, weight) is read once.
    """
    V, E = n_vertices, n_edges
    flops = 0.0
    nbytes = 12.0 * E
    gas = 0.0
    d_in = n_features
    for _ in range(n_layers):
        layer_gas = (E * d_in + V * d_in) * F32
        gas += layer_gas
        nbytes += layer_gas + V * d_in * F32 + V * hidden * F32
        flops += 2 * E * d_in + 2 * V * (2 * d_in) * hidden
        d_in = hidden
    flops += 2 * V * d_in * n_classes
    nbytes += V * n_classes * F32
    return {"flops": float(flops), "bytes": float(nbytes),
            "gas_bytes": float(gas)}


def least_time(work: Dict[str, float], peaks: Dict[str, float]):
    """(seconds, bound): the larger of operations over peak FLOP/s and bytes
    over peak HBM bandwidth, and which of the two it is."""
    t_flops = work["flops"] / peaks["flops_bf16"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
