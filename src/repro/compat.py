"""The single door for every JAX API this repo uses whose spelling has moved
between releases. Call sites import ``shard_map``/``make_mesh``/
``AxisType``/``psum_scatter`` from ``repro.compat`` and never touch
``jax.shard_map``, ``jax.sharding.AxisType`` or ``axis_types=`` directly, so
the next move is a one-file change (the ``compat-door`` lint enforces it).

Written for the installed JAX (0.9.0) only; there are no branches for other
versions. ``FEATURES`` records what is in use; ``scripts/check_env.py``
prints it as the support matrix.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
from jax import lax
from jax.sharding import AxisType, Mesh

FEATURES: Dict[str, object] = {
    "jax_version": jax.__version__,
    "shard_map": "jax.shard_map(check_vma=...)",
    "make_mesh": "jax.make_mesh(axis_types=...)",
    "axis_type": "jax.sharding.AxisType",
    "psum_scatter": "lax.psum_scatter",
}

psum_scatter = lax.psum_scatter


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: Optional[bool] = None):
    """``jax.shard_map`` with its keyword-only convention; ``None`` keeps the
    installed default of the replication (varying-manual-axes) check."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types: Optional[Tuple] = None, devices=None) -> Mesh:
    """``jax.make_mesh``; ``axis_types=None`` keeps JAX's default types."""
    kwargs = {} if axis_types is None else {"axis_types": axis_types}
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         devices=devices, **kwargs)


# ---------------------------------------------------------------------------
# collective primitive NAMES (the trace side of the same single-door rule)
# ---------------------------------------------------------------------------

#: canonical collective name → every jaxpr primitive spelling that means it
#: (the canonical name first). ``lax.psum_scatter`` traces as
#: ``reduce_scatter`` and a ``psum`` inside ``shard_map`` as
#: ``psum_invariant``.
#: Anything that reads jaxprs (``launch/jaxpr_stats``, ``analysis/contracts``)
#: counts under the canonical key so committed budgets survive a rename.
COLLECTIVE_ALIASES: Dict[str, Tuple[str, ...]] = {
    "all_to_all": ("all_to_all",),
    "all_gather": ("all_gather",),
    "psum": ("psum", "psum_invariant"),
    "psum_scatter": ("psum_scatter", "reduce_scatter"),
    "ppermute": ("ppermute",),
    "pmax": ("pmax",),
    "pmin": ("pmin",),
}

_SPELLING_TO_CANONICAL: Dict[str, str] = {
    spelling: canon
    for canon, spellings in COLLECTIVE_ALIASES.items()
    for spelling in spellings
}


def canonical_collective(primitive_name: str) -> Optional[str]:
    """Canonical collective name for a jaxpr primitive name, or ``None`` if
    the primitive is not a cross-shard collective."""
    return _SPELLING_TO_CANONICAL.get(primitive_name)


def feature_matrix() -> Dict[str, object]:
    """Snapshot of the JAX APIs the compat layer routes to."""
    return dict(FEATURES)
