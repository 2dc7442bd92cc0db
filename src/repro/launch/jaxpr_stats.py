"""Deterministic primitive counters over traced jaxprs.

``hlo_analysis`` measures what XLA *compiled* (bytes, FLOPs) — but compiled
HLO is downstream of optimization passes (collective combiners, DCE,
fusion), so "how many collectives does this dataflow ISSUE?" is better
answered one level up, on the jaxpr the program traces to. This module
counts primitive equations recursively through every sub-jaxpr (``pjit``,
``shard_map``, ``scan``/``while`` bodies, ``custom_vjp`` branches, …), which
makes the counts

* **deterministic** — a pure function of the traced program, independent of
  backend, optimization level, or combiner passes;
* **complete** — a collective inside a ``shard_map`` body or a kernel
  dispatch inside a custom-VJP backward is counted exactly like a top-level
  one.

Counts are *static dispatch sites*: a ``lax.scan`` body is counted once, not
once per iteration (the chunked request stream issues its collectives per
chunk at run time but traces them once — exactly the "command block" view
the coalescing work optimizes).

Used by ``tests/test_cgtrans_coalesce.py`` and
``benchmarks/collective_bytes.py`` to assert the request-coalescing claim:
the coalesced sampled dataflow issues ONE ``all_to_all`` + ONE ``all_gather``
(+ one kernel gather) where the separate two-stream form issued two of each.
"""

from __future__ import annotations

from collections import Counter
from typing import Iterable, Optional

import jax

from repro.compat import COLLECTIVE_ALIASES, canonical_collective

#: the cross-shard communication primitives of the CGTrans dataflows, by
#: CANONICAL name — the jaxpr spellings differ from the API names (``psum``
#: inside shard_map traces as ``psum_invariant``, ``lax.psum_scatter`` as
#: ``reduce_scatter``), so the alias table lives in ``repro.compat`` per the
#: single-door rule and every count this module reports is folded onto the
#: canonical key.
COLLECTIVE_PRIMITIVES = tuple(COLLECTIVE_ALIASES)


def canonicalize_collectives(counts: Counter) -> Counter:
    """Fold version-specific collective spellings onto their canonical names
    (``psum_invariant`` → ``psum``, ``reduce_scatter`` → ``psum_scatter``,
    …);
    non-collective primitive names pass through unchanged."""
    out: Counter = Counter()
    for name, n in counts.items():
        out[canonical_collective(name) or name] += n
    return out


def _sub_jaxprs(value):
    """Yield every jaxpr reachable from one eqn-param value (duck-typed so
    it works across JAX versions that moved ``Jaxpr``/``ClosedJaxpr``)."""
    if hasattr(value, "eqns"):                       # a raw Jaxpr
        yield value
    elif hasattr(value, "jaxpr") and hasattr(value.jaxpr, "eqns"):
        yield value.jaxpr                            # a ClosedJaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)
    elif isinstance(value, dict):
        for v in value.values():
            yield from _sub_jaxprs(v)


def count_primitives(jaxpr) -> Counter:
    """Counter of primitive-name → static occurrence count, recursing into
    every sub-jaxpr. Accepts a ``Jaxpr`` or ``ClosedJaxpr``."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    counts: Counter = Counter()
    stack = [jaxpr]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:       # pjit caches share jaxpr objects — count once
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            counts[eqn.primitive.name] += 1
            for v in eqn.params.values():
                stack.extend(_sub_jaxprs(v))
    return counts


def primitive_counts(fn, *args, keys: Optional[Iterable[str]] = None,
                     **kwargs) -> Counter:
    """Trace ``fn(*args, **kwargs)`` and count its primitives.

    ``keys`` restricts the result (missing keys read 0 from the Counter
    anyway; restricting just keeps reports small). The trace is exactly what
    ``jax.jit`` would stage, so the counts describe the program XLA receives
    — before any combiner/DCE pass can blur the picture. Collective
    spellings are canonicalized (see ``canonicalize_collectives``), so
    ``keys`` should use canonical names.
    """
    counts = canonicalize_collectives(
        count_primitives(jax.make_jaxpr(fn)(*args, **kwargs)))
    if keys is not None:
        return Counter({k: counts[k] for k in keys})
    return counts


def collective_counts(fn, *args, **kwargs) -> Counter:
    """``primitive_counts`` restricted to the cross-shard collectives
    (canonical names)."""
    return primitive_counts(fn, *args, keys=COLLECTIVE_PRIMITIVES, **kwargs)
