"""Where JAX keeps its persistent compilation cache for this repo's entry
points (``chip_smoke.py``, ``examples/train_graphsage.py``,
``python -m repro.launch.serve``).

One rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here. Otherwise the cache goes to ``<checkout>/.jax_cache`` —
a fixed path, because the path is part of what a later run must find again
(never a temp name, a pid or the time). ``.gitignore`` lists it.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[Path]:
    """Apply the rule above; returns the in-checkout path when it was set,
    ``None`` when the environment variable decides."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return CACHE_DIR
