"""Per-layer metric ``chunk_overhead_ms.train``: see ``yard.scopes.chunk_overhead_ms``."""

from yard.scopes import chunk_overhead_ms as read  # noqa: F401
