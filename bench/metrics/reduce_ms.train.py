"""Per-layer metric ``reduce_ms.train``: see ``yard.scopes.reduce_ms``."""

from yard.scopes import reduce_ms as read  # noqa: F401
