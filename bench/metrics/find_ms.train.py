"""Per-layer metric ``find_ms.train``: see ``yard.scopes.find_ms``."""

from yard.scopes import find_ms as read  # noqa: F401
