"""Per-layer metric ``schedule_ms.train``: see ``yard.scopes.schedule_ms``."""

from yard.scopes import schedule_ms as read  # noqa: F401
