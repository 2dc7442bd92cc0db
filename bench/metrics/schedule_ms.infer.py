"""Per-layer metric ``schedule_ms.infer``: see ``yard.scopes.schedule_ms``."""

from yard.scopes import schedule_ms as read  # noqa: F401
