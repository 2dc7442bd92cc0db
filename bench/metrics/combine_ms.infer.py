"""Per-layer metric ``combine_ms.infer``: see ``yard.scopes.combine_ms``."""

from yard.scopes import combine_ms as read  # noqa: F401
