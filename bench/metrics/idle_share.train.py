"""Per-layer metric ``idle_share.train``: see ``yard.readers.idle_share``."""

from yard.readers import idle_share as read  # noqa: F401
