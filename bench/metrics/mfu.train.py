"""Per-layer metric ``mfu.train``: see ``yard.readers.mfu``."""

from yard.readers import mfu as read  # noqa: F401
