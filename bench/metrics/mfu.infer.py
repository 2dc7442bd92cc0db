"""Per-layer metric ``mfu.infer``: see ``yard.readers.mfu``."""

from yard.readers import mfu as read  # noqa: F401
