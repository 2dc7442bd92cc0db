"""Per-layer metric ``sampler_ms.train``: see ``yard.readers.sampler_ms``."""

from yard.readers import sampler_ms as read  # noqa: F401
