"""Per-layer metric ``collective_exposed_ms.train``: see ``yard.exchange.collective_exposed_ms``."""

from yard.exchange import collective_exposed_ms as read  # noqa: F401
