"""Per-layer metric ``exchange_ms.train``: see ``yard.exchange.exchange_ms``."""

from yard.exchange import exchange_ms as read  # noqa: F401
