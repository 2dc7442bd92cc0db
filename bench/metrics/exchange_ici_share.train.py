"""Per-layer metric ``exchange_ici_share.train``: see ``yard.exchange.exchange_ici_share``."""

from yard.exchange import exchange_ici_share as read  # noqa: F401
