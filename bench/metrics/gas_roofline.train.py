"""Per-layer metric ``gas_roofline.train``: see ``yard.readers.gas_roofline``."""

from yard.readers import gas_roofline as read  # noqa: F401
