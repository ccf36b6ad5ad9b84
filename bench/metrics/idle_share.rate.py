"""Device idle share of the traced window: 1 - (union of device-operation
intervals / window), in percent."""
from bench.core.readers import idle_share as read  # noqa: F401
