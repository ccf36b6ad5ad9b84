"""Whole serve step: model FLOPs of the real positions processed in the
traced window over window x chips x bf16 peak, in percent."""
from bench.core.readers import mfu as read  # noqa: F401
