"""Planter gate kernel's device time (fused in the serve step and in the
admission launch) over device busy time, in percent."""
from bench.core.readers import gate_share as read  # noqa: F401
