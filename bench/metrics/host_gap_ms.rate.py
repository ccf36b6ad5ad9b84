"""Mean device-idle gap between consecutive fused serve-step executions,
in ms: the host's share of each run() round trip."""
from bench.core.readers import host_gap_ms as read  # noqa: F401
