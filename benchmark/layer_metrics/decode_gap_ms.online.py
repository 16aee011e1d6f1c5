"""Median device-idle gap between consecutive decode programs with no
prefill between: the host's sampling and scheduling per step."""
from benchmark.layer_metrics._common import decode_gap_ms as read  # noqa: F401

LAYER = "engine"
UNIT = "ms"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"
