"""Median device time of one execution of the decode program."""
from benchmark.layer_metrics._common import decode_program_ms as read  # noqa: F401

LAYER = "models"
UNIT = "ms"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"
