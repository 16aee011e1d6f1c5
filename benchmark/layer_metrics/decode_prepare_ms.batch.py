"""Median per decode step of the scheduler's span rtpu.llm.decode.prepare:
block growth, copy-on-write and building the program's four host arrays."""
from benchmark.layer_metrics._program import decode_span_ms

LAYER = "engine"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(view):
    return decode_span_ms(view, "prepare")
