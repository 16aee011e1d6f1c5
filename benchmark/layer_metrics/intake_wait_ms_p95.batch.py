"""95th percentile of the time a request waited for the engine's lock at
intake, from the sampled stats() counters of the window."""
from benchmark.layer_metrics._program import intake_wait_ms_p95 as read  # noqa: F401

LAYER = "engine"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"
