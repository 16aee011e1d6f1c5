"""Share of the device's idle time in the traced stretch that lies under no
program span: what the tracing still cannot see."""
from benchmark.layer_metrics._program import idle_unattributed_share as read  # noqa: F401

LAYER = "engine"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"
