"""Mean of stats()["running"] sampled through the window, over max_batch."""
from benchmark.layer_metrics._common import window_samples

LAYER = "engine"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(view):
    s = window_samples(view)
    if not s:
        return None
    return 100.0 * sum(x["running"] for x in s) / len(s) \
        / view["cell"]["engine"]["max_batch"]
