"""How late the generator ran: send time minus due time, 95th percentile."""
from benchmark.layer_metrics._common import percentile

LAYER = "load generator"
UNIT = "ms"
MOVES = "ttft_p95_ms"
SOURCE = "host_clock"


def read(view):
    lat = view.get("latencies")
    if not lat or not lat["late_s"]:
        return None
    return 1e3 * percentile(lat["late_s"], 95)
