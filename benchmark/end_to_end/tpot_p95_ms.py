"""95th percentile over requests of (last token time - first token
time) / (tokens - 1) at the client; a failed request is +inf."""
from benchmark.lib.stats import percentile

UNIT = "ms"
SOURCE = "host_clock"


def read(view):
    lat = view.get("latencies")
    if not lat or not lat["tpot_s"]:
        return None
    return 1e3 * percentile(lat["tpot_s"], 95)
