"""95th percentile over ALL requests of the time from a request's due
time to its first streamed token at the client; a failed request is +inf."""
from benchmark.lib.stats import percentile

UNIT = "ms"
SOURCE = "host_clock"


def read(view):
    lat = view.get("latencies")
    if not lat or not lat["ttft_s"]:
        return None
    return 1e3 * percentile(lat["ttft_s"], 95)
