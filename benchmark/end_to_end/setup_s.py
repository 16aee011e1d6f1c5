"""Process start to the start of the measured window: cluster, chip
worker, jax start-up, weights, warm-up from the compile cache, and for a
serving cell the proxy and one request over HTTP."""

UNIT = "s"
SOURCE = "host_clock"


def read(view):
    return view["spans"]["process_start_to_window"]
