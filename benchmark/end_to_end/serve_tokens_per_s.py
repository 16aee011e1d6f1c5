"""Output tokens received by the clients inside the window, per second
of the window (saturated closed loop)."""
from benchmark.lib.stats import tokens_in_window

UNIT = "tokens/s"
SOURCE = "host_clock"


def read(view):
    w = view.get("window")
    if not w:
        return None
    n = tokens_in_window(w["records"], w["t0"], w["t0"] + w["seconds"])
    return n / w["seconds"]
