"""Per request: the client's time from sending to its first token, less
the replica-side time from entering the deployment's callable to yielding
the first token; the median. What proxy, handle, replica and the stream's
way back add."""
from benchmark.layer_metrics._common import median

LAYER = "serve front"
UNIT = "ms"
MOVES = "ttft_p95_ms"
SOURCE = "program_span"


def read(view):
    w, fin = view.get("window"), view.get("finish")
    if not w or not fin:
        return None
    out = []
    for r in w["records"]:
        t = fin["req_times"].get(r["tag"])
        if t and r["first"] is not None:
            out.append((r["first"] - r["sent"]) - (t[1] - t[0]))
    return 1e3 * median(out) if out else None
