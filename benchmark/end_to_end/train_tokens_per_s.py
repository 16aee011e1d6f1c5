"""Tokens trained per second through JaxTrainer over the whole window:
host feed and train.report included, closed by block_until_ready."""
UNIT = "tokens/s"
SOURCE = "host_clock"


def read(view):
    t = view.get("train")
    if not t or not t["steps"]:
        return None
    return t["tokens"] / t["elapsed_s"]
