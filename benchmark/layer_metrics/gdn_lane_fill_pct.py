"""How much of what the Gated DeltaNet kernels read of a head is the
model's: 100 x (``d_k`` + ``d_v``) / (``lanes_k`` + ``lanes_v``) of the
cell's ``rtpu.ops.kda.path`` event (the chip worker's ring of the run's
flight record; the newest event with ``decay`` ``head``, which a train
step's trace leaves once a Gated DeltaNet run of the stack). ``d_k`` and
``d_v`` are the model's head sizes, ``lanes_k`` and ``lanes_v`` the lanes a
head's keys and values occupy in what the route reads: 100 where heads are
whole 128-lane tiles or the plain route runs, 75 where keys of 96 are
zero-padded to 128 and values of 192 to 256. What a packed layout for heads
that are not whole tiles would win back is the rest (ROADMAP). None where
the record holds no such event or the event states no lanes (a tree before
PR 67)."""
from benchmark.layer_metrics import _flight

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"

KIND = "rtpu.ops.kda.path"


def read(view):
    events = [ev.get("data") or {}
              for ev in (_flight.rings(view) or {}).get(_flight.WORKER, ())
              if ev.get("kind") == KIND]
    facts = [d for d in events if d.get("decay") == "head"
             and d.get("lanes_k") and d.get("lanes_v")]
    if not facts:
        return None
    d = facts[-1]
    return 100.0 * (d["d_k"] + d["d_v"]) / (d["lanes_k"] + d["lanes_v"])
