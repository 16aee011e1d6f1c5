"""What the chunked scans share that is no part of any recurrence: cutting
the sequence into whole chunks and saying which route a call took.
``kda_scan.py`` is built on it; ``ssd_scan.py`` and ``selective_scan.py``
still carry their own copies (ROADMAP C26: moving them is a change to two
cells' compiled programs and comes with its own measurement)."""
from __future__ import annotations

import collections
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..perf.recorder import record as _record


def pad_tokens(arrays: Sequence[jax.Array], multiple: int
               ) -> Tuple[Tuple[jax.Array, ...], int]:
    """[B, T, ...] arrays with zeros appended along T up to a whole number
    of ``multiple`` tokens -> (the arrays, the tokens added). A scan pads
    with what makes a step a no-op for its recurrence (a zero gate neither
    decays, a zero key or step writes nothing), which for every scan here
    is zeros in every input."""
    pad = -arrays[0].shape[1] % multiple
    if not pad:
        return tuple(arrays), 0
    return tuple(jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                 for v in arrays), pad


def record_path(event: str, counts: collections.Counter, route: str,
                facts: Dict[str, Any]) -> None:
    """One traced call of a scan: counted by route in the module's
    ``PATH_COUNTS`` and written to the flight recorder as ``event`` with
    the route and what the call showed."""
    counts[route] += 1
    _record(event, route, dict({"route": route}, **facts))
