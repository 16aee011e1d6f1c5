"""What the Pallas kernel files share that is no part of any one kernel:
the switch that says whether a kernel is compiled or interpreted, the
chip's tile and memory sizes, the block products and the two helpers of
the chunked scans. ``flash_attention``, ``expert_layer``,
``sparse_attention``, ``ssd_scan``, ``selective_scan``,
``hyper_connection``, ``kda_scan`` and ``lightning_attention`` are built on
it; a new kernel file
asks here and imports no private name of another kernel file
(``tests/test_chip_compile.py`` holds both).
"""
from __future__ import annotations

import collections
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..perf.recorder import record as _record

LANES = 128                         # lanes of a vector register tile
VMEM_BYTES = 64 * 1024 * 1024       # ``vmem_limit_bytes`` of a kernel call
NEG_INF = -1e30                     # a masked score: exp() of it is 0.0


def use_interpret() -> bool:
    """Whether ``pl.pallas_call`` interprets its kernel: everywhere but on
    a TPU. Every kernel file asks it as ``kernel_common.use_interpret()``,
    through the module, so that ONE assignment here steers them all: a
    described-chip compile (``scripts/train_step_hlo.py``,
    ``tests/test_chip_compile.py``) runs where the backend is the CPU and
    wants the program the chip gets."""
    return jax.default_backend() != "tpu"


def fit_block(block: int, seq: int) -> int:
    """Largest multiple of 128 that is <= block and divides seq. The
    kernel path requires seq % 128 == 0 (flash_attention routes anything
    else to mha_reference), so a 128-multiple divisor always exists —
    sub-128 blocks would lower to illegal / silently padded Mosaic tiles
    on real TPU."""
    block = min(block, seq)
    if seq % block == 0:
        return block
    for b in range(block - block % 128, 127, -128):
        if seq % b == 0:
            return b
    return 128


def dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


AB = ((1,), (0,))    # a @ b
ABT = ((1,), (1,))   # a @ b^T
ATB = ((0,), (0,))   # a^T @ b


def spread(v, shape):
    """[rows, 1] along the lanes, [1, lanes] down the rows, or [1, 1] over
    both, of ``shape``."""
    return jax.lax.broadcast_in_dim(v, shape, (0, 1))


def lane_sum(v, axis: int):
    """Sum over one axis of a 2-d v, the axis kept."""
    return jax.lax.expand_dims(jax.lax.reduce_sum(v, (axis,)), (axis,))


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
