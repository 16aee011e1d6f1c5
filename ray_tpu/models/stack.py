"""The one walker of a stack of UNLIKE layers (``granite_hybrid.py``,
``sambay.py``): the layer kinds in their published order are cut into runs
of like PERIODS, each run one rematerialised body over its own stacked
parameters, a run of several periods that body scanned, a run of one a
plain call.

A period is a short tuple of kinds that repeats: ``("mamba",)`` five times
is a run of like layers; ``("mamba", "swa")`` eight times is a run whose
scanned body holds one layer of each kind. ``period_runs`` finds them
greedily, the longest repetition first, periods of up to ``max_period``
kinds.

Parameters are one flat dict: ``<run>.<kind>.<name>``, stacked over the
run's periods (a kind stands once in a period).

A SIDE STATE is a dict of arrays that a layer may write and later layers
read (a layer's keys and values, a scan's output). A layer's body gets it
as an ARGUMENT, not by closure: what a rematerialised body is handed it
keeps and does not make again, and the gradient of an entry is the sum
over all its readers, the scanned ones included. A layer that writes
stands in a run of one period: a scanned body cannot hand on what each of
its turns would overwrite.

Which runs a trace walked, what each keeps and the side state's bytes is
the event ``rtpu.models.stack.runs``.

``draw_params`` and ``vocab_row_shardings`` are what the delta-rule families
on the walker (``kimi_linear.py``, ``qwen3_next.py``) share of ``init`` and
``param_shardings``: one table name -> (shape, how it is drawn) a model.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..perf.recorder import record as _record

Period = Tuple[str, ...]


def period_runs(kinds: Sequence[str],
                max_period: int = 1) -> List[Tuple[Period, int]]:
    """``kinds`` as runs of like periods: [(period, repeats), ...]. At each
    position the period (of 1 to ``max_period`` kinds, each kind once) whose
    repetitions cover the most layers wins, the shorter on a tie; where
    nothing repeats, one layer is a run of its own."""
    kinds = tuple(kinds)
    runs: List[Tuple[Period, int]] = []
    i = 0
    while i < len(kinds):
        best = (kinds[i:i + 1], 1)
        for p in range(1, max_period + 1):
            period = kinds[i:i + p]
            if len(period) < p or len(set(period)) < p:
                break
            n = 1
            while kinds[i + n * p:i + (n + 1) * p] == period:
                n += 1
            if n > 1 and n * p > len(best[0]) * best[1]:
                best = (period, n)
        runs.append(best)
        i += len(best[0]) * best[1]
    return runs


def run_label(period: Period) -> str:
    return "+".join(period)


def run_params(params: Dict[str, jax.Array], run: int) -> Dict[str, Dict]:
    """{kind: {name: stacked array}} of run ``run``'s parameters."""
    prefix = f"{run}."
    out: Dict[str, Dict[str, jax.Array]] = {}
    for name, v in params.items():
        if name.startswith(prefix):
            kind, leaf = name[len(prefix):].split(".", 1)
            out.setdefault(kind, {})[leaf] = v
    return out


def draw_params(shapes: Dict[str, Tuple[Tuple[int, ...], Any]],
                rng: jax.Array, dtype, conv_taps: int) -> Dict[str, jax.Array]:
    """The flat parameter dict of a model on this walker from its table
    name -> (shape, how it is drawn): a std of a normal draw, None for
    ones, 0.0 for zeros, or one of the three rules of a delta-rule layer:
    ``A_log`` the log of a uniform draw in [1, 16], ``dt_bias`` the inverse
    softplus of a dt drawn log-uniformly in [0.001, 0.1] (the family's
    public initialisation: the decays exp(g) run from 0.2 to 0.999 a
    token), ``conv`` uniform in +-1/sqrt(taps) (a depthwise conv1d's
    default). One key of ``rng`` a name, in the table's order."""
    def draw(key, shape, how):
        if how is None:
            return jnp.ones(shape, dtype)
        if how == "A_log":
            return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))
        if how == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, dtype, math.log(1e-3), math.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if how == "conv":
            bound = 1.0 / math.sqrt(conv_taps)
            return jax.random.uniform(key, shape, dtype, -bound, bound)
        return jax.random.normal(key, shape, dtype) * how

    keys = jax.random.split(rng, len(shapes))
    return {n: draw(k, shape, how)
            for k, (n, (shape, how)) in zip(keys, shapes.items())}


def vocab_row_shardings(shapes: Dict[str, Tuple[Tuple[int, ...], Any]], mesh,
                        rules=None):
    """Replicated but for the vocabulary's rows (``wte``, ``lm_head``): a
    model that is one chip's share of an expert-parallel job (the experts
    it holds are its own), so no axis of the mesh cuts a layer."""
    from jax.sharding import NamedSharding

    from ..parallel.mesh import AxisRules

    rules = rules or AxisRules()
    return {n: NamedSharding(mesh, rules.mesh_axes(
        ("vocab", "embed") if n in ("wte", "lm_head")
        else (None,) * len(shape)))
        for n, (shape, _) in shapes.items()}


def walk_stack(x: jax.Array, runs: List[Tuple[Period, int]],
               params: Dict[str, jax.Array], block: Callable,
               kept: Sequence[Sequence[str]], *, model: str,
               layer_xs: Optional[Sequence[Any]] = None,
               facts: Optional[Dict[str, Any]] = None):
    """The one place a stack is walked. ``block(kind, x, layer_params,
    side, layer_x) -> (x, writes)`` is one layer: ``side`` the side state
    so far (read only), ``writes`` the entries it adds ({} for most),
    ``layer_x`` the layer's own entry of ``layer_xs[run][kind]`` (a
    constant a layer, stacked like its parameters) or None. Run i keeps
    for its backward, beside each layer's input, the ``checkpoint_name``s
    ``kept[i]``. -> (x, the side state at the end)."""
    side: Dict[str, jax.Array] = {}
    for i, (period, n) in enumerate(runs):
        lp = run_params(params, i)
        xs = layer_xs[i] if layer_xs is not None else None

        def layers(h, p, c, s, period=period):
            writes: Dict[str, jax.Array] = {}
            for kind in period:
                h, w = block(kind, h, p[kind], dict(s, **writes),
                             None if c is None else c[kind])
                writes.update(w)
            return h, writes

        body = jax.checkpoint(
            layers, policy=jax.checkpoint_policies.save_only_these_names(
                *kept[i]))
        if n == 1:
            first = lambda t: jax.tree.map(lambda v: v[0], t)  # noqa: E731
            x, writes = body(x, first(lp), None if xs is None else first(xs),
                             side)
            side.update(writes)
        else:
            def turn(h, pc, body=body, side=side, period=period):
                h, writes = body(h, pc[0], pc[1], side)
                if writes:
                    raise ValueError(
                        f"a layer of the scanned run {run_label(period)} "
                        f"writes the side state {sorted(writes)}: a writer "
                        "stands in a run of one period")
                return h, None

            x, _ = jax.lax.scan(turn, x, (lp, xs))
    _record("rtpu.models.stack.runs", model, dict({
        "runs": [[run_label(period), n] for period, n in runs],
        "kept": [list(names) for names in kept],
        "side_state": {k: list(v.shape) for k, v in side.items()},
        "side_state_bytes": sum(
            math.prod(v.shape) * v.dtype.itemsize for v in side.values()),
    }, **(facts or {})))
    return x, side
