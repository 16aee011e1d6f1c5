"""Pipeline parallelism over the `pp` mesh axis.

The reference has NO pipeline engine (SURVEY.md §5: tensor/pipeline
parallelism is first-class new work for the TPU build; RLlib/Train are DP
only — ref: python/ray/train/torch/train_loop_utils.py:329 wraps DDP/FSDP,
nothing stage-parallel). Two layers live here:

1. `pipeline_spmd` — the TPU-native core: a collective microbatch pipeline
   INSIDE one jitted program. Stage parameters are stacked on a leading
   axis sharded over `pp`; activations flow stage-to-stage with
   `lax.ppermute` (ICI neighbor hops) inside a `lax.scan` over
   M + P - 1 ticks (GPipe schedule). `jax.shard_map(axis_names={'pp'})`
   keeps `pp` manual while dp/fsdp/tp stay GSPMD-auto, so the pipeline
   composes with data/tensor sharding without hand-written collectives.
   The whole thing is differentiable: AD reverses the scan and transposes
   each ppermute, yielding the backward pipeline automatically.

2. `schedule_1f1b` — the explicit per-stage 1F1B order (warmup fwds, then
   alternating 1F/1B, then cooldown bwds). The actor-hosted engine
   (ray_tpu/train/pipeline_cgraph.py) executes it across stage actors
   in its interleaved form, `schedule_interleaved_1f1b`, which is this
   order at one chunk per actor; tests assert its bubble structure.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


# ---------------------------------------------------------------------------
# 1F1B schedule (host-level description; used by the actor engine + tests)
# ---------------------------------------------------------------------------


def schedule_1f1b(num_stages: int, num_microbatches: int
                  ) -> List[List[Tuple[str, int]]]:
    """Per-stage operation order for one training step.

    Returns `sched[stage] = [("fwd", mb) | ("bwd", mb), ...]` with the
    classic 1F1B structure: stage i runs `min(num_stages - i, M)` warmup
    forwards, then alternates one-forward-one-backward, then drains the
    remaining backwards. Properties (asserted by tests):
      - each stage does M forwards and M backwards, each microbatch once;
      - backward of mb j on stage i only after forward of mb j on stage i;
      - in-flight forwards on stage i never exceed num_stages - i
        (the activation-memory bound that motivates 1F1B over GPipe).
    """
    P_, M = num_stages, num_microbatches
    sched: List[List[Tuple[str, int]]] = []
    for i in range(P_):
        ops: List[Tuple[str, int]] = []
        warmup = min(P_ - i, M)
        f = b = 0
        for _ in range(warmup):
            ops.append(("fwd", f))
            f += 1
        while b < M:
            ops.append(("bwd", b))
            b += 1
            if f < M:
                ops.append(("fwd", f))
                f += 1
        sched.append(ops)
    return sched


def schedule_interleaved_1f1b(num_stages: int, num_microbatches: int,
                              virtual: int = 1
                              ) -> List[List[Tuple[str, int, int]]]:
    """Per-ACTOR op order for interleaved 1F1B with ``virtual`` model
    chunks per actor (the Megatron/MPMD interleaved schedule shape:
    actor i hosts global chunks i, i+P, i+2P, ...).

    Returns ``sched[actor] = [(kind, v, mb), ...]`` where ``v`` is the
    local virtual-stage index (global chunk ``g = v*P + i``). For
    virtual == 1 this is exactly :func:`schedule_1f1b` lifted to
    triples, so the non-interleaved engine path keeps the proven
    schedule bit-for-bit.

    For virtual > 1 the order comes from a tick-based list-scheduling
    simulation: each actor executes at most one op per tick, preferring
    a ready backward (eager-backward bounds in-flight activations),
    else the shallowest ready forward. Because the emitted per-actor
    order IS a linear extension of the fwd/bwd dependency DAG realized
    by the simulation, executing it with blocking channel reads (and
    non-blocking sends, i.e. >= M slots per edge) cannot deadlock.
    """
    P_, M, V = num_stages, num_microbatches, virtual
    if V <= 1:
        return [[(kind, 0, mb) for kind, mb in ops]
                for ops in schedule_1f1b(P_, M)]
    G = P_ * V
    done: Dict[Tuple[str, int, int], int] = {}  # (kind, g, mb) -> tick
    fnext = [0] * G  # next fwd microbatch per global chunk
    bnext = [0] * G  # next bwd microbatch per global chunk
    sched: List[List[Tuple[str, int, int]]] = [[] for _ in range(P_)]
    t = 0
    total = 2 * G * M
    while len(done) < total:
        progressed = False
        picks = []
        for i in range(P_):
            best = None
            for v in range(V):
                g = v * P_ + i
                mb = bnext[g]
                if mb < M and ("fwd", g, mb) in done \
                        and done[("fwd", g, mb)] <= t \
                        and (g == G - 1
                             or done.get(("bwd", g + 1, mb), t + 1) <= t):
                    cand = ("bwd", v, mb, g)
                    # drain the oldest microbatch first, deepest chunk
                    # first (its grad unblocks the longest chain)
                    if best is None \
                            or (cand[2], -cand[3]) < (best[2], -best[3]):
                        best = cand
            if best is None:
                for v in range(V):
                    g = v * P_ + i
                    mb = fnext[g]
                    if mb < M and (g == 0
                                   or done.get(("fwd", g - 1, mb),
                                               t + 1) <= t):
                        cand = ("fwd", v, mb, g)
                        # fill shallow chunks first: warmup order
                        if best is None or (cand[1], cand[2]) \
                                < (best[1], best[2]):
                            best = cand
            if best is not None:
                kind, v, mb, g = best
                picks.append((kind, g, mb))
                sched[i].append((kind, v, mb))
                if kind == "fwd":
                    fnext[g] += 1
                else:
                    bnext[g] += 1
                progressed = True
        # ops picked this tick complete at t+1 (unit latency keeps the
        # realized order consistent with the cross-actor dependencies)
        for kind, g, mb in picks:
            done[(kind, g, mb)] = t + 1
        t += 1
        if not progressed and len(done) < total:
            raise RuntimeError(
                "interleaved 1F1B simulation stalled (bug): "
                f"P={P_} M={M} V={V} done={len(done)}/{total}")
    return sched


# ---------------------------------------------------------------------------
# In-XLA collective pipeline (GPipe schedule, AD gives the reverse pipeline)
# ---------------------------------------------------------------------------


def pipeline_spmd(stage_fn: Callable[[Any, jax.Array], jax.Array],
                  stage_params: Any,
                  x_mb: jax.Array,
                  mesh: Mesh,
                  pp_axis: str = "pp") -> jax.Array:
    """Run `stage_fn` over P pipeline stages for M microbatches.

    stage_params: pytree whose leaves have leading axis P (one slice per
        stage); sharded over `pp_axis` by the shard_map in_spec.
    x_mb: [M, ...] microbatched input of stage 0. Batch/seq sharding over
        other mesh axes is preserved (they stay GSPMD-auto).
    Returns [M, ...] outputs of the last stage, replicated over `pp_axis`.
    """
    P_ = mesh.shape[pp_axis]
    M = x_mb.shape[0]
    if P_ == 1:
        sp = jax.tree.map(lambda a: a[0], stage_params)
        return jnp.stack([stage_fn(sp, x_mb[i]) for i in range(M)])

    perm = [(i, (i + 1) % P_) for i in range(P_)]

    def body(sp_local, x_loc):
        # sp_local leaves: [1, ...] (this stage's slice) — drop the axis
        sp = jax.tree.map(lambda a: a[0], sp_local)
        idx = jax.lax.axis_index(pp_axis)
        # initial carries must be marked pp-varying: the ticks fill them
        # with per-stage values, and scan requires carry types to be stable
        def _vary(x):
            return jax.lax.pcast(x, (pp_axis,), to="varying")
        state = _vary(jnp.zeros_like(x_loc[0]))
        ybuf = _vary(jnp.zeros_like(x_loc))

        def tick(carry, t):
            state, ybuf = carry
            mb = jax.lax.dynamic_index_in_dim(
                x_loc, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            inp = jnp.where(idx == 0, mb, state)
            out = stage_fn(sp, inp)
            # stage P-1 emitted microbatch t-(P-1) this tick
            ot = t - (P_ - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                ybuf, out, jnp.clip(ot, 0, M - 1), 0)
            ybuf = jnp.where(jnp.logical_and(idx == P_ - 1, ot >= 0),
                             upd, ybuf)
            state = jax.lax.ppermute(out, pp_axis, perm)
            return (state, ybuf), None

        (_, ybuf), _ = jax.lax.scan(tick, (state, ybuf),
                                    jnp.arange(M + P_ - 1))
        # only the last stage holds real outputs; replicate over the ring
        ybuf = jax.lax.psum(
            jnp.where(idx == P_ - 1, ybuf, jnp.zeros_like(ybuf)), pp_axis)
        return ybuf

    param_specs = jax.tree.map(lambda _: P(pp_axis), stage_params)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(param_specs, P()), out_specs=P(),
                       axis_names=frozenset({pp_axis}))
    return fn(stage_params, x_mb)


def stack_stages(layer_params: Dict[str, jax.Array], num_stages: int
                 ) -> Dict[str, jax.Array]:
    """[L, ...] stacked per-layer params -> [P, L/P, ...] per-stage."""
    out = {}
    for k, v in layer_params.items():
        L = v.shape[0]
        if L % num_stages:
            raise ValueError(
                f"{k}: {L} layers not divisible into {num_stages} stages")
        out[k] = v.reshape(num_stages, L // num_stages, *v.shape[1:])
    return out
