"""Lowering helpers — the two ways a program enters a MeshOwner's mesh.

- :func:`lower_jit` — the GSPMD/pjit plane: annotate inputs/outputs
  with PartitionSpecs and let XLA's SPMD partitioner place the
  collectives. This is the serve-tp path (LLM prefill/decode lowered
  with heads/FFN on ``tp`` and the KV pool block-sharded) — the
  original brief's "pjit-compiled inference shards".

- :func:`lower_shard_map` — the manual plane: the body is written
  per-shard and collectives are explicit (``jax.lax.psum`` etc. over
  axes the *owning mesh* binds). This is the fsdp plane's path, and
  the one graftcheck GC020/GC021 police: the helper always passes
  ``axis_names=`` derived from the owner's mesh, so a collective over
  an unbound axis is a static error, not an XLA lowering surprise.

Both return jitted callables; specs may be PartitionSpecs or pytrees
of them, pruned per-mesh by the owner (absent axes replicate).
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Union

from jax import shard_map

from .owner import MeshOwner


def _shardings(owner: MeshOwner, specs):
    import jax
    from jax.sharding import PartitionSpec

    return jax.tree.map(
        lambda s: owner.sharding(s),
        specs, is_leaf=lambda s: s is None or isinstance(s,
                                                        PartitionSpec))


def lower_jit(fn: Callable, owner: MeshOwner, *,
              in_specs=None, out_specs=None,
              donate_argnums: Union[int, Sequence[int]] = (),
              static_argnums: Union[int, Sequence[int]] = ()) -> Callable:
    """jit ``fn`` under the owner's mesh with PartitionSpec-annotated
    inputs/outputs (GSPMD partitions the body automatically).

    ``in_specs``/``out_specs`` mirror ``jax.jit``'s
    ``in_shardings``/``out_shardings`` trees but hold PartitionSpecs
    (or logical-axis tuples); ``None`` leaves let GSPMD propagate.
    ``donate_argnums`` passes through — the tp decode step donates its
    KV cache buffers so XLA reuses the pool allocation in place.
    """
    import jax

    kw: dict = {}
    if in_specs is not None:
        kw["in_shardings"] = _shardings(owner, in_specs)
    if out_specs is not None:
        kw["out_shardings"] = _shardings(owner, out_specs)
    if donate_argnums != ():
        kw["donate_argnums"] = donate_argnums
    if static_argnums != ():
        kw["static_argnums"] = static_argnums
    return jax.jit(fn, **kw)


def lower_shard_map(fn: Callable, owner: MeshOwner, *,
                    in_specs, out_specs,
                    axis_names: Optional[frozenset] = None,
                    jit: bool = True) -> Callable:
    """shard_map ``fn`` over the owner's mesh, manual over
    ``axis_names`` (default: every axis the mesh carries).

    The body sees per-shard arrays and must name only bound axes in
    its collectives — graftcheck GC020 statically checks call sites
    written against this helper's convention.
    """
    import jax

    if axis_names is None:
        axis_names = frozenset(owner.mesh.axis_names)
    mapped = shard_map(fn, mesh=owner.mesh, in_specs=in_specs,
                       out_specs=out_specs, axis_names=axis_names)
    return jax.jit(mapped) if jit else mapped


def sharded_init(init_fn: Callable, owner: MeshOwner,
                 out_specs) -> Callable:
    """jit an init so its outputs materialize already sharded on the
    owner's mesh (no replicated transient of the full tree)."""
    import jax

    return jax.jit(init_fn, out_shardings=_shardings(owner, out_specs))
