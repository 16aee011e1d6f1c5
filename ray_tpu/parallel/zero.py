"""ZeRO-style cross-replica sharding of the weight update.

"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (PAPERS.md): in plain data parallelism every replica holds the
FULL optimizer state and applies the FULL update — O(N) redundant memory
and compute per replica. Sharding the update makes both scale with the
dp axis: each replica reduce-scatters gradients (so it receives only its
1/dp shard, already summed), applies the optimizer to that shard with
1/dp of the optimizer state, and all-gathers the fresh parameters.
Elementwise optimizers (sgd/adam/adamw) commute with the flat-vector
sharding, so the sharded update is numerically the replicated update.

Two planes, mirroring parallel/collective.py's stance:

- **Host plane** (:class:`ZeroUpdater`): cross-ACTOR dp groups over the
  object-store collective (reducescatter/allgather from
  parallel/collective.py). This is what the compiled-graph pipeline
  engine (train/pipeline_cgraph.py) uses between dp replicas of one
  stage — replicas live in different processes, often different hosts.

- **In-jit plane** (:func:`make_zero_update_spmd`): ``psum_scatter`` /
  ``all_gather`` inside one jitted program over a mesh dp axis, for the
  case where a stage's replicas are chips of one mesh.

Both operate on the FLAT parameter vector: pytrees are raveled into one
1-D array (uniform dtype enforced), sharded in contiguous chunks that
match ``np.array_split`` boundaries (what collective.reducescatter
emits), and unraveled after the gather.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

__all__ = [
    "TreeSpec", "flatten_tree", "unflatten_tree", "shard_bounds",
    "tree_bytes", "ZeroUpdater", "make_zero_update_spmd",
    "merge_opt_shards", "split_opt_state", "flatten_opt_state",
    "unflatten_opt_state",
]


class TreeSpec:
    """Shapes/dtype/treedef needed to unflatten a flat vector."""

    __slots__ = ("treedef", "shapes", "dtype", "size")

    def __init__(self, treedef, shapes, dtype, size):
        self.treedef = treedef
        self.shapes = shapes
        self.dtype = dtype
        self.size = size


def flatten_tree(tree) -> Tuple[Any, TreeSpec]:
    """Pytree -> (flat 1-D array, spec). Leaves must share one dtype —
    the flat shard boundary would otherwise cut through a dtype change
    and reinterpret bytes."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        raise ValueError("cannot flatten an empty pytree")
    dtypes = {jnp.asarray(l).dtype for l in leaves}
    if len(dtypes) > 1:
        raise ValueError(
            f"ZeRO flat sharding needs a uniform leaf dtype, got "
            f"{sorted(str(d) for d in dtypes)}")
    shapes = [jnp.asarray(l).shape for l in leaves]
    flat = jnp.concatenate([jnp.asarray(l).ravel() for l in leaves])
    return flat, TreeSpec(treedef, shapes, flat.dtype, int(flat.size))


def unflatten_tree(flat, spec: TreeSpec):
    import jax
    import numpy as _np

    leaves = []
    off = 0
    for shape in spec.shapes:
        n = int(_np.prod(shape)) if shape else 1
        leaves.append(flat[off:off + n].reshape(shape))
        off += n
    return jax.tree.unflatten(spec.treedef, leaves)


def shard_bounds(n: int, world: int) -> List[Tuple[int, int]]:
    """Contiguous (lo, hi) per rank, matching np.array_split: the first
    n % world shards get one extra element."""
    base, extra = divmod(n, world)
    bounds = []
    lo = 0
    for r in range(world):
        hi = lo + base + (1 if r < extra else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def tree_bytes(tree) -> int:
    """Total bytes across a pytree's array leaves (optimizer-state
    footprint accounting; scalars count their numpy size)."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        total += int(np.asarray(leaf).nbytes)
    return total


# ---------------------------------------------------------------------------
# opt-state resharding — the elastic-capacity vocabulary
# (docs/FAULT_TOLERANCE.md "Elasticity"): ZeRO shards saved at one dp
# width re-split exactly across another, and the flat plane converts
# to/from the replicated tree plane, so `resize(dp±k)` and
# cross-width checkpoint restores are pure byte movement — bit-exact.
# ---------------------------------------------------------------------------


def merge_opt_shards(shards: List[Any]):
    """Per-rank ZeRO opt-state shards (rank order) -> one flat-vector
    opt state over the FULL parameter vector. Array leaves are
    shard-sized (optimizer moments) and concatenate in rank order —
    matching the ``shard_bounds`` contiguous layout they were split
    under; scalar leaves (adam's step count) are collectively identical
    and come from rank 0."""
    import jax
    import numpy as _np

    if not shards:
        raise ValueError("merge_opt_shards needs at least one shard")

    def _merge(*leaves):
        if _np.ndim(leaves[0]) >= 1:
            return _np.concatenate([_np.asarray(l) for l in leaves])
        return leaves[0]

    return jax.tree.map(_merge, *shards)


def split_opt_state(full, world: int, size: int) -> List[Any]:
    """Inverse of :func:`merge_opt_shards`: a flat-vector opt state over
    ``size`` parameters -> ``world`` per-rank shards on the
    ``shard_bounds`` layout. Array leaves of length ``size`` are
    sliced; everything else (scalars, oddly-shaped leaves) replicates."""
    import jax
    import numpy as _np

    bounds = shard_bounds(size, world)

    def _slice(lo, hi):
        def f(leaf):
            arr = _np.asarray(leaf)
            if arr.ndim == 1 and arr.shape[0] == size:
                return arr[lo:hi]
            return leaf
        return f

    return [jax.tree.map(_slice(lo, hi), full) for lo, hi in bounds]


def flatten_opt_state(state, params):
    """Replicated TREE-plane opt state (``tx.init(params_tree)``) -> the
    flat-vector plane (``tx.init(flat_params)``): every params-shaped
    subtree of the state (adam's mu/nu, momentum's trace, ...) collapses
    into one raveled vector on the :func:`flatten_tree` layout; scalar
    leaves pass through. This is the grow path — a dp=1 engine's full
    opt state becomes ZeRO shards for dp>1."""
    import jax
    import jax.numpy as jnp

    p_def = jax.tree.structure(params)
    p_shapes = [jnp.shape(l) for l in jax.tree.leaves(params)]

    def _params_shaped(x) -> bool:
        try:
            if jax.tree.structure(x) != p_def:
                return False
            return [jnp.shape(l) for l in jax.tree.leaves(x)] == p_shapes
        except Exception:
            return False

    def _collapse(sub):
        if _params_shaped(sub):
            return jnp.concatenate(
                [jnp.asarray(l).ravel() for l in jax.tree.leaves(sub)])
        return sub

    return jax.tree.map(_collapse, state, is_leaf=_params_shaped)


def unflatten_opt_state(flat_state, spec: TreeSpec):
    """Flat-vector-plane opt state -> the replicated TREE plane: leaves
    of length ``spec.size`` unflatten back into params-shaped subtrees
    (the shrink-to-dp=1 path)."""
    import jax
    import numpy as _np

    def _expand(leaf):
        arr = _np.asarray(leaf)
        if arr.ndim == 1 and arr.shape[0] == spec.size:
            return unflatten_tree(leaf, spec)
        return leaf

    return jax.tree.map(_expand, flat_state)


# ---------------------------------------------------------------------------
# host plane: cross-actor dp groups over parallel/collective.py
# ---------------------------------------------------------------------------


class ZeroUpdater:
    """Rank-local view of a ZeRO-sharded optimizer over a host collective
    group.

    Each dp replica constructs one with its rank, inits optimizer state
    for ITS shard only (the ~1/dp memory win), and calls
    :meth:`update` once per optimizer step. The gradient mean, shard
    update, and parameter gather all ride the named collective group —
    every rank must call update() collectively.

    ``grad_codec`` (``"int8"``/``"e4m3"``, docs/COLLECTIVES.md)
    compresses BOTH wire legs of the dp sync with the block-scaled
    codec: the gradient reduce-scatter ships quantized grads (summed in
    fp32 after dequantize) and the parameter all-gather ships quantized
    fresh shards. So the wire-precision params don't become the
    optimization state itself (sub-quantization-step updates would
    round away and training would stall on the int8 grid), each rank
    keeps a persistent fp32 MASTER copy of its own shard: the optimizer
    updates the master, the wire carries its quantized image, and
    compute everywhere runs on the wire-precision params — standard
    master-weight mixed precision, applied to the ZeRO gather.
    ``grad_codec=None`` is bit-identical to the pre-codec updater.
    """

    def __init__(self, tx, world: int, rank: int,
                 group_name: str = "default",
                 grad_codec: Optional[str] = None):
        from . import quant as _quant

        self.tx = tx
        self.world = int(world)
        self.rank = int(rank)
        self.group_name = group_name
        self.grad_codec = _quant.check_codec(grad_codec)
        self._spec: Optional[TreeSpec] = None
        self._opt_state = None
        self._master = None   # fp32 shard master copy (codec path only)
        self._jit_update = None
        # collective sync-exposed wall time (step profiler, ISSUE 17):
        # the two wire legs of the last update() and the running total
        self.last_rs_s = 0.0   # gradient reduce-scatter leg
        self.last_ag_s = 0.0   # parameter all-gather leg
        self.sync_s = 0.0      # cumulative rs+ag over this updater's life

    def init(self, params) -> "ZeroUpdater":
        import jax

        flat, spec = flatten_tree(params)
        self._spec = spec
        lo, hi = shard_bounds(spec.size, self.world)[self.rank]
        self._opt_state = jax.jit(self.tx.init)(flat[lo:hi])
        if self.grad_codec is not None:
            self._master = flat[lo:hi]

        @jax.jit
        def _upd(g_shard, opt_state, p_shard):
            import optax

            updates, new_state = self.tx.update(g_shard, opt_state,
                                                p_shard)
            return optax.apply_updates(p_shard, updates), new_state

        self._jit_update = _upd
        return self

    def opt_state_bytes(self) -> int:
        """Bytes of optimizer state THIS replica holds (~ full/dp)."""
        return tree_bytes(self._opt_state)

    def opt_state(self):
        """This rank's optimizer-state SHARD (checkpointing surface —
        the pipeline engine persists one shard per dp rank and hands it
        back through :meth:`set_opt_state` on restore). With a
        ``grad_codec`` the fp32 master shard rides along as a shard-
        sized leaf (``{"tx": ..., "master": ...}``) so the elastic
        reshard vocabulary (merge/split over shard-sized leaves) moves
        it across dp widths like any other moment."""
        if self.grad_codec is not None:
            return {"tx": self._opt_state,
                    "master": np.asarray(self._master)}
        return self._opt_state

    def set_opt_state(self, state) -> None:
        """Restore this rank's shard (must come from the same (rank,
        world, param-tree) layout it was saved under). Accepts both the
        raw optimizer state and the codec-era ``{"tx", "master"}``
        wrapper; a raw state under a codec updater re-seeds the master
        from the next update's incoming params."""
        if self._spec is None:
            raise RuntimeError("ZeroUpdater.set_opt_state() before init()")
        if isinstance(state, dict) and set(state) == {"tx", "master"}:
            self._opt_state = state["tx"]
            self._master = state["master"]
        else:
            self._opt_state = state
            if self.grad_codec is not None:
                self._master = None  # lazily re-seeded at next update()

    def update(self, params, grads):
        """Collective optimizer step: reduce-scatter the gradient mean,
        update this rank's shard, all-gather fresh parameters. Returns
        the full updated parameter pytree. With ``grad_codec`` both
        collectives ship block-scaled quantized payloads and the
        optimizer runs on this rank's fp32 master shard."""
        import jax.numpy as jnp

        from . import collective

        if self._spec is None:
            raise RuntimeError("ZeroUpdater.update() before init()")
        flat_g, gspec = flatten_tree(grads)
        if gspec.size != self._spec.size:
            raise ValueError(
                f"grad tree size {gspec.size} != param tree size "
                f"{self._spec.size}")
        codec = self.grad_codec
        # reducescatter SUMS then slices; divide for the dp mean
        # (codec: rows dequantize to fp32 BEFORE the sum, so gradient
        # accumulation precision is full — only the wire is narrow)
        import time as _time

        t0 = _time.perf_counter()
        g_shard = collective.reducescatter(
            np.asarray(flat_g), self.group_name, codec=codec) / self.world
        self.last_rs_s = _time.perf_counter() - t0
        flat_p, _ = flatten_tree(params)
        lo, hi = shard_bounds(self._spec.size, self.world)[self.rank]
        if codec is not None and self._master is None:
            self._master = flat_p[lo:hi]
        p_shard = flat_p[lo:hi] if codec is None \
            else jnp.asarray(self._master, dtype=self._spec.dtype)
        new_shard, self._opt_state = self._jit_update(
            jnp.asarray(g_shard, dtype=self._spec.dtype),
            self._opt_state, p_shard)
        if codec is not None:
            self._master = new_shard
        t1 = _time.perf_counter()
        parts = collective.allgather(np.asarray(new_shard),
                                     self.group_name, codec=codec)
        self.last_ag_s = _time.perf_counter() - t1
        self.sync_s += self.last_rs_s + self.last_ag_s
        full = jnp.asarray(np.concatenate(parts), dtype=self._spec.dtype)
        return unflatten_tree(full, self._spec)


# ---------------------------------------------------------------------------
# in-jit plane: psum_scatter / all_gather over a mesh dp axis
# ---------------------------------------------------------------------------


def make_zero_update_spmd(tx, mesh, axis: str = "dp",
                          grad_codec: Optional[str] = None,
                          codec_block: int = 256
                          ) -> Tuple[Callable, Callable]:
    """Build the in-mesh sharded update: ``(init_fn, update_fn)``.

    ``grad_codec`` ("int8"/"e4m3") swaps the gradient ``psum_scatter``
    for the quantized scatter kernel
    (parallel/sharding/codec.quantized_scatter_mean): per-block absmax
    quantize → all_to_all → dequantize → fp32 sum, so the dp wire
    carries ~1/4 of the gradient bytes; the parameter all-gather stays
    full precision (the in-jit plane syncs over ICI/one host, where
    params are cheap relative to the DCN-crossing host plane).
    ``grad_codec=None`` compiles the exact pre-codec program.

    - ``init_fn(params)`` -> flat optimizer state laid out over the
      mesh ``axis`` (each device materializes only its 1/dp chunk under
      shard_map).
    - ``update_fn(params, grads_stacked, opt_state)`` ->
      ``(new_params, new_opt_state)`` where ``grads_stacked`` carries a
      leading ``axis``-sharded replica dimension (each replica's own
      gradients, e.g. from per-shard ``value_and_grad``). Inside the
      program: ``psum_scatter`` hands each device its summed 1/dp
      gradient chunk, the optimizer updates that chunk, and a tiled
      ``all_gather`` rebuilds the full parameter vector — no device
      ever holds full optimizer state.

    The flat vector is zero-padded to a multiple of the axis size so
    chunks tile exactly.
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from . import quant as _quant

    _quant.check_codec(grad_codec)
    world = mesh.shape[axis]

    def _pad(flat):
        pad = (-flat.size) % world
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), flat.dtype)])
        return flat

    def _opt_specs(chunk, dtype):
        # moment leaves ([chunk] per rank) shard over the axis; scalar
        # leaves (adam's step count) stay replicated
        shapes = jax.eval_shape(tx.init,
                                jax.ShapeDtypeStruct((chunk,), dtype))
        return jax.tree.map(
            lambda s: P(axis) if len(s.shape) >= 1 else P(), shapes)

    def init_fn(params):
        flat, _spec = flatten_tree(params)
        flat = _pad(flat)
        chunk = flat.size // world

        def _init_local(p_local):
            idx = jax.lax.axis_index(axis)
            p_shard = jax.lax.dynamic_slice(p_local, (idx * chunk,),
                                            (chunk,))
            return tx.init(p_shard)

        fn = shard_map(_init_local, mesh=mesh, in_specs=(P(),),
                       out_specs=_opt_specs(chunk, flat.dtype),
                       axis_names=frozenset({axis}))
        return jax.jit(fn)(flat)

    # one jitted program per (param size, grad width, dtype) — a fresh
    # shard_map closure per call would miss jit's identity-keyed cache
    # and re-trace + re-compile the update EVERY training step
    _progs: dict = {}

    def _update_prog(chunk, g_width, dtype):
        key = (chunk, g_width, str(dtype))
        prog = _progs.get(key)
        if prog is not None:
            return prog

        def _upd_local(p_local, g_local, opt_local):
            idx = jax.lax.axis_index(axis)
            # g_local: [1, Np] — this replica's own full gradient.
            # psum_scatter hands back chunk #idx of the cross-replica
            # SUM; with a codec the quantized kernel decomposes it so
            # only narrow payloads cross the wire (fp32 sum after
            # dequantize — parallel/sharding/codec.py)
            if grad_codec is None:
                g_shard = jax.lax.psum_scatter(
                    g_local[0], axis, tiled=True) / world
            else:
                from .sharding.codec import quantized_scatter_mean

                g_shard = quantized_scatter_mean(
                    g_local[0], axis, world, codec=grad_codec,
                    block=codec_block)
            p_shard = jax.lax.dynamic_slice(p_local, (idx * chunk,),
                                            (chunk,))
            updates, new_opt = tx.update(g_shard, opt_local, p_shard)
            return optax.apply_updates(p_shard, updates), new_opt

        ospecs = _opt_specs(chunk, dtype)
        mapped = shard_map(_upd_local, mesh=mesh,
                           in_specs=(P(), P(axis), ospecs),
                           out_specs=(P(axis), ospecs),
                           axis_names=frozenset({axis}))

        def _upd(p_flat, g_stacked, opt):
            # the body returns each rank's updated chunk; resharding the
            # assembled vector to P() is the all-gather, placed by the
            # partitioner, so the result is replicated by construction
            new_flat, new_opt = mapped(p_flat, g_stacked, opt)
            return jax.lax.with_sharding_constraint(
                new_flat, NamedSharding(mesh, P())), new_opt

        prog = jax.jit(_upd)
        _progs[key] = prog
        return prog

    def update_fn(params, grads_stacked, opt_state):
        flat_p, spec = flatten_tree(params)
        flat_p = _pad(flat_p)
        chunk = flat_p.size // world
        g_leaves, _ = jax.tree.flatten(grads_stacked)
        flat_g = jnp.concatenate(
            [jnp.asarray(l).reshape(world, -1) for l in g_leaves],
            axis=1)
        pad = (-flat_g.shape[1]) % world
        if pad:
            flat_g = jnp.concatenate(
                [flat_g, jnp.zeros((world, pad), flat_g.dtype)], axis=1)
        prog = _update_prog(chunk, flat_g.shape[1], flat_p.dtype)
        new_flat, new_opt = prog(flat_p, flat_g, opt_state)
        return unflatten_tree(new_flat[:spec.size], spec), new_opt

    return init_fn, update_fn
