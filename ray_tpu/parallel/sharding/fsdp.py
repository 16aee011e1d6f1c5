"""The fsdp plane — in-jit sharded parameter/optimizer storage for the
pipeline stage programs.

ZeRO-3-style storage over a mesh ``fsdp`` axis: between steps each chip
holds only its contiguous 1/fsdp chunk of the FLAT parameter vector and
1/fsdp of the optimizer moments; the forward gathers the exact full
vector once per step (a tiled ``all_gather`` is a pure concatenation —
bit-exact), and the update runs entirely shard-local (each chip
``dynamic_slice`` s its gradient chunk and applies the elementwise
optimizer to its shard — no collective at all in the update program).

Because the gather is exact and elementwise optimizers commute with
contiguous sharding, a stage trained on this plane produces a loss
trajectory **bit-identical** to the replicated stage — the property
test_sharding.py / test_pipeline_cgraph assert and the design carries
over from parallel/zero.py (same flat-vector discipline, same
"Automatic Cross-Replica Sharding of Weight Update" lineage). Compute
is replicated across the fsdp chips on this plane (the memory win is
the point; on real TPU meshes the GSPMD plane in lower.py additionally
splits the batch — docs/SHARDING.md).

Composition: the dp axis stays OUTSIDE (host-collective grad sync
between stage replicas — pipeline_cgraph.py), the pp axis stays in the
cgraph schedule; fsdp is the in-actor chip axis. That's the full 3D:
pp x dp x fsdp.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

from ..zero import TreeSpec, flatten_tree, tree_bytes, unflatten_tree
from .lower import lower_jit, lower_shard_map
from .owner import MeshOwner

__all__ = ["FsdpPlane", "FsdpParams"]


class FsdpParams:
    """One pytree's sharded residence: the padded flat vector (sharded
    over fsdp) plus the spec to unflatten it."""

    __slots__ = ("flat", "spec", "pad")

    def __init__(self, flat, spec: TreeSpec, pad: int):
        self.flat = flat
        self.spec = spec
        self.pad = pad

    def nbytes_per_device(self) -> Dict[int, int]:
        return {sh.device.id: int(sh.data.nbytes)
                for sh in self.flat.addressable_shards}


class FsdpPlane:
    """Sharded param/opt-state storage + the three jitted programs
    (gather / opt-init / update) over one MeshOwner's fsdp axis.

    Programs are cached per flat size+dtype, so hosting several model
    chunks (interleaved virtual stages) reuses compilations of equal
    geometry.
    """

    def __init__(self, owner: MeshOwner, tx=None):
        self.owner = owner
        self.axis = owner.layout.fsdp_axis
        self.world = owner.axis_size(self.axis)
        if self.world < 2:
            raise ValueError(
                f"FsdpPlane needs a mesh {self.axis!r} axis of size "
                f">= 2, got {self.world}")
        self.tx = tx
        self._progs: Dict[tuple, Any] = {}

    # -- placement ----------------------------------------------------------

    def shard(self, tree) -> FsdpParams:
        """Pytree -> sharded flat residence (1/fsdp per chip)."""
        import jax
        import jax.numpy as jnp

        flat, spec = flatten_tree(tree)
        pad = (-flat.size) % self.world
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), flat.dtype)])
        sharded = jax.device_put(
            flat, self.owner.sharding(self.owner.layout.flat_params()))
        return FsdpParams(sharded, spec, pad)

    def gather(self, fp: FsdpParams):
        """Sharded residence -> the full pytree (exact reassembly; the
        per-step transient the forward consumes)."""
        prog = self._gather_prog(fp.flat.size, fp.flat.dtype)
        full = prog(fp.flat)
        return unflatten_tree(full[:fp.spec.size], fp.spec)

    # -- optimizer ----------------------------------------------------------

    def init_opt(self, fp: FsdpParams):
        """Optimizer state for the LOCAL shard only — each chip
        materializes 1/fsdp of the moments under shard_map."""
        if self.tx is None:
            raise ValueError("FsdpPlane built without an optimizer")
        prog = self._init_prog(fp.flat.size, fp.flat.dtype)
        return prog(fp.flat)

    def update(self, fp: FsdpParams, grads, opt_state
               ) -> Tuple[FsdpParams, Any]:
        """One sharded optimizer step. ``grads`` is the FULL gradient
        pytree (already dp-synced by the caller when dp > 1); each chip
        slices its chunk and updates its param/moment shards in place —
        zero collectives, bit-identical to the replicated update."""
        import jax.numpy as jnp

        if self.tx is None:
            raise ValueError("FsdpPlane built without an optimizer")
        flat_g, gspec = flatten_tree(grads)
        if gspec.size != fp.spec.size:
            raise ValueError(
                f"grad tree size {gspec.size} != param tree size "
                f"{fp.spec.size}")
        if fp.pad:
            flat_g = jnp.concatenate(
                [flat_g, jnp.zeros((fp.pad,), flat_g.dtype)])
        prog = self._update_prog(fp.flat.size, fp.flat.dtype)
        new_flat, new_opt = prog(fp.flat, flat_g, opt_state)
        return FsdpParams(new_flat, fp.spec, fp.pad), new_opt

    # -- accounting / checkpointing -----------------------------------------

    def opt_state_bytes(self, opt_state) -> int:
        return tree_bytes(opt_state)

    def per_device_bytes(self, fp: FsdpParams, opt_state=None
                         ) -> Dict[int, int]:
        """device id -> resident bytes (params + moments) — the
        ~1/fsdp acceptance number."""
        out = fp.nbytes_per_device()
        if opt_state is not None:
            for dev, b in self.owner.per_device_bytes(opt_state).items():
                out[dev] = out.get(dev, 0) + b
        return out

    def to_host(self, fp: FsdpParams, opt_state=None):
        """Checkpoint payload: full params pytree + opt-state leaves as
        numpy. Params restore onto any geometry; the flat moment
        leaves carry this width's padding, so opt state restores onto
        the SAME fsdp width only (the pipeline engine's geometry check
        enforces it)."""
        import numpy as np

        import jax

        params = jax.tree.map(np.asarray, self.gather(fp))
        opt = None if opt_state is None else jax.tree.map(
            np.asarray, opt_state)
        return params, opt

    def from_host(self, params, opt) -> Tuple[FsdpParams, Any]:
        """Restore a to_host() payload (same fsdp width for the opt
        leaves — they were saved in sharded-flat layout)."""
        fp = self.shard(params)
        if opt is None:
            return fp, None
        return fp, self.place_opt(fp, opt)

    def place_opt(self, fp: FsdpParams, opt_host):
        """Re-shard host (numpy) optimizer state onto the mesh in the
        layout init_opt produced (moments on fsdp, scalars replicated)."""
        import jax

        ospecs = self._opt_specs(fp.flat.size // self.world,
                                 fp.flat.dtype)
        return jax.tree.map(
            lambda leaf, spec: jax.device_put(
                leaf, self.owner.sharding(spec)),
            opt_host, ospecs)

    # -- cached programs ----------------------------------------------------

    def _opt_specs(self, chunk: int, dtype):
        """Spec tree for the sharded opt state: moment vectors ([chunk]
        per chip) on the fsdp axis, scalar leaves (adam's step count)
        replicated."""
        import jax
        from jax.sharding import PartitionSpec as P

        shapes = jax.eval_shape(self.tx.init,
                                jax.ShapeDtypeStruct((chunk,), dtype))
        return jax.tree.map(
            lambda s: P(self.axis) if len(s.shape) >= 1 else P(),
            shapes)

    def _gather_prog(self, size: int, dtype):
        from jax.sharding import PartitionSpec as P

        key = ("gather", size, str(dtype))
        if key not in self._progs:
            # a reshard, not a manual collective: the partitioner places
            # the all-gather and the result is replicated by construction
            self._progs[key] = lower_jit(
                lambda flat: flat, self.owner,
                in_specs=(P(self.axis),), out_specs=P())
        return self._progs[key]

    def _init_prog(self, size: int, dtype):
        import jax
        from jax.sharding import PartitionSpec as P

        key = ("init", size, str(dtype))
        if key not in self._progs:
            axis, world, tx = self.axis, self.world, self.tx
            chunk = size // world

            def _init_local(p_shard):
                return tx.init(p_shard)

            self._progs[key] = lower_shard_map(
                _init_local, self.owner,
                in_specs=(P(axis),),
                out_specs=self._opt_specs(chunk, dtype),
                axis_names=frozenset({axis}))
        return self._progs[key]

    def _update_prog(self, size: int, dtype):
        import jax
        from jax.sharding import PartitionSpec as P

        key = ("update", size, str(dtype))
        if key not in self._progs:
            axis, world, tx = self.axis, self.world, self.tx
            chunk = size // world

            def _upd_local(p_shard, g_full, opt_local):
                import optax

                idx = jax.lax.axis_index(axis)
                g_shard = jax.lax.dynamic_slice(
                    g_full, (idx * chunk,), (chunk,))
                updates, new_opt = tx.update(g_shard, opt_local,
                                             p_shard)
                return optax.apply_updates(p_shard, updates), new_opt

            ospecs = self._opt_specs(chunk, dtype)
            self._progs[key] = lower_shard_map(
                _upd_local, self.owner,
                in_specs=(P(axis), P(), ospecs),
                out_specs=(P(axis), ospecs),
                axis_names=frozenset({axis}))
        return self._progs[key]
