"""Device-mesh construction and sharding rules.

TPU-native replacement for the reference's process-group bootstrap
(ref: python/ray/train/torch/config.py:69 _setup_torch_process_group,
python/ray/util/collective/collective.py:258-615). On TPU there is no
per-tensor NCCL group: the unit of parallelism is a `jax.sharding.Mesh`
over which pjit/shard_map place XLA collectives on ICI. This module owns:

- `MeshSpec`: declarative parallelism degrees (dp/fsdp/tp/sp/ep/pp).
- `build_mesh`: devices -> Mesh, preferring ICI-contiguous axis order.
- logical axis rules: model code annotates pytrees with *logical* axes
  ("batch", "embed", "heads", ...) which map to mesh axes here — the
  flax `logical_axis_rules` idea, reimplemented standalone.

Mesh OWNERSHIP (who builds/validates the mesh and hands out
NamedShardings) lives one level up in `parallel.sharding.MeshOwner`:
this module provides the topology primitives, the sharding package the
layer both serve (LLM tp) and train (pipeline fsdp) consume
(docs/SHARDING.md).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical mesh axis names, outermost (slowest/DCN-most) first.  Ordering
# matters: jax lays devices out so the *last* axes are ICI-nearest, so we put
# tensor/seq (latency-sensitive, every-layer collectives) last and dp/pp
# (per-step collectives, DCN-tolerant) first.  This mirrors the scaling-book
# recipe: data outermost, model innermost.
MESH_AXES = ("pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclass(frozen=True)
class MeshSpec:
    """Parallelism degrees. -1 on exactly one axis means "fill with all
    remaining devices" (like torch DeviceMesh / t5x partitioning).

    `slices` > 1 declares a MULTI-SLICE job: devices span that many TPU
    slices joined by DCN (no ICI between slices). The mesh gains an
    outermost "slice" axis; per-slice ICI meshes compose under it, so
    collectives over "slice" ride DCN and everything else stays on ICI —
    the megascale recipe (dp over DCN, model axes within a slice)."""
    dp: int = -1      # pure data parallel (replicated params)
    fsdp: int = 1     # data parallel with sharded params (zero-3 style)
    tp: int = 1       # tensor (megatron) parallel
    sp: int = 1       # sequence/context parallel (ring attention axis)
    ep: int = 1       # expert parallel (MoE)
    pp: int = 1       # pipeline parallel
    slices: int = 1   # DCN-connected slices (outermost axis when > 1)

    def degrees(self) -> Dict[str, int]:
        return {"pp": self.pp, "dp": self.dp, "fsdp": self.fsdp,
                "ep": self.ep, "sp": self.sp, "tp": self.tp}

    def resolve(self, n_devices: int) -> Dict[str, int]:
        """Fill the single -1 axis so the per-slice product equals
        n_devices / slices."""
        if self.slices < 1:
            raise ValueError(f"slices must be >= 1, got {self.slices}")
        if n_devices % self.slices:
            raise ValueError(
                f"{n_devices} devices not divisible into {self.slices} slices")
        per_slice = n_devices // self.slices
        d = self.degrees()
        wild = [k for k, v in d.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"At most one mesh axis may be -1, got {wild}")
        fixed = math.prod(v for v in d.values() if v != -1)
        if wild:
            if per_slice % fixed:
                raise ValueError(
                    f"{per_slice} per-slice devices not divisible by fixed "
                    f"axes product {fixed}")
            d[wild[0]] = per_slice // fixed
        elif fixed != per_slice:
            raise ValueError(
                f"Mesh {d} wants {fixed} devices/slice but {per_slice} "
                f"are available")
        return d


def build_mesh(spec: Union[MeshSpec, Dict[str, int], None] = None,
               devices: Optional[Sequence[jax.Device]] = None,
               axis_names: Sequence[str] = MESH_AXES) -> Mesh:
    """Build a Mesh from a spec over the given (default: all) devices.

    Uses `mesh_utils.create_device_mesh` when possible so the physical ICI
    topology lines up with the logical axes; falls back to a plain reshape
    on virtual/CPU devices. A MeshSpec with slices > 1 produces a
    DCN-aware mesh: outermost "slice" axis over per-slice ICI meshes.
    """
    devices = list(devices if devices is not None else jax.devices())
    if spec is None:
        spec = MeshSpec()
    if isinstance(spec, MeshSpec) and spec.slices > 1:
        return build_multislice_mesh(spec, devices, axis_names)
    degrees = spec.resolve(len(devices)) if isinstance(spec, MeshSpec) else dict(spec)
    shape = tuple(degrees[a] for a in axis_names)
    return Mesh(_device_array(shape, devices), axis_names)


def _device_array(shape: Tuple[int, ...],
                  devices: Sequence[jax.Device]) -> np.ndarray:
    """Devices laid out as ``shape``. On a TPU the physical topology
    decides the order, and a layout it cannot give raises: a silent
    reshape would put collectives on the wrong links. CPU devices have
    no topology, so there a plain reshape is the layout."""
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        return mesh_utils.create_device_mesh(
            shape, devices=devices, allow_split_physical_axes=True)
    return np.asarray(devices).reshape(shape)


def group_devices_by_slice(devices: Sequence[jax.Device],
                           num_slices: int) -> List[List[jax.Device]]:
    """Partition devices into their physical slices. Real multi-slice TPU
    devices carry `slice_index`; virtual/CPU devices (tests) split into
    contiguous equal groups."""
    by_idx: Dict[int, List[jax.Device]] = {}
    if all(getattr(d, "slice_index", None) is not None for d in devices):
        for d in devices:
            by_idx.setdefault(d.slice_index, []).append(d)
        if len(by_idx) == num_slices:
            return [by_idx[i] for i in sorted(by_idx)]
        # topology disagrees with the spec: fall through to error
        raise ValueError(
            f"spec wants {num_slices} slices but devices report "
            f"{len(by_idx)} distinct slice_index values")
    per = len(devices) // num_slices
    return [list(devices[i * per:(i + 1) * per]) for i in range(num_slices)]


def build_multislice_mesh(spec: MeshSpec,
                          devices: Optional[Sequence[jax.Device]] = None,
                          axis_names: Sequence[str] = MESH_AXES) -> Mesh:
    """Compose per-slice ICI meshes under an outermost "slice" DCN axis
    (SURVEY §5 comm-backend: DCN-aware multi-slice meshes; the analog of
    mesh_utils.create_hybrid_device_mesh). Collectives that name "slice"
    lower to DCN transfers; all other axes stay within a slice's ICI."""
    devices = list(devices if devices is not None else jax.devices())
    degrees = spec.resolve(len(devices))
    inner_shape = tuple(degrees[a] for a in axis_names)
    groups = group_devices_by_slice(devices, spec.slices)
    dev_array = np.stack([_device_array(inner_shape, g) for g in groups],
                         axis=0)
    return Mesh(dev_array, ("slice", *axis_names))


def virtual_mesh(n_devices: int,
                 spec: Union[MeshSpec, Dict[str, int], None] = None) -> Mesh:
    """Mesh over the first n host/virtual devices — the test path
    (conftest sets xla_force_host_platform_device_count)."""
    return build_mesh(spec, devices=jax.devices()[:n_devices])


def local_mesh() -> Mesh:
    """Single-process mesh over all local devices, dp-major."""
    return build_mesh(MeshSpec(dp=-1), devices=jax.local_devices())


def mesh_shape_for(n_devices: int, prefer_tp: int = 1) -> MeshSpec:
    """Heuristic spec: cap tp at prefer_tp (and at n), rest goes to dp."""
    tp = math.gcd(prefer_tp, n_devices)
    return MeshSpec(dp=-1, tp=tp)


# ---------------------------------------------------------------------------
# Logical axis rules
# ---------------------------------------------------------------------------

#: rule list: logical axis name -> mesh axis (or tuple of mesh axes, or None)
Rules = Sequence[Tuple[str, Union[str, Tuple[str, ...], None]]]


@dataclass
class AxisRules:
    """Maps logical axis names used by model code to physical mesh axes.

    Equivalent in spirit to flax.linen.logical_axis_rules; standalone so
    models can be plain pytrees. First matching rule wins; unknown logical
    axes are unsharded (None).
    """
    rules: Rules = field(default_factory=lambda: default_axis_rules())

    def mesh_axes(self, logical: Sequence[Optional[str]]) -> P:
        out: List[Union[str, Tuple[str, ...], None]] = []
        for name in logical:
            if name is None:
                out.append(None)
                continue
            for key, axes in self.rules:
                if key == name:
                    out.append(axes)
                    break
            else:
                out.append(None)
        # Trim trailing Nones (canonical PartitionSpec form).
        while out and out[-1] is None:
            out.pop()
        return P(*out)


def default_axis_rules(fsdp_enabled: bool = True,
                       multislice: bool = False) -> Rules:
    """The standard decoder-LM mapping (scaling-book style):
    batch -> dp(+fsdp), sequence -> sp, embed -> fsdp (param sharding),
    heads/mlp -> tp, experts -> ep, pipeline stage handled outside.
    multislice=True prepends the DCN "slice" axis to the batch mapping —
    data parallel across slices, model axes within a slice."""
    if multislice:
        batch_axes = (("slice", "dp", "fsdp") if fsdp_enabled
                      else ("slice", "dp"))
        return (("batch", batch_axes),) + tuple(
            r for r in default_axis_rules(fsdp_enabled) if r[0] != "batch")
    return (
        ("batch", ("dp", "fsdp") if fsdp_enabled else "dp"),
        ("seq", "sp"),
        ("embed", "fsdp" if fsdp_enabled else None),
        ("heads", "tp"),
        ("kv", None),
        ("mlp", "tp"),
        ("vocab", "tp"),
        ("expert", "ep"),
        ("stage", "pp"),
    )


def logical_to_mesh(tree: Any, logical_tree: Any, mesh: Mesh,
                    rules: Optional[AxisRules] = None) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    rules = rules or AxisRules()
    return jax.tree.map(
        lambda logical: NamedSharding(mesh, rules.mesh_axes(logical)),
        logical_tree, is_leaf=lambda x: isinstance(x, tuple))


def named_sharding(mesh: Mesh, *axes: Union[str, Tuple[str, ...], None]) -> NamedSharding:
    return NamedSharding(mesh, P(*axes))


def shard_constraint(x: Any, mesh: Mesh,
                     *logical: Optional[str],
                     rules: Optional[AxisRules] = None) -> Any:
    """with_sharding_constraint via logical axis names. Safe to call outside
    jit (no-op annotation will still place the array)."""
    rules = rules or AxisRules()
    spec = rules.mesh_axes(logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
