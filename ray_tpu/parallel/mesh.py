"""Device meshes and logical-axis sharding.

The TPU-native answer to the reference's parallelism delegation (SURVEY §2d):
instead of handing TP/PP/SP to an external engine, parallelism here is a
property of a named device mesh. Pick a MeshConfig, annotate arrays with
logical axis names, and GSPMD inserts the collectives (allreduce /
all-gather / reduce-scatter over ICI, DCN axes across slices).

Axis vocabulary (sizes of 1 are legal and erased at trace time):
  data      — pure data parallelism (batch sharding, gradient allreduce)
  fsdp      — data parallelism with parameter/optimizer sharding (ZeRO-3:
              params all-gathered per layer, grads reduce-scattered)
  tensor    — tensor parallelism (megatron-style head/mlp sharding)
  sequence  — sequence/context parallelism (ring attention, Ulysses)
  expert    — expert parallelism for MoE
  pipeline  — pipeline stages (microbatched shard_map loop)

Logical axis names used by the model libraries are mapped to mesh axes by
LOGICAL_AXIS_RULES (t5x-style), overridable per MeshConfig.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# The mesh the program being traced will run on, with the logical-axis
# rules its arrays were laid out by, set by whoever builds the jitted
# program (the serving engine, the train step). GSPMD cannot partition a
# Mosaic custom call, so model code reads this to shard_map its Pallas
# kernels (flash, paged attention) over the mesh, and to pin its
# activations' layout (`constrain`) — our own channel, no dependency on
# jax's legacy thread-resources internals or flax's global rule state.
_KERNEL_MESH: contextvars.ContextVar[
    Tuple[Optional[Mesh], Optional[Dict[str, object]]]] = \
    contextvars.ContextVar("rtpu_kernel_mesh", default=(None, None))


@contextlib.contextmanager
def kernel_mesh(mesh: Optional[Mesh],
                rules: Optional[Dict[str, object]] = None):
    """Mark `mesh` active for model-side kernel sharding and activation
    constraints (trace-time: wrap every trace that should see it).
    `rules` are the logical-axis rules the program's parameters were
    placed by; None means DEFAULT_LOGICAL_AXIS_RULES."""
    token = _KERNEL_MESH.set((mesh, rules))
    try:
        yield mesh
    finally:
        _KERNEL_MESH.reset(token)


def current_kernel_mesh() -> Optional[Mesh]:
    return _KERNEL_MESH.get()[0]

AXIS_ORDER = ("data", "fsdp", "expert", "pipeline", "sequence", "tensor")

# logical axis -> mesh axis (or tuple of mesh axes, or None = replicated)
DEFAULT_LOGICAL_AXIS_RULES: Tuple[Tuple[str, object], ...] = (
    ("batch", ("data", "fsdp")),
    ("activation_batch", ("data", "fsdp")),
    ("activation_seq", "sequence"),
    ("activation_embed", None),
    ("activation_heads", "tensor"),
    ("activation_kv", None),
    ("activation_mlp", "tensor"),
    ("embed", "fsdp"),
    ("vocab", "tensor"),
    ("heads", "tensor"),
    ("kv_heads", "tensor"),
    ("head_dim", None),
    ("mlp", "tensor"),
    ("expert", "expert"),
    ("layers", None),
    ("stage", "pipeline"),
    ("seq", "sequence"),
)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape. Unset axes default to 1; `data=-1` absorbs
    whatever devices remain (like a reshape wildcard)."""
    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    sequence: int = 1
    pipeline: int = 1
    expert: int = 1
    # Axes that cross slice boundaries ride DCN, not ICI; list them here
    # so multi-slice topologies lay out correctly: `build()` places each
    # DCN axis ACROSS slices (device groups) and every other axis within
    # one slice, the layout `jax.experimental.mesh_utils.
    # create_hybrid_device_mesh` produces (reference analog: multi-host
    # topology in train/v2/api/config.py:114-123). The product of the
    # DCN axes' sizes must equal the slice count.
    dcn_axes: Tuple[str, ...] = ()
    logical_axis_rules: Tuple[Tuple[str, object], ...] = \
        DEFAULT_LOGICAL_AXIS_RULES

    def axis_sizes(self, num_devices: int) -> Dict[str, int]:
        sizes = {
            "data": self.data, "fsdp": self.fsdp, "tensor": self.tensor,
            "sequence": self.sequence, "pipeline": self.pipeline,
            "expert": self.expert,
        }
        fixed = math.prod(v for v in sizes.values() if v != -1)
        wildcard = [k for k, v in sizes.items() if v == -1]
        if len(wildcard) > 1:
            raise ValueError("at most one mesh axis may be -1")
        if wildcard:
            if num_devices % fixed != 0:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes "
                    f"product {fixed}")
            sizes[wildcard[0]] = num_devices // fixed
        elif fixed != num_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices, have {num_devices}")
        return sizes

    def build(self, devices: Optional[Sequence] = None,
              num_slices: Optional[int] = None) -> Mesh:
        devices = list(devices if devices is not None else jax.devices())
        sizes = self.axis_sizes(len(devices))
        if not self.dcn_axes:
            shape = tuple(sizes[a] for a in AXIS_ORDER)
            dev_array = np.asarray(devices).reshape(shape)
            return Mesh(dev_array, AXIS_ORDER)
        return self._build_hybrid(devices, sizes, num_slices)

    def _sliced_devices(self, devices: List, sizes: Dict[str, int],
                        num_slices: Optional[int]) -> Tuple[List, int]:
        """Validate + order devices for a hybrid layout: detect real
        slices via `device.slice_index` (sorted by it) or emulate
        contiguous virtual slices; check the DCN-axes product matches
        the slice count and divides the device count. Returns the
        ordered devices and the slice count."""
        for axis in self.dcn_axes:
            if axis not in sizes:
                raise ValueError(f"unknown dcn axis {axis!r}")
        dcn_total = math.prod(sizes[a] for a in self.dcn_axes)
        slice_ids = {getattr(d, "slice_index", 0) for d in devices}
        if len(slice_ids) > 1:
            if num_slices is not None and num_slices != len(slice_ids):
                raise ValueError(
                    f"num_slices={num_slices} but devices span "
                    f"{len(slice_ids)} slices")
            num_slices = len(slice_ids)
            devices = sorted(
                devices, key=lambda d: (getattr(d, "slice_index", 0),
                                        getattr(d, "id", 0)))
        elif num_slices is None:
            num_slices = dcn_total
        if dcn_total != num_slices:
            raise ValueError(
                f"dcn axes {self.dcn_axes} have total size {dcn_total} "
                f"but the topology has {num_slices} slices")
        if len(devices) % num_slices:
            raise ValueError(
                f"{len(devices)} devices not divisible into "
                f"{num_slices} slices")
        return devices, num_slices

    def _build_hybrid(self, devices: List, sizes: Dict[str, int],
                      num_slices: Optional[int]) -> Mesh:
        """Hybrid ICI×DCN mesh: DCN axes vary across slices, ICI axes
        within one. Real TPU slices are detected via `device.slice_index`
        (devices grouped and ordered by it); hosts without slice ids
        (CPU dryruns, single slice) emulate slices as contiguous device
        groups — pass `num_slices` or let it default to the DCN-axes
        product."""
        devices, num_slices = self._sliced_devices(devices, sizes,
                                                   num_slices)
        dcn_shape = tuple(sizes[a] if a in self.dcn_axes else 1
                          for a in AXIS_ORDER)
        ici_shape = tuple(1 if a in self.dcn_axes else sizes[a]
                          for a in AXIS_ORDER)
        # [dcn..., ici...] then interleave per axis: each final axis is
        # dcn_i * ici_i (one factor is 1), DCN major — so stepping a DCN
        # axis crosses a slice boundary, stepping an ICI axis stays in
        # the same contiguous slice group.
        arr = np.asarray(devices).reshape(dcn_shape + ici_shape)
        n = len(AXIS_ORDER)
        arr = arr.transpose([x for i in range(n) for x in (i, n + i)])
        arr = arr.reshape(tuple(sizes[a] for a in AXIS_ORDER))
        return Mesh(arr, AXIS_ORDER)

    def rules_dict(self) -> Dict[str, object]:
        return dict(self.logical_axis_rules)

    def slice_groups(self, devices: Optional[Sequence] = None,
                     num_slices: Optional[int] = None) -> List[List]:
        """Device groups per slice, in DCN-axis order — the unit for
        host-plane (out-of-program) cross-slice collectives: one leader
        per group talks over the `util.collective` ring while
        in-program collectives stay on ICI within a group."""
        devices = list(devices if devices is not None else jax.devices())
        if not self.dcn_axes:
            return [devices]
        sizes = self.axis_sizes(len(devices))
        devices, num_slices = self._sliced_devices(devices, sizes,
                                                   num_slices)
        per = len(devices) // num_slices
        return [devices[i * per:(i + 1) * per] for i in range(num_slices)]

    def host_topology(self, world_size: int):
        """Collective-backend `Topology` for a host-plane group of
        `world_size` ranks laid out like this mesh's slices: one
        contiguous rank group per slice (the `slice_groups` order), so
        the backend's algorithm selector knows which hops ride DCN.
        The DCN axes must have fixed sizes (their product is the slice
        count)."""
        from ..util.collective.topology import Topology
        return Topology.from_mesh_config(self, world_size)


def dp_rules(dp_axes: Sequence[str],
             base: Optional[Sequence[Tuple[str, object]]] = None
             ) -> Dict[str, object]:
    """Logical-axis rules for a PURE data-parallel layout over
    `dp_axes` (the ZeRO-1 sharded-update requirement: params replicated
    over the update axes). Batch-like logical axes map onto the dp
    axes; any other rule that would shard a tensor over one of them is
    dropped to replicated."""
    dp = tuple(dp_axes)
    dp_set = set(dp)
    out: Dict[str, object] = {}
    for name, target in (base if base is not None
                         else DEFAULT_LOGICAL_AXIS_RULES):
        if name in ("batch", "activation_batch"):
            out[name] = dp if len(dp) > 1 else dp[0]
            continue
        targets = target if isinstance(target, tuple) else (target,)
        if any(t in dp_set for t in targets if t is not None):
            out[name] = None
        else:
            out[name] = target
    return out


def logical_to_mesh_axes(logical_axes: Sequence[Optional[str]],
                         rules: Dict[str, object]) -> P:
    """Map ('batch','seq','embed') -> PartitionSpec(('data','fsdp'),...)"""
    out = []
    for name in logical_axes:
        if name is None:
            out.append(None)
        else:
            out.append(rules.get(name))
    return P(*out)


def named_sharding(mesh: Mesh, logical_axes: Sequence[Optional[str]],
                   rules: Optional[Dict[str, object]] = None) -> NamedSharding:
    rules = rules if rules is not None else dict(DEFAULT_LOGICAL_AXIS_RULES)
    return NamedSharding(mesh, logical_to_mesh_axes(logical_axes, rules))


def shard_logical(x, mesh: Mesh, logical_axes: Sequence[Optional[str]],
                  rules: Optional[Dict[str, object]] = None):
    """In-jit sharding constraint by logical axis names."""
    spec = logical_to_mesh_axes(
        logical_axes, rules if rules is not None
        else dict(DEFAULT_LOGICAL_AXIS_RULES))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain(x, logical_axes: Sequence[Optional[str]]):
    """Pin `x` to the layout its logical axis names have on the active
    kernel mesh (`shard_logical` under `kernel_mesh`'s mesh and rules):
    how the model states its activations' layout to the partitioner,
    which otherwise carries the parameters' into them. Returns `x`
    untouched when no kernel mesh is active, when the mesh has one
    device, or when a dimension does not divide over its mesh axes (one
    kv head on `tensor=2`: the partitioner is left to choose, not made
    to pad)."""
    mesh, rules = _KERNEL_MESH.get()
    if mesh is None or mesh.size == 1:
        return x
    rules = rules if rules is not None else dict(DEFAULT_LOGICAL_AXIS_RULES)
    for size, axes in zip(x.shape, logical_to_mesh_axes(logical_axes, rules)):
        axes = axes if isinstance(axes, tuple) else (axes,)
        if size % math.prod(mesh.shape[a] for a in axes if a is not None):
            return x
    return shard_logical(x, mesh, logical_axes, rules)


_COLLECTIVE = re.compile(
    r" (all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute)"
    r"(?:-start)?\(.*?\bchannel_id=(\d+)")


def collective_counts(compiled_text: str) -> Dict[str, int]:
    """Collectives of a compiled program (`compiled.as_text()`) by kind.
    An asynchronous pair counts at its `-start`, and clones that share a
    `channel_id` (one collective the compiler fused twice) count once.
    How a step shows that its layout engaged: left to propagation, the
    train step's all-to-alls grow by eight a layer (PERF.md, PR 31)."""
    seen = set(_COLLECTIVE.findall(compiled_text))
    return dict(collections.Counter(kind for kind, _ in seen))


def params_shardings(params, mesh: Mesh,
                     rules: Optional[Dict[str, object]] = None):
    """Build a pytree of NamedShardings from flax logical-axis metadata
    (nn.with_logical_partitioning names on each param)."""
    import flax.linen as nn
    rules_d = rules if rules is not None else dict(DEFAULT_LOGICAL_AXIS_RULES)

    def one(leaf):
        if isinstance(leaf, nn.Partitioned):
            return named_sharding(mesh, leaf.names, rules_d)
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        one, params, is_leaf=lambda x: isinstance(x, nn.Partitioned))


def unbox(params):
    """Strip flax Partitioned boxes to raw arrays."""
    import flax.linen as nn
    return jax.tree_util.tree_map(
        lambda x: x.value if isinstance(x, nn.Partitioned) else x, params,
        is_leaf=lambda x: isinstance(x, nn.Partitioned))


def mesh_info(mesh: Mesh) -> Dict[str, int]:
    return {axis: int(size) for axis, size in mesh.shape.items()}
