"""In-program (ICI) collectives.

The reference's NCCL backend has no analog here by design: inside a jitted
SPMD program, collectives are jax.lax primitives lowered by GSPMD onto ICI
(SURVEY §2d, §5). These are thin aliases plus standalone jitted wrappers for
applying a collective to an already-sharded global array outside any
user jit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

# In-jit aliases (use inside shard_map bodies).
allreduce = jax.lax.psum
allreduce_mean = jax.lax.pmean
all_gather = jax.lax.all_gather
ppermute = jax.lax.ppermute
all_to_all = jax.lax.all_to_all
axis_index = jax.lax.axis_index


def psum_scatter(x, axis_name, **kwargs):
    return jax.lax.psum_scatter(x, axis_name, **kwargs)


def device_allreduce(x, mesh: Mesh, axis_name: str = "data",
                     in_spec: P = None):
    """Allreduce a global array sharded over `axis_name` (one jitted op)."""
    spec = in_spec if in_spec is not None else P(axis_name)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
    def _ar(blk):
        return jax.lax.psum(blk, axis_name)

    return jax.jit(_ar)(x)


def device_allgather(x, mesh: Mesh, axis_name: str = "data"):
    spec = P(axis_name)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
                       out_specs=P(), check_vma=False)
    def _ag(blk):
        return jax.lax.all_gather(blk, axis_name, tiled=True)

    return jax.jit(_ag)(x)


# ---------------------------------------------------------------------------
# hierarchical + quantized schedules (the collective-backend lowering:
# intra-slice over ICI, inter-slice over DCN — PAPERS: arxiv 2504.18658
# topology-aware selection, arxiv 2506.17615 EQuARX block quantization)
# ---------------------------------------------------------------------------

def hierarchical_allreduce(x, mesh: Mesh, ici_axis: str = "fsdp",
                           dcn_axis: str = "data", in_spec: P = None):
    """The hierarchical allreduce as ONE jitted op: reduce-scatter over
    the intra-slice (ICI) axis, allreduce of the scattered shards over
    the cross-slice (DCN) axis, all-gather back over ICI. Numerically
    an allreduce over both axes; only 1/Ws of the payload ever crosses
    the slice boundary. The local block must divide by the ICI axis
    size (psum_scatter's tiling contract)."""
    spec = in_spec if in_spec is not None else P((dcn_axis, ici_axis))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
    def _h(blk):
        part = jax.lax.psum_scatter(blk, ici_axis, tiled=True)
        part = jax.lax.psum(part, dcn_axis)
        return jax.lax.all_gather(part, ici_axis, tiled=True)

    return jax.jit(_h)(x)


def quantized_psum(blk, axis_name: str, block: int = 64):
    """In-jit EQuARX psum for shard_map bodies: block-int8 quantize the
    local shard once, all-gather codes + per-block fp32 scales along
    `axis_name`, dequantize each peer's payload and accumulate in fp32
    ("accumulate wide"), cast back. Moves ~4x fewer bytes along the
    axis than a fp32 psum; error is bounded by one quantization per
    participant (never compounded)."""
    from . import quant
    q, scales = quant.quantize_traced(blk, block)
    qs = jax.lax.all_gather(q, axis_name)          # [S, nb, block] int8
    ss = jax.lax.all_gather(scales, axis_name)     # [S, nb] f32
    deq = (qs.astype(jnp.float32) * ss[..., None]).sum(axis=0)
    flat = deq.reshape(-1)[:blk.size]
    return flat.reshape(blk.shape).astype(blk.dtype)


def quantized_allreduce(x, mesh: Mesh, axis_name: str = "data",
                        block: int = 64, in_spec: P = None):
    """Standalone jitted quantized allreduce over one (DCN) axis."""
    spec = in_spec if in_spec is not None else P(axis_name)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
    def _qar(blk):
        return quantized_psum(blk, axis_name, block=block)

    return jax.jit(_qar)(x)


def hierarchical_quantized_allreduce(x, mesh: Mesh,
                                     ici_axis: str = "fsdp",
                                     dcn_axis: str = "data",
                                     block: int = 64, in_spec: P = None):
    """The full tentpole schedule, jitted: intra-slice reduce-scatter
    over ICI, block-int8 quantized allreduce of the shards over DCN,
    intra-slice all-gather."""
    spec = in_spec if in_spec is not None else P((dcn_axis, ici_axis))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
    def _hq(blk):
        part = jax.lax.psum_scatter(blk, ici_axis, tiled=True)
        part = quantized_psum(part, dcn_axis, block=block)
        return jax.lax.all_gather(part, ici_axis, tiled=True)

    return jax.jit(_hq)(x)
