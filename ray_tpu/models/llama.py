"""Llama-family causal LM, TPU-first.

The flagship model of the framework (the reference delegates modeling to
torch/vLLM; here it is native): flax.linen with logical-axis partitioning on
every parameter and activation, so one definition serves every parallelism
mix — DP/FSDP/TP/SP via `ray_tpu.parallel.MeshConfig`, and the mesh decides
the collectives.

Design notes for the MXU/HBM:
- all matmuls in bf16 with fp32 accumulation (`preferred_element_type`)
- attention via ops.attention.flash_attention (Pallas on TPU)
- per-block jax.checkpoint with dots-saveable policy for rematerialization
- RoPE applied in fp32; RMSNorm in fp32 then cast back
- decode path keeps a KV cache laid out [batch, kv_heads, max_seq, head_dim]

Parity map (reference models live outside Ray; shapes follow the public
Llama-2/3 configs).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..ops.attention import (attend_cache, flash_attention,
                             flash_uses_pallas)
from ..ops.paged_attention import paged_attend
from ..parallel.mesh import constrain, current_kernel_mesh

Dtype = Any

# Activation layouts of the no-cache (training) forward, by logical axis
# (parallel.mesh.DEFAULT_LOGICAL_AXIS_RULES: batch over data x fsdp, heads
# / mlp / vocab over tensor, the hidden dimension whole). Stated with
# `constrain` at every site below: left to propagation, GSPMD carries the
# parameters' `embed -> fsdp` into the residual stream instead and pays
# eight all-to-alls and an all-reduce of each MLP projection's partial
# sums a layer (PERF.md, PR 31). The cache branches (serving) keep the
# programs propagation gives them: `_pin` passes their arrays through.
_STREAM = ("activation_batch", "activation_seq", "activation_embed")
_HEADS = ("activation_batch", "activation_seq", "activation_heads", None)
_MLP_HIDDEN = ("activation_batch", "activation_seq", "activation_mlp")
_LOGITS = ("activation_batch", "activation_seq", "vocab")


def _pin(x, names, no_cache: bool):
    return constrain(x, names) if no_cache else x


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    use_flash: bool = True
    # "flash" (pallas fwd + chunked bwd), "chunked", or "reference"
    # (full-logits, XLA-fused — fastest backward at moderate seq lengths).
    attention_impl: str = "flash"
    # LoRA (Hu et al. 2021; reference workload: BASELINE config_3's
    # Llama-2-7B LoRA fine-tune). rank 0 = disabled. Each target
    # projection W gains (alpha/rank) * A @ B with B zero-initialized,
    # so enabling LoRA never changes the initial forward. Train only
    # the adapters with models.lora.lora_optimizer; fold them for
    # serving with models.lora.merge_lora.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: Tuple[str, ...] = ("q_proj", "k_proj", "v_proj",
                                     "o_proj")

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def module(self) -> "LlamaModel":
        return LlamaModel(self)

    # ---- presets ----
    @staticmethod
    def tiny_test():
        """4-layer toy for tests / graft entry compile checks."""
        return LlamaConfig(vocab_size=256, hidden_size=128,
                           intermediate_size=352, num_layers=4, num_heads=4,
                           num_kv_heads=2, max_seq_len=256, remat=False)

    @staticmethod
    def llama2_7b():
        return LlamaConfig()  # the defaults above are llama-2-7b

    @staticmethod
    def llama3_8b():
        return LlamaConfig(vocab_size=128256, hidden_size=4096,
                           intermediate_size=14336, num_layers=32,
                           num_heads=32, num_kv_heads=8, max_seq_len=8192,
                           rope_theta=500000.0)

    @staticmethod
    def bench_350m():
        """~350M-param config sized for a single v5e chip benchmark.

        8 heads of head_dim=128 (not 16x64): the MXU is a 128x128 systolic
        array, so a 128-deep attention contraction keeps it full — measured
        57.9% vs 38.0% MFU on v5e for the same parameter count.
        """
        return LlamaConfig(vocab_size=32000, hidden_size=1024,
                           intermediate_size=2816, num_layers=24,
                           num_heads=8, num_kv_heads=8, max_seq_len=2048)

    def num_params(self) -> int:
        d, v = self.hidden_size, self.vocab_size
        hd = self.head_dim_
        attn = d * (self.num_heads * hd) + 2 * d * (self.num_kv_heads * hd) \
            + (self.num_heads * hd) * d
        mlp = 3 * d * self.intermediate_size
        per_layer = attn + mlp + 2 * d
        embed = v * d * (1 if self.tie_embeddings else 2)
        return self.num_layers * per_layer + embed + d


def _partitioned(init, names):
    return nn.with_logical_partitioning(init, names)


def _lora_delta(x, feats, in_names, out_names, name, cfg,
                axis=-1):
    """(x @ A) @ B * (alpha/rank): the LoRA low-rank path, computed
    WITHOUT materializing the dense delta (the x@A bottleneck is [.., r]
    — at rank 8-64 this is bandwidth-free next to the base matmul).
    B is zero-init, so the adapted model starts exactly at the base
    model. The 'lora' logical axis has no mesh rule -> adapters
    replicate (they are KBs; the base weights stay sharded)."""
    r = cfg.lora_rank
    a = nn.DenseGeneral(
        r, axis=axis, use_bias=False, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, name=f"{name}_lora_a",
        kernel_init=_partitioned(nn.initializers.lecun_normal(),
                                 in_names + ("lora",)))(x)
    b = nn.DenseGeneral(
        feats, axis=-1, use_bias=False, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, name=f"{name}_lora_b",
        kernel_init=_partitioned(nn.initializers.zeros_init(),
                                 ("lora",) + out_names))(a)
    return b * (cfg.lora_alpha / r)


def _maybe_lora(x, y, feats, in_names, out_names, name, cfg, axis=-1):
    """y = base_projection(x); adds the LoRA path when enabled."""
    if cfg.lora_rank and name in cfg.lora_targets:
        return y + _lora_delta(x, feats, in_names, out_names, name, cfg,
                               axis=axis)
    return y


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", _partitioned(nn.initializers.ones,
                                                 ("embed",)), (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(self.dtype)


def rope_frequencies(head_dim: int, max_seq: int, theta: float):
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta ** exponents)
    positions = jnp.arange(max_seq, dtype=jnp.float32)
    angles = jnp.outer(positions, inv_freq)  # [seq, head_dim/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin, positions):
    """x: [b, heads, seq, head_dim]; positions: [b, seq]"""
    cos_p = cos[positions][:, None, :, :]      # [b, 1, seq, hd/2]
    sin_p = sin[positions][:, None, :, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    rotated = jnp.concatenate(
        [x1 * cos_p - x2 * sin_p, x2 * cos_p + x1 * sin_p], axis=-1)
    return rotated.astype(x.dtype)


def _flash_on_mesh(q, k, v):
    """Causal flash attention under the active kernel mesh. GSPMD cannot
    partition a Mosaic custom call, so where the Pallas kernel will run
    on a multi-device mesh it is shard_mapped over batch (data, fsdp)
    and heads (tensor): attention is independent across both, so no
    collectives. The jnp paths partition on their own."""
    mesh = current_kernel_mesh()
    if mesh is None or mesh.size == 1 or not flash_uses_pallas(q, k):
        return flash_attention(q, k, v)
    if mesh.shape["sequence"] > 1:
        raise NotImplementedError(
            "the flash kernel cannot run on a sequence-sharded mesh; "
            "use parallel.ring_attention")
    spec = PartitionSpec(("data", "fsdp"), "tensor", None, None)
    return jax.shard_map(
        flash_attention, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)(q, k, v)


def write_token_rows(pool, rows, block_tables, lengths):
    """One decode token per batch row into a page pool.
    pool [kv_heads, num_pages, page_size, head_dim]; rows [kv_heads, B,
    head_dim]; row b lands at (block_tables[b, lengths[b] // page_size],
    lengths[b] % page_size) of every kv head. The scatter indexes all
    three leading dimensions, so its window is head_dim alone and is
    contiguous in the pool's own layout, the paged kernel's: indexed by
    (page, offset) with the kv heads as a window, XLA:TPU relays the whole
    pool out and back around the scatter, every layer, every tick. Rows
    that are not decoding write to the null page their table names and
    may collide there."""
    page_size = pool.shape[2]
    batch = jnp.arange(rows.shape[1])
    page_of = block_tables[batch, lengths // page_size]
    offset = lengths % page_size
    heads = jnp.arange(pool.shape[0])[:, None]
    return pool.at[heads, page_of[None, :], offset[None, :]].set(rows)


class Attention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, cache_index=None):
        cfg = self.config
        hd = cfg.head_dim_
        no_cache = kv_cache is None
        dense = lambda feats, names, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name,
            kernel_init=_partitioned(
                nn.initializers.lecun_normal(), names))
        q = dense((cfg.num_heads, hd), ("embed", "heads", "head_dim"),
                  "q_proj")(x)
        q = _maybe_lora(x, q, (cfg.num_heads, hd), ("embed",),
                        ("heads", "head_dim"), "q_proj", cfg)
        k = dense((cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                  "k_proj")(x)
        k = _maybe_lora(x, k, (cfg.num_kv_heads, hd), ("embed",),
                        ("kv_heads", "head_dim"), "k_proj", cfg)
        v = dense((cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                  "v_proj")(x)
        v = _maybe_lora(x, v, (cfg.num_kv_heads, hd), ("embed",),
                        ("kv_heads", "head_dim"), "v_proj", cfg)
        # k and v by kv head: where those do not divide the tensor axis,
        # `constrain` leaves them to the partitioner
        q, k, v = (_pin(a, _HEADS, no_cache) for a in (q, k, v))
        # [b, s, h, d] -> [b, h, s, d]
        q = jnp.transpose(q, (0, 2, 1, 3))
        k = jnp.transpose(k, (0, 2, 1, 3))
        v = jnp.transpose(v, (0, 2, 1, 3))
        cos, sin = rope_frequencies(hd, cfg.max_seq_len, cfg.rope_theta)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)

        new_cache = None
        if isinstance(kv_cache, dict):
            # Paged decode (q_len == 1): the cache is a page pool
            #   k/v: [kv_heads, num_pages, page_size, head_dim]
            #   block_tables: [B, pages_per_seq] physical page ids
            #   lengths: [B] tokens already cached (this token's position)
            # Write lands at (table[len//ps], len%ps); attention is
            # ops.paged_attention.paged_attend: a Pallas kernel on TPU, a
            # gather fallback elsewhere.
            kp, vp = kv_cache["k"], kv_cache["v"]
            block_tables = kv_cache["block_tables"]
            lengths = kv_cache["lengths"]
            # k,v are [B, kvh, 1, hd] -> write [kvh, B, hd] rows
            k_rows = jnp.transpose(k[:, :, 0, :], (1, 0, 2)).astype(kp.dtype)
            v_rows = jnp.transpose(v[:, :, 0, :], (1, 0, 2)).astype(vp.dtype)
            q1 = q[:, :, 0, :]  # [B, heads, hd]

            def write_then_attend(q_, kp_, vp_, k_rows_, v_rows_, lengths_,
                                  tables_):
                """Per shard (LOCAL heads and kv heads; the whole arrays
                when there is no tensor axis): the token's rows go into
                the pools, then the kernel reads the pools."""
                kp_ = write_token_rows(kp_, k_rows_, tables_, lengths_)
                vp_ = write_token_rows(vp_, v_rows_, tables_, lengths_)
                out_ = paged_attend(
                    q_, kp_, vp_, lengths_, tables_,
                    reference=cfg.attention_impl == "reference")
                return out_, kp_, vp_

            # Tensor-parallel serving: when tracing under a serving mesh
            # whose `tensor` axis is >1, write and attend per-shard via
            # shard_map (heads/kv_heads sharded, attention is
            # head-parallel so no collectives). GSPMD cannot partition
            # the Pallas custom call itself, and an index over the
            # sharded kv-head dimension written outside the map could
            # make it gather the pool, hence the explicit map
            # (reference places TP engine workers via
            # vllm_models.py:169-178; here TP is a mesh axis).
            pm = current_kernel_mesh()
            tp = int(pm.shape.get("tensor", 1)) if pm is not None else 1
            step = write_then_attend
            if tp > 1:
                pool = PartitionSpec("tensor")
                heads = PartitionSpec(None, "tensor", None)
                whole = PartitionSpec()
                step = jax.shard_map(
                    write_then_attend, mesh=pm,
                    in_specs=(heads, pool, pool, pool, pool, whole, whole),
                    out_specs=(heads, pool, pool), check_vma=False)
            out1, kp, vp = step(
                q1, kp, vp, k_rows, v_rows, lengths, block_tables)
            new_cache = dict(kv_cache, k=kp, v=vp)
            out = out1[:, :, None, :].astype(cfg.dtype)
        elif kv_cache is not None:
            # Dense cache: write new K/V at cache_index (a scalar: the
            # whole batch at one position — a prefill chunk, or a
            # single-sequence decode), attend over the cache.
            ck, cv = kv_cache
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), cache_index, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), cache_index, axis=2)
            new_cache = (ck, cv)
            out = attend_cache(q, ck, cv, cache_index, positions)
        else:
            impl = cfg.attention_impl if cfg.use_flash else "chunked"
            if impl == "reference":
                from ..ops.attention import attention_reference
                out = attention_reference(q, k, v, True)
            elif impl == "chunked":
                from ..ops.attention import attention_chunked
                out = attention_chunked(q, k, v, True)
            else:
                out = _flash_on_mesh(q, k, v)
        out = jnp.transpose(out, (0, 2, 1, 3))  # [b, s, h, d]
        out = _pin(out, _HEADS, no_cache)
        proj = nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="o_proj",
            kernel_init=_partitioned(nn.initializers.lecun_normal(),
                                     ("heads", "head_dim", "embed")))(out)
        proj = _maybe_lora(out, proj, cfg.hidden_size,
                           ("heads", "head_dim"), ("embed",), "o_proj",
                           cfg, axis=(-2, -1))
        return _pin(proj, _STREAM, no_cache), new_cache


class MLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, no_cache: bool = False):
        cfg = self.config
        gate = nn.DenseGeneral(
            cfg.intermediate_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="gate_proj",
            kernel_init=_partitioned(nn.initializers.lecun_normal(),
                                     ("embed", "mlp")))(x)
        gate = _maybe_lora(x, gate, cfg.intermediate_size, ("embed",),
                           ("mlp",), "gate_proj", cfg)
        up = nn.DenseGeneral(
            cfg.intermediate_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="up_proj",
            kernel_init=_partitioned(nn.initializers.lecun_normal(),
                                     ("embed", "mlp")))(x)
        up = _maybe_lora(x, up, cfg.intermediate_size, ("embed",),
                         ("mlp",), "up_proj", cfg)
        hidden = nn.silu(gate) * up
        hidden = _pin(hidden, _MLP_HIDDEN, no_cache)
        down = nn.DenseGeneral(
            cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="down_proj",
            kernel_init=_partitioned(nn.initializers.lecun_normal(),
                                     ("mlp", "embed")))(hidden)
        down = _maybe_lora(hidden, down, cfg.hidden_size, ("mlp",),
                           ("embed",), "down_proj", cfg)
        return _pin(down, _STREAM, no_cache)


class DecoderBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, kv_cache=None, cache_index=None):
        cfg = self.config
        no_cache = kv_cache is None
        normed = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="attn_norm")(x)
        attn_out, new_cache = Attention(cfg, name="attn")(
            _pin(normed, _STREAM, no_cache), positions, kv_cache,
            cache_index)
        x = _pin(x + attn_out, _STREAM, no_cache)
        normed = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="mlp_norm")(x)
        x = x + MLP(cfg, name="mlp")(_pin(normed, _STREAM, no_cache),
                                     no_cache)
        return _pin(x, _STREAM, no_cache), new_cache


class LlamaModel(nn.Module):
    """Causal LM: tokens -> logits. `kv_caches` enables decode mode.

    The head alone is `head` (`model.apply(variables, x, method="head")`),
    and `head=False` stops a call in front of it and hands back the final
    norm's output where the logits would be: a caller that needs logits
    at few of its positions (a prefill chunk needs one, or none) applies
    the head to those rows itself."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, tokens, positions=None, kv_caches=None,
                 cache_index=None, head=True):
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None, :], tokens.shape)
        embed = self.param(
            "embed", _partitioned(nn.initializers.normal(0.02),
                                  ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        x = jnp.take(embed, tokens, axis=0).astype(cfg.dtype)
        x = _pin(x, _STREAM, kv_caches is None)

        block = DecoderBlock
        if cfg.remat and kv_caches is None:
            block = nn.remat(
                DecoderBlock, policy=jax.checkpoint_policies.
                checkpoint_dots_with_no_batch_dims, static_argnums=(3,))
        new_caches = []
        for layer in range(cfg.num_layers):
            cache = kv_caches[layer] if kv_caches is not None else None
            x, new_cache = block(cfg, name=f"layer_{layer}")(
                x, positions, cache, cache_index)
            new_caches.append(new_cache)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        x = _pin(x, _STREAM, kv_caches is None)
        out = self.head(x) if head else x
        if kv_caches is not None:
            return out, new_caches
        return constrain(out, _LOGITS) if head else out

    @nn.compact
    def head(self, x):
        """Logits of the final norm's output `x` [batch, rows, hidden]."""
        cfg = self.config
        if cfg.tie_embeddings:
            embed = nn.unbox(self.get_variable("params", "embed"))
            return jnp.einsum("bsd,vd->bsv", x, embed.astype(cfg.dtype))
        return nn.DenseGeneral(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="lm_head",
            kernel_init=_partitioned(nn.initializers.lecun_normal(),
                                     ("embed", "vocab")))(x)


def init_kv_caches(config: LlamaConfig, batch: int, max_len: int,
                   dtype=None):
    dtype = dtype or config.dtype
    shape = (batch, config.num_kv_heads, max_len, config.head_dim_)
    return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
            for _ in range(config.num_layers)]


def cross_entropy_loss(logits, targets, mask=None, z_loss: float = 0.0):
    """Causal LM loss with optional z-loss regularizer."""
    logits = logits.astype(jnp.float32)
    log_z = jax.scipy.special.logsumexp(logits, axis=-1)
    target_logits = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    losses = log_z - target_logits
    if z_loss:
        losses = losses + z_loss * log_z ** 2
    if mask is not None:
        losses = losses * mask
        return losses.sum() / jnp.maximum(mask.sum(), 1)
    return losses.mean()
