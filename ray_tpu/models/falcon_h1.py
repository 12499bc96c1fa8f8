"""Hybrid decoder: a Mamba-2 mixer beside attention in every block (the
Falcon-H1 family, `model_type: falcon_h1`; equations from the published
config's keys, which the field names below repeat).

    x0 = embedding_multiplier * E[token]
    u  = RMSNorm(x)
    x  = x + ssm_out_multiplier * Mamba(u)
           + attention_out_multiplier * Attn(attention_in_multiplier * u)
    x  = x + MLP(RMSNorm(x))
    logits = lm_head_multiplier * (RMSNorm(x) W_head)

Attention and the mixer read the same normed input and are summed. The
attention half is the dense decoder's (RoPE over the whole head, GQA, the
same paged / flash calls), with `k <- key_multiplier * (u W_k)`. The mixer:

    p    = ((ssm_in_multiplier * u) W_in) * m     m: ssm_multipliers over
    z, xBC, dt = split(p)                            the z|x|B|C|dt spans
    xBC  = silu(causal depthwise conv1d(xBC, width mamba_d_conv) + bias)
    S_t  = exp(D_t A) S_{t-1} + D_t x_t (x) B_t   D = softplus(dt + dt_bias)
    y_t  = S_t C_t + D_skip * x_t                 A = -exp(A_log)
    y    = w * RMSNorm_per_group(y * silu(z))     (gate, then norm)
    Mamba(u) = y W_out

What a row carries between calls, besides its K/V: the last
`mamba_d_conv - 1` inputs of the convolution and the state S
(`state_shapes`). Three paths, chosen by `kv_caches` as in the dense model:
None = the whole sequence from a zero state; per-layer dicts = one paged
decode token a row (state pools updated in place, rows not `active` left
alone); per-layer `(k, v, conv, ssm)` tuples = one chunk of a prefill,
whose first `valid` tokens are real and whose padded tail the mixer must
not see.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attend_cache
from ..ops.paged_attention import paged_attend
from ..ops.ssm import ssd_chunked_scan, ssm_step
from .llama import (RMSNorm, _flash_on_mesh, _partitioned, apply_rope,
                    rope_frequencies, write_token_rows)

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_layers: int = 72
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    max_seq_len: int = 4096          # length of the rotary table
    rope_theta: float = 1e11
    rms_norm_eps: float = 1e-5
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # the recurrent state accumulates over every token of a request
    state_dtype: Any = jnp.float32
    # "flash" (Pallas on a TPU) or "reference" (jnp), as in LlamaConfig
    attention_impl: str = "flash"

    @property
    def head_dim_(self) -> int:
        return self.head_dim

    @property
    def mamba_d_head(self) -> int:
        return self.mamba_d_ssm // self.mamba_n_heads

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads

    # ---- what the paged engine asks of a model's configuration ----

    def module(self) -> "FalconH1Model":
        return FalconH1Model(self)

    def state_shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """Per layer, what one row holds besides K/V: name -> (shape
        without the row dimension, type). The convolution's window is
        kept [taps, channels]: the published layout is [channels, taps],
        whose 3-wide minor dimension a TPU pads to 128 lanes."""
        return {"conv": ((self.mamba_d_conv - 1, self.conv_dim), self.dtype),
                "ssm": ((self.mamba_n_heads, self.mamba_d_head,
                         self.mamba_d_state), self.state_dtype)}

    def init_state(self, rows: int):
        """Zeroed state for `rows` rows, per layer (conv, ssm)."""
        shapes = self.state_shapes()
        return [tuple(jnp.zeros((rows,) + shapes[k][0], shapes[k][1])
                      for k in ("conv", "ssm"))
                for _ in range(self.num_layers)]


def _dense(feats, names, name, cfg, axis=-1):
    return nn.DenseGeneral(
        feats, axis=axis, use_bias=False, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, name=name,
        kernel_init=_partitioned(nn.initializers.lecun_normal(), names))


def _a_log_init(key, shape, dtype):
    # Mamba-2's default: A = -U[1, 16]
    return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0)
                   ).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    # Mamba-2's default: softplus(dt_bias) log-uniform in [1e-3, 1e-1]
    dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                    math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class HybridAttention(nn.Module):
    """The attention half of a block: `cache` is None (whole sequence), a
    dict (paged decode) or a (k, v) pair of dense caches written at
    `cache_index` (a prefill chunk)."""
    config: FalconH1Config

    @nn.compact
    def __call__(self, u, positions, cache=None, cache_index=None):
        cfg = self.config
        hd = cfg.head_dim
        u = u * cfg.attention_in_multiplier
        q = _dense((cfg.num_heads, hd), ("embed", "heads", "head_dim"),
                   "q_proj", cfg)(u)
        k = _dense((cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                   "k_proj", cfg)(u) * cfg.key_multiplier
        v = _dense((cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                   "v_proj", cfg)(u)
        q, k, v = (jnp.transpose(a, (0, 2, 1, 3)) for a in (q, k, v))
        cos, sin = rope_frequencies(hd, cfg.max_seq_len, cfg.rope_theta)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        new_cache = None
        if isinstance(cache, dict):
            kp, vp = cache["k"], cache["v"]
            tables, lengths = cache["block_tables"], cache["lengths"]
            rows = lambda a, pool: jnp.transpose(  # noqa: E731
                a[:, :, 0, :], (1, 0, 2)).astype(pool.dtype)
            kp = write_token_rows(kp, rows(k, kp), tables, lengths)
            vp = write_token_rows(vp, rows(v, vp), tables, lengths)
            out = paged_attend(q[:, :, 0, :], kp, vp, lengths, tables,
                               reference=cfg.attention_impl == "reference")
            out = out[:, :, None, :].astype(cfg.dtype)
            new_cache = (kp, vp)
        elif cache is not None:
            ck, cv = cache
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), cache_index, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), cache_index, axis=2)
            new_cache = (ck, cv)
            out = attend_cache(q, ck, cv, cache_index, positions)
        elif cfg.attention_impl == "reference":
            from ..ops.attention import attention_reference
            out = attention_reference(q, k, v, True)
        else:
            out = _flash_on_mesh(q, k, v)
        out = jnp.transpose(out, (0, 2, 1, 3))
        out = _dense(cfg.hidden_size, ("heads", "head_dim", "embed"),
                     "o_proj", cfg, axis=(-2, -1))(out)
        return out * cfg.attention_out_multiplier, new_cache


class MambaMixer(nn.Module):
    """The state-space half. `state` is None (zero state, whole sequence),
    or (conv, ssm): with `active` [rows] one decode token a row, else one
    prefill chunk of a single row whose first `valid` tokens are real."""
    config: FalconH1Config

    @nn.compact
    def __call__(self, u, state=None, active=None, valid=None):
        cfg = self.config
        heads, p = cfg.mamba_n_heads, cfg.mamba_d_head
        groups, n = cfg.mamba_n_groups, cfg.mamba_d_state
        d_ssm, taps = cfg.mamba_d_ssm, cfg.mamba_d_conv
        batch, length = u.shape[0], u.shape[1]
        spans = (d_ssm, d_ssm, groups * n, groups * n, heads)
        scale = jnp.concatenate([jnp.full((w,), m, F32) for w, m
                                 in zip(spans, cfg.ssm_multipliers)])
        proj = _dense(cfg.in_proj_dim, ("embed", "mlp"), "in_proj", cfg)(
            u * cfg.ssm_in_multiplier)
        proj = (proj * scale.astype(proj.dtype))
        z = proj[..., :d_ssm]
        xbc = proj[..., d_ssm:d_ssm + cfg.conv_dim]
        dt_raw = proj[..., d_ssm + cfg.conv_dim:]

        conv_w = self.param(
            "conv_kernel", _partitioned(nn.initializers.lecun_normal(),
                                        (None, "mlp")),
            (taps, cfg.conv_dim), cfg.param_dtype)
        conv_b = self.param(
            "conv_bias", _partitioned(nn.initializers.normal(0.02),
                                      ("mlp",)),
            (cfg.conv_dim,), cfg.param_dtype)
        a_log = self.param("A_log", _partitioned(_a_log_init, (None,)),
                           (heads,), F32)
        dt_bias = self.param("dt_bias", _partitioned(_dt_bias_init, (None,)),
                             (heads,), F32)
        d_skip = self.param("D", _partitioned(nn.initializers.ones, (None,)),
                            (heads,), F32)
        norm_w = self.param("norm_scale",
                            _partitioned(nn.initializers.ones, ("mlp",)),
                            (d_ssm,), F32)

        window = jnp.zeros((batch, taps - 1, cfg.conv_dim), xbc.dtype) \
            if state is None else state[0].astype(xbc.dtype)
        full = jnp.concatenate([window, xbc], axis=1)   # [b, taps-1+len, c]
        conv = sum(full[:, j:j + length].astype(F32) * conv_w[j].astype(F32)
                   for j in range(taps)) + conv_b.astype(F32)
        conv = jax.nn.silu(conv).astype(cfg.dtype)
        x = conv[..., :d_ssm].reshape(batch, length, heads, p)
        b, c = (t.reshape(batch, length, groups, n)
                for t in jnp.split(conv[..., d_ssm:], 2, axis=-1))
        dt = jax.nn.softplus(dt_raw.astype(F32) + dt_bias)
        a = -jnp.exp(a_log)

        new_state = None
        if active is not None:
            # a row that is not decoding gets dt 0 and keeps its window:
            # its state is left as it is
            conv_pool, ssm_pool = state
            y, ssm_pool = ssm_step(
                x[:, 0], jnp.where(active[:, None], dt[:, 0], 0.0), a,
                b[:, 0], c[:, 0], ssm_pool)
            conv_pool = jnp.where(active[:, None, None],
                                  full[:, 1:].astype(conv_pool.dtype),
                                  conv_pool)
            new_state = (conv_pool, ssm_pool)
            y = y[:, None]
        else:
            start = jnp.zeros((batch, heads, p, n), F32) if state is None \
                else state[1]
            if valid is not None:
                real = jnp.arange(length) < valid
                dt = jnp.where(real[None, :, None], dt, 0.0)
            y, last = ssd_chunked_scan(x, dt, a, b, c, start,
                                       cfg.mamba_chunk_size)
            if state is not None:
                # the window after the last real token: inputs
                # valid-taps+1 .. valid-1 are full[valid : valid+taps-1]
                upto = length if valid is None else valid
                new_state = (
                    jax.lax.dynamic_slice_in_dim(
                        full, upto, taps - 1, axis=1).astype(state[0].dtype),
                    last.astype(state[1].dtype))
        y = y + d_skip[:, None] * x.astype(F32)
        y = y.reshape(batch, length, d_ssm) * jax.nn.silu(z.astype(F32))
        # gate, then norm, per group (mamba_norm_before_gate false)
        grouped = y.reshape(batch, length, groups, d_ssm // groups)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, -1, keepdims=True) + cfg.rms_norm_eps)
        y = (grouped.reshape(batch, length, d_ssm) * norm_w).astype(cfg.dtype)
        out = _dense(cfg.hidden_size, ("mlp", "embed"), "out_proj", cfg)(y)
        return out * cfg.ssm_out_multiplier, new_state


class HybridMLP(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate_m, down_m = cfg.mlp_multipliers
        gate = _dense(cfg.intermediate_size, ("embed", "mlp"), "gate_proj",
                      cfg)(x)
        up = _dense(cfg.intermediate_size, ("embed", "mlp"), "up_proj",
                    cfg)(x)
        down = _dense(cfg.hidden_size, ("mlp", "embed"), "down_proj", cfg)(
            nn.silu(gate * gate_m) * up)
        return down * down_m


class ParallelBlock(nn.Module):
    config: FalconH1Config

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_index=None,
                 valid=None):
        cfg = self.config
        u = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_norm")(x)
        if cache is None:
            kv, state, active = None, None, None
        elif isinstance(cache, dict):
            kv, state, active = cache, (cache["conv"], cache["ssm"]), \
                cache["active"]
        else:
            kv, state, active = cache[:2], cache[2:], None
        attn, new_kv = HybridAttention(cfg, name="attn")(
            u, positions, kv, cache_index)
        mixed, new_state = MambaMixer(cfg, name="mamba")(
            u, state, active, valid)
        x = x + mixed + attn
        x = x + HybridMLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="mlp_norm")(x))
        new_cache = None if cache is None else tuple(new_kv) + new_state
        return x, new_cache


class FalconH1Model(nn.Module):
    """tokens -> logits; with `kv_caches`, (logits, per-layer (k, v, conv,
    ssm)) — k/v pools and state pools in paged decode, dense caches and
    one row's state in a prefill chunk. `head=False` and the method `head`
    as `LlamaModel`'s: the final norm's output in place of the logits, and
    the head alone."""
    config: FalconH1Config

    @nn.compact
    def __call__(self, tokens, positions=None, kv_caches=None,
                 cache_index=None, valid=None, head=True):
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None, :], tokens.shape)
        embed = self.param(
            "embed", _partitioned(nn.initializers.normal(0.02),
                                  ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        x = (jnp.take(embed, tokens, axis=0)
             * cfg.embedding_multiplier).astype(cfg.dtype)
        new_caches = []
        for layer in range(cfg.num_layers):
            cache = kv_caches[layer] if kv_caches is not None else None
            x, new_cache = ParallelBlock(cfg, name=f"layer_{layer}")(
                x, positions, cache, cache_index, valid)
            new_caches.append(new_cache)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        out = self.head(x) if head else x
        if kv_caches is not None:
            return out, new_caches
        return out

    @nn.compact
    def head(self, x):
        """Logits of the final norm's output `x` [batch, rows, hidden]."""
        cfg = self.config
        return _dense(cfg.vocab_size, ("embed", "vocab"), "lm_head",
                      cfg)(x) * cfg.lm_head_multiplier
