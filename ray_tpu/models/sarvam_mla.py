"""Decoder with multi-head latent attention beside routed SwiGLU experts (the
Sarvam-105B family, `model_type: sarvam_mla`; equations from the published
config's keys, which the field names below repeat). `LatentAttention`,
`GatedMLP`, `SharedAndRouted`, the rotary table and the chunk's row write
are also what models/xing_mhc.py builds its blocks from.

    x = E[token]
    x = x + MLA(N(x)) ;  x = x + F(N(x))         N: RMSNorm, eps 1e-6
    logits = N(x) W_head                          untied

MLA (h = N(x) of one token at position t; H heads):

    q_i = W_q,i h = [q_i^nope (128) ; q_i^rope (64)]
        `q_lora_rank=None`: no query latent (this file's model, Sarvam-105B,
        whose config gives none). With a rank (models/xing_mhc.py, 768):
        c_q = RMSNorm(W_dq h; g_q) ;  q_i = W_uq,i c_q
    [c~ (512) ; k~^rope (64)] = W_kva h
    c = RMSNorm(c~; g_kv)            `use_qk_norm`, read as the norm on the
                                     compressed latent (see `assumed` in the
                                     configuration file)
    k^rope = R_t k~^rope (one for all heads) ;  q_i^rope <- R_t q_i^rope
    [k_i^nope (128) ; v_i (128)] = W_kvb,i c
    s_i(t, u) = sigma (q_i^nope(t) . k_i^nope(u) + q_i^rope(t) . k^rope(u))
    o = W_o [ sum_{u<=t} softmax_u(s_i) v_i(u) ]_i
    sigma = (128 + 64)^-1/2 m^2 ,  m = 0.1 mscale_all_dim ln(factor) + 1

R is the `deepseek_yarn` table (`yarn_inverse_frequencies`): each of the 32
rotary pairs turns at theta_j / factor, at theta_j, or in between by the
published ramp over `beta_fast` / `beta_slow`. The pairs are lanes (j,
j + 32) of the rotary part, where the published code pairs (2j, 2j + 1)
after a permutation of its own: a fixed relabelling of W_q's and W_kva's
rotary columns, the same in the reference, invisible under seeded weights.

What a row keeps between calls is ONE latent row a token a layer,
`[c ; k^rope]`, 576 wide (the config's `head_dim`): the key of every head
AND (its first 512 lanes) the value. Through a cache the layer runs in the
ABSORBED form, which never expands a cached row:

    q^_i = (W_i^K)^T q_i^nope (512) ,  W_kvb,i = [W_i^K ; W_i^V]
    s_i(t, u) = sigma (q^_i . c(u) + q_i^rope . k^rope(u))
    o_i = W_i^V sum_u p_i(u) c(u)

Without one (the whole sequence, nothing cached) it runs expanded, as the
equations above stand; tests hold the two together. The cached row stands
in the pool at `latent_cache()[0]` lanes, 576 rounded up to whole 128-lane
tiles (640): what the device's tiled layout holds for a 576-wide row
whatever the array's shape says; the pad lanes are zero in rows and
queries.

F is SwiGLU, `W_d (silu(W_g h) * W_u h)`: dense and `intermediate_size` wide
in the first `first_k_dense_replace` layers; in the others `num_experts`
routed experts `moe_intermediate_size` wide, `num_experts_per_tok` a token
(`moe.sigmoid_top_k`: sigmoid scores, the bias chooses and does not weigh,
weights `routed_scaling_factor` s / sum of the chosen s), beside
`num_shared_experts` shared ones on every token. `held_experts = (first,
count)` is this chip's share of every expert layer (`moe.RoutedExperts`):
the router keeps its width, the layer returns the part of the sum its own
experts give.

Three paths, chosen by `kv_caches`: None = the whole sequence, expanded;
per-layer dicts with `lengths` = one paged decode token a row, absorbed,
through `ops.latent_attention.latent_attend`; per-layer dicts with `table`
= one prefill chunk of one row whose first `valid` tokens are real,
absorbed, written straight into the row's pages and attended over them in
blocks (`latent_attend_chunk`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.latent_attention import (NUM_LANES, latent_attend,
                                    latent_attend_chunk)
from .llama import RMSNorm, _partitioned, write_token_rows
from .moe import RoutedExperts

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SarvamMLAConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 16384       # the dense layers' SwiGLU
    num_layers: int = 32
    num_heads: int = 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    num_experts: int = 128               # the router's width
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    # (first, count) of the routed experts this chip holds in every layer
    held_experts: Tuple[int, int] = (0, 128)
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # rope_scaling, type deepseek_yarn
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_seq_len: int = 131072            # how far positions may run
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # "flash" (the latent Pallas kernel on a TPU) or "reference" (jnp)
    attention_impl: str = "flash"

    @property
    def latent_dim(self) -> int:
        """The cached row, `[c ; k_rope]`: the config's `head_dim`."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    # ---- what the paged engine asks of a model's configuration ----

    @property
    def num_kv_heads(self) -> int:
        return 1

    @property
    def head_dim_(self) -> int:
        return self.latent_cache()[0]

    def module(self) -> "SarvamMLAModel":
        return SarvamMLAModel(self)

    def latent_cache(self) -> Tuple[int, int]:
        """(lanes a cached row takes in the pool, lanes of it that are the
        value): every layer keeps ONE pool `[1, pages, page_size, lanes]`
        and no second one."""
        return (-(-self.latent_dim // NUM_LANES) * NUM_LANES,
                self.kv_lora_rank)

    def expert_layer(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    def layer_caches(self) -> Tuple[Tuple[bool, bool, bool], ...]:
        """Per layer (keeps pages, keeps recurrent state, carries expert
        counters through a decode step)."""
        return tuple((True, False, self.expert_layer(i))
                     for i in range(self.num_layers))

    def init_counters(self):
        """Per expert layer, per held expert: (tokens routed to it, decode
        steps in which it had at least one), int32, on the device."""
        held = self.held_experts[1]
        return [(jnp.zeros((held,), jnp.int32), jnp.zeros((held,), jnp.int32))
                for i in range(self.num_layers) if self.expert_layer(i)]


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inverse_frequencies(cfg: SarvamMLAConfig) -> np.ndarray:
    """The 32 inverse frequencies of the `deepseek_yarn` table, float32.
    theta_j = theta^(-2j/d); pairs that turn more than `beta_fast` times
    over the original context keep theta_j, those that turn fewer than
    `beta_slow` times are slowed by `factor`, a linear ramp in between."""
    dim = cfg.qk_rope_head_dim
    theta = cfg.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_turning(turns: float) -> float:
        return dim * math.log(cfg.rope_original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(pair_turning(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(pair_turning(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp
    return (theta / cfg.rope_factor * (1 - keep) + theta * keep
            ).astype(np.float32)


def _rotary(cfg: SarvamMLAConfig, positions):
    """cos, sin [b, seq, 1, d_rope / 2] at `positions` [b, seq], float32,
    scaled by yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim) (1 as published)."""
    angles = positions.astype(F32)[..., None, None] \
        * jnp.asarray(yarn_inverse_frequencies(cfg))
    scale = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rotate(x, cos, sin):
    """[b, seq, heads, d_rope] by the table, halves paired, in float32."""
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _dense(feats, names, name, cfg, axis=-1):
    return nn.DenseGeneral(
        feats, axis=axis, use_bias=False, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, name=name,
        kernel_init=_partitioned(nn.initializers.lecun_normal(), names))


class LatentAttention(nn.Module):
    """`cache` is None (whole sequence, expanded), a dict with `lengths`
    (paged decode, a token a row) or a dict with `table` (a prefill chunk
    of one row whose first position is `cache_index` and whose first
    `valid` tokens are real); the last two absorbed, over the pool.
    `q_lora_rank`: the width of the query latent, `q = W_uq RMSNorm(W_dq
    u)`; None (static) is one `W_q` and leaves that program as it was."""
    config: SarvamMLAConfig
    q_lora_rank: Optional[int] = None

    @nn.compact
    def __call__(self, u, rotary, cache=None, cache_index=None, valid=None):
        cfg = self.config
        heads, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        with jax.named_scope("mla/q"):
            if self.q_lora_rank is None:
                q = _dense((heads, nope + rope),
                           ("embed", "heads", "head_dim"), "q_proj", cfg)(u)
            else:
                c_q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_a_norm")(
                    _dense(self.q_lora_rank, ("embed", None), "q_a_proj",
                           cfg)(u))
                q = _dense((heads, nope + rope),
                           (None, "heads", "head_dim"), "q_b_proj", cfg)(c_q)
            # [b, s, heads, 192]
            q_nope = q[..., :nope]
            q_rope = _rotate(q[..., nope:], *rotary)
        with jax.named_scope("mla/latent"):
            kva = _dense(rank + rope, ("embed", None), "kv_a_proj", cfg)(u)
            c = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="kv_a_norm")(
                kva[..., :rank])                         # [b, s, rank]
            k_rope = _rotate(kva[..., None, rank:], *rotary)[..., 0, :]
        w_kvb = self.param(
            "kv_b_proj", _partitioned(
                nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2)),
                (None, "heads", "head_dim")),
            (rank, heads, nope + vd), cfg.param_dtype)
        if cache is None:
            with jax.named_scope("mla/attend"):
                out = self._expanded(q_nope, q_rope, c, k_rope, w_kvb)
        else:
            width, _ = cfg.latent_cache()
            lanes = [(0, width - cfg.latent_dim)]
            pool = cache["pool"]
            with jax.named_scope("mla/latent"):
                rows = jnp.pad(jnp.concatenate([c, k_rope], -1),
                               [(0, 0)] * 2 + lanes).astype(pool.dtype)
                if "lengths" in cache:
                    pool = write_token_rows(
                        pool, jnp.transpose(rows, (1, 0, 2)),
                        cache["block_tables"], cache["lengths"])
                else:
                    pool = _write_chunk_rows(pool, rows[0], cache["table"],
                                             cache_index, valid)
            with jax.named_scope("mla/absorb"):
                q_hat = jnp.einsum("bshd,rhd->bshr", q_nope,
                                   w_kvb[..., :nope].astype(cfg.dtype),
                                   preferred_element_type=F32)
                q_abs = jnp.pad(
                    jnp.concatenate([q_hat, q_rope.astype(F32)], -1)
                    * cfg.softmax_scale, [(0, 0)] * 3 + lanes)
            with jax.named_scope("mla/attend"):
                if "lengths" in cache:
                    attended = latent_attend(
                        q_abs[:, 0], pool, cache["lengths"],
                        cache["block_tables"], value_dim=rank,
                        reference=cfg.attention_impl == "reference",
                        schedule=cache.get("schedule"))[:, None]
                else:
                    attended = latent_attend_chunk(
                        q_abs[0], pool, cache["table"], cache_index,
                        value_dim=rank)[None]
            with jax.named_scope("mla/out"):
                out = jnp.einsum("bshr,rhd->bshd", attended.astype(cfg.dtype),
                                 w_kvb[..., nope:].astype(cfg.dtype),
                                 preferred_element_type=F32)
            cache = dict(cache, pool=pool)
        # what the softmax gave, in front of W_o (a caller that asks for
        # "intermediates" compares it with the reference's)
        self.sow("intermediates", "attended", out)
        with jax.named_scope("mla/out"):
            out = _dense(cfg.hidden_size, ("heads", "head_dim", "embed"),
                         "o_proj", cfg, axis=(-2, -1))(out.astype(cfg.dtype))
        return out, cache

    def _expanded(self, q_nope, q_rope, c, k_rope, w_kvb):
        """Every position of the sequence at once, nothing cached: keys and
        values expanded from the latent, as the equations stand."""
        cfg = self.config
        nope = cfg.qk_nope_head_dim
        kv = jnp.einsum("bsr,rhd->bshd", c, w_kvb.astype(cfg.dtype),
                        preferred_element_type=F32).astype(cfg.dtype)
        logits = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, kv[..., :nope],
                             preferred_element_type=F32)
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                               preferred_element_type=F32)) \
            * cfg.softmax_scale
        s = c.shape[1]
        causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, logits, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cfg.dtype),
                          kv[..., nope:], preferred_element_type=F32)


def _write_chunk_rows(pool, rows, table, start, valid):
    """A prefill chunk's latent rows [chunk, width] into one row's pages:
    token i lands at (table[(start + i) // page_size], (start + i) %
    page_size). The padded tail (i >= valid) lands on the null page."""
    page_size = pool.shape[2]
    at = start + jnp.arange(rows.shape[0])
    page = jnp.where(
        jnp.arange(rows.shape[0]) < valid,
        table[jnp.minimum(at // page_size, table.shape[0] - 1)], 0)
    return pool.at[0, page, at % page_size].set(rows)


class GatedMLP(nn.Module):
    """W_d (silu(W_g h) * W_u h), `width` wide."""
    config: SarvamMLAConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate = _dense(self.width, ("embed", "mlp"), "gate_proj", cfg)(x)
        up = _dense(self.width, ("embed", "mlp"), "up_proj", cfg)(x)
        return _dense(cfg.hidden_size, ("mlp", "embed"), "down_proj", cfg)(
            nn.silu(gate) * up)


class SharedAndRouted(nn.Module):
    """An expert layer. `mask` [batch, len] bool: the tokens that count
    (None: all). Returns (out, pairs [held]: tokens routed to each held
    expert)."""
    config: SarvamMLAConfig

    @nn.compact
    def __call__(self, u, mask=None):
        cfg = self.config
        routed, pairs = RoutedExperts(
            num_experts=cfg.num_experts,
            experts_per_token=cfg.num_experts_per_tok,
            held=cfg.held_experts, mlp_dim=cfg.moe_intermediate_size,
            routed_scaling=cfg.routed_scaling_factor, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, gated=True, name="routed")(
                u, u, mask)
        with jax.named_scope("moe/shared"):
            shared = GatedMLP(
                cfg, cfg.moe_intermediate_size * cfg.num_shared_experts,
                name="shared")(u)
        return routed.astype(cfg.dtype) + shared, pairs


class Block(nn.Module):
    config: SarvamMLAConfig
    experts: bool

    @nn.compact
    def __call__(self, x, rotary, cache=None, cache_index=None, valid=None):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.rms_norm_eps, cfg.dtype, name=name)
        attended, new_cache = LatentAttention(cfg, name="attn")(
            norm("attn_norm")(x), rotary, cache, cache_index, valid)
        x = x + attended.astype(x.dtype)
        u = norm("mlp_norm")(x)
        kept = () if new_cache is None else (new_cache["pool"],)
        if not self.experts:
            with jax.named_scope("mlp"):
                mixed = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(u)
        else:
            decoding = cache is not None and "lengths" in cache
            mask = None
            if decoding:
                mask = cache["active"][:, None]
            elif valid is not None:
                mask = jnp.broadcast_to(
                    jnp.arange(x.shape[1]) < valid, x.shape[:2])
            mixed, pairs = SharedAndRouted(cfg, name="moe")(u, mask)
            if decoding:
                kept += (cache["pairs"] + pairs,
                         cache["steps"] + (pairs > 0).astype(jnp.int32))
        return x + mixed.astype(x.dtype), kept


class SarvamMLAModel(nn.Module):
    """tokens -> logits; with `kv_caches`, (logits, per-layer tuples of
    what the layer carries: (pool,) and, for an expert layer in paged
    decode, (pool, pairs, steps)). `head=False` and the method `head` as
    `LlamaModel`'s: the final norm's output in place of the logits, and
    the head alone."""
    config: SarvamMLAConfig

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
        x = jnp.take(embed, tokens, axis=0).astype(cfg.dtype)
        rotary = _rotary(cfg, positions)
        new_caches = []
        for layer in range(cfg.num_layers):
            cache = kv_caches[layer] if kv_caches is not None else None
            x, kept = Block(cfg, cfg.expert_layer(layer),
                            name=f"layer_{layer}")(
                x, rotary, cache, cache_index, valid)
            new_caches.append(kept)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        out = self.head(x) if head else x
        if kv_caches is not None:
            return out, new_caches
        return out

    @nn.compact
    def head(self, x):
        """Logits of the final norm's output `x` [batch, rows, hidden]."""
        cfg = self.config
        return _dense(cfg.vocab_size, ("embed", "vocab"), "lm_head", cfg)(x)
