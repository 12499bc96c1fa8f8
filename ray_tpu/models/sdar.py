"""Decoder that GENERATES BY DIFFUSION OVER BLOCKS (the SDAR family,
`model_type: sdar_moe`; the layer is Qwen3-MoE's, whose config keys the
fields below repeat; N = RMSNorm with a gain).

    x0 = E[token]                       `mask_token_id` where a position of
                                        the open block is still masked
    layer l:
      u = N(x)                                            attn_norm
      q, k, v = W_q u, W_k u, W_v u     32 / 4 / 4 heads of 128, no bias
      q_h = rot(N(q_h)), k_h = rot(N(k_h))   a norm a head BEFORE the rotary
                                        map, pairs (j, j + 64)
      y = W_o softmax(q k^T / sqrt(hd) + BLOCK MASK) v            GQA
                                        position p attends every position
                                        < (p // L + 1) L: all earlier blocks
                                        and its own block BOTH WAYS
      x = x + y
      f = N(x)                                            mlp_norm
      p = softmax(W_r f) in float32 over all experts; the
          `num_experts_per_tok` largest, renormalised to sum to 1
          (`norm_topk_prob`); x = x + sum w_e E_e(f), E_e SwiGLU
          `moe_intermediate_size` wide, no shared expert; every layer is
          such a layer
    logits = N(x) W_head                final_norm; the head is untied;
                                        logits at position i are FOR
                                        position i (no shift)

A block of L = `block_length` positions is denoised by forwards over its L
ids against the committed cache, its own K/V not kept; from the logits at
the masked positions a candidate and its confidence; a rule fixes some
(`llm.sampling.unmask_block`); when no mask is left the block is forwarded
once more with its K/V kept (the commit) and the next block opens. The
prompt's whole blocks are prefilled under the same mask.

What a row carries between calls: K/V pages a layer, and an expert layer's
counter pair through a step. Three paths, chosen by `kv_caches`: None = the
whole sequence under the block mask; per-layer dicts = ONE FORWARD OF EVERY
ROW'S OPEN BLOCK (tokens [rows, L] at positions lengths + 0 .. L - 1: the
block's K/V rows written into the row's pages at those positions,
overwriting what the forward before wrote there, and attended with L
queries a row over lengths + L positions; a denoising forward and a commit
are this one program, told apart by whether the ids hold a mask); per-layer
tuples `(k pool, v pool, table)` = one chunk of a prefill of one row whose
first `valid` tokens are real (whole blocks), written into the row's pages
and attended there under the block mask.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import NEG_INF
from ..ops.paged_attention import (paged_attend_block, paged_attend_chunk,
                                   write_block_rows, write_chunk_pages)
from .llama import RMSNorm, _partitioned, apply_rope, rope_frequencies
from .moe import RoutedExperts

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128               # the router's width
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    # (first, count) of the routed experts this chip holds in every layer
    held_experts: Tuple[int, int] = (0, 128)
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_seq_len: int = 32768             # length of the rotary table
    # the generator's settings (the published config.json carries none)
    block_length: int = 4
    mask_token_id: int = 151669
    # what a request that does not say takes: forwards a block, the rule
    # ("static" / "dynamic") and the dynamic rule's threshold
    denoising_steps: int = 4
    remasking: str = "static"
    confidence_threshold: float = 0.9
    # standard deviation of the seeded embedding's rows. At 0.02 a position's
    # stream is its sequence's attention output and little else, so the
    # tokens of one sequence route alike; at 1 a position's own token
    # decides its routing
    embed_std: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # "reference": the jnp paths (the CPU); anything else the kernels
    attention_impl: str = "flash"

    def __post_init__(self):
        if self.block_length & (self.block_length - 1):
            raise ValueError("block_length is not a power of two")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError("mask_token_id outside the vocabulary")

    @property
    def head_dim_(self) -> int:
        return self.head_dim

    # ---- what the paged engine asks of a model's configuration ----

    def module(self) -> "SdarModel":
        return SdarModel(self)

    def layer_caches(self) -> Tuple[Tuple[bool, bool, bool], ...]:
        """Every layer keeps K/V pages and carries expert counters."""
        return ((True, False, True),) * self.num_layers

    def active_params(self) -> int:
        """The parameters one position's forward multiplies by: the
        attentions, the routers, the chosen experts of every layer and the
        head; not the embedding, which is a lookup, nor the norms'
        gains."""
        d, hd = self.hidden_size, self.head_dim
        attention = 2 * d * hd * (self.num_heads + self.num_kv_heads)
        expert = 3 * d * self.moe_intermediate_size
        return self.num_layers * (
            attention + d * self.num_experts
            + self.num_experts_per_tok * expert) + d * self.vocab_size

    def init_counters(self):
        """Per layer, per held expert: (token positions routed to it,
        block forwards in which it had at least one), int32, on the device."""
        held = self.held_experts[1]
        return [(jnp.zeros((held,), jnp.int32), jnp.zeros((held,), jnp.int32))
                for _ in range(self.num_layers)]


def _dense(feats, names, name, cfg, axis=-1):
    return nn.DenseGeneral(
        feats, axis=axis, use_bias=False, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, name=name,
        kernel_init=_partitioned(nn.initializers.lecun_normal(), names))


def block_mask(positions, block_length: int):
    """[.., q, k] bool: query position p sees key position r iff
    r < (p // L + 1) L. positions [.., s] (queries and keys alike)."""
    ends = positions | (block_length - 1)
    return positions[..., None, :] <= ends[..., :, None]


class BlockAttention(nn.Module):
    """Grouped-query attention under the block mask, with an RMSNorm a head
    on q and k in front of the rotary map. `cache` is None (the whole
    sequence), a dict (one forward of every row's open block) or `(k pool,
    v pool, table)` (a prefill chunk of one row from `cache_index`, its
    first `valid` tokens real)."""
    config: SdarConfig

    @nn.compact
    def __call__(self, u, positions, cache=None, cache_index=None,
                 valid=None):
        cfg = self.config
        hd, L = cfg.head_dim, cfg.block_length
        reference = cfg.attention_impl == "reference"
        q = _dense((cfg.num_heads, hd), ("embed", "heads", "head_dim"),
                   "q_proj", cfg)(u)
        k = _dense((cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                   "k_proj", cfg)(u)
        v = _dense((cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                   "v_proj", cfg)(u)
        with jax.named_scope("attn/qk_norm"):
            q = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)
            q, k, v = (jnp.transpose(a, (0, 2, 1, 3)) for a in (q, k, v))
            cos, sin = rope_frequencies(hd, cfg.max_seq_len, cfg.rope_theta)
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
        new_cache = None
        with jax.named_scope("sdar/attend"):
            if isinstance(cache, dict):
                tables, lengths = cache["block_tables"], cache["lengths"]
                # [b, kvh, L, hd] -> [kvh, b, L, hd]
                rows = lambda a: jnp.transpose(a, (1, 0, 2, 3))  # noqa: E731
                kp = write_block_rows(cache["k"], rows(k), tables, lengths)
                vp = write_block_rows(cache["v"], rows(v), tables, lengths)
                out = paged_attend_block(
                    jnp.transpose(q, (0, 2, 1, 3)), kp, vp, lengths, tables,
                    reference=reference)
                out = jnp.transpose(out, (0, 2, 1, 3)).astype(cfg.dtype)
                new_cache = (kp, vp)
            elif cache is not None:
                kp, vp, table = cache
                by_token = lambda a: jnp.transpose(a[0], (1, 0, 2))  # noqa: E731
                kp = write_chunk_pages(kp, by_token(k), table, cache_index,
                                       valid)
                vp = write_chunk_pages(vp, by_token(v), table, cache_index,
                                       valid)
                out = paged_attend_chunk(
                    by_token(q) * hd ** -0.5, kp, vp, table, cache_index,
                    block_length=L)
                out = jnp.transpose(out, (1, 0, 2))[None].astype(cfg.dtype)
                new_cache = (kp, vp)
            else:
                groups = cfg.num_heads // cfg.num_kv_heads
                logits = jnp.einsum(
                    "bhqd,bhkd->bhqk", q.astype(F32),
                    jnp.repeat(k, groups, axis=1).astype(F32)) * hd ** -0.5
                seen = block_mask(positions, L)[:, None]
                probs = jax.nn.softmax(jnp.where(seen, logits, NEG_INF), -1)
                out = jnp.einsum(
                    "bhqk,bhkd->bhqd", probs,
                    jnp.repeat(v, groups, axis=1).astype(F32)
                ).astype(cfg.dtype)
        out = jnp.transpose(out, (0, 2, 1, 3))
        out = _dense(cfg.hidden_size, ("heads", "head_dim", "embed"),
                     "o_proj", cfg, axis=(-2, -1))(out)
        return out, new_cache


class Block(nn.Module):
    config: SdarConfig

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_index=None,
                 valid=None):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.rms_norm_eps, cfg.dtype, name=name)
        stepping = isinstance(cache, dict)
        # what the layer read: a caller that asks for "intermediates" holds
        # each layer to the reference ON ITS OWN INPUT
        self.sow("intermediates", "stream", x)
        attended, kept = BlockAttention(cfg, name="attn")(
            norm("attn_norm")(x), positions, cache, cache_index, valid)
        x = x + attended
        f = norm("mlp_norm")(x)
        # what the router reads (a caller that asks for "intermediates"
        # recomputes the routing from it in float64)
        self.sow("intermediates", "router_input", f)
        mask = None
        if stepping:
            mask = jnp.broadcast_to(cache["active"][:, None], x.shape[:2])
        elif valid is not None:
            mask = jnp.broadcast_to(
                jnp.arange(x.shape[1]) < valid, x.shape[:2])
        fed, pairs = RoutedExperts(
            num_experts=cfg.num_experts,
            experts_per_token=cfg.num_experts_per_tok,
            held=cfg.held_experts, mlp_dim=cfg.moe_intermediate_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, gated=True,
            scoring="softmax", name="moe")(f, f, mask)
        kept = () if kept is None else tuple(kept)
        if stepping:
            kept += (cache["pairs"] + pairs,
                     cache["steps"] + (pairs > 0).astype(jnp.int32))
        elif kept:
            kept += (pairs,)     # a chunk's: its caller counts or drops them
        return x + fed.astype(x.dtype), kept


class SdarModel(nn.Module):
    """tokens -> logits; with `kv_caches`, (logits, per-layer tuples of
    what the layer carries: (k pool, v pool), and behind them in a block
    forward (pairs, steps), in a prefill chunk the pairs it routed).
    `head=False` and the method `head` as `LlamaModel`'s: the final norm's
    output in place of the logits, and the head alone."""
    config: SdarConfig

    @nn.compact
    def __call__(self, tokens, positions=None, kv_caches=None,
                 cache_index=None, valid=None, head=True):
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None, :], tokens.shape)
        embed = self.param(
            "embed", _partitioned(nn.initializers.normal(cfg.embed_std),
                                  ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        x = jnp.take(embed, tokens, axis=0).astype(cfg.dtype)
        new_caches = []
        for layer in range(cfg.num_layers):
            cache = kv_caches[layer] if kv_caches is not None else None
            x, kept = Block(cfg, name=f"layer_{layer}")(
                x, positions, cache, cache_index, valid)
            new_caches.append(kept)
        self.sow("intermediates", "stream", x)
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
