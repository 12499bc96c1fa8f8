"""Decoder with learned sparse attention beside softmax-routed SwiGLU experts
(the language model of Keye-VL-2.0-30B-A3B, `model_type: KeyeVL2`; the
equations from the published config's keys, which the field names below
repeat, and the family's description: DeepSeek-Sparse-Attention's lightning
indexer on a grouped-query layer).

    x = E[token]
    x = x + A(N(x)) ;  x = x + F(N(x))            N: RMSNorm, eps 1e-6
    logits = N(x) W_head                           untied

A (u = N(x) of the token at position t; 32 heads over 4 kv heads, 8 : 1):

    q = n(u W_q) (32 x 128) ; k = n(u W_k) ; v = u W_v (4 x 128 each)
                      n: a per-head RMSNorm (assumed: the family's q_norm /
                      k_norm; the config has no key for it)
    q, k <- R_t q, R_t k       rotary at `rope_theta` over all 128 lanes,
                      pairs (j, j + 64); `mrope_section` splits the pairs
                      over three position streams, which a text token
                      carries equal: on text ids the plain map
    qI = R_t (u W_qI) (16 x 64) ; kI = R_t (u W_kI) (ONE 64-wide index key
                      a token a layer) ; w = u W_w (16 numbers)
    I[t, s] = 16^-1/2 64^-1/2 sum_h w[t, h] relu(qI[t, h] . kI[s])   s <= t
    S_t = the `index_topk` positions of largest I[t, .] among 0..t, ties to
          the earlier position (all of them while t < index_topk)
    o[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k[s, g(h)] / 128^1/2)
              v[s, g(h)]
    A = W_o [o]                                    4096 -> 2048

F, every layer: router logits u W_r (128) -> softmax over all 128 in float32
-> the 8 largest -> renormalised to sum 1 (`moe.softmax_top_k`); E_i(u) =
W_d,i (silu(W_g,i u) * W_u,i u), 768 wide, no shared expert. `held_experts =
(first, count)` is this chip's share of every layer (`moe.RoutedExperts`):
the router keeps its width, the layer returns the part of the sum its own
experts give.

What a row keeps between calls, a token a layer: K and V (token-major
pools, a token's four kv heads in one row) and the index key, the last in
`index_cache()` lanes (64 in whole 128-lane tiles, the pad lanes zero in
keys and queries).

Three paths, chosen by `kv_caches` (`ops.sparse_attention`): None = the
whole sequence (every query's scores against every position, the same
selection rule as a mask); per-layer dicts with `lengths` = one paged
decode token a row (scores over the row's pages, the exact top-k, the
selected tokens gathered); per-layer dicts with `table` = one prefill chunk
of one row whose first `valid` tokens are real, written straight into the
row's pages, scored there, and attended in blocks under each query's
threshold. No path attends more than `index_topk` tokens a query and no
switch makes one attend all: a row under `index_topk` tokens selects all it
has, by data.

The vision tower is not built: the published config describes the language
model only, and the engine serves text ids."""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.sparse_attention import (NEG_INF, NUM_LANES, chunk_candidates,
                                    dense_selection, paged_index_scores,
                                    select_top_k, sparse_attend,
                                    sparse_attend_chunk)
from .llama import RMSNorm, _partitioned, write_token_rows
from .moe import RoutedExperts
from .sarvam_mla import _dense, _rotate, _write_chunk_rows

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class KeyeDSAConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    # sa_config
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    num_experts: int = 128               # the router's width
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    # (first, count) of the routed experts this chip holds in every layer
    held_experts: Tuple[int, int] = (0, 128)
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    max_seq_len: int = 262144            # how far positions may run
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # "reference": the whole-array forms of ops.sparse_attention
    attention_impl: str = "flash"

    @property
    def index_scale(self) -> float:
        """Of a score: a positive constant that changes no selection."""
        return self.index_heads ** -0.5 * self.index_head_dim ** -0.5

    # ---- what the paged engine asks of a model's configuration ----

    @property
    def head_dim_(self) -> int:
        return self.head_dim

    def module(self) -> "KeyeDSAModel":
        return KeyeDSAModel(self)

    def index_cache(self) -> int:
        """Lanes an index key takes in its pool: every layer keeps a pool
        `[1, pages, page_size, lanes]` beside its K and V pools."""
        return -(-self.index_head_dim // NUM_LANES) * NUM_LANES

    def layer_caches(self) -> Tuple[Tuple[bool, bool, bool], ...]:
        """Per layer (keeps pages, keeps recurrent state, carries expert
        counters through a decode step)."""
        return ((True, False, True),) * self.num_layers

    def init_counters(self):
        """Per layer, per held expert: (tokens routed to it, decode steps
        in which it had at least one), int32, on the device."""
        held = self.held_experts[1]
        return [(jnp.zeros((held,), jnp.int32), jnp.zeros((held,), jnp.int32))
                for _ in range(self.num_layers)]


def _rotary(theta: float, dim: int, positions):
    """cos, sin [b, seq, 1, dim / 2] at `positions` [b, seq], float32."""
    inverse = theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    angles = positions.astype(F32)[..., None, None] * inverse
    return jnp.cos(angles), jnp.sin(angles)


class SparseAttention(nn.Module):
    """`cache` is None (the whole sequence), a dict with `lengths` (paged
    decode, a token a row) or a dict with `table` (a prefill chunk of one
    row whose first position is `cache_index` and whose first `valid`
    tokens are real)."""
    config: KeyeDSAConfig

    @nn.compact
    def __call__(self, u, positions, cache=None, cache_index=None,
                 valid=None):
        cfg = self.config
        heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        ih, idim = cfg.index_heads, cfg.index_head_dim
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.rms_norm_eps, cfg.dtype, name=name)
        with jax.named_scope("attn/qkv"):
            rotary = _rotary(cfg.rope_theta, hd, positions)
            q = _dense((heads, hd), ("embed", "heads", "head_dim"),
                       "q_proj", cfg)(u)                 # [b, s, 32, 128]
            k = _dense((kvh, hd), ("embed", "kv_heads", "head_dim"),
                       "k_proj", cfg)(u)
            v = _dense((kvh, hd), ("embed", "kv_heads", "head_dim"),
                       "v_proj", cfg)(u)
            # (the softmax's scale, in float32: 128^-1/2 is no bf16 number)
            q = _rotate(norm("q_norm")(q), *rotary).astype(F32) * hd ** -0.5
            k = _rotate(norm("k_norm")(k), *rotary)
        with jax.named_scope("dsa/index"):
            rotary = _rotary(cfg.rope_theta, idim, positions)
            qi = _rotate(_dense((ih, idim), ("embed", None, None),
                                "qi_proj", cfg)(u), *rotary)
            ki = _rotate(_dense((1, idim), ("embed", None, None),
                                "ki_proj", cfg)(u), *rotary)[..., 0, :]
            w_w = self.param(
                "w_proj", _partitioned(nn.initializers.lecun_normal(),
                                       ("embed", None)),
                (cfg.hidden_size, ih), cfg.param_dtype)
            w = jnp.einsum("bsd,dh->bsh", u, w_w.astype(cfg.dtype),
                           preferred_element_type=F32) * cfg.index_scale
        if cache is None:
            out = self._whole(q, k, v, qi, ki, w)
        else:
            lanes = [(0, cfg.index_cache() - idim)]
            qi = jnp.pad(qi, [(0, 0)] * 3 + lanes)
            rows = [k.reshape(k.shape[:2] + (kvh * hd,)),
                    v.reshape(v.shape[:2] + (kvh * hd,)),
                    jnp.pad(ki, [(0, 0)] * 2 + lanes)]
            pools = [cache["k"], cache["v"], cache["index"]]
            if "lengths" in cache:
                out, pools = self._decode(q, qi, w, rows, pools, cache)
            else:
                out, pools = self._chunk(q, qi, w, rows, pools,
                                         cache["table"], cache_index, valid)
            cache = dict(cache, k=pools[0], v=pools[1], index=pools[2])
        # what the softmax gave, in front of W_o (a caller that asks for
        # "intermediates" compares it with the reference's)
        self.sow("intermediates", "attended", out)
        with jax.named_scope("attn/out"):
            out = _dense(cfg.hidden_size, ("heads", "head_dim", "embed"),
                         "o_proj", cfg, axis=(-2, -1))(out.astype(cfg.dtype))
        return out, cache

    def _decode(self, q, qi, w, rows, pools, cache):
        """One token a row, its K, V and index rows written at `lengths`
        and attended among the row's selection."""
        cfg = self.config
        tables, lengths = cache["block_tables"], cache["lengths"]
        reference = cfg.attention_impl == "reference"
        with jax.named_scope("attn/qkv"):
            pools = [write_token_rows(
                pool, jnp.transpose(new, (1, 0, 2)).astype(pool.dtype),
                tables, lengths) for pool, new in zip(pools, rows)]
        with jax.named_scope("dsa/index"):
            scores = paged_index_scores(qi[:, 0], w[:, 0], pools[2],
                                        lengths + 1, tables,
                                        reference=reference)
        with jax.named_scope("dsa/select"):
            chosen, count = select_top_k(scores, lengths + 1,
                                         cfg.index_topk, reference=reference)
        self.sow("intermediates", "index_scores", scores)
        self.sow("intermediates", "selected", (chosen, count))
        with jax.named_scope("dsa/attend"):
            out = sparse_attend(q[:, 0], pools[0], pools[1], chosen, count,
                                tables, kv_heads=cfg.num_kv_heads,
                                reference=reference)
        return out[:, None], pools

    def _chunk(self, q, qi, w, rows, pools, table, start, valid):
        cfg = self.config
        with jax.named_scope("attn/qkv"):
            pools = [_write_chunk_rows(pool, new[0].astype(pool.dtype),
                                       table, start, valid)
                     for pool, new in zip(pools, rows)]
        with jax.named_scope("dsa/index"):
            u = chunk_candidates(qi[0], w[0], pools[2], table, start)
        self.sow("intermediates", "candidates", u)
        # the threshold (`dsa/select`) is taken inside: its passes run
        # over what the scoring left
        with jax.named_scope("dsa/attend"):
            out = sparse_attend_chunk(
                q[0], u, pools[0], pools[1], table, start,
                top_k=cfg.index_topk, kv_heads=cfg.num_kv_heads)
        return out[None], pools

    def _whole(self, q, k, v, qi, ki, w):
        """Every position of the sequence at once, nothing cached."""
        cfg = self.config
        group = cfg.num_heads // cfg.num_kv_heads
        with jax.named_scope("dsa/index"):
            scores = (jax.nn.relu(jnp.einsum(
                "bqhd,bkd->bqhk", qi, ki, preferred_element_type=F32))
                * w[..., None]).sum(-2)                  # [b, q, k]
        with jax.named_scope("dsa/select"):
            keep = jax.vmap(lambda s: dense_selection(s, cfg.index_topk))(
                scores)
        self.sow("intermediates", "index_scores", scores)
        self.sow("intermediates", "selected", keep)
        with jax.named_scope("dsa/attend"):
            b, s = q.shape[:2]
            queries = q.reshape(b, s, cfg.num_kv_heads, group, cfg.head_dim)
            logits = jnp.einsum("bqgjd,bkgd->bgjqk", queries, k,
                                preferred_element_type=F32)
            probs = jax.nn.softmax(
                jnp.where(keep[:, None, None], logits, NEG_INF), axis=-1)
            out = jnp.einsum("bgjqk,bkgd->bqgjd", probs.astype(cfg.dtype), v,
                             preferred_element_type=F32)
        return out.reshape(b, s, cfg.num_heads, cfg.head_dim)


class Block(nn.Module):
    config: KeyeDSAConfig

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_index=None,
                 valid=None):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.rms_norm_eps, cfg.dtype, name=name)
        attended, new_cache = SparseAttention(cfg, name="attn")(
            norm("attn_norm")(x), positions, cache, cache_index, valid)
        x = x + attended.astype(x.dtype)
        u = norm("mlp_norm")(x)
        # what the router reads (a caller that asks for "intermediates"
        # recomputes the routing from it in float64)
        self.sow("intermediates", "router_input", u)
        decoding = cache is not None and "lengths" in cache
        mask = None
        if decoding:
            mask = cache["active"][:, None]
        elif valid is not None:
            mask = jnp.broadcast_to(
                jnp.arange(x.shape[1]) < valid, x.shape[:2])
        mixed, pairs = RoutedExperts(
            num_experts=cfg.num_experts,
            experts_per_token=cfg.num_experts_per_tok,
            held=cfg.held_experts, mlp_dim=cfg.moe_intermediate_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, gated=True,
            scoring="softmax", name="moe")(u, u, mask)
        kept = () if new_cache is None else (
            new_cache["k"], new_cache["v"], new_cache["index"])
        if decoding:
            kept += (cache["pairs"] + pairs,
                     cache["steps"] + (pairs > 0).astype(jnp.int32))
        return x + mixed.astype(x.dtype), kept


class KeyeDSAModel(nn.Module):
    """tokens -> logits; with `kv_caches`, (logits, per-layer tuples of
    what the layer carries: (k, v, index) pools and, in paged decode,
    (k, v, index, pairs, steps)). `head=False` and the method `head` as
    `LlamaModel`'s: the final norm's output in place of the logits, and
    the head alone."""
    config: KeyeDSAConfig

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
        new_caches = []
        for layer in range(cfg.num_layers):
            cache = kv_caches[layer] if kv_caches is not None else None
            x, kept = Block(cfg, name=f"layer_{layer}")(
                x, positions, cache, cache_index, valid)
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
