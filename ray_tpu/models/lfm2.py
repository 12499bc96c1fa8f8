"""Hybrid decoder whose mixers are gated SHORT CONVOLUTIONS with attention
every few layers, over sigmoid-routed SwiGLU experts (the LFM2 family's
mixture models, `model_type: lfm2_moe`; equations from the published
config's keys, which the field names below repeat; N = RMSNorm with a gain).

    x0 = E[token]
    layer l, mixer `layer_types[l]`:
      u = N(x)                                             operator_norm
      conv:            B, C, X = split(W_in u)    d -> 3 d, in this order
                       v_t = B_t * X_t
                       c_t = w_0 v_{t-2} + w_1 v_{t-1} + w_2 v_t
                                         depthwise, causal, `conv_L_cache`
                                         taps, no bias, no activation
                       y_t = W_out (C_t * c_t)
      full_attention:  q, k, v = W_q u, W_k u, W_v u
                       q_h = rot(N(q_h)), k_h = rot(N(k_h))   a norm a head
                                         BEFORE the rotary map
                       y = W_o softmax(q k^T / sqrt(hd) + causal) v    GQA
      x = x + y
      f = N(x)                                             ffn_norm
      l <  num_dense_layers:  x = x + W_2 (silu(W_1 f) * W_3 f)
      l >= num_dense_layers:  s = sigmoid(W_g f) in float32; the
                       `num_experts_per_tok` experts of largest s + bias
                       are chosen and weigh s / (sum of the chosen s + 1e-6)
                       * routed_scaling_factor (served with the tree's one
                       sigmoid router, whose constant is 1e-20: 5e-7 of a
                       weight, `moe.sigmoid_top_k`); x = x + sum a_i E_i(f),
                       E_i SwiGLU `moe_intermediate_size` wide, no shared
                       expert
    logits = N(x) E^T                    embedding_norm; the head is tied

The mixer kind (`conv` / `full_attention`) and the feed-forward kind (dense
/ experts) vary independently a layer. `held_experts = (first, count)`:
this chip's share of every expert layer (`moe.RoutedExperts`).

What a row carries between calls, by layer: a `conv` layer the last
`conv_L_cache - 1` gated inputs v (`state_shapes`: ONE entry, two rows of
`hidden_size` in the model's type, laid [taps, channels] as
`falcon_h1`'s window is); a `full_attention` layer its K/V pages, whose
heads are 64 wide and stand two to a 128-lane row
(`ops.paged_attention.packed_pool_shape`; `page_pool`); an expert
feed-forward its counter pair through a decode step. Three paths, chosen
by `kv_caches` as in falcon_h1.py: None = the whole sequence from a zero
window; per-layer dicts = one paged decode token a row (rows not `active`
leave their window alone); per-layer tuples = one chunk of a prefill whose
first `valid` tokens are real, `(k pool, v pool, table)` for a layer that
attends (the chunk's K/V go straight into the row's pages through its
table and are attended there: nothing of a row is staged densely) and
`(window,)` for a layer that convolves; the padded tail enters neither a
window nor an expert's count, and the window handed on is v_{valid-2},
v_{valid-1} (from the incoming window where `valid < 2`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.paged_attention import (packed_pool_shape, paged_attend,
                                   paged_attend_chunk, write_chunk_pages)
from .llama import (RMSNorm, _flash_on_mesh, _partitioned, apply_rope,
                    rope_frequencies, write_token_rows)
from .moe import RoutedExperts
from .sarvam_mla import GatedMLP

F32 = jnp.float32
# one period of the published pattern after the two leading layers
_PERIOD = ("full_attention", "conv", "conv", "conv")


def published_layer_types(num_layers: int = 40) -> Tuple[str, ...]:
    """`layer_types` as LFM2-24B-A2B publishes it, cut to `num_layers`:
    two `conv`, then periods of `full_attention, conv, conv, conv`."""
    return (("conv", "conv") + _PERIOD * num_layers)[:num_layers]


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776       # the dense layers' SwiGLU
    layer_types: Tuple[str, ...] = published_layer_types()
    num_heads: int = 32
    num_kv_heads: int = 8
    conv_L_cache: int = 3                # the filter's taps
    num_dense_layers: int = 2
    num_experts: int = 64                # the router's width
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    routed_scaling_factor: float = 1.0
    # (first, count) of the routed experts this chip holds in every layer
    held_experts: Tuple[int, int] = (0, 64)
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_seq_len: int = 128000            # length of the rotary table
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # "flash" (Pallas on a TPU) or "reference" (jnp), as in LlamaConfig
    attention_impl: str = "flash"

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def head_dim_(self) -> int:
        return self.head_dim

    def expert_layer(self, layer: int) -> bool:
        return layer >= self.num_dense_layers

    # ---- what the paged engine asks of a model's configuration ----

    def module(self) -> "Lfm2Model":
        return Lfm2Model(self)

    def layer_caches(self) -> Tuple[Tuple[bool, bool, bool], ...]:
        """Per layer (keeps K/V pages, keeps a convolution window, carries
        expert counters through a decode step): the third beside either
        of the first two."""
        return tuple((kind == "full_attention", kind == "conv",
                      self.expert_layer(layer))
                     for layer, kind in enumerate(self.layer_types))

    def state_shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """What one row holds in one `conv` layer: the window alone."""
        return {"conv": ((self.conv_L_cache - 1, self.hidden_size),
                         self.dtype)}

    def init_state(self, rows: int):
        """Zeroed windows for `rows` rows, a 1-tuple a `conv` layer."""
        (shape, dtype), = self.state_shapes().values()
        return [(jnp.zeros((rows,) + shape, dtype),)
                for kind in self.layer_types if kind == "conv"]

    def init_counters(self):
        """Per expert layer, per held expert: (tokens routed to it, decode
        steps in which it had at least one), int32, on the device."""
        held = self.held_experts[1]
        return [(jnp.zeros((held,), jnp.int32), jnp.zeros((held,), jnp.int32))
                for layer in range(self.num_layers)
                if self.expert_layer(layer)]

    def page_pool(self, pages: int, page_size: int) -> Tuple[int, ...]:
        """The shape of one K (or V) pool: a model that says so lays its
        own pools, and its prefill chunk writes and attends the row's pages
        through its table (`PagedEngineConfig`)."""
        return packed_pool_shape(self.num_kv_heads, self.head_dim, pages,
                                 page_size)


def _dense(feats, names, name, cfg, axis=-1):
    return nn.DenseGeneral(
        feats, axis=axis, use_bias=False, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, name=name,
        kernel_init=_partitioned(nn.initializers.lecun_normal(), names))


class ShortConv(nn.Module):
    """The gated short convolution. `state` is None (a zero window, the
    whole sequence) or `(window,)`: with `active` [rows] one decode token
    a row, else one prefill chunk of a single row whose first `valid`
    tokens are real. Returns (y, the window handed on or None)."""
    config: Lfm2Config

    @nn.compact
    def __call__(self, u, state=None, active=None, valid=None):
        cfg = self.config
        d, taps = cfg.hidden_size, cfg.conv_L_cache
        batch, length = u.shape[0], u.shape[1]
        with jax.named_scope("conv/in"):
            proj = _dense(3 * d, ("embed", "mlp"), "in_proj", cfg)(u)
            gate_in, gate_out, x = jnp.split(proj, 3, axis=-1)   # B, C, X
            v = gate_in * x
        # [taps, channels]: the published layout is [channels, 1, taps],
        # whose 3-wide minor dimension a TPU pads to 128 lanes
        w = self.param(
            "conv_kernel", _partitioned(nn.initializers.lecun_normal(),
                                        (None, "mlp")),
            (taps, d), cfg.param_dtype)
        with jax.named_scope("conv/filter"):
            window = jnp.zeros((batch, taps - 1, d), v.dtype) \
                if state is None else state[0].astype(v.dtype)
            full = jnp.concatenate([window, v], axis=1)  # [b, taps-1+len, d]
            c = sum(full[:, j:j + length].astype(F32) * w[j].astype(F32)
                    for j in range(taps)).astype(cfg.dtype)
            new_state = None
            if active is not None:
                # a row that is not decoding keeps its window
                new_state = (jnp.where(
                    active[:, None, None],
                    full[:, 1:].astype(state[0].dtype), state[0]),)
            elif state is not None:
                # the window after the last real token: v_{valid-taps+1} ..
                # v_{valid-1} are full[valid : valid + taps - 1]
                upto = length if valid is None else valid
                new_state = (jax.lax.dynamic_slice_in_dim(
                    full, upto, taps - 1, axis=1).astype(state[0].dtype),)
        with jax.named_scope("conv/out"):
            y = _dense(d, ("mlp", "embed"), "out_proj", cfg)(gate_out * c)
        return y, new_state


class NormedAttention(nn.Module):
    """Grouped-query attention with an RMSNorm a head on q and k in front
    of the rotary map. `cache` is None (whole sequence), a dict (paged
    decode) or `(k pool, v pool, table)` (a prefill chunk of one row whose
    first position is `cache_index` and whose first `valid` tokens are
    real)."""
    config: Lfm2Config

    @nn.compact
    def __call__(self, u, positions, cache=None, cache_index=None,
                 valid=None):
        cfg = self.config
        hd = cfg.head_dim
        q = _dense((cfg.num_heads, hd), ("embed", "heads", "head_dim"),
                   "q_proj", cfg)(u)
        k = _dense((cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                   "k_proj", cfg)(u)
        v = _dense((cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                   "v_proj", cfg)(u)
        with jax.named_scope("attn/qk_norm"):
            q = RMSNorm(cfg.norm_eps, cfg.dtype, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, cfg.dtype, name="k_norm")(k)
            q, k, v = (jnp.transpose(a, (0, 2, 1, 3)) for a in (q, k, v))
            cos, sin = rope_frequencies(hd, cfg.max_seq_len, cfg.rope_theta)
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
        new_cache = None
        with jax.named_scope("attn/attend"):
            if isinstance(cache, dict):
                kp, vp = cache["k"], cache["v"]
                tables, lengths = cache["block_tables"], cache["lengths"]
                # [b, kvh, 1, hd] -> [rows of the pool, b, lanes]: two kv
                # heads side by side in a packed pool's row
                rows = lambda a, pool: jnp.transpose(  # noqa: E731
                    a[:, :, 0, :].reshape(-1, pool.shape[0], pool.shape[3]),
                    (1, 0, 2)).astype(pool.dtype)
                kp = write_token_rows(kp, rows(k, kp), tables, lengths)
                vp = write_token_rows(vp, rows(v, vp), tables, lengths)
                out = paged_attend(
                    q[:, :, 0, :], kp, vp, lengths, tables,
                    reference=cfg.attention_impl == "reference")
                out = out[:, :, None, :].astype(cfg.dtype)
                new_cache = (kp, vp)
            elif cache is not None:
                kp, vp, table = cache
                by_token = lambda a: jnp.transpose(a[0], (1, 0, 2))  # noqa: E731
                kp = write_chunk_pages(kp, by_token(k), table, cache_index,
                                       valid)
                vp = write_chunk_pages(vp, by_token(v), table, cache_index,
                                       valid)
                out = paged_attend_chunk(
                    by_token(q) * hd ** -0.5, kp, vp, table, cache_index)
                out = jnp.transpose(out, (1, 0, 2))[None].astype(cfg.dtype)
                new_cache = (kp, vp)
            elif cfg.attention_impl == "reference":
                from ..ops.attention import attention_reference
                out = attention_reference(q, k, v, True)
            else:
                out = _flash_on_mesh(q, k, v)
        out = jnp.transpose(out, (0, 2, 1, 3))
        out = _dense(cfg.hidden_size, ("heads", "head_dim", "embed"),
                     "o_proj", cfg, axis=(-2, -1))(out)
        return out, new_cache


class Block(nn.Module):
    config: Lfm2Config
    layer: int

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_index=None,
                 valid=None):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.norm_eps, cfg.dtype, name=name)
        decoding = isinstance(cache, dict)
        # what the layer read (a caller that asks for "intermediates" holds
        # each layer to the reference ON ITS OWN INPUT: a deep stack of
        # bf16 layers of random weights carries a rounding a tenth of the
        # stream deep)
        self.sow("intermediates", "stream", x)
        u = norm("operator_norm")(x)
        if cfg.layer_types[self.layer] == "conv":
            state = None if cache is None else \
                (cache["conv"],) if decoding else tuple(cache)
            mixed, kept = ShortConv(cfg, name="conv")(
                u, state, cache["active"] if decoding else None, valid)
        else:
            mixed, kept = NormedAttention(cfg, name="attn")(
                u, positions, cache, cache_index, valid)
        x = x + mixed
        f = norm("ffn_norm")(x)
        if not cfg.expert_layer(self.layer):
            with jax.named_scope("mlp"):
                fed = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(f)
        else:
            mask = None
            if decoding:
                mask = cache["active"][:, None]
            elif valid is not None:
                mask = jnp.broadcast_to(
                    jnp.arange(x.shape[1]) < valid, x.shape[:2])
            fed, pairs = RoutedExperts(
                num_experts=cfg.num_experts,
                experts_per_token=cfg.num_experts_per_tok,
                held=cfg.held_experts, mlp_dim=cfg.moe_intermediate_size,
                routed_scaling=cfg.routed_scaling_factor, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, gated=True, name="moe")(f, f, mask)
            if decoding:
                kept = tuple(kept) + (
                    cache["pairs"] + pairs,
                    cache["steps"] + (pairs > 0).astype(jnp.int32))
        return x + fed.astype(x.dtype), kept


class Lfm2Model(nn.Module):
    """tokens -> logits; with `kv_caches`, (logits, per-layer tuples of
    what the layer carries: (k pool, v pool) or (window,), and in paged
    decode an expert layer's (pairs, steps) behind them). `head=False` and
    the method `head` as `LlamaModel`'s: the final norm's output in place
    of the logits, and the head alone (the embedding again: tied)."""
    config: Lfm2Config

    def _embedding(self):
        cfg = self.config
        return self.param(
            "embed", _partitioned(nn.initializers.normal(0.02),
                                  ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)

    @nn.compact
    def __call__(self, tokens, positions=None, kv_caches=None,
                 cache_index=None, valid=None, head=True):
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None, :], tokens.shape)
        embed = self._embedding()
        x = jnp.take(embed, tokens, axis=0).astype(cfg.dtype)
        new_caches = []
        for layer in range(cfg.num_layers):
            cache = kv_caches[layer] if kv_caches is not None else None
            x, kept = Block(cfg, layer, name=f"layer_{layer}")(
                x, positions, cache, cache_index, valid)
            new_caches.append(kept)
        self.sow("intermediates", "stream", x)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, name="embedding_norm")(x)
        out = self.head(x, embed) if head else x
        if kv_caches is not None:
            return out, new_caches
        return out

    @nn.compact
    def head(self, x, embed=None):
        """Logits of the final norm's output `x` [batch, rows, hidden]:
        the embedding again (`embed`: what `__call__` already read)."""
        if embed is None:
            embed = self._embedding()
        return jnp.dot(x, embed.T.astype(self.config.dtype))
