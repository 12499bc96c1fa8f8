"""Byte-level decoder with EVA attention (the EvaByte family, `model_type:
evabyte`, `attention_class: "eva"`; equations from the published config's
keys, which the field names below repeat).

    x  = E[byte]                                  float32 residual stream
    x  = x + EVA(N(x)) ;  x = x + W_d(silu(W_g N(x)) * W_u N(x))
    N(x) = x / rms(x) * (1 + g)                   unit offset: g starts at 0
    logits_m = N(x) W_head[:, m]                  m = 0..7, float32

EVA attention (Zheng et al., ICLR 2023, arXiv:2302.04542, in the
deterministic form of the EvaByte release). Positions are cut into windows
of `window_size` W and chunks of `chunk_size` C. A query attends exactly to
the positions of its own window up to itself, and to ONE summary (a pooled
key, a pooled value) of every chunk of every earlier window, under one
softmax. A chunk's summary, from a learned phi and mu a head:

    a_j = softmax_{j in chunk}(s * k_j . phi)     s = head_dim ** -0.5
    k~  = sum_j a_j k_j + mu ;  v~ = sum_j a_j v_j

Keys are pooled after the rotary map. No summary of an open window is ever
visible, and a summary, once made, never changes.

What a row keeps between calls is therefore not the K/V of its context: the
W/C summaries of each closed window, then the exact K/V of the open one.
Both are rows of the same shape, so they share the engine's page pool and
stand contiguous in the row's block table, summaries first. A row of `n`
positions keeps `cache_rows(n) = (W/C) * (n // W) + n % W` rows, and causal
attention over those rows in table order IS the attention above: the
decode path is the dense one's (`write_token_rows`, `paged_attend`) at
`cache_rows(lengths)`. When a window fills, `compress_window` pools its
pages into summary rows, in place in the window's first pages; the host
gives the rest back to the pool.

Three paths, chosen by `kv_caches` as in the dense model: None = the whole
sequence, summaries recomputed; per-layer dicts with `lengths` = one paged
decode token a row; per-layer dicts with `table` = one prefill chunk of one
row, written straight into the row's pages and attended over them.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.paged_attention import paged_attend
from .llama import _partitioned, write_token_rows

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320            # 256 bytes + 64 special ids
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32              # one query a kv head: no grouping
    head_dim: int = 128
    num_pred_heads: int = 8          # head m predicts byte t + 1 + m
    window_size: int = 2048
    chunk_size: int = 16
    rope_theta: float = 1e5
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 32768         # how far positions may run
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # the pooling softmax and sums run in it (a summary leaves in the page
    # pool's type either way)
    pool_dtype: Any = jnp.float32
    # "flash" (the paged Pallas kernel on a TPU) or "reference" (jnp)
    attention_impl: str = "flash"

    def __post_init__(self):
        if self.window_size % self.chunk_size:
            raise ValueError("window_size is not whole chunks")

    @property
    def num_kv_heads(self) -> int:
        return self.num_heads

    @property
    def head_dim_(self) -> int:
        return self.head_dim

    @property
    def window_summaries(self) -> int:
        """Summary rows a closed window leaves."""
        return self.window_size // self.chunk_size

    # ---- what the paged engine asks of a model's configuration ----

    def module(self) -> "EvaByteModel":
        return EvaByteModel(self)

    def cache_rows(self, length):
        """Rows of its pages a row of `length` positions keeps, which are
        the rows its next token attends before itself: a summary a chunk
        of every closed window, then the open window's positions (an int,
        or an array of lengths)."""
        return (length // self.window_size) * self.window_summaries \
            + length % self.window_size

    def attended_rows(self, lengths):
        """(summary rows, window rows) a decode token at each of
        `lengths` attends, itself included."""
        return ((lengths // self.window_size) * self.window_summaries,
                lengths % self.window_size + 1)

    def window_closes(self, length: int) -> bool:
        """Whether position `length - 1` was the last of a window: the
        row's open window is then compressed before its next step."""
        return length > 0 and length % self.window_size == 0

    def compress_window_pages(self, params, k_pages, v_pages, pages):
        """What the engine's `compress_window` program runs."""
        return compress_window_pages(self, params, k_pages, v_pages, pages)

    def check_pages(self, page_size: int, buckets) -> None:
        """A closed window's summaries are whole pages, a window is whole
        prefill chunks and a chunk whole pages, so that no chunk straddles
        a close and no page holds rows of two meanings."""
        if self.window_summaries % page_size \
                or self.window_size % buckets[-1] \
                or any(b % page_size for b in buckets):
            raise ValueError(
                f"pages of {page_size} rows and prefill buckets {buckets} "
                f"do not tile windows of {self.window_size} positions "
                f"with {self.window_summaries} summaries")

    def pages_held(self, length: int, page_size: int) -> int:
        """Pages a row of `length` positions holds between steps."""
        return -(-self.cache_rows(length) // page_size)

    def prefill_pages(self, length: int, page_size: int) -> int:
        """The most pages a row holds on its way to `length` positions: a
        full window stands whole until it is compressed. At the engine's
        longest row this is the block table's width."""
        closed = length // self.window_size
        full = (closed - 1) * self.window_summaries + self.window_size \
            if closed else 0
        return max(self.pages_held(length, page_size),
                   -(-full // page_size))

def pool_chunks(k, v, phi, mu, out_dtype, pool_dtype=F32):
    """Summaries of chunks. k, v [heads, chunks, chunk_size, hd]; phi, mu
    [heads, hd]. Pooling in `pool_dtype` (float32) whatever the operands'
    type; the summaries leave in `out_dtype` (the page pool's). Returns
    k~, v~ [heads, chunks, hd]."""
    kw, vw = k.astype(pool_dtype), v.astype(pool_dtype)
    scores = jnp.einsum("hcjd,hd->hcj", kw, phi.astype(pool_dtype)) \
        * k.shape[-1] ** -0.5
    a = jax.nn.softmax(scores, axis=-1)
    pooled_k = jnp.einsum("hcj,hcjd->hcd", a, kw) \
        + mu.astype(pool_dtype)[:, None, :]
    pooled_v = jnp.einsum("hcj,hcjd->hcd", a, vw)
    return pooled_k.astype(out_dtype), pooled_v.astype(out_dtype)


def compress_window_pages(cfg: EvaByteConfig, params, k_pages, v_pages,
                          pages):
    """One row's full window, in every layer, from exact K/V to summaries.
    `pages` [window_size / page_size] are the window's page ids in order;
    the W/C summary rows replace the first of them in place (a page of
    `page_size` chunks becomes `page_size` rows), the rest hold nothing
    afterwards. k_pages / v_pages: a pool per layer, returned updated."""
    page_size = k_pages[0].shape[2]
    kept = pages[:cfg.window_summaries // page_size]
    new_k, new_v = [], []
    for layer, (kp, vp) in enumerate(zip(k_pages, v_pages)):
        attn = params[f"layer_{layer}"]["attn"]
        heads = kp.shape[0]
        with jax.named_scope("eva/pool"):
            chunks = lambda pool: pool[:, pages].reshape(  # noqa: E731
                heads, cfg.window_summaries, cfg.chunk_size, cfg.head_dim)
            pooled_k, pooled_v = pool_chunks(
                chunks(kp), chunks(vp), attn["phi"], attn["mu"], kp.dtype,
                cfg.pool_dtype)
            rows = lambda a: a.reshape(  # noqa: E731
                heads, kept.shape[0], page_size, cfg.head_dim)
            head_ix = jnp.arange(heads)[:, None]
            new_k.append(kp.at[head_ix, kept[None, :]].set(rows(pooled_k)))
            new_v.append(vp.at[head_ix, kept[None, :]].set(rows(pooled_v)))
    return new_k, new_v


def _rotary(positions, head_dim: int, theta: float):
    """cos, sin [b, 1, seq, hd/2] at `positions` [b, seq], float32: the
    angles `rope_frequencies` tabulates, taken at the positions asked."""
    exponents = jnp.arange(0, head_dim, 2, dtype=F32) / head_dim
    angles = positions.astype(F32)[:, None, :, None] \
        * (1.0 / (theta ** exponents))
    return jnp.cos(angles), jnp.sin(angles)


def _rotate(x, cos, sin):
    """The rotary map over all of a head ([b, heads, seq, hd]), halves
    paired as `apply_rope` pairs them, in float32."""
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


class UnitOffsetRMSNorm(nn.Module):
    """x / rms(x) * (1 + g): the stored scale is the offset from one
    (`norm_add_unit_offset`). In float32 (the stream is), out in `dtype`."""
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        g = self.param("scale", _partitioned(nn.initializers.zeros,
                                             ("embed",)), (x.shape[-1],),
                       F32)
        x32 = x.astype(F32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * (1.0 + g)).astype(self.dtype)


def _dense(feats, names, name, cfg, axis=-1):
    return nn.DenseGeneral(
        feats, axis=axis, use_bias=False, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype, name=name,
        kernel_init=_partitioned(nn.initializers.lecun_normal(), names))


def _clipped_normal(key, shape, dtype):
    # assumed (the config gives no initialiser): N(0, 1) clipped to [-1, 1]
    return jnp.clip(jax.random.normal(key, shape, F32), -1.0, 1.0
                    ).astype(dtype)


class EvaAttention(nn.Module):
    """`cache` is None (whole sequence), a dict with `lengths` (paged
    decode, a token a row) or a dict with `table` (a prefill chunk of one
    row whose first position is `cache_index`)."""
    config: EvaByteConfig

    @nn.compact
    def __call__(self, u, rotary, cache=None, cache_index=None):
        cfg = self.config
        heads, hd = cfg.num_heads, cfg.head_dim
        names = ("embed", "heads", "head_dim")
        with jax.named_scope("eva/qkv"):
            q = _dense((heads, hd), names, "q_proj", cfg)(u)
            k = _dense((heads, hd), ("embed", "kv_heads", "head_dim"),
                       "k_proj", cfg)(u)
            v = _dense((heads, hd), ("embed", "kv_heads", "head_dim"),
                       "v_proj", cfg)(u)
            q, k, v = (jnp.transpose(a, (0, 2, 1, 3)) for a in (q, k, v))
            q, k = _rotate(q, *rotary), _rotate(k, *rotary)
        phi = self.param("phi", _partitioned(_clipped_normal,
                                             ("kv_heads", "head_dim")),
                         (heads, hd), cfg.param_dtype)
        mu = self.param("mu", _partitioned(_clipped_normal,
                                           ("kv_heads", "head_dim")),
                        (heads, hd), cfg.param_dtype)
        new_cache = None
        if cache is None:
            with jax.named_scope("eva/attend"):
                out = self._whole_sequence(q, k, v, phi, mu)
        elif "lengths" in cache:
            kp, vp = cache["k"], cache["v"]
            tables = cache["block_tables"]
            at = cfg.cache_rows(cache["lengths"])
            rows = lambda a, pool: jnp.transpose(  # noqa: E731
                a[:, :, 0, :], (1, 0, 2)).astype(pool.dtype)
            with jax.named_scope("eva/attend"):
                kp = write_token_rows(kp, rows(k, kp), tables, at)
                vp = write_token_rows(vp, rows(v, vp), tables, at)
                out = paged_attend(
                    q[:, :, 0, :], kp, vp, at, tables,
                    reference=cfg.attention_impl == "reference")
            out = out[:, :, None, :].astype(cfg.dtype)
            new_cache = dict(cache, k=kp, v=vp)
        else:
            with jax.named_scope("eva/attend"):
                out, kp, vp = self._chunk_over_pages(
                    q, k, v, cache["k"], cache["v"], cache["table"],
                    cache_index)
            new_cache = dict(cache, k=kp, v=vp)
        out = jnp.transpose(out, (0, 2, 1, 3))      # [b, s, heads, hd]
        # what the softmax gave, in front of W_o (a caller that asks for
        # "intermediates" compares it with the reference's)
        self.sow("intermediates", "attended", out)
        return _dense(cfg.hidden_size, ("heads", "head_dim", "embed"),
                      "o_proj", cfg, axis=(-2, -1))(out), new_cache

    def _whole_sequence(self, q, k, v, phi, mu):
        """Every position of [b, heads, s, hd] at once, nothing cached:
        the exact part masked to the query's window, the summaries of
        every chunk recomputed and masked to the windows before it."""
        cfg = self.config
        window, chunk = cfg.window_size, cfg.chunk_size
        b, heads, s, hd = q.shape
        n_chunks = -(-s // chunk)
        pad = ((0, 0), (0, 0), (0, n_chunks * chunk - s), (0, 0))
        pooled_k, pooled_v = jax.vmap(
            lambda k_, v_: pool_chunks(
                k_.reshape(heads, n_chunks, chunk, hd),
                v_.reshape(heads, n_chunks, chunk, hd), phi, mu, cfg.dtype,
                cfg.pool_dtype)
        )(jnp.pad(k, pad), jnp.pad(v, pad))
        qs = (q * hd ** -0.5).astype(cfg.dtype)
        exact = jnp.einsum("bhqd,bhkd->bhqk", qs, k,
                           preferred_element_type=F32)
        coarse = jnp.einsum("bhqd,bhcd->bhqc", qs, pooled_k,
                            preferred_element_type=F32)
        at = jnp.arange(s)
        same = (at[None, :] // window == at[:, None] // window) \
            & (at[None, :] <= at[:, None])
        before = (jnp.arange(n_chunks)[None, :] * chunk) // window \
            < at[:, None] // window
        logits = jnp.concatenate(
            [jnp.where(same, exact, -1e30),
             jnp.where(before, coarse, -1e30)], axis=-1)
        probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs[..., :s], v,
                         preferred_element_type=F32) \
            + jnp.einsum("bhqc,bhcd->bhqd", probs[..., s:], pooled_v,
                         preferred_element_type=F32)
        return out.astype(cfg.dtype)

    def _chunk_over_pages(self, q, k, v, kp, vp, table, start):
        """One prefill chunk of one row ([1, heads, chunk, hd]) whose
        first position is `start` (a whole number of pages into its
        window): its K/V rows go into the row's pages, then every query
        attends the row's rows in table order up to its own, which are the
        summaries of the closed windows, the open window so far and the
        chunk, causally. Entries of `table` the row does not hold name the
        null page: a padded tail's rows land there, and no real query sees
        a row past its own."""
        cfg = self.config
        heads, page_size, hd = kp.shape[0], kp.shape[2], kp.shape[3]
        chunk = q.shape[2]
        first = cfg.cache_rows(start)
        ids = jax.lax.dynamic_slice_in_dim(
            table, first // page_size, chunk // page_size)
        head_ix = jnp.arange(heads)[:, None]
        paged = lambda a, pool: a[0].reshape(  # noqa: E731
            heads, chunk // page_size, page_size, hd).astype(pool.dtype)
        kp = kp.at[head_ix, ids[None, :]].set(paged(k, kp))
        vp = vp.at[head_ix, ids[None, :]].set(paged(v, vp))
        span = table.shape[0] * page_size
        held_k = kp[:, table].reshape(heads, span, hd)
        held_v = vp[:, table].reshape(heads, span, hd)
        qs = (q[0] * hd ** -0.5).astype(kp.dtype)
        logits = jnp.einsum("hqd,hkd->hqk", qs, held_k,
                            preferred_element_type=F32)
        seen = jnp.arange(span)[None, :] \
            <= (first + jnp.arange(chunk))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
        out = jnp.einsum("hqk,hkd->hqd", probs.astype(vp.dtype), held_v,
                         preferred_element_type=F32)
        return out[None].astype(cfg.dtype), kp, vp


class EvaMLP(nn.Module):
    config: EvaByteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate = _dense(cfg.intermediate_size, ("embed", "mlp"), "gate_proj",
                      cfg)(x)
        up = _dense(cfg.intermediate_size, ("embed", "mlp"), "up_proj",
                    cfg)(x)
        return _dense(cfg.hidden_size, ("mlp", "embed"), "down_proj", cfg)(
            nn.silu(gate) * up)


class EvaBlock(nn.Module):
    config: EvaByteConfig

    @nn.compact
    def __call__(self, x, rotary, cache=None, cache_index=None):
        cfg = self.config
        norm = lambda name: UnitOffsetRMSNorm(  # noqa: E731
            cfg.rms_norm_eps, cfg.dtype, name=name)
        attn, new_cache = EvaAttention(cfg, name="attn")(
            norm("attn_norm")(x), rotary, cache, cache_index)
        # both adds in float32 (`fp32_skip_add`): x is
        x = x + attn.astype(F32)
        with jax.named_scope("mlp"):
            x = x + EvaMLP(cfg, name="mlp")(norm("mlp_norm")(x)).astype(F32)
        return x, new_cache


class EvaByteModel(nn.Module):
    """bytes -> logits of prediction head 0, [b, s, vocab] float32: the
    engine yields one token a row a step and has no verifier, so heads
    1..7 (the published generator's drafts) are loaded and left out of its
    steps. `head="all"`: every head's, [b, s, heads, vocab]. `head=False`
    and the method `head` as `LlamaModel`'s: the final norm's output in
    place of the logits, and head 0 alone. With `kv_caches`, (that,
    per-layer caches with their pools updated)."""
    config: EvaByteConfig

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
        x = jnp.take(embed, tokens, axis=0).astype(F32)
        rotary = _rotary(positions, cfg.head_dim, cfg.rope_theta)
        new_caches = []
        for layer in range(cfg.num_layers):
            cache = kv_caches[layer] if kv_caches is not None else None
            x, new_cache = EvaBlock(cfg, name=f"layer_{layer}")(
                x, rotary, cache, cache_index)
            new_caches.append(new_cache)
        # kept in float32 for the head (`fp32_logits`)
        x = UnitOffsetRMSNorm(cfg.rms_norm_eps, F32, name="final_norm")(x)
        out = self.heads(x) if head == "all" \
            else self.head(x) if head else x
        if kv_caches is not None:
            return out, new_caches
        return out

    def _head_kernel(self):
        cfg = self.config
        return self.param(
            "lm_head", _partitioned(
                # fan-in scaling over the hidden size alone
                nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2)),
                ("embed", None, "vocab")),
            (cfg.hidden_size, cfg.num_pred_heads, cfg.vocab_size),
            cfg.param_dtype)

    @nn.compact
    def head(self, x):
        """Head 0's logits of the final norm's output `x` [b, rows,
        hidden], float32."""
        return jnp.einsum("bsd,dv->bsv", x.astype(F32),
                          self._head_kernel()[:, 0].astype(F32))

    @nn.compact
    def heads(self, x):
        """All prediction heads' logits, [b, rows, heads, vocab]."""
        return jnp.einsum("bsd,dmv->bsmv", x.astype(F32),
                          self._head_kernel().astype(F32))
