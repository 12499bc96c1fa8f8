"""Decoder whose residual is n streams mixed by manifold-constrained
hyper-connections (mHC, arXiv:2512.24880, over Hyper-Connections,
arXiv:2409.19606) around every sublayer (the Xing4.0 family, `model_type:
xing4_0`; equations from the published config's keys, which the field
names below repeat). Only what is new stands here: the attention (with a
query latent, `q_lora_rank`), the MLPs, the expert layer, the rotary table
and the chunk's row write are models/sarvam_mla.py's.

    X_0[j] = E[token]                        j = 1..n   (n = `hc_mult` copies)
    for each layer:  X <- HC(X; MLA) ;  X <- HC(X; F)
    logits = N(sum_j X_L[j]) W_head                      untied

HC(X; G), with its own phi [n d, n^2 + 2n], b [n^2 + 2n] and three gains:

    z     = vec(X) / rms(vec(X))        float32, ONE norm over all n d numbers
    [p~ (n) ; q~ (n) ; R~ (n x n)] = (z phi) * [a_pre ; a_post ; a_res] + b
    H_pre = sigmoid(p~) ;  H_post = 2 sigmoid(q~)
    M_0   = exp(clip(R~, `mhc_h_res_clamp_min`, `mhc_h_res_clamp_max`))
    M_t   = cols(rows(M_{t-1})), t = 1..`hc_sinkhorn_iters` ;  H_res = M_last
            rows(M) = M / (M 1 + `hc_eps`) ,  cols(M) = M / (1^T M + `hc_eps`)
    u     = sum_j H_pre[j] X[j]
    y     = G(RMSNorm(u))               the sublayer, behind its own pre-norm
    X'[i] = H_post[i] y + sum_j H_res[i, j] X[j]

The chain from z to the three H (`HyperConnection`) runs in float32 whatever
the model computes in, as the router does; the streams are the model's type.
H_res is (nearly) doubly stochastic, so the streams' mean is carried through
every sublayer as one residual stream carries x. Per token it is n^2 + 2n =
24 numbers from a [n d] x [n d, 24] product and 2 x 20 normalisations of a
4 x 4 matrix: nothing in bytes or FLOPs beside the sublayer it wraps, and a
chain of dependent small operations on the step's critical path. The
stream axes lead (`[n, batch, len, d]`, the coefficients `[24, batch,
len]`: no axis of 4 among a tile's two), and the 4 x 4 matrix's rows and
columns are summed as adds of its slices, so that all of the chain is
elementwise over tokens and fuses.

F is `sarvam_mla.GatedMLP` (`intermediate_size`) in the first
`first_k_dense_replace` layers and `sarvam_mla.SharedAndRouted` in the
others (`moe.sigmoid_top_k`, `moe.RoutedExperts(gated=True)`). The three
paths `kv_caches` selects (None: the whole sequence, expanded; dicts with
`lengths`: one paged decode token a row; dicts with `table`: one prefill
chunk into a row's pages) and what a layer hands back are sarvam_mla.py's.

The multi-token-prediction module (`num_nextn_predict_layers`) is a draft
head, not part of the next-token forward pass, and is not built
(ROADMAP R8).
"""

from __future__ import annotations

import dataclasses
import functools
import operator
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .llama import RMSNorm, _partitioned
from .sarvam_mla import (GatedMLP, LatentAttention, SarvamMLAConfig,
                         SharedAndRouted, _dense, _rotary)

F32 = jnp.float32
# the seeded initialisation of a connection (the published config is silent;
# benchmarks/configs/xing4.0-29b-a4b-serve.json `assumed.hc_init`): the
# three gains, and b_res = HC_INIT_RES x I (b_pre = b_post = 0)
HC_INIT_GAIN, HC_INIT_RES = 0.1, 4.0


@dataclasses.dataclass(frozen=True)
class XingMHCConfig(SarvamMLAConfig):
    """`SarvamMLAConfig`'s fields (and with them what the paged engine asks
    of a latent model: `latent_cache()`, `layer_caches()`,
    `init_counters()`) at this family's published values, and the keys of
    the query latent and of the hyper-connections."""
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    num_layers: int = 40
    num_heads: int = 32
    first_k_dense_replace: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1024
    routed_scaling_factor: float = 2.0
    held_experts: Tuple[int, int] = (0, 64)
    rope_factor: float = 64.0
    max_seq_len: int = 262144
    q_lora_rank: int = 768
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp: Tuple[float, float] = (-30.0, 30.0)

    def module(self) -> "XingMHCModel":
        return XingMHCModel(self)


def _total(parts):
    return functools.reduce(operator.add, parts)


def sinkhorn(m, iters: int, eps: float):
    """`iters` times rows-then-columns of a positive n x n matrix a token,
    given as n rows of n arrays of one shape (`m[i][j]`: row i, column j,
    of every token): each row over its sum + eps, then each column over its
    sum + eps. Sixteen arrays and no axis of 4: every step is elementwise
    over tokens. A loop, not its 20 trips written out: written out the
    chip's compiler makes 30 kernels of them (81 of a stacked `[n, n,
    tokens]` matrix) and the CPU's takes a minute a program over the
    thousands of small operations (PERF.md section 6, PR 52)."""
    def rows_then_columns(_, m):
        sums = [_total(row) + eps for row in m]
        m = [[a / by for a in row] for row, by in zip(m, sums)]
        sums = [_total(column) + eps for column in zip(*m)]
        return [[a / by for a, by in zip(row, sums)] for row in m]
    return jax.lax.fori_loop(0, iters, rows_then_columns,
                             [list(row) for row in m])


class HyperConnection(nn.Module):
    """The three mixing matrices of one sublayer from its input streams
    [n, batch, len, d], float32, each entry an array [batch, len] of its
    own: (H_pre: n of them, H_post: n, H_res: n rows of n)."""
    config: XingMHCConfig

    @nn.compact
    def __call__(self, streams):
        cfg = self.config
        n, d = streams.shape[0], streams.shape[-1]
        width = n * n + 2 * n
        phi = self.param(
            "phi", _partitioned(nn.initializers.lecun_normal(
                in_axis=(0, 1), out_axis=2), (None, None, None)),
            (n, d, width), cfg.param_dtype)
        bias = self.param(
            "bias", _partitioned(_identity_leaning(n, HC_INIT_RES),
                                 (None,)), (width,), F32)
        gains = self.param(
            "gains", _partitioned(nn.initializers.constant(HC_INIT_GAIN),
                                  (None,)), (3,), F32)
        with jax.named_scope("mhc/coeff"):
            # z phi = (vec(X) phi) / rms(vec(X)): the product first, over
            # the streams as stored (operands of the model's type multiply
            # exactly into float32 sums), the norm's scale after
            x = streams.astype(F32)
            scale = jax.lax.rsqrt(
                jnp.mean(x * x, axis=(0, -1)) + cfg.rms_norm_eps)
            # [24, batch, len]: the chain below is elementwise over tokens
            raw = jnp.einsum("nbsd,ndk->kbs", streams, phi,
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=F32) * scale[None]
            raw = raw * jnp.repeat(gains, np.array([n, n, n * n]))[
                :, None, None] + bias[:, None, None]
            pre = [jax.nn.sigmoid(raw[j]) for j in range(n)]
            post = [2.0 * jax.nn.sigmoid(raw[n + j]) for j in range(n)]
            res = sinkhorn(
                [[jnp.exp(jnp.clip(raw[(2 + i) * n + j],
                                   *cfg.mhc_h_res_clamp))
                  for j in range(n)] for i in range(n)],
                cfg.hc_sinkhorn_iters, cfg.hc_eps)
        if self.is_mutable_collection("intermediates"):
            # what the chain read and what it gave, [24, batch, len] in
            # `bias`'s order (a caller that asks for them recomputes the
            # one from the other)
            self.sow("intermediates", "streams", streams)
            self.sow("intermediates", "coefficients", jnp.stack(
                pre + post + [a for row in res for a in row]))
        return pre, post, res


def _identity_leaning(n: int, res: float):
    """b = [0 (n) ; 0 (n) ; res x I (n x n, row-major)]."""
    def init(key, shape, dtype=F32):
        del key
        return jnp.concatenate(
            [jnp.zeros((2 * n,), dtype),
             (res * jnp.eye(n, dtype=dtype)).reshape(-1)]).reshape(shape)
    return init


def hyper_connect(streams, coefficients, sublayer):
    """HC(X; G) given its three matrices (as `HyperConnection` gives them;
    arrays [n, batch, len] and [n, n, batch, len] do as well):
    `sublayer(u)` is G behind its pre-norm, [batch, len, d] -> ([batch,
    len, d], whatever else it hands back). Returns (X' [n, batch, len, d]
    in the streams' type, that). Every product is a scalar a token times a
    row: elementwise, in float32."""
    pre, post, res = coefficients
    n = streams.shape[0]
    x = streams.astype(F32)
    with jax.named_scope("mhc/pre"):
        u = _total([pre[j][..., None] * x[j] for j in range(n)]
                   ).astype(streams.dtype)
    y, kept = sublayer(u)
    with jax.named_scope("mhc/post"):
        y = y.astype(F32)
        mixed = jnp.stack(
            [post[i][..., None] * y
             + _total([res[i][j][..., None] * x[j] for j in range(n)])
             for i in range(n)])
        # the streams ARE the model's type, and the next connection's chain
        # reads them as stored: behind the barrier the compiler cannot
        # compute them again inside a consumer's kernel with the cast to
        # bf16 and back left out (its default; it did so for the one
        # connection whose producer is cheap, layer 0's second, whose
        # chain then read numbers no stream held, 5e-4 off in a
        # coefficient: PERF.md section 6, PR 52)
        mixed = jax.lax.optimization_barrier(mixed.astype(streams.dtype))
    return mixed, kept


class Block(nn.Module):
    config: XingMHCConfig
    experts: bool

    @nn.compact
    def __call__(self, streams, rotary, cache=None, cache_index=None,
                 valid=None):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.rms_norm_eps, cfg.dtype, name=name)

        def attend(u):
            return LatentAttention(cfg, cfg.q_lora_rank, name="attn")(
                norm("attn_norm")(u), rotary, cache, cache_index, valid)

        streams, new_cache = hyper_connect(
            streams, HyperConnection(cfg, name="attn_hc")(streams), attend)
        kept = () if new_cache is None else (new_cache["pool"],)
        decoding = cache is not None and "lengths" in cache

        def mix(u):
            u = norm("mlp_norm")(u)
            if not self.experts:
                with jax.named_scope("mlp"):
                    return GatedMLP(cfg, cfg.intermediate_size,
                                    name="mlp")(u), None
            mask = None
            if decoding:
                mask = cache["active"][:, None]
            elif valid is not None:
                mask = jnp.broadcast_to(
                    jnp.arange(u.shape[1]) < valid, u.shape[:2])
            # what the router read (as "attended" in the attention)
            self.sow("intermediates", "router_input", u)
            return SharedAndRouted(cfg, name="moe")(u, mask)

        streams, pairs = hyper_connect(
            streams, HyperConnection(cfg, name="mlp_hc")(streams), mix)
        if self.experts and decoding:
            kept += (cache["pairs"] + pairs,
                     cache["steps"] + (pairs > 0).astype(jnp.int32))
        return streams, kept


class XingMHCModel(nn.Module):
    """tokens -> logits; with `kv_caches`, `head=False` and the method
    `head` as `SarvamMLAModel`."""
    config: XingMHCConfig

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
        streams = jnp.broadcast_to(x[None], (cfg.hc_mult,) + x.shape)
        rotary = _rotary(cfg, positions)
        new_caches = []
        for layer in range(cfg.num_layers):
            cache = kv_caches[layer] if kv_caches is not None else None
            streams, kept = Block(cfg, cfg.expert_layer(layer),
                                  name=f"layer_{layer}")(
                streams, rotary, cache, cache_index, valid)
            new_caches.append(kept)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(
            streams.astype(F32).sum(0).astype(cfg.dtype))
        out = self.head(x) if head else x
        if kv_caches is not None:
            return out, new_caches
        return out

    @nn.compact
    def head(self, x):
        """Logits of the final norm's output `x` [batch, rows, hidden]."""
        cfg = self.config
        return _dense(cfg.vocab_size, ("embed", "vocab"), "lm_head", cfg)(x)
