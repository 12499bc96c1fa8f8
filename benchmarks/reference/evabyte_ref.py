"""Plain reference for the EvaByte decoder (EVA attention: an exact window
beside one learned summary a chunk of everything before it), written from
the published config.json's keys and independent of ray_tpu.models and
ray_tpu.ops:

  x = embed[bytes]
  per layer:
    u = N(x);  q_i = R_i Wq u_i;  k_j = R_j Wk u_j;  v_j = Wv u_j
    chunk c = positions 16c .. 16c+15, in window floor(c / 128):
      a_j = softmax_{j in c}(s k_j . phi);  k~_c = sum_j a_j k_j + mu
                                            v~_c = sum_j a_j v_j
    o_i = (sum_{j: w(j) = w(i), j <= i} e^{s q_i.k_j} v_j
           + sum_{c: w(c) < w(i)} e^{s q_i.k~_c} v~_c) / (the same sums of e)
    x = x + Wo o
    x = x + Wdown (silu(Wgate N(x)) * Wup N(x))
  logits[m] = N(x) . lm_head[:, m]        m = 0 .. num_pred_heads - 1

N(x) = x / sqrt(mean(x^2) + eps) * (1 + g) (`norm_add_unit_offset`); R the
rotary map over all of a head, pairs (i, i + d/2), angle position *
theta^(-2i/d); s = head_dim^-0.5; w(i) = floor(i / window_size).

float32 throughout under jax.default_matmul_precision("highest"). The
attention is the displayed sum over explicit masks [i, j] and [i, c], the
summaries recomputed from the whole sequence: no cache, no pages, no
batching, no compression step. Queries are taken in blocks only so that
the scores of a 4,000-byte sequence at 32 heads fit beside a serving
engine; the MLP in blocks of its width for the same reason. It takes the
very weights under test and upcasts each at its use.

What the published config fixes: window_size, chunk_size, the heads, theta,
eps, the unit offset, the count of prediction heads. What it does not, and
is ASSUMED here and in ray_tpu/models/evabyte.py alike (stated once, in
this file; benchmarks/configs/evabyte-6.5b-serve.json repeats the list):
  - the scale inside the pooling softmax is s, the attention's own;
  - mu is added to the pooled key and not to the pooled value;
  - keys are pooled after the rotary map (the cache holds rotated keys);
  - a summary becomes visible when its whole WINDOW has closed, never
    before, so it never changes once made;
  - the prediction heads are plain linear heads on the final norm's
    output, no block of their own (the config gives a count);
  - phi and mu start as N(0, 1) clipped to [-1, 1] (weights are random
    here anyway; the comparison does not care).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
MLP_BLOCKS = 4
QUERY_BLOCK = 512


class Shape(NamedTuple):
    """The published keys a layer needs, hashable (a jit static)."""
    heads: int
    head_dim: int
    window: int
    chunk: int
    theta: float
    eps: float


def shape_of(config: Dict[str, Any]) -> Shape:
    """From a config file's keys (the published names)."""
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("the published model has one query a kv head")
    return Shape(
        heads=config["num_attention_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        window=config["window_size"], chunk=config["chunk_size"],
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]))


def _norm(x, g, eps):
    # the stored scale is the offset from one
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g.astype(F32))


def _rope(x, positions, theta):
    # x: [s, heads, d]; positions: [s]
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = positions.astype(F32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], -1)


def summaries(k, v, phi, mu, sh: Shape):
    """k, v [s, heads, d] -> k~, v~ [chunks, heads, d] for every chunk the
    sequence holds WHOLE (a tail shorter than a chunk has no summary: its
    window is open), and the mean entropy of the pooling weights (ln of
    the chunk size for a mean-pool, 0 for a single position)."""
    whole = k.shape[0] // sh.chunk
    kc = k[:whole * sh.chunk].reshape(whole, sh.chunk, sh.heads, sh.head_dim)
    vc = v[:whole * sh.chunk].reshape(whole, sh.chunk, sh.heads, sh.head_dim)
    # assumed: the pooling softmax is scaled as the attention's is
    scores = jnp.einsum("cjhd,hd->chj", kc, phi.astype(F32)) \
        * sh.head_dim ** -0.5
    a = jax.nn.softmax(scores, axis=-1)
    # assumed: mu shifts the pooled key, not the pooled value
    entropy = -jnp.sum(a * jnp.log(jnp.maximum(a, 1e-30)), -1).mean()
    return (jnp.einsum("chj,cjhd->chd", a, kc) + mu.astype(F32)[None],
            jnp.einsum("chj,cjhd->chd", a, vc), entropy)


def _attention(u, p, sh: Shape, with_summaries: bool = True):
    """EVA(u) for u [s, hidden]: (Wo o, o [s, heads, d], (k~, v~, the
    pooling weights' mean entropy))."""
    s = u.shape[0]
    q = jnp.einsum("sd,dhk->shk", u, p["q_proj"]["kernel"].astype(F32))
    k = jnp.einsum("sd,dhk->shk", u, p["k_proj"]["kernel"].astype(F32))
    v = jnp.einsum("sd,dhk->shk", u, p["v_proj"]["kernel"].astype(F32))
    positions = jnp.arange(s)
    # assumed: keys are pooled after the rotary map
    q, k = _rope(q, positions, sh.theta), _rope(k, positions, sh.theta)
    pooled_k, pooled_v, entropy = summaries(k, v, p["phi"], p["mu"], sh)
    chunk_window = (jnp.arange(pooled_k.shape[0]) * sh.chunk) // sh.window
    scale = sh.head_dim ** -0.5
    blocks = []
    for at in range(0, s, QUERY_BLOCK):
        i = positions[at:at + QUERY_BLOCK]
        exact = jnp.einsum("qhk,thk->hqt", q[at:at + QUERY_BLOCK], k) * scale
        # mask [i, j]: the same window, not after the query
        same = (positions[None, :] // sh.window == i[:, None] // sh.window) \
            & (positions[None, :] <= i[:, None])
        coarse = jnp.einsum("qhk,chk->hqc", q[at:at + QUERY_BLOCK],
                            pooled_k) * scale
        # mask [i, c]: the chunk's whole window lies before the query's
        before = chunk_window[None, :] < i[:, None] // sh.window
        if not with_summaries:
            # the control: the sum over c dropped
            before = jnp.zeros_like(before)
        weights = jax.nn.softmax(jnp.concatenate(
            [jnp.where(same, exact, -jnp.inf),
             jnp.where(before, coarse, -jnp.inf)], axis=-1), axis=-1)
        blocks.append(jnp.einsum("hqt,thk->qhk", weights[..., :s], v)
                      + jnp.einsum("hqc,chk->qhk", weights[..., s:],
                                   pooled_v))
    o = jnp.concatenate(blocks, axis=0)
    return (jnp.einsum("qhk,hkd->qd", o, p["o_proj"]["kernel"].astype(F32)),
            o, (pooled_k, pooled_v, entropy))


@functools.partial(jax.jit, static_argnames=("sh", "with_summaries"))
def mixing(x, p, norm, *, sh: Shape, with_summaries: bool = True):
    with jax.default_matmul_precision("highest"):
        out, o, pooled = _attention(_norm(x, norm, sh.eps), p, sh,
                                    with_summaries)
        return x + out, o, pooled


@functools.partial(jax.jit, static_argnames=("eps",))
def _mlp_block(x, norm, gate, up, down, *, eps: float):
    """One slice of the intermediate width's share of W_down(...)."""
    with jax.default_matmul_precision("highest"):
        n = _norm(x, norm, eps)
        return (jax.nn.silu(n @ gate.astype(F32))
                * (n @ up.astype(F32))) @ down.astype(F32)


def layer(x, p, sh: Shape, with_summaries: bool = True):
    """One block on x [s, hidden] (float32): its output, the attention's o
    [s, heads, d] in front of W_o, and the chunks' summaries."""
    h, o, pooled = mixing(x, p["attn"], p["attn_norm"]["scale"], sh=sh,
                          with_summaries=with_summaries)
    m = p["mlp"]
    width = m["gate_proj"]["kernel"].shape[1]
    step = -(-width // MLP_BLOCKS)
    out = 0.0
    for at in range(0, width, step):
        out = out + _mlp_block(
            h, p["mlp_norm"]["scale"],
            m["gate_proj"]["kernel"][:, at:at + step],
            m["up_proj"]["kernel"][:, at:at + step],
            m["down_proj"]["kernel"][at:at + step], eps=sh.eps)
    return h + out, o, pooled


@functools.partial(jax.jit, static_argnames=("eps",))
def _heads(x, norm, lm_head, *, eps: float):
    with jax.default_matmul_precision("highest"):
        # assumed: plain linear heads, head m predicts byte t + 1 + m
        return jnp.einsum("sd,dmv->smv", _norm(x, norm, eps),
                          lm_head.astype(F32))


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any], *,
           num_layers: int, embed_scale=None, details: bool = False,
           with_summaries: bool = True, attended_layers=(0,)):
    """tokens [s] -> logits of every prediction head [s, heads, vocab],
    float32. `embed_scale` [s, hidden] multiplies the embedded tokens: the
    parity check wobbles them by a bf16 rounding's worth to find the
    positions whose logits a rounding moves far (harness/parity.py). With
    `details`, also per layer the summaries (k~, v~) [chunks, heads, d] of
    every whole chunk, the mean entropy of their pooling weights and, for `attended_layers`, the attention's output o
    [s, heads, d] in front of W_o. `with_summaries=False` is the control that drops the sum
    over c."""
    sh = shape_of(config)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    if embed_scale is not None:
        x = x * embed_scale
    kept = []
    for i in range(num_layers):
        x, o, pooled = layer(x, params[f"layer_{i}"], sh, with_summaries)
        if details:
            kept.append({"attended": o if i in attended_layers else None,
                         "summaries": pooled[:2],
                         "pool_entropy": pooled[2]})
    out = _heads(x, params["final_norm"]["scale"], params["lm_head"],
                 eps=sh.eps)
    return (out, kept) if details else out
