"""Plain reference for the two dense decoder families the benchmark runs
(Mistral-7B-v0.3 and Yi-1.5-9B: the same block, other sizes), written from
their published description and independent of ray_tpu.models:

  x   = embed[tokens]
  per layer:  h = x + Wo . attention(rope(Wq . n1(x)), rope(Wk . n1(x)),
                                     Wv . n1(x))
              x = h + Wdown . (silu(Wgate . n2(h)) * (Wup . n2(h)))
  logits = lm_head . norm(x)

RMSNorm: x / sqrt(mean(x^2) + eps) * scale.  RoPE: pairs (i, i + d/2) of a
head rotate by position * theta^(-2i/d) (the "rotate-half" layout of the
published checkpoints).  Attention: causal softmax(q . k / sqrt(d)) . v with
each key/value head shared by heads/kv_heads query heads.  No sliding window
(Mistral v0.3 has none), no bias, untied embeddings.

float32 throughout under jax.default_matmul_precision("highest"); no kernel,
no cache, no batching tricks. It takes the very weights under test and
upcasts each at its use, so it holds no second copy; one layer is jitted
once and called per layer, so a deep model compiles one small program.

Departures from the published models: none in the mathematics. Weights are
random (from the seed), which the comparison does not care about.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, positions, theta):
    # x: [b, s, heads, d]; positions: [b, s]
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = positions.astype(F32)[..., None, None] * inv   # [b,s,1,d/2]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], -1)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def layer(x, p, positions, *, theta: float, eps: float):
    """One decoder block on x [b, s, hidden] (float32)."""
    with jax.default_matmul_precision("highest"):
        a = p["attn"]
        n = _norm(x, p["attn_norm"]["scale"], eps)
        q = jnp.einsum("bsd,dhk->bshk", n, a["q_proj"]["kernel"].astype(F32))
        k = jnp.einsum("bsd,dhk->bshk", n, a["k_proj"]["kernel"].astype(F32))
        v = jnp.einsum("bsd,dhk->bshk", n, a["v_proj"]["kernel"].astype(F32))
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        groups = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
        s = jnp.einsum("bqhk,bthk->bhqt", q, k) * q.shape[-1] ** -0.5
        causal = positions[:, None, :, None] >= positions[:, None, None, :]
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, -1), v)
        h = x + jnp.einsum("bqhk,hkd->bqd", o,
                           a["o_proj"]["kernel"].astype(F32))
        m = p["mlp"]
        n = _norm(h, p["mlp_norm"]["scale"], eps)
        gate = n @ m["gate_proj"]["kernel"].astype(F32)
        up = n @ m["up_proj"]["kernel"].astype(F32)
        return h + (jax.nn.silu(gate) * up) \
            @ m["down_proj"]["kernel"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, lm_head, *, eps: float):
    with jax.default_matmul_precision("highest"):
        return _norm(x, scale, eps) @ lm_head.astype(F32)


def logits(params: Dict[str, Any], tokens, *, num_layers: int,
           theta: float, eps: float, embed_scale=None):
    """tokens [b, s] -> logits [b, s, vocab], float32. `embed_scale`
    [b, s, hidden] multiplies the embedded tokens: the parity check wobbles
    them by a bf16 rounding's worth to find the positions whose logits a
    rounding moves far (harness/parity.py)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None],
                                 tokens.shape)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    if embed_scale is not None:
        x = x * embed_scale
    for i in range(num_layers):
        x = layer(x, params[f"layer_{i}"], positions, theta=theta, eps=eps)
    return _head(x, params["final_norm"]["scale"],
                 params["lm_head"]["kernel"], eps=eps)


@jax.jit
def _token_losses(lg, targets):
    logz = jax.scipy.special.logsumexp(lg, -1)
    return (logz - jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
            ).sum()


def next_token_loss(params: Dict[str, Any], tokens, *, num_layers: int,
                    theta: float, eps: float, rows_at_once: int = 1):
    """Mean next-token cross-entropy over a batch [b, s], a few rows at a
    time so the float32 activations of a deep model stay small."""
    tokens = jnp.asarray(tokens, jnp.int32)
    total = 0.0
    for at in range(0, tokens.shape[0], rows_at_once):
        part = tokens[at:at + rows_at_once]
        lg = logits(params, part, num_layers=num_layers, theta=theta,
                    eps=eps)
        total += float(_token_losses(lg[:, :-1], part[:, 1:]))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))
