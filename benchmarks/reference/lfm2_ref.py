"""Plain reference for the LFM2-24B-A2B decoder (`model_type: lfm2_moe`):
gated short convolutions with grouped-query attention every few layers, over
sigmoid-routed SwiGLU experts. Written from the published config.json's keys
and independent of ray_tpu.models and ray_tpu.ops: no cache, no kernel, no
batching, the convolution as the three-term sum below over the whole
sequence, the attention expanded (every query against every key).

  x_0 = embed[token]
  layer l, kind layer_types[l]:
    u = n(x; operator_norm)
    conv:            [B ; C ; X] = W_in u                      `_split`
                     v_t = B_t * X_t
                     c_t = w[0] v_{t-2} + w[1] v_{t-1} + w[2] v_t   `_filter`
                     y_t = W_out (C_t * c_t)
    full_attention:  q, k, v = W_q u, W_k u, W_v u  (32 / 8 / 8 heads x 64)
                     q_h = rot(n(q_h; g_q)), k_h = rot(n(k_h; g_k))
                                                               `_head_norms`
                     y = W_o concat_h softmax(q_h k_{h/4}^T / 8 + causal)
                         v_{h/4}
    x = x + y
    f = n(x; ffn_norm)
    l < num_dense_layers:   x = x + W_2 (silu(W_1 f) * W_3 f)
    l >= num_dense_layers:  s = sigmoid(W_g f) in R^64; chosen = the 4
                     largest s + b; a_i = s_i / (sum_chosen s + 1e-6)
                     * routed_scaling_factor                   `_route`
                     x = x + sum_{i chosen, i held} a_i E_i(f)
  logits = n(x; embedding_norm) embed^T                        `_tied_head`

n(x; g) = x / sqrt(mean(x^2) + norm_eps) * g. float32 throughout under
jax.default_matmul_precision("highest"), a LAYER at a time, and the feed-forward
parts a block of their width or of their experts at a time (weights are
cast to float32 inside the jitted call that multiplies them, at most 0.25
GB at once: 3.76 B parameters do not fit in float32 beside a serving
engine). Given `held_experts` = (first,
count), the sum over the chosen experts runs over the held ones only, as
the program's does. Routing is discontinuous, so the reference can be told
which experts the program under test chose (`routes`) and follows them,
computing the weights from its OWN scores of those; it returns its own
selection scores beside.

The expert sum is dense over the held experts (for every held expert, E_e
of every position times the position's weight for it, 0 where it was not
chosen): no sorting, no grouping, nothing shared with the system's routed
layer. ISSUE 56 asked for a loop over each token's chosen experts; that
form cost Xing's check 620-760 s on the chip (PERF.md section 6, PR 52)
and the dense form is the same sum.

Assumed (the configuration file says the same; the function named is the
one place to read each otherwise):
  - `_tied_head`: the head is the embedding (the LFM2 family ties), behind
    the family's `embedding_norm`.
  - `_split`: W_in's 3 x hidden outputs are B, C, X in this order.
  - `_filter`: `conv_L_cache` taps, depthwise, causal (zeros before the
    sequence), no bias, NO activation; the kernel stored [taps, channels]
    (published [channels, 1, taps]: a transpose), tap 0 the oldest.
  - `_head_norms`: an RMSNorm a head on q and on k (the family's
    q_layernorm / k_layernorm; the config has no key for them), BEFORE the
    rotary map, whose pairs are lanes (j, j + d/2) (half-split).
  - `_route`: the expert bias chooses and does not weigh; it is zero, as
    initialised; 1e-6 stands in the weights' sum.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
VOCAB_BLOCK = 8192
QUERY_BLOCK = 256       # queries whose scores against every key stand at once
EXPERT_BLOCK = 2        # float32 experts at once: 3 x 2048 x 1536 x 4 B each
DENSE_BLOCK = 2944      # columns of the dense layer's width at once


class Shape(NamedTuple):
    """The published keys the layers need, hashable (a jit static)."""
    heads: int
    kv_heads: int
    theta: float
    eps: float
    taps: int
    experts_per_token: int
    routed_scaling: float
    held: Tuple[int, int]
    # the control of the parity check: how many of the filter's OLDEST taps
    # are left out (0: the mathematics as published)
    taps_dropped: int = 0


def shape_of(config: Dict[str, Any]) -> Shape:
    """From a config file's keys (the published names) plus
    `held_experts`; without it every routed expert is held."""
    held = tuple(config.get("held_experts") or (0, config["num_experts"]))
    rope = config.get("rope_parameters") or {}
    return Shape(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        theta=float(rope.get("rope_theta", config.get("rope_theta", 1e6))),
        eps=float(config["norm_eps"]), taps=config["conv_L_cache"],
        experts_per_token=config["num_experts_per_tok"],
        routed_scaling=float(config["routed_scaling_factor"]), held=held)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _split(projected):
    """W_in's output as (B, C, X)."""
    return jnp.split(projected, 3, axis=-1)


def _filter(v, w, dropped: int = 0):
    """c_t = sum_j w[j] v_{t - (taps - 1 - j)}, v_t = 0 for t < 0; v [s, d],
    w [taps, d]."""
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, v.shape[1]), F32), v])
    return sum(w[j].astype(F32) * padded[j:j + v.shape[0]]
               for j in range(dropped, taps))


@functools.partial(jax.jit, static_argnames=("sh",))
def conv_layer(x, p, *, sh: Shape):
    """x + the gated short convolution of n(x), and the gated inputs v
    [s, d] (whose last taps - 1 rows are what a cache would keep)."""
    with jax.default_matmul_precision("highest"):
        u = _norm(x, p["operator_norm"]["scale"], sh.eps)
        m = p["conv"]
        gate_in, gate_out, inner = _split(
            u @ m["in_proj"]["kernel"].astype(F32))
        v = gate_in * inner
        c = _filter(v, m["conv_kernel"], sh.taps_dropped)
        return x + (gate_out * c) @ m["out_proj"]["kernel"].astype(F32), v


def _rotate(x, positions, theta):
    # x: [s, heads, d]; pairs (j, j + d/2)
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = positions.astype(F32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], -1)


def _head_norms(q, k, a, eps):
    return _norm(q, a["q_norm"]["scale"], eps), \
        _norm(k, a["k_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("sh",))
def _qkv(x, p, positions, *, sh: Shape):
    with jax.default_matmul_precision("highest"):
        u = _norm(x, p["operator_norm"]["scale"], sh.eps)
        a = p["attn"]
        q = jnp.einsum("sd,dhk->shk", u, a["q_proj"]["kernel"].astype(F32))
        k = jnp.einsum("sd,dhk->shk", u, a["k_proj"]["kernel"].astype(F32))
        v = jnp.einsum("sd,dhk->shk", u, a["v_proj"]["kernel"].astype(F32))
        q, k = _head_norms(q, k, a, sh.eps)
        return (_rotate(q, positions, sh.theta),
                _rotate(k, positions, sh.theta), v)


@functools.partial(jax.jit, static_argnames=("sh",), donate_argnums=(0,))
def _attend_block(x, q, k, v, w_o, positions, first, *, sh: Shape):
    """x with the attention of QUERY_BLOCK queries from `first` added."""
    with jax.default_matmul_precision("highest"):
        queries = jax.lax.dynamic_slice_in_dim(q, first, QUERY_BLOCK, 0)
        at = jax.lax.dynamic_slice_in_dim(positions, first, QUERY_BLOCK, 0)
        groups = sh.heads // sh.kv_heads
        kk, vv = jnp.repeat(k, groups, axis=1), jnp.repeat(v, groups, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", queries, kk) \
            * q.shape[-1] ** -0.5
        seen = positions[None, :] <= at[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), -1)
        out = jnp.einsum("hqk,khd->qhd", probs, vv)
        rows = jax.lax.dynamic_slice_in_dim(x, first, QUERY_BLOCK, 0)
        rows = rows + jnp.einsum("qhd,hdm->qm", out, w_o.astype(F32))
        return jax.lax.dynamic_update_slice_in_dim(x, rows, first, 0)


def attention_layer(x, p, positions, sh: Shape):
    """x + attention of n(x), a block of queries at a time, in x's own
    buffer (the caller's `x` is consumed); also the rotated keys and the
    values [s, kv_heads, hd] (what a cache would keep)."""
    s = x.shape[0]
    pad = -s % QUERY_BLOCK
    if pad:
        # whole blocks: the padded queries sit at positions past every key
        # and are cut off again
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), F32)])
        positions = jnp.concatenate(
            [positions, positions[-1] + 1 + jnp.arange(pad)])
    q, k, v = _qkv(x, p, positions, sh=sh)
    w_o = p["attn"]["o_proj"]["kernel"]
    for first in range(0, s + pad, QUERY_BLOCK):
        x = _attend_block(x, q, k, v, w_o, positions, first, sh=sh)
    return x[:s], k[:s], v[:s]


@functools.partial(jax.jit, donate_argnums=(0,))
def _swiglu_block(out, f, w_gate, w_up, w_down):
    """out + a block of the width's part of the sum, in out's buffer."""
    with jax.default_matmul_precision("highest"):
        return out + (jax.nn.silu(f @ w_gate.astype(F32))
                      * (f @ w_up.astype(F32))) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=("sh",))
def _ffn_input(x, scale, *, sh: Shape):
    return _norm(x, scale, sh.eps)


def dense_layer(x, p, *, sh: Shape):
    """x + W_2 (silu(W_1 f) * W_3 f), DENSE_BLOCK columns of the width at
    a time (the sum over the width splits; a whole layer's float32 weights
    are 0.87 GB)."""
    f = _ffn_input(x, p["ffn_norm"]["scale"], sh=sh)
    x = x + 0.0      # the blocks add into a buffer of their own
    gate, up, down = (p["mlp"][k]["kernel"] for k in
                      ("gate_proj", "up_proj", "down_proj"))
    for at in range(0, gate.shape[1], DENSE_BLOCK):
        x = _swiglu_block(x, f, gate[:, at:at + DENSE_BLOCK],
                          up[:, at:at + DENSE_BLOCK],
                          down[at:at + DENSE_BLOCK])
    return x


def _route(f, m, routes, sh: Shape):
    """The selection scores s + bias [s, E] and each position's weight for
    each expert [s, E] (0 where not chosen): over the reference's own
    top-k, or over `routes` [s, k]."""
    scores = jax.nn.sigmoid(f @ m["router"].astype(F32))
    selection = scores + m["e_score_correction_bias"].astype(F32)
    if routes is None:
        _, routes = jax.lax.top_k(selection, sh.experts_per_token)
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], routes].set(True)
    picked = jnp.where(chosen, scores, 0.0)
    weights = sh.routed_scaling * picked \
        / (picked.sum(-1, keepdims=True) + 1e-6)
    return selection, weights


@functools.partial(jax.jit, static_argnames=("sh",))
def _routed_input(x, p, routes, *, sh: Shape):
    with jax.default_matmul_precision("highest"):
        f = _norm(x, p["ffn_norm"]["scale"], sh.eps)
        return (f,) + _route(f, p["moe"], routes, sh)


@functools.partial(jax.jit, donate_argnums=(0,))
def _expert_block(out, f, weights, w_gate, w_up, w_down):
    """out + the sum over a block of experts of weight[s, e] * E_e(f[s]),
    in out's buffer."""
    with jax.default_matmul_precision("highest"):
        hidden = jax.nn.silu(
            jnp.einsum("sd,edf->esf", f, w_gate.astype(F32))) \
            * jnp.einsum("sd,edf->esf", f, w_up.astype(F32))
        each = jnp.einsum("esf,efd->esd", hidden, w_down.astype(F32))
        return out + jnp.einsum("se,esd->sd", weights, each)


def expert_layer(x, p, routes, *, sh: Shape):
    """(x + the held experts' part of the routed sum of n(x), the
    selection scores [s, E]), EXPERT_BLOCK held experts at a time (all
    eight in float32 are 0.9 GB)."""
    f, selection, weights = _routed_input(x, p, routes, sh=sh)
    x = x + 0.0      # the blocks add into a buffer of their own
    m = p["moe"]
    first, count = sh.held
    for at in range(0, count, EXPERT_BLOCK):
        upto = min(at + EXPERT_BLOCK, count)
        x = _expert_block(x, f, weights[:, first + at:first + upto],
                          m["w_gate"][at:upto], m["w_in"][at:upto],
                          m["w_out"][at:upto])
    return x, selection


@jax.jit
def _tied_head(n, embed_rows):
    with jax.default_matmul_precision("highest"):
        return n @ embed_rows.astype(F32).T


def layer(x, p, kind: str, dense: bool, positions, sh: Shape, route=None):
    """One layer on the stream x [s, d] (consumed): the mixer of `kind`,
    then the dense feed-forward or the experts (along `route` [s, k] where
    given). Returns (x', {"gated" | "keys", "values", "selection"}): what
    a cache would keep of the layer and what the router ranked by."""
    kept = {}
    if kind == "conv":
        x, kept["gated"] = conv_layer(x, p, sh=sh)
    else:
        x, kept["keys"], kept["values"] = attention_layer(x, p, positions,
                                                          sh)
    if dense:
        return dense_layer(x, p, sh=sh), kept
    x, kept["selection"] = expert_layer(x, p, route, sh=sh)
    return x, kept


def head(x, params, sh: Shape):
    """Logits of the final stream x [rows, d], on the HOST: the blocks of
    the vocabulary are joined there (1,164 rows of 65,536 float32 logits
    are 0.3 GB, and joining them on the device holds them twice)."""
    n = _norm(x, params["embedding_norm"]["scale"], sh.eps)
    embed = params["embed"]
    return np.concatenate(
        [np.asarray(_tied_head(n, embed[at:at + VOCAB_BLOCK]))
         for at in range(0, embed.shape[0], VOCAB_BLOCK)], -1)


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any], *,
           embed_scale=None, routes: Optional[list] = None, rows=None,
           details: bool = False, taps_dropped: int = 0):
    """tokens [s] -> logits [s, vocab], float32; `config` holds the
    published keys and optionally `held_experts`. `embed_scale` [s, hidden]
    multiplies the embedded tokens (the parity check's wobble). `routes`:
    per expert layer, [s, k] expert ids to follow in place of the
    reference's own top-k. `rows`: the indices whose logits are wanted
    (all). `details`: also {"selection": per expert layer the scores [s, E]
    the experts were ranked by, "gated": per `conv` layer the gated inputs
    v [s, d], "keys" / "values": per attending layer [s, kv_heads, hd], on
    the host}. `taps_dropped`: the parity check's control (a filter
    without its oldest taps)."""
    sh = shape_of(config)._replace(taps_dropped=taps_dropped)
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0])
    wanted = positions if rows is None else jnp.asarray(rows, jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    if embed_scale is not None:
        x = x * embed_scale
    kept = {"selection": [], "gated": [], "keys": [], "values": []}
    for i, kind in enumerate(config["layer_types"]):
        dense = i < config["num_dense_layers"]
        route = None if routes is None or dense \
            else jnp.asarray(routes[len(kept["selection"])], jnp.int32)
        x, of_layer = layer(x, params[f"layer_{i}"], kind, dense, positions,
                            sh, route)
        for name, value in of_layer.items():
            kept[name].append(np.asarray(value) if details
                              and name != "selection" else value)
        x.block_until_ready()     # a layer at a time
    out = head(x[wanted], params, sh)
    if details:
        return out, kept
    return out
