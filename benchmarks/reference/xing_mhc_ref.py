"""Plain reference for the Xing4.0-29B-A4B decoder (`model_type: xing4_0`):
a residual of n = `hc_mult` streams mixed by manifold-constrained
hyper-connections (mHC, arXiv:2512.24880, over Hyper-Connections,
arXiv:2409.19606) around every sublayer, multi-head latent attention with a
query latent, routed SwiGLU experts beside a shared one. Written from the
published config.json's keys and independent of ray_tpu.models and
ray_tpu.ops: no cache, no absorbed products, no kernel, no batching (several
sequences may stand one after another in ONE array: `logits`), the
attention EXPANDED, the expert sum dense (for every held expert, E_e of every
position times the position's weight for it, 0 where it was not chosen: no
sorting, no grouping, nothing shared with the system's routed layer. ISSUE 52
asked for a loop over each token's chosen experts; that form, one token after
another under `lax.map`, twelve gathered matrices a token, stood in this
file's first version, with which the check took 620-760 s on the chip, and
was replaced by sarvam_mla_ref's: PERF.md section 6, PR 52).
What acts on a position alone as Sarvam-105B's reference has it (the
rotary table, the RMSNorm, SwiGLU in blocks of its width, the softmax of a
block of heads and queries against every key) is imported from
benchmarks/reference/sarvam_mla_ref.py, whose docstring writes those
equations out; everything this architecture adds is written here.

  X_0[j] = embed[token]                  j = 1..n          `_initial_streams`
  for each layer:  X <- HC(X; MLA) ;  X <- HC(X; F)
  logits = lm_head . n(sum_j X_L[j])     untied             `_read_out`

  HC(X; G), with its own phi [n d, n^2 + 2n], b [n^2 + 2n], three gains:
     z = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)       `_stream_norm`
         ONE norm over all n d numbers of a position, no scale; vec(X) is
         X[1], then X[2], ...
     [p~ (n) ; q~ (n) ; R~ (n x n, row-major)]
         = (z phi) * [a_pre (n times) ; a_post (n) ; a_res (n^2)] + b
     H_pre = sigmoid(p~) ;  H_post = 2 sigmoid(q~)
     M_0 = exp(clip(R~, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
     M_t = cols(rows(M_{t-1})),  t = 1..hc_sinkhorn_iters ;  H_res = M_last
         rows(M) = M / (M 1 + hc_eps),  cols(M) = M / (1^T M + hc_eps)
                                                            `_sinkhorn`
     u = sum_j H_pre[j] X[j] ;  y = G(n(u; the sublayer's own scale))
     X'[i] = H_post[i] y + sum_j H_res[i, j] X[j]

  MLA(h), H heads:                                          `_queries`,
     c_q = n(Wdq . h; g_q)  (q_lora_rank)                   `_latent`
     [q_i^nope ; q_i^rope] = Wuq_i . c_q
     [c~ (kv_lora_rank) ; k~^rope] = Wkva . h ;  c = n(c~; g_kv)
     then sarvam_mla_ref's: k^rope = R_t k~^rope (ONE for all heads),
     q_i^rope <- R_t q_i^rope, [k_i^nope ; v_i] = Wkvb_i . c, causal
     softmax of sigma (q_i^nope . k_i^nope + q_i^rope . k^rope), sigma =
     (qk_nope_head_dim + qk_rope_head_dim)^-1/2 m^2, m = 0.1
     mscale_all_dim ln(factor) + 1 (1.4159 as published), o = Wo . [..]_i
  F, the first `first_k_dense_replace` layers: Wd . (silu(Wg . h) * Wu . h)
  F, the others:                                            `_route`
     s = sigmoid(Wr . h) in R^E, float32
     chosen = the k experts with the largest s + bias (the bias chooses
              and does not weigh)
     w_e = routed_scaling_factor s_e / (sum_chosen s + 1e-20)
     sum_chosen w_e E_e(h) + S(h),  E_e, S: SwiGLU, moe_intermediate_size

float32 throughout under jax.default_matmul_precision("highest"). Given
`held_experts` = (first, count), the sum over the chosen experts runs over
the held ones only (the published deployment holds all 64). Routing is
discontinuous, so the reference can be told which experts the program under
test chose (`routes`) and follows them, computing the weights from its OWN
scores of those; it returns its own selection scores beside.

Assumed (the configuration file says the same; the function named is the
one place to read each otherwise):
  - `_initial_streams`: X_0 is n copies of the embedding; `_read_out`: the
    head reads the SUM of the streams (Hyper-Connections' convention; the
    config names no head mixing).
  - `_stream_norm`: rms_norm_eps under the root, as every RMSNorm here.
  - `_sinkhorn`: hc_eps is added to each row's and each column's sum; rows
    first, then columns; `hc_sinkhorn_iters` of each.
  - `_queries`, `_latent`: an RMSNorm on both latents (the family's
    q_a_layernorm / kv_a_layernorm).
  - a rotary pair is lanes (j, j + d/2) (sarvam_mla_ref's relabelling).
  - `_route`: one routing group, the expert bias zero as initialised.
  - the multi-token-prediction module (`num_nextn_predict_layers`) is not
    part of the next-token forward pass and is not here.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .sarvam_mla_ref import (DENSE_BLOCK, EXPERT_BLOCK, HEAD_BLOCK,
                             QUERY_BLOCK, VOCAB_BLOCK, Shape, _add_projected,
                             _attended, _expert_block, _head_block, _norm,
                             _rotate, _row_blocks, _swiglu, rotary_table)

F32 = jnp.float32


class Mixing(NamedTuple):
    """The published keys of the hyper-connections, hashable (a jit
    static)."""
    streams: int
    iters: int
    eps: float
    clamp: Tuple[float, float]
    norm_eps: float


def shapes_of(config: Dict[str, Any]) -> Tuple[Shape, Mixing]:
    """From a config file's keys (the published names) plus
    `held_experts`; without it every routed expert is held."""
    scaling = config["rope_scaling"]
    if scaling["type"] != "yarn":
        raise ValueError(f"rope_scaling type {scaling['type']!r}")
    held = tuple(config.get("held_experts")
                 or (0, config["n_routed_experts"]))
    return Shape(
        heads=config["num_attention_heads"], rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]), factor=float(scaling["factor"]),
        original_max=int(scaling["original_max_position_embeddings"]),
        beta_fast=float(scaling["beta_fast"]),
        beta_slow=float(scaling["beta_slow"]),
        mscale=float(scaling["mscale"]),
        mscale_all_dim=float(scaling["mscale_all_dim"]),
        experts_per_token=config["num_experts_per_tok"],
        routed_scaling=float(config["routed_scaling_factor"]),
        held=held), Mixing(
        streams=config["hc_mult"], iters=config["hc_sinkhorn_iters"],
        eps=float(config["hc_eps"]),
        clamp=(float(config["mhc_h_res_clamp_min"]),
               float(config["mhc_h_res_clamp_max"])),
        norm_eps=float(config["rms_norm_eps"]))


# ---------------------------------------------------------------------------
# the hyper-connections
# ---------------------------------------------------------------------------

def _initial_streams(x, n: int):
    """x [s, d] -> X_0 [s, n, d]: n copies."""
    return jnp.repeat(x[:, None, :], n, axis=1)


def _read_out(streams):
    """What the final norm reads: the sum of the streams."""
    return streams.sum(1)


def _stream_norm(streams, eps: float):
    """z [s, n d]: vec(X) over its root mean square, one norm a position."""
    flat = streams.reshape(streams.shape[0], -1)
    return flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)


def _sinkhorn(m, iters: int, eps: float):
    """m [s, n, n] positive; `iters` times rows, then columns."""
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


@functools.partial(jax.jit, static_argnames=("mix",))
def coefficients(streams, hc, *, mix: Mixing):
    """H_pre [s, n], H_post [s, n], H_res [s, n, n] of the positions'
    streams [s, n, d], from one connection's parameters."""
    with jax.default_matmul_precision("highest"):
        n = mix.streams
        z = _stream_norm(streams, mix.norm_eps)
        gains = hc["gains"].astype(F32)
        each = jnp.concatenate([jnp.full((n,), gains[0]),
                                jnp.full((n,), gains[1]),
                                jnp.full((n * n,), gains[2])])
        raw = (z @ hc["phi"].astype(F32).reshape(z.shape[1], -1)) * each \
            + hc["bias"].astype(F32)
        pre = jax.nn.sigmoid(raw[:, :n])
        post = 2.0 * jax.nn.sigmoid(raw[:, n:2 * n])
        res = _sinkhorn(
            jnp.exp(jnp.clip(raw[:, 2 * n:], *mix.clamp)).reshape(-1, n, n),
            mix.iters, mix.eps)
        return pre, post, res


@jax.jit
def _read(streams, pre):
    return jnp.einsum("sn,snd->sd", pre, streams)


@jax.jit
def _write(streams, y, post, res):
    return post[:, :, None] * y[:, None, :] \
        + jnp.einsum("sij,sjd->sid", res, streams)


def hyper_connect(streams, hc, mix: Mixing, sublayer):
    """HC(X; G): `sublayer(u)` is G behind its pre-norm, [s, d] -> ([s, d],
    whatever else it returns)."""
    pre, post, res = _row_blocks(
        lambda x: coefficients(x, hc, mix=mix), streams)
    y, kept = sublayer(_row_blocks(lambda x, pre: (_read(x, pre),),
                                   streams, pre)[0])
    return _row_blocks(lambda *per_row: (_write(*per_row),),
                       streams, y, post, res)[0], kept


# ---------------------------------------------------------------------------
# latent attention with a query latent
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sh",))
def _latent(u, p, positions, *, sh: Shape):
    """Of every position's h = n(u): the query latent c_q [s, q_lora_rank],
    the kv latent c [s, kv_lora_rank] and the rotated shared key k^rope [s,
    rope]."""
    with jax.default_matmul_precision("highest"):
        a = p["attn"]
        h = _norm(u, p["attn_norm"]["scale"], sh.eps)
        c_q = _norm(h @ a["q_a_proj"]["kernel"].astype(F32),
                    a["q_a_norm"]["scale"], sh.eps)
        kva = h @ a["kv_a_proj"]["kernel"].astype(F32)
        c = _norm(kva[:, :sh.rank], a["kv_a_norm"]["scale"], sh.eps)
        k_rope = _rotate(kva[:, sh.rank:], *rotary_table(sh, positions))
        return c_q, c, k_rope


@functools.partial(jax.jit, static_argnames=("sh",))
def _queries(c_q, c, w_uq, w_kvb, positions, *, sh: Shape):
    """A block of heads: the queries [s, heads, nope + rope] (the rotary
    part turned) from the query latent and, expanded from the kv latent,
    the keys' nope parts and the values."""
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("sr,rhk->shk", c_q, w_uq.astype(F32))
        q = jnp.concatenate(
            [q[..., :sh.nope],
             _rotate(q[..., sh.nope:], *rotary_table(sh, positions))], -1)
        kv = jnp.einsum("sr,rhk->shk", c, w_kvb.astype(F32))
        return q, kv[..., :sh.nope], kv[..., sh.nope:]


def attention(u, p, positions, branch, sh: Shape):
    """MLA(n(u)) [s, d], a block of heads and a block of queries at a
    time; a token attends the tokens of its own `branch` that stand no
    later in the array."""
    c_q, c, k_rope = _row_blocks(
        lambda u, positions: _latent(u, p, positions, sh=sh), u, positions)
    a = p["attn"]
    everyone = jnp.arange(u.shape[0])
    out = jnp.zeros_like(u)
    for at in range(0, sh.heads, HEAD_BLOCK):
        heads = slice(at, min(at + HEAD_BLOCK, sh.heads))
        q, k_nope, v = _row_blocks(
            lambda c_q, c, positions: _queries(
                c_q, c, a["q_b_proj"]["kernel"][:, heads],
                a["kv_b_proj"][:, heads], positions, sh=sh),
            c_q, c, positions)
        for first in range(0, u.shape[0], QUERY_BLOCK):
            out = _add_projected(
                out, _attended(q, k_nope, v, k_rope, branch,
                               everyone[first:first + QUERY_BLOCK], sh=sh),
                a["o_proj"]["kernel"][heads], first)
        out.block_until_ready()     # a block of heads at a time
    return out


# ---------------------------------------------------------------------------
# the experts
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sh",))
def _route(u, p, routes, *, sh: Shape):
    """The expert layer's input h = n(u), the selection scores s + bias [s,
    E] and each position's weight for each expert [s, E] (0 where not
    chosen): over the reference's own top-k, or over `routes` [s, k]."""
    with jax.default_matmul_precision("highest"):
        h = _norm(u, p["mlp_norm"]["scale"], sh.eps)
        m = p["moe"]["routed"]
        scores = jax.nn.sigmoid(h @ m["router"].astype(F32))
        selection = scores + m["e_score_correction_bias"].astype(F32)
        if routes is None:
            _, routes = jax.lax.top_k(selection, sh.experts_per_token)
        chosen = jnp.zeros(scores.shape, bool).at[
            jnp.arange(scores.shape[0])[:, None], routes].set(True)
        picked = jnp.where(chosen, scores, 0.0)
        weights = sh.routed_scaling * picked \
            / (picked.sum(-1, keepdims=True) + 1e-20)
        return h, selection, weights


def experts(u, p, sh: Shape, routes=None, shared: bool = True):
    """(routed + shared of h = n(u) [s, d], the selection scores [s, E])
    for one expert layer: for a block of held experts at a time, E_e of
    every position times the position's weight for it (0 where it was not
    chosen), as sarvam_mla_ref's expert sum. `shared=False` leaves the
    shared expert out."""
    first, count = sh.held

    def rows(u, routes):
        h, selection, weights = _route(u, p, routes, sh=sh)
        m = p["moe"]["routed"]
        out = jnp.zeros_like(u)
        for at in range(0, count, EXPERT_BLOCK):
            upto = min(at + EXPERT_BLOCK, count)
            out = _expert_block(
                out, h, weights[:, first + at:first + upto],
                m["w_gate"][at:upto], m["w_in"][at:upto], m["w_out"][at:upto])
        if shared:
            out = out + _swiglu(h, p["moe"]["shared"], DENSE_BLOCK)
        return out, selection
    return _row_blocks(rows, u, routes)


def dense(u, p, sh: Shape):
    return _row_blocks(lambda u: (_swiglu(
        _norm(u, p["mlp_norm"]["scale"], sh.eps), p["mlp"], DENSE_BLOCK),),
        u)[0]


# ---------------------------------------------------------------------------

def logits(params: Dict[str, Any], tokens, config: Dict[str, Any], *,
           positions=None, branch=None, embed_scale=None,
           routes: Optional[list] = None, rows=None, details: bool = False):
    """tokens [s] -> logits [s, vocab], float32; `config` holds the
    published keys and optionally `held_experts`. `positions`, `branch`
    [s]: several sequences, one after another, as ONE array (`branch`
    names each token's sequence, 1 and up, `positions` its position in
    it): token t attends token u where u stands no later in the array and
    is of t's own sequence, exactly the causal attention of each sequence
    alone, since everything else acts on a position alone. Without them:
    one sequence, positions 0 .. s - 1. `embed_scale` [s, hidden]
    multiplies the embedded tokens (the parity check's wobble). `routes`:
    per expert layer, [s, k] expert ids to follow in place of the
    reference's own top-k. `rows`: the indices whose logits are wanted
    (all). `details`: also {"selection": per expert layer the scores [s,
    E] the experts were ranked by, "streams": per connection, in the order
    they run, the streams [rows, n, d] it read at `rows`, on the host}."""
    sh, mix = shapes_of(config)
    tokens = jnp.asarray(tokens, jnp.int32)
    everyone = jnp.arange(tokens.shape[0])
    positions = everyone if positions is None \
        else jnp.asarray(positions, jnp.int32)
    # (sarvam_mla_ref._attended lets every token attend branch 0)
    branch = jnp.ones_like(everyone) if branch is None \
        else jnp.asarray(branch, jnp.int32)
    wanted = everyone if rows is None else jnp.asarray(rows, jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    if embed_scale is not None:
        x = x * embed_scale
    streams = _initial_streams(x, mix.streams)
    selections, read = [], []
    for i in range(config["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        if details:
            read.append(np.asarray(streams[wanted]))
        streams, _ = hyper_connect(
            streams, p["attn_hc"], mix,
            lambda u: (attention(u, p, positions, branch, sh), None))
        if details:
            read.append(np.asarray(streams[wanted]))
        if i < config["first_k_dense_replace"]:
            streams, _ = hyper_connect(
                streams, p["mlp_hc"], mix, lambda u: (dense(u, p, sh), None))
        else:
            route = None if routes is None \
                else jnp.asarray(routes[len(selections)], jnp.int32)
            streams, selection = hyper_connect(
                streams, p["mlp_hc"], mix,
                lambda u: experts(u, p, sh, route))
            selections.append(selection)
        streams.block_until_ready()     # a layer at a time
    n = _norm(_read_out(streams)[wanted], params["final_norm"]["scale"],
              sh.eps)
    head = params["lm_head"]["kernel"]
    out = jnp.concatenate(
        [_head_block(n, head[:, at:at + VOCAB_BLOCK])
         for at in range(0, head.shape[1], VOCAB_BLOCK)], -1)
    if details:
        return out, {"selection": selections, "streams": read}
    return out
