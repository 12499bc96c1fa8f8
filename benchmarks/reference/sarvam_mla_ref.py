"""Plain reference for the Sarvam-105B decoder (`model_type: sarvam_mla`):
multi-head latent attention beside routed SwiGLU experts, written from the
published config.json's keys and independent of ray_tpu.models and
ray_tpu.ops. The EXPANDED form only: no cache, no absorbed products, no
kernel, no batching. h = n(x) is a layer's input after its RMSNorm (eps
`rms_norm_eps`), t, u positions, H = `num_attention_heads`:

  attention
     q_i = Wq_i . h = [q_i^nope (qk_nope_head_dim) ; q_i^rope (qk_rope_head_dim)]
     [c~ (kv_lora_rank) ; k~^rope] = Wkva . h
     c = n(c~; g_kv)                      RMSNorm on the compressed latent
     k^rope = R_t k~^rope (ONE for all heads) ; q_i^rope <- R_t q_i^rope
     [k_i^nope ; v_i (v_head_dim)] = Wkvb_i . c
     s_i(t, u) = sigma (q_i^nope(t) . k_i^nope(u) + q_i^rope(t) . k^rope(u)),  u <= t
     sigma = (qk_nope_head_dim + qk_rope_head_dim)^-1/2 m^2
     m = 0.1 mscale_all_dim ln(factor) + 1            (1.3689 as published)
     o = Wo . [ sum_u softmax_u(s_i) v_i(u) ]_i
  R, `rope_scaling.type: deepseek_yarn`, per pair j of the d = 64 rotary
  dimensions (`_inverse_frequencies`, written out here, not imported):
     theta_j = rope_theta^(-2j/d)
     inv_j = theta_j / factor (1 - r_j) + theta_j r_j
     r_j = 1 - clip((j - lo) / (hi - lo), 0, 1)
     lo = floor(D(beta_fast)), hi = ceil(D(beta_slow)), clipped to [0, d - 1]
     D(b) = d ln(original_max_position_embeddings / (2 pi b)) / (2 ln rope_theta)
     cos and sin scaled by yarn(factor, mscale) / yarn(factor, mscale_all_dim)
     (1 as published), yarn(s, a) = 0.1 a ln s + 1
  a pair is lanes (j, j + d/2) of the rotary part
  dense layers (the first `first_k_dense_replace`):
     x <- x + Wd . (silu(Wg . h) * Wu . h)            intermediate_size wide
  expert layers (the others):
     s = sigmoid(Wr . h) in R^E, float32               E = the router's width
     chosen = the k experts with the largest s + bias  (the bias chooses and
                                                       does not weigh)
     w_e = routed_scaling_factor s_e / (sum_chosen s + 1e-20)
     E_e(h) = Wd_e . (silu(Wg_e . h) * Wu_e . h)       moe_intermediate_size wide
     x <- x + sum_chosen w_e E_e(h) + S(h)             S: one shared expert of
                                                       the same form, weight 1
  x = embed[tokens]; logits = lm_head . n(x)           untied

Given `held_experts` = (first, count), the sum over the chosen experts runs
over the held ones only: what the absent experts would have added is left
out, as the program under test leaves it out. The router is never cut.

float32 throughout under jax.default_matmul_precision("highest"). The
expert sum is dense: for every held expert, E_e of EVERY position, times
the position's weight for it (0 where it was not chosen): no sorting, no
grouping, nothing shared with the system's routed layer. Everything that
acts on a position alone (the projections, the experts, the dense layer,
the head) is computed a block of positions and of its width at a time, and
the attention a block of heads and a block of queries at a time, each query
block against every key under the mask, only so that the float32 copies of
a sequence of ~18k positions fit in the ~2.5 GB a serving engine leaves
free.

Several sequences that begin with the same tokens may be given as ONE: the
common beginning (the trunk) once, then each continuation (a branch), with
`branch` [s] naming each token's branch (0 the trunk) and `positions` [s]
its position in its own sequence. Token t attends token u where u stands
no later in the array and is of the trunk or of t's own branch: exactly
the causal attention of each sequence alone, since everything else acts on
a position alone. Without them: one sequence, positions 0 .. s - 1.

Routing is discontinuous, so the reference can be told which experts the
program under test chose (`routes`) and follows them, computing the weights
from its OWN scores of those; it returns its own selection scores beside
(benchmarks/reference/nemotron_h_ref.py does the same, and
harness/parity_nemotron_h.routing_check certifies every departure).

Assumed (the configuration file says the same, with the same one place to
read each otherwise):
  - `use_qk_norm: true` is read as the RMSNorm on the compressed latent c~
    (the family's `kv_a_layernorm`). The config gives no `q_lora_rank`, so
    there is no query latent and no norm on q; a per-head norm on expanded
    keys would not survive a cache of `head_dim` 576 = 512 + 64, which the
    config declares. `_latent` below is the one place to read it
    otherwise.
  - sigmoid scoring, normalised over the chosen (the config says "expert
    bias" and a scaling factor and names no scoring function): `_route`.
  - one routing group; the expert bias is zero as initialised.
Departures: the weights are random (from the seed); the rotary pairs are
lanes (j, j + 32) where the published code pairs (2j, 2j + 1) after a
permutation of its own (a relabelling of Wq's and Wkva's rotary columns).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EXPERT_BLOCK = 2        # float32 experts at once: 3 x 4096 x 2048 x 4 B each
DENSE_BLOCK = 4096      # columns of the dense layer's width at once
VOCAB_BLOCK = 8192
HEAD_BLOCK = 4          # heads whose [queries, s] scores stand at once
QUERY_BLOCK = 128       # queries whose scores against every key stand at once
ROW_BLOCK = 2048        # positions a position-wise part takes at once


class Shape(NamedTuple):
    """The published keys the layers need, hashable (a jit static)."""
    heads: int
    rank: int
    nope: int
    rope: int
    v_dim: int
    eps: float
    theta: float
    factor: float
    original_max: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    experts_per_token: int
    routed_scaling: float
    held: Tuple[int, int]


def shape_of(config: Dict[str, Any]) -> Shape:
    """From a config file's keys (the published names) plus
    `held_experts`; without it every routed expert is held."""
    scaling = config["rope_scaling"]
    if scaling["type"] != "deepseek_yarn":
        raise ValueError(f"rope_scaling type {scaling['type']!r}")
    held = tuple(config.get("held_experts")
                 or (0, config["num_experts"]))
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
        routed_scaling=float(config["routed_scaling_factor"]), held=held)


def _yarn(scale: float, a: float) -> float:
    return 0.1 * a * math.log(scale) + 1.0 if scale > 1 else 1.0


def _inverse_frequencies(sh: Shape) -> np.ndarray:
    d = sh.rope
    j = np.arange(d // 2, dtype=np.float64)
    theta = sh.theta ** (-2.0 * j / d)

    def pair(turns):
        return d * math.log(sh.original_max / (2 * math.pi * turns)) \
            / (2 * math.log(sh.theta))

    lo = max(math.floor(pair(sh.beta_fast)), 0)
    hi = min(math.ceil(pair(sh.beta_slow)), d - 1)
    if lo == hi:
        hi += 0.001
    r = 1.0 - np.clip((j - lo) / (hi - lo), 0.0, 1.0)
    return theta / sh.factor * (1.0 - r) + theta * r


def rotary_table(sh: Shape, positions) -> Tuple[Any, Any]:
    """cos, sin [s, d/2] at `positions` [s], float32."""
    angles = jnp.asarray(positions, F32)[:, None] \
        * jnp.asarray(_inverse_frequencies(sh), F32)
    scale = _yarn(sh.factor, sh.mscale) / _yarn(sh.factor, sh.mscale_all_dim)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rotate(x, cos, sin):
    """x [s, ..., d] with cos, sin [s, d/2] broadcast over the middle."""
    half = x.shape[-1] // 2
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _row_blocks(part, *per_row):
    """`part(*per_row)` -> a tuple of arrays, a block of positions at a
    time (it acts on each position alone), the blocks' results joined.
    `per_row`: arrays whose rows are positions, or None."""
    size = next(a.shape[0] for a in per_row if a is not None)
    parts = []
    for at in range(0, size, ROW_BLOCK):
        parts.append(part(*(None if a is None else a[at:at + ROW_BLOCK]
                            for a in per_row)))
        # (what a call returns is allocated when the call is queued:
        # wait, so that one block's temporaries stand at a time)
        jax.block_until_ready(parts[-1])
    return tuple(jnp.concatenate(column) for column in zip(*parts))


@functools.partial(jax.jit, static_argnames=("sh",))
def _latent(x, p, positions, *, sh: Shape):
    """The latent c [s, rank] and the rotated shared key k^rope [s, rope]
    of every position."""
    with jax.default_matmul_precision("highest"):
        h = _norm(x, p["attn_norm"]["scale"], sh.eps)
        kva = h @ p["attn"]["kv_a_proj"]["kernel"].astype(F32)
        c = _norm(kva[:, :sh.rank], p["attn"]["kv_a_norm"]["scale"], sh.eps)
        k_rope = _rotate(kva[:, sh.rank:], *rotary_table(sh, positions))
        return c, k_rope


@functools.partial(jax.jit, static_argnames=("sh",))
def _expand(x, c, scale, w_q, w_kvb, positions, *, sh: Shape):
    """A block of heads: the queries [s, heads, nope + rope] of h = n(x;
    scale) (the rotary part turned) and, expanded from the latent, the
    keys' nope parts and the values [s, heads, nope], [s, heads,
    v_head_dim]."""
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("sd,dhk->shk", _norm(x, scale, sh.eps),
                       w_q.astype(F32))
        q = jnp.concatenate(
            [q[..., :sh.nope],
             _rotate(q[..., sh.nope:], *rotary_table(sh, positions))], -1)
        kv = jnp.einsum("sr,rhk->shk", c, w_kvb.astype(F32))
        return q, kv[..., :sh.nope], kv[..., sh.nope:]


@functools.partial(jax.jit, static_argnames=("sh",))
def _attended(q, k_nope, v, k_rope, branch, rows, *, sh: Shape):
    """What the softmax gives, in front of Wo, for a block of heads at the
    queries `rows` (indices into the array): [rows, heads, v_head_dim]."""
    with jax.default_matmul_precision("highest"):
        m = _yarn(sh.factor, sh.mscale_all_dim)
        sigma = (sh.nope + sh.rope) ** -0.5 * m * m
        scores = sigma * (
            jnp.einsum("qhk,thk->hqt", q[rows][..., :sh.nope], k_nope)
            + jnp.einsum("qhk,tk->hqt", q[rows][..., sh.nope:], k_rope))
        own = branch[rows][:, None]
        seen = (rows[:, None] >= jnp.arange(q.shape[0])[None, :]) & (
            (branch[None, :] == 0) | (branch[None, :] == own))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hqt,thk->qhk", probs, v)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_projected(x, attended, w_o, first):
    """x with Wo . attended added to its rows from `first` on, in place."""
    with jax.default_matmul_precision("highest"):
        out = jnp.einsum("qhk,hkd->qd", attended, w_o.astype(F32))
        rows = jax.lax.dynamic_slice_in_dim(x, first, out.shape[0])
        return jax.lax.dynamic_update_slice_in_dim(x, rows + out, first, 0)


def attention_layer(x, p, positions, branch, sh: Shape, rows=None):
    """x + MLA(n(x)), a block of heads and a block of queries at a time,
    added into x's own buffer (the caller's `x` is consumed). With `rows`
    (indices), also the latent rows `[c ; k_rope]` [s, rank + rope] and
    what the softmax gave at `rows` [rows, heads, v_head_dim]."""
    c, k_rope = _row_blocks(
        lambda x, positions: _latent(x, p, positions, sh=sh), x, positions)
    a = p["attn"]
    # the layer's input, which every block of heads projects its queries
    # from while x itself takes the blocks' outputs
    before = x + 0.0
    everyone = jnp.arange(x.shape[0])
    kept = []
    for at in range(0, sh.heads, HEAD_BLOCK):
        heads = slice(at, min(at + HEAD_BLOCK, sh.heads))
        q, k_nope, v = _row_blocks(
            lambda x, c, positions: _expand(
                x, c, p["attn_norm"]["scale"],
                a["q_proj"]["kernel"][:, heads],
                a["kv_b_proj"][:, heads], positions, sh=sh),
            before, c, positions)
        attend = lambda queries: _attended(  # noqa: E731
            q, k_nope, v, k_rope, branch, queries, sh=sh)
        for first in range(0, x.shape[0], QUERY_BLOCK):
            x = _add_projected(
                x, attend(everyone[first:first + QUERY_BLOCK]),
                a["o_proj"]["kernel"][heads], first)
        # (the device allocates what a call returns when the call is
        # queued: wait, so that one block of heads stands at a time)
        x.block_until_ready()
        if rows is not None:
            kept.append(jnp.concatenate([
                attend(rows[first:first + QUERY_BLOCK])
                for first in range(0, rows.shape[0], QUERY_BLOCK)]))
    if rows is None:
        return x
    return x, jnp.concatenate([c, k_rope], -1), jnp.concatenate(kept, 1)


@functools.partial(jax.jit, donate_argnums=(0,))
def _swiglu_block(out, h, w_gate, w_up, w_down):
    """out + a block of the width's part of the sum, in out's buffer."""
    with jax.default_matmul_precision("highest"):
        return out + (jax.nn.silu(h @ w_gate.astype(F32))
                      * (h @ w_up.astype(F32))) @ w_down.astype(F32)


def _swiglu(h, m, block: int):
    """Wd . (silu(Wg . h) * Wu . h), `block` columns of the width at once
    (the sum over the width splits)."""
    gate, up, down = (m[k]["kernel"] for k in
                      ("gate_proj", "up_proj", "down_proj"))
    out = jnp.zeros((h.shape[0], down.shape[1]), F32)
    for at in range(0, gate.shape[1], block):
        out = _swiglu_block(out, h, gate[:, at:at + block],
                            up[:, at:at + block], down[at:at + block])
    return out


@functools.partial(jax.jit, static_argnames=("sh",))
def _route(x, p, routes, *, sh: Shape):
    """The expert layer's input h, the selection scores s + bias [s, E]
    and each position's weight for each expert [s, E] (0 where not
    chosen): over the reference's own top-k, or over `routes` [s, k]."""
    with jax.default_matmul_precision("highest"):
        h = _norm(x, p["mlp_norm"]["scale"], sh.eps)
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


@functools.partial(jax.jit, donate_argnums=(0,))
def _expert_block(out, h, weights, w_gate, w_up, w_down):
    """out + the sum over a block of experts of weight[s, e] * E_e(h[s]),
    in out's buffer."""
    with jax.default_matmul_precision("highest"):
        hidden = jax.nn.silu(jnp.einsum("sd,edf->esf", h, w_gate.astype(F32))) \
            * jnp.einsum("sd,edf->esf", h, w_up.astype(F32))
        each = jnp.einsum("esf,efd->esd", hidden, w_down.astype(F32))
        return out + jnp.einsum("se,esd->sd", weights, each)


def expert_layer(x, p, sh: Shape, routes=None, shared: bool = True):
    """x + routed + shared for one expert layer on x [s, hidden], and the
    selection scores [s, E]. `shared=False` leaves the shared expert out
    (a test adds the shares of several chips and counts it once)."""
    return _row_blocks(
        lambda x, routes: _expert_rows(x, p, sh, routes, shared), x, routes)


def _expert_rows(x, p, sh: Shape, routes, shared: bool):
    h, selection, weights = _route(x, p, routes, sh=sh)
    first, count = sh.held
    experts = p["moe"]["routed"]
    routed = jnp.zeros_like(x)
    for at in range(0, count, EXPERT_BLOCK):
        upto = min(at + EXPERT_BLOCK, count)
        routed = _expert_block(
            routed, h, weights[:, first + at:first + upto],
            experts["w_gate"][at:upto], experts["w_in"][at:upto],
            experts["w_out"][at:upto])
    x = x + routed
    if shared:
        x = x + _swiglu(h, p["moe"]["shared"], DENSE_BLOCK)
    return x, selection


def dense_layer(x, p, sh: Shape):
    return _row_blocks(lambda x: (x + _swiglu(
        _norm(x, p["mlp_norm"]["scale"], sh.eps), p["mlp"], DENSE_BLOCK),),
        x)[0]


@jax.jit
def _head_block(n, lm_head):
    with jax.default_matmul_precision("highest"):
        return n @ lm_head.astype(F32)


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any], *,
           positions=None, branch=None, embed_scale=None,
           routes: Optional[list] = None, rows=None, details=None):
    """tokens [s] -> logits [s, vocab], float32; `config` holds the
    published keys (`num_hidden_layers`, `first_k_dense_replace`,
    `rope_scaling` among them) and optionally `held_experts`.
    `positions`, `branch` [s]: several sequences with a common beginning
    as one array (the module's docstring); one sequence without them.
    `embed_scale` [s, hidden] multiplies the embedded tokens (the parity
    check's wobble). `routes`: per expert layer, [s, k] expert ids to
    follow in place of the reference's own top-k. `rows`: the indices
    whose logits are wanted (all). `details`: the layers (a tuple, maybe
    empty) of which to return more; then (logits, {"selection": per expert
    layer the scores [s, E] the experts were ranked by, "latent": {layer:
    the rows `[c ; k_rope]` a cache of that layer must hold [s, rank +
    rope]}, "attended": {layer: what its softmax gave in front of Wo at
    `rows` [rows, heads, v_head_dim]}})."""
    sh = shape_of(config)
    tokens = jnp.asarray(tokens, jnp.int32)
    everyone = jnp.arange(tokens.shape[0])
    positions = everyone if positions is None \
        else jnp.asarray(positions, jnp.int32)
    branch = jnp.zeros_like(everyone) if branch is None \
        else jnp.asarray(branch, jnp.int32)
    wanted = everyone if rows is None else jnp.asarray(rows, jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    if embed_scale is not None:
        x = x * embed_scale
    selections, latent, attended = [], {}, {}
    for i in range(config["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        if details and i in details:
            x, latent[i], attended[i] = attention_layer(
                x, p, positions, branch, sh, wanted)
        else:
            x = attention_layer(x, p, positions, branch, sh)
        if i < config["first_k_dense_replace"]:
            x = dense_layer(x, p, sh)
        else:
            route = None if routes is None \
                else jnp.asarray(routes[len(selections)], jnp.int32)
            x, selection = expert_layer(x, p, sh, route)
            selections.append(selection)
        x.block_until_ready()       # a layer at a time (as above)
    n = _norm(x[wanted], params["final_norm"]["scale"], sh.eps)
    head = params["lm_head"]["kernel"]
    out = jnp.concatenate(
        [_head_block(n, head[:, at:at + VOCAB_BLOCK])
         for at in range(0, head.shape[1], VOCAB_BLOCK)], -1)
    if details is not None:
        return out, {"selection": selections, "latent": latent,
                     "attended": attended}
    return out
