"""Plain reference for the Nemotron-H decoder with latent experts, written
from the published config.json's keys and independent of ray_tpu.models and
ray_tpu.ops. Every block is x <- x + mixer(n(x)) with ONE mixer, its kind
the layer's character in `hybrid_override_pattern`:

  M  p = Win . u, split z | xBC | dt        widths d_ssm | d_ssm + 2 G N | H
     xBC = silu(conv(xBC) + bias)           causal, depthwise, conv_kernel taps
     S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t   D_t = softplus(dt_t + dt_bias)
     y_t = S_t C_t + D_skip * x_t           A = -exp(A_log); head h reads the
                                            B, C of group h // (H / G)
     out = Wout . (w * n_group(y * silu(z)))  gate, then RMSNorm within each
                                            of the G groups
  *  q, k, v = Wq . u, Wk . u, Wv . u; causal softmax(q . k / sqrt(d)) . v,
     each kv head shared by heads / kv_heads query heads; Wo. NO positional
     embedding: the family's published description ("no position
     embeddings", arXiv:2504.03624, the architecture section) and forward
     pass. The config does carry `rope_theta` and `partial_rotary_factor`;
     THIS is the one place it could be read otherwise (a rotation of q and
     k here, by falcon_h1_ref._rope).
  E  s = sigmoid(Wg . u) in R^E             the router, on the full width
     chosen = the k experts with the largest s + e_score_correction_bias
     w_i = routed_scaling_factor * s_i / (sum_chosen s + 1e-20)
     u_l = Wdown . u                        hidden -> moe_latent_size
     E_i(u_l) = W2_i . relu(W1_i . u_l)^2   not gated
     out = Wup . (sum_chosen w_i E_i(u_l)) + W2s . relu(W1s . u)^2

  x = embed[tokens]; logits = lm_head . n(x_last)

Given `held_experts` = (first, count), the sum over the chosen experts runs
over the held ones only: what the absent experts would have added is left
out, as the program under test leaves it out. The router is never cut.

float32 throughout under jax.default_matmul_precision("highest"). The
recurrence is a `lax.scan` over single tokens; the expert sum is dense: for
every held expert, E_i of EVERY position, times the position's weight for
it (0 where it was not chosen) -- no sorting, no grouping, nothing shared
with the system's routed layer. No kernel, no cache, no batching; the
experts and the head are computed in blocks only so that the float32 copies
of their weights fit beside a serving engine.

Routing is discontinuous: a rounding's worth of difference in a score near
the cut between the k-th and the (k+1)-th expert swaps them. So the
reference can be told which experts the program under test chose (`routes`)
and follows them, computing the weights from its OWN scores of those; it
returns its own selection scores beside, so the caller can certify that
every expert taken or left against the reference's own order lies within a
rounding of the cut (harness/parity_nemotron_h.py). This is what feeding
the program's greedy tokens already does for the other discrete choice.

Departures from the published model: the multi-token-prediction module
(`num_nextn_predict_layers`: 1) is a draft head for speculative decoding
and is not part of the forward pass here; the weights are random (from the
seed), including those that shape the recurrence; the convolution's kernel
is stored [taps, channels] (published: [channels, 1, taps]), a transpose.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXPERT_BLOCK = 16
VOCAB_BLOCK = 16384


class Shape(NamedTuple):
    """The published keys the layers need, hashable (a jit static)."""
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    groups: int
    d_conv: int
    experts_per_token: int
    routed_scaling: float
    held: Tuple[int, int]


def shape_of(config: Dict[str, Any]) -> Shape:
    """From a config file's keys (the published names) plus
    `held_experts`; without it every routed expert is held."""
    held = tuple(config.get("held_experts")
                 or (0, config["n_routed_experts"]))
    return Shape(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        eps=float(config["layer_norm_epsilon"]),
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        d_state=config["ssm_state_size"], groups=config["n_groups"],
        d_conv=config["conv_kernel"],
        experts_per_token=config["num_experts_per_tok"],
        routed_scaling=float(config["routed_scaling_factor"]), held=held)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _relu2(h):
    return jnp.square(jnp.maximum(h, 0.0))


def _attention(u, p, sh: Shape):
    s = u.shape[0]
    q = jnp.einsum("sd,dhk->shk", u, p["q_proj"]["kernel"].astype(F32))
    k = jnp.einsum("sd,dhk->shk", u, p["k_proj"]["kernel"].astype(F32))
    v = jnp.einsum("sd,dhk->shk", u, p["v_proj"]["kernel"].astype(F32))
    # no rotation of q and k: see the module's docstring
    k = jnp.repeat(k, sh.heads // sh.kv_heads, axis=1)
    v = jnp.repeat(v, sh.heads // sh.kv_heads, axis=1)
    scores = jnp.einsum("qhk,thk->hqt", q, k) * sh.head_dim ** -0.5
    positions = jnp.arange(s)
    causal = positions[:, None] >= positions[None, :]
    o = jnp.einsum("hqt,thk->qhk",
                   jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1), v)
    return jnp.einsum("qhk,hkd->qd", o, p["o_proj"]["kernel"].astype(F32))


def _mixer(u, p, sh: Shape):
    s = u.shape[0]
    heads, n, groups = sh.ssm_heads, sh.d_state, sh.groups
    d_ssm = heads * sh.ssm_head_dim
    conv_dim = d_ssm + 2 * groups * n
    proj = u @ p["in_proj"]["kernel"].astype(F32)
    z = proj[:, :d_ssm]
    xbc = proj[:, d_ssm:d_ssm + conv_dim]
    dt = jax.nn.softplus(proj[:, d_ssm + conv_dim:]
                         + p["dt_bias"].astype(F32))            # [s, heads]
    # causal depthwise convolution: output t sees inputs t-taps+1 .. t
    w = p["conv_kernel"].astype(F32)                             # [taps, c]
    padded = jnp.concatenate([jnp.zeros((sh.d_conv - 1, conv_dim), F32), xbc])
    window = padded[s:]                 # what the next token's taps reach
    xbc = jax.nn.silu(sum(padded[j:j + s] * w[j] for j in range(sh.d_conv))
                      + p["conv_bias"].astype(F32))
    x = xbc[:, :d_ssm].reshape(s, heads, sh.ssm_head_dim)
    b = xbc[:, d_ssm:d_ssm + groups * n].reshape(s, groups, n)
    c = xbc[:, d_ssm + groups * n:].reshape(s, groups, n)
    a = -jnp.exp(p["A_log"].astype(F32))                          # [heads]
    group_of = jnp.arange(heads) // (heads // groups)

    def token(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[group_of][:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t[group_of])

    state, y = jax.lax.scan(
        token, jnp.zeros((heads, sh.ssm_head_dim, n), F32), (x, b, c, dt))
    y = y + p["D"].astype(F32)[:, None] * x
    y = y.reshape(s, d_ssm) * jax.nn.silu(z)
    y = y.reshape(s, groups, d_ssm // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + sh.eps)
    y = y.reshape(s, d_ssm) * p["norm_scale"].astype(F32)
    return y @ p["out_proj"]["kernel"].astype(F32), (window, state)


@functools.partial(jax.jit, static_argnames=("sh",))
def mamba_layer(x, p, *, sh: Shape):
    with jax.default_matmul_precision("highest"):
        mixed, carried = _mixer(_norm(x, p["norm"]["scale"], sh.eps),
                                p["mamba"], sh)
        return x + mixed, carried


@functools.partial(jax.jit, static_argnames=("sh",))
def attention_layer(x, p, *, sh: Shape):
    with jax.default_matmul_precision("highest"):
        return x + _attention(_norm(x, p["norm"]["scale"], sh.eps),
                              p["attn"], sh)


@functools.partial(jax.jit, static_argnames=("sh",))
def _route(x, p, routes, *, sh: Shape):
    """The E layer's input u, its latent projection, the selection scores
    s + bias [s, E] and each position's weight for each expert [s, E] (0
    where not chosen): over the reference's own top-k, or over `routes`
    [s, k] where given."""
    with jax.default_matmul_precision("highest"):
        u = _norm(x, p["norm"]["scale"], sh.eps)
        m = p["moe"]
        scores = jax.nn.sigmoid(u @ m["routed"]["router"].astype(F32))
        selection = scores + m["routed"]["e_score_correction_bias"] \
            .astype(F32)
        if routes is None:
            _, routes = jax.lax.top_k(selection, sh.experts_per_token)
        chosen = jnp.zeros(scores.shape, bool).at[
            jnp.arange(scores.shape[0])[:, None], routes].set(True)
        picked = jnp.where(chosen, scores, 0.0)
        weights = sh.routed_scaling * picked \
            / (picked.sum(-1, keepdims=True) + 1e-20)
        latent = u @ m["latent_down"]["kernel"].astype(F32)
        return u, latent, selection, weights


@jax.jit
def _expert_block(latent, weights, w_in, w_out):
    """sum over a block of experts of weight[s, e] * E_e(latent[s])."""
    with jax.default_matmul_precision("highest"):
        hidden = _relu2(jnp.einsum("sl,elf->esf", latent, w_in.astype(F32)))
        out = jnp.einsum("esf,efl->esl", hidden, w_out.astype(F32))
        return jnp.einsum("se,esl->sl", weights, out)


@jax.jit
def _moe_close(x, u, routed, m):
    with jax.default_matmul_precision("highest"):
        shared = _relu2(u @ m["shared_up"]["kernel"].astype(F32)) \
            @ m["shared_down"]["kernel"].astype(F32)
        return x + routed @ m["latent_up"]["kernel"].astype(F32) + shared


def moe_layer(x, p, sh: Shape, routes=None):
    """One E layer on x [s, hidden]: its output and the selection scores
    [s, E] it ranked the experts by."""
    u, latent, selection, weights = _route(x, p, routes, sh=sh)
    first, count = sh.held
    experts = p["moe"]["routed"]
    routed = jnp.zeros_like(latent)
    for at in range(0, count, EXPERT_BLOCK):
        upto = min(at + EXPERT_BLOCK, count)
        routed = routed + _expert_block(
            latent, weights[:, first + at:first + upto],
            experts["w_in"][at:upto], experts["w_out"][at:upto])
    return _moe_close(x, u, routed, p["moe"]), selection


@jax.jit
def _head_block(n, lm_head):
    with jax.default_matmul_precision("highest"):
        return n @ lm_head.astype(F32)


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any], *,
           embed_scale=None, routes: Optional[list] = None,
           details: bool = False):
    """tokens [s] -> logits [s, vocab], float32; `config` holds the
    published keys (`hybrid_override_pattern` among them) and optionally
    `held_experts`. `embed_scale` [s, hidden] multiplies the embedded
    tokens (the parity check's wobble). `routes`: per E layer, [s, k]
    expert ids to follow in place of the reference's own top-k. With
    `details`, also {"states": per M layer (window [taps - 1, channels],
    S [heads, head dim, d_state]) after the last token, "selection": per
    E layer the scores [s, E] the experts were ranked by}."""
    sh = shape_of(config)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    if embed_scale is not None:
        x = x * embed_scale
    states, selections = [], []
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        p = params[f"layer_{i}"]
        if kind == "M":
            x, carried = mamba_layer(x, p, sh=sh)
            states.append(carried)
        elif kind == "*":
            x = attention_layer(x, p, sh=sh)
        elif kind == "E":
            route = None if routes is None \
                else jnp.asarray(routes[len(selections)], jnp.int32)
            x, selection = moe_layer(x, p, sh, route)
            selections.append(selection)
        else:
            raise ValueError(f"layer kind {kind!r} is not M, * or E")
    n = _norm(x, params["final_norm"]["scale"], sh.eps)
    head = params["lm_head"]["kernel"]
    out = jnp.concatenate(
        [_head_block(n, head[:, at:at + VOCAB_BLOCK])
         for at in range(0, head.shape[1], VOCAB_BLOCK)], -1)
    if details:
        return out, {"states": states, "selection": selections}
    return out
