"""Plain reference for the Falcon-H1 block (a Mamba-2 mixer beside
attention), written from the published config.json's keys and independent
of ray_tpu.models and ray_tpu.ops:

  x = embedding_multiplier * embed[tokens]
  per layer:
    u = n1(x)
    a = attention_out_multiplier * Wo . attention(rope(Wq . v), rope(
          key_multiplier * Wk . v), Wv . v),    v = attention_in_multiplier * u
    p = ((ssm_in_multiplier * u) . Win) * m     m = ssm_multipliers[0..4] over
                                                the z | x | B | C | dt spans
    xBC = silu(conv(p[x,B,C]) + bias)           causal, depthwise, d_conv taps
    S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t  D_t = softplus(dt_t + dt_bias)
    y_t = S_t C_t + D_skip * x_t                A = -exp(A_log), head h reads
                                                the B, C of group h // (H / G)
    s = ssm_out_multiplier * Wout . (w * n_group(y * silu(z)))
    h = x + s + a
    x = h + down_multiplier * Wdown . (silu(gate_multiplier * Wgate . n2(h))
                                       * (Wup . n2(h)))
  logits = lm_head_multiplier * (lm_head . norm(x))

RMSNorm: x / sqrt(mean(x^2) + eps) * scale; `n_group` the same over each of
the `mamba_n_groups` slices of the mixer's width (gate first, then norm:
`mamba_norm_before_gate` false). RoPE: pairs (i, i + d/2) of a head rotate
by position * theta^(-2i/d). Attention: causal softmax(q . k / sqrt(d)) . v,
each kv head shared by heads / kv_heads query heads.

float32 throughout under jax.default_matmul_precision("highest"). The
recurrence is a `lax.scan` over single tokens: it shares no algorithm with
the system's chunked scan. No kernel, no cache, no batching; the MLP and the
head are computed in blocks (over the intermediate width, over the
vocabulary) only so that the float32 copies of their weights fit beside a
serving engine's 15 GB. It takes the very weights under test and upcasts
each at its use.

Departures from the published model: none in the mathematics. The weights
are random (from the seed), including those that shape the recurrence
(A_log, dt_bias, D), which the comparison does not care about; the
convolution's kernel is stored [taps, channels] (published: [channels, 1,
taps]), a transpose.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
MLP_BLOCKS = 4
VOCAB_BLOCK = 16384


class Shape(NamedTuple):
    """The published keys the layer needs, hashable (a jit static)."""
    heads: int
    kv_heads: int
    head_dim: int
    theta: float
    eps: float
    d_ssm: int
    ssm_heads: int
    d_state: int
    groups: int
    d_conv: int
    attention_in: float
    attention_out: float
    key: float
    ssm_in: float
    ssm_out: float
    ssm: Tuple[float, ...]
    mlp: Tuple[float, float]


def shape_of(config: Dict[str, Any]) -> Shape:
    """From a config file's keys (the published names)."""
    return Shape(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        d_ssm=config["mamba_d_ssm"], ssm_heads=config["mamba_n_heads"],
        d_state=config["mamba_d_state"], groups=config["mamba_n_groups"],
        d_conv=config["mamba_d_conv"],
        attention_in=float(config["attention_in_multiplier"]),
        attention_out=float(config["attention_out_multiplier"]),
        key=float(config["key_multiplier"]),
        ssm_in=float(config["ssm_in_multiplier"]),
        ssm_out=float(config["ssm_out_multiplier"]),
        ssm=tuple(float(m) for m in config["ssm_multipliers"]),
        mlp=tuple(float(m) for m in config["mlp_multipliers"]))


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, positions, theta):
    # x: [s, heads, d]; positions: [s]
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = positions.astype(F32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], -1)


def _attention(u, p, sh: Shape):
    s = u.shape[0]
    v_in = u * sh.attention_in
    q = jnp.einsum("sd,dhk->shk", v_in, p["q_proj"]["kernel"].astype(F32))
    k = jnp.einsum("sd,dhk->shk", v_in, p["k_proj"]["kernel"].astype(F32)) \
        * sh.key
    v = jnp.einsum("sd,dhk->shk", v_in, p["v_proj"]["kernel"].astype(F32))
    positions = jnp.arange(s)
    q, k = _rope(q, positions, sh.theta), _rope(k, positions, sh.theta)
    k = jnp.repeat(k, sh.heads // sh.kv_heads, axis=1)
    v = jnp.repeat(v, sh.heads // sh.kv_heads, axis=1)
    scores = jnp.einsum("qhk,thk->hqt", q, k) * sh.head_dim ** -0.5
    causal = positions[:, None] >= positions[None, :]
    o = jnp.einsum("hqt,thk->qhk",
                   jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1), v)
    return jnp.einsum("qhk,hkd->qd", o, p["o_proj"]["kernel"].astype(F32)) \
        * sh.attention_out


def _mixer(u, p, sh: Shape):
    s = u.shape[0]
    heads, n, groups = sh.ssm_heads, sh.d_state, sh.groups
    head_dim = sh.d_ssm // heads
    spans = (sh.d_ssm, sh.d_ssm, groups * n, groups * n, heads)
    m = jnp.concatenate([jnp.full((w,), mult, F32)
                         for w, mult in zip(spans, sh.ssm)])
    proj = ((u * sh.ssm_in) @ p["in_proj"]["kernel"].astype(F32)) * m
    conv_dim = sh.d_ssm + 2 * groups * n
    z = proj[:, :sh.d_ssm]
    xbc = proj[:, sh.d_ssm:sh.d_ssm + conv_dim]
    dt = jax.nn.softplus(proj[:, sh.d_ssm + conv_dim:]
                         + p["dt_bias"].astype(F32))            # [s, heads]
    # causal depthwise convolution: output t sees inputs t-taps+1 .. t
    w = p["conv_kernel"].astype(F32)                             # [taps, c]
    padded = jnp.concatenate(
        [jnp.zeros((sh.d_conv - 1, conv_dim), F32), xbc])
    window = padded[s:]                 # what the next token's taps reach
    xbc = jax.nn.silu(sum(padded[j:j + s] * w[j] for j in range(sh.d_conv))
                      + p["conv_bias"].astype(F32))
    x = xbc[:, :sh.d_ssm].reshape(s, heads, head_dim)
    b = xbc[:, sh.d_ssm:sh.d_ssm + groups * n].reshape(s, groups, n)
    c = xbc[:, sh.d_ssm + groups * n:].reshape(s, groups, n)
    a = -jnp.exp(p["A_log"].astype(F32))                          # [heads]
    group_of = jnp.arange(heads) // (heads // groups)

    def token(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[group_of][:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t[group_of])

    state, y = jax.lax.scan(token, jnp.zeros((heads, head_dim, n), F32),
                            (x, b, c, dt))
    y = y + p["D"].astype(F32)[:, None] * x
    y = y.reshape(s, sh.d_ssm) * jax.nn.silu(z)
    y = y.reshape(s, groups, sh.d_ssm // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + sh.eps)
    y = y.reshape(s, sh.d_ssm) * p["norm_scale"].astype(F32)
    return (y @ p["out_proj"]["kernel"].astype(F32)) * sh.ssm_out, \
        (window, state)


@functools.partial(jax.jit, static_argnames=("sh",))
def mixing(x, p, *, sh: Shape):
    """The block's first half on x [s, hidden]: returns h = x + mixer +
    attention, n2(h), the MLP's input, and what the mixer would carry to
    a next token (`states`)."""
    with jax.default_matmul_precision("highest"):
        u = _norm(x, p["input_norm"]["scale"], sh.eps)
        mixed, carried = _mixer(u, p["mamba"], sh)
        h = x + mixed + _attention(u, p["attn"], sh)
        return h, _norm(h, p["mlp_norm"]["scale"], sh.eps), carried


@functools.partial(jax.jit, static_argnames=("gate_m",))
def _mlp_block(n, gate, up, down, *, gate_m: float):
    """One slice of the intermediate width's share of W_down(...)."""
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu((n @ gate.astype(F32)) * gate_m)
                * (n @ up.astype(F32))) @ down.astype(F32)


def layer(x, p, sh: Shape):
    """One block on x [s, hidden] (float32): its output and the mixer's
    carried (window, state)."""
    h, n, carried = mixing(x, {k: v for k, v in p.items() if k != "mlp"},
                           sh=sh)
    m = p["mlp"]
    width = m["gate_proj"]["kernel"].shape[1]
    step = -(-width // MLP_BLOCKS)
    out = 0.0
    for at in range(0, width, step):
        out = out + _mlp_block(
            n, m["gate_proj"]["kernel"][:, at:at + step],
            m["up_proj"]["kernel"][:, at:at + step],
            m["down_proj"]["kernel"][at:at + step], gate_m=sh.mlp[0])
    return h + out * sh.mlp[1], carried


@jax.jit
def _head_block(n, lm_head):
    with jax.default_matmul_precision("highest"):
        return n @ lm_head.astype(F32)


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any], *,
           num_layers: int, embed_scale=None, states: bool = False):
    """tokens [s] -> logits [s, vocab], float32. `embed_scale` [s, hidden]
    multiplies the embedded tokens: the parity check wobbles them by a bf16
    rounding's worth to find the positions whose logits a rounding moves
    far (harness/parity.py). With `states`, also what each layer's mixer
    holds after the last token, as a serving row would carry it: per layer
    (window [d_conv - 1, channels], the convolution's last inputs, and S
    [heads, head dim, d_state])."""
    sh = shape_of(config)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32) \
        * float(config["embedding_multiplier"])
    if embed_scale is not None:
        x = x * embed_scale
    carried = []
    for i in range(num_layers):
        x, after = layer(x, params[f"layer_{i}"], sh)
        carried.append(after)
    n = _norm(x, params["final_norm"]["scale"], sh.eps)
    head = params["lm_head"]["kernel"]
    out = [_head_block(n, head[:, at:at + VOCAB_BLOCK])
           for at in range(0, head.shape[1], VOCAB_BLOCK)]
    out = jnp.concatenate(out, -1) * float(config["lm_head_multiplier"])
    return (out, carried) if states else out
