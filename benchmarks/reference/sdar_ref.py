"""Plain reference for SDAR-30B-A3B-Chat (`model_type: sdar_moe`): a
Qwen3-MoE decoder that generates by diffusion over blocks. Written from the
published config.json's keys and independent of ray_tpu.models, ray_tpu.ops
and ray_tpu.llm: no cache, no kernel, no batching; every forward is the FULL
forward over all of a row's tokens, the attention expanded (every query
against every key) under the block mask.

  x_0 = embed[token]                      (`mask_token_id` at a masked place)
  layer l (every layer: decoder_sparse_step 1, mlp_only_layers []):
    u = n(x; attn_norm)
    q, k, v = W_q u, W_k u, W_v u         32 / 4 / 4 heads x 128, no bias
    q_h = rot(n(q_h; g_q)), k_h = rot(n(k_h; g_k))          `_head_norms`
    y = W_o concat_h softmax(q_h k_{h/8}^T / sqrt(128) + M) v_{h/8}
        M: position p sees r iff r < (p // L + 1) L          `_mask`
    x = x + y
    f = n(x; mlp_norm)
    p = softmax(W_r f) over all 128; the 8 largest, renormalised to sum
        to 1                                                  `_route`
    x = x + sum_{e chosen, e held} w_e W_down,e (silu(W_gate,e f) * W_up,e f)
  logits = n(x; final_norm) W_head        untied; logits at i are FOR i:
                                          no shift              `head`

n(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g. float32 throughout (unless
told another `dtype` or `bits8`: the parity check's lower-precision
controls) under jax.default_matmul_precision("highest"), a LAYER at a time,
the experts a
block of them at a time and the head a block of the vocabulary at a time
(weights are cast to float32 inside the jitted call that multiplies them: a
layer's experts are 2.4 GB in float32, which do not fit beside a serving
engine). Routing is discontinuous, so the reference can be told which
experts the program under test chose (`routes`) and follows them, computing
the weights from its OWN probabilities of those; it returns its own
probabilities beside. The expert sum is dense over the held experts: no
sorting, no grouping, nothing shared with the system's routed layer.

The generator (`generate`; the family's public generate.py as far as it can
be stated without the file). The prompt's whole blocks stand as they are;
its last `len % L` tokens open the first answer block as fixed tokens, the
rest of that block and every later block start as `mask_token_id`. A block
takes denoising forwards over the committed tokens and its L ids; from the
logits at its masked positions a candidate and its confidence
(`candidates`); `static_rule` fixes the k_t most confident masked positions
(`unmask_counts`), `dynamic_rule` every masked position whose confidence
passes the threshold where those are at least k_t, else the k_t most
confident. When no mask is left the block is forwarded once more (the
commit; without a cache it computes nothing new, and the loop keeps it so
that its forwards are the published ones), and the next block opens.

Assumed (the configuration file says the same; the function named is the one
place to read each otherwise):
  - `_mask`: block length 4; the prefill is block-causal too (the prompt's
    whole blocks attend both ways inside a block). `causal_inside=True` is
    the parity check's control.
  - `_head_norms`: Qwen3's q_norm / k_norm (the config has no key for
    them), BEFORE the rotary map, whose pairs are lanes (j, j + 64).
  - `head`: no shift between a position and its logits.
  - `candidates`: greedy; the mask id is never a candidate; the confidence
    is the candidate's softmax probability among the other ids.
  - `static_rule` / `dynamic_rule` / `unmask_counts`: as above; ties go to
    the earlier position.
  - `generate`: `mask_token_id` 151,669; the commit forward.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
VOCAB_BLOCK = 8192
QUERY_BLOCK = 256       # queries whose scores against every key stand at once
EXPERT_BLOCK = 8        # float32 experts at once: 3 x 2048 x 768 x 4 B each


class Shape(NamedTuple):
    """The published keys the layers need, hashable (a jit static)."""
    heads: int
    kv_heads: int
    theta: float
    eps: float
    experts_per_token: int
    held: Tuple[int, int]
    block_length: int
    # the parity check's controls: a causal mask inside a block; every
    # product's operands rounded to 8 bits (`_r`)
    causal_inside: bool = False
    bits8: bool = False


def shape_of(config: Dict[str, Any]) -> Shape:
    """From a config file's keys (the published names) plus
    `held_experts` and `block_length`; without the first every routed
    expert is held."""
    held = tuple(config.get("held_experts") or (0, config["num_experts"]))
    return Shape(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        experts_per_token=config["num_experts_per_tok"], held=held,
        block_length=int(config["block_length"]))


def _r(a, sh: Shape):
    """`a` as an operand of a product: as it is, or (the parity check's
    lower-precision control) rounded to 8 bits, 5 of exponent and 2 of
    mantissa, which keeps bfloat16's reach for the weights' small numbers
    (4 and 3 would flush half of a lecun-normal matrix to zero)."""
    return jax.lax.reduce_precision(a, exponent_bits=5, mantissa_bits=2) \
        if sh.bits8 else a


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(x.dtype)


def _rotate(x, positions, theta):
    # x: [s, heads, d]; pairs (j, j + d/2)
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = positions.astype(F32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x2 * jnp.cos(angle) + x1 * jnp.sin(angle)],
                           -1).astype(x.dtype)


def _head_norms(q, k, a, eps):
    return _norm(q, a["q_norm"]["scale"], eps), \
        _norm(k, a["k_norm"]["scale"], eps)


def _mask(at, positions, sh: Shape):
    """[q, k] bool: the query at position `at[q]` sees the key at
    `positions[k]`."""
    if sh.causal_inside:
        return positions[None, :] <= at[:, None]
    ends = (at // sh.block_length + 1) * sh.block_length
    return positions[None, :] < ends[:, None]


@functools.partial(jax.jit, static_argnames=("sh",))
def _qkv(x, p, positions, *, sh: Shape):
    with jax.default_matmul_precision("highest"):
        u = _norm(x, p["attn_norm"]["scale"], sh.eps)
        a = p["attn"]
        q, k, v = (jnp.einsum("sd,dhk->shk", _r(u, sh),
                              _r(a[name]["kernel"].astype(x.dtype), sh))
                   for name in ("q_proj", "k_proj", "v_proj"))
        q, k = _head_norms(q, k, a, sh.eps)
        return (_r(_rotate(q, positions, sh.theta), sh),
                _r(_rotate(k, positions, sh.theta), sh), _r(v, sh))


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
        seen = _mask(at, positions, sh)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), -1)
        out = jnp.einsum("hqk,khd->qhd", _r(probs, sh), vv)
        rows = jax.lax.dynamic_slice_in_dim(x, first, QUERY_BLOCK, 0)
        rows = rows + jnp.einsum("qhd,hdm->qm", _r(out, sh),
                                 _r(w_o.astype(x.dtype), sh))
        return jax.lax.dynamic_update_slice_in_dim(x, rows, first, 0)


def attention_layer(x, p, positions, sh: Shape):
    """x + attention of n(x) under the block mask, a block of queries at a
    time, in x's own buffer (the caller's `x` is consumed); also the
    rotated keys and the values [s, kv_heads, hd] (what a cache would
    keep)."""
    s = x.shape[0]
    pad = -s % QUERY_BLOCK
    if pad:
        # whole query blocks: the padded rows sit at positions in blocks
        # past every real one, are seen by no real query and are cut off
        far = (positions[-1] // sh.block_length + 1) * sh.block_length
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
        positions = jnp.concatenate([positions, far + jnp.arange(pad)])
    q, k, v = _qkv(x, p, positions, sh=sh)
    w_o = p["attn"]["o_proj"]["kernel"]
    for first in range(0, s + pad, QUERY_BLOCK):
        x = _attend_block(x, q, k, v, w_o, positions, first, sh=sh)
    return x[:s], k[:s], v[:s]


def _route(f, m, routes, sh: Shape):
    """The probabilities [s, E] and each position's weight for each expert
    [s, E] (0 where not chosen): over the reference's own top-k, or over
    `routes` [s, k]."""
    scores = f @ m["router"].astype(f.dtype)
    probs = jax.nn.softmax(scores, axis=-1)
    if routes is None:
        _, routes = jax.lax.top_k(probs, sh.experts_per_token)
    chosen = jnp.zeros(probs.shape, bool).at[
        jnp.arange(probs.shape[0])[:, None], routes].set(True)
    picked = jnp.where(chosen, probs, 0.0)
    return probs, picked / picked.sum(-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("sh",))
def _routed_input(x, p, routes, *, sh: Shape):
    with jax.default_matmul_precision("highest"):
        f = _norm(x, p["mlp_norm"]["scale"], sh.eps)
        return (f,) + _route(f, p["moe"], routes, sh)


@functools.partial(jax.jit, static_argnames=("sh",), donate_argnums=(0,))
def _expert_block(out, f, weights, w_gate, w_up, w_down, *, sh: Shape):
    """out + the sum over a block of experts of weight[s, e] * E_e(f[s]),
    in out's buffer."""
    with jax.default_matmul_precision("highest"):
        f = _r(f, sh)
        hidden = jax.nn.silu(
            jnp.einsum("sd,edf->esf", f, _r(w_gate.astype(f.dtype), sh))) \
            * jnp.einsum("sd,edf->esf", f, _r(w_up.astype(f.dtype), sh))
        each = jnp.einsum("esf,efd->esd", _r(hidden, sh),
                          _r(w_down.astype(f.dtype), sh))
        return out + jnp.einsum("se,esd->sd", weights, each)


def expert_layer(x, p, routes, *, sh: Shape):
    """(x + the held experts' part of the routed sum of n(x), the router's
    probabilities [s, E]), EXPERT_BLOCK held experts at a time."""
    f, probs, weights = _routed_input(x, p, routes, sh=sh)
    x = x + 0.0      # the blocks add into a buffer of their own
    m = p["moe"]
    first, count = sh.held
    for at in range(0, count, EXPERT_BLOCK):
        upto = min(at + EXPERT_BLOCK, count)
        x = _expert_block(x, f, weights[:, first + at:first + upto],
                          m["w_gate"][at:upto], m["w_in"][at:upto],
                          m["w_out"][at:upto], sh=sh)
    return x, probs


def layer(x, p, positions, sh: Shape, route=None):
    """One layer on the stream x [s, d] (consumed). Returns (x', {"keys",
    "values", "probs"}): what a cache would keep of the layer and what the
    router ranked by."""
    x, keys, values = attention_layer(x, p, positions, sh)
    x, probs = expert_layer(x, p, route, sh=sh)
    return x, {"keys": keys, "values": values, "probs": probs}


@functools.partial(jax.jit, static_argnames=("sh",))
def _head_block(x, scale, kernel, *, sh: Shape):
    with jax.default_matmul_precision("highest"):
        return (_r(_norm(x, scale, sh.eps), sh)
                @ _r(kernel.astype(x.dtype), sh)).astype(F32)


def head(x, params, sh: Shape):
    """Logits of the final stream x [rows, d], on the HOST: the blocks of
    the vocabulary are joined there."""
    kernel = params["lm_head"]["kernel"]
    return np.concatenate(
        [np.asarray(_head_block(x, params["final_norm"]["scale"],
                                kernel[:, at:at + VOCAB_BLOCK], sh=sh))
         for at in range(0, kernel.shape[1], VOCAB_BLOCK)], -1)


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any], *,
           routes: Optional[list] = None, rows=None, details: bool = False,
           causal_inside: bool = False, dtype=F32, bits8: bool = False):
    """tokens [s] (masked places hold `mask_token_id`) -> logits [s, vocab],
    float32, position i's FOR position i; `config` holds the published keys,
    `block_length` and optionally `held_experts`. `routes`: per layer,
    [s, k] expert ids to follow in place of the reference's own top-k.
    `rows`: the indices whose logits are wanted (all). `details`: also
    {"probs": per layer the router's probabilities [s, E], "keys" /
    "values": per layer [s, kv_heads, hd], on the host}. `causal_inside`,
    `dtype` (bfloat16: every product, sum, norm and softmax in it, none
    kept in float32) and `bits8` (the operands of every product but the
    router's rounded to 8 bits: `_r`) are the parity check's controls."""
    sh = shape_of(config)._replace(causal_inside=causal_inside, bits8=bits8)
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0])
    wanted = positions if rows is None else jnp.asarray(rows, jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype)
    kept = {"probs": [], "keys": [], "values": []}
    for i in range(config["num_hidden_layers"]):
        route = None if routes is None else jnp.asarray(routes[i], jnp.int32)
        x, of_layer = layer(x, params[f"layer_{i}"], positions, sh, route)
        for name, value in of_layer.items():
            kept[name].append(np.asarray(value) if details
                              and name != "probs" else value)
        x.block_until_ready()     # a layer at a time
    out = head(x[wanted], params, sh)
    if details:
        return out, kept
    return out


# ---- the generator ---------------------------------------------------------


def unmask_counts(block_length: int, denoising_steps: int) -> List[int]:
    """k_t of the T denoising forwards of a block: L // T, the first L % T
    forwards one more; T held to 1 .. L."""
    steps = max(1, min(int(denoising_steps), block_length))
    return [block_length // steps + (t < block_length % steps)
            for t in range(steps)]


def candidates(block_logits, mask_id: int):
    """Greedy candidates of a block's positions and their confidences:
    the argmax over every id but the mask's and its softmax probability
    among them. block_logits [L, vocab]. Returns (ids [L], confidence [L])
    in float64 on the host."""
    z = np.array(block_logits, np.float64)
    z[:, mask_id] = -np.inf
    ids = z.argmax(-1)
    top = z.max(-1)
    return ids, 1.0 / np.exp(z - top[:, None]).sum(-1)


def _most_confident(confidence, masked, count: int):
    """The `count` masked positions of largest confidence, ties to the
    earlier position."""
    order = sorted(np.flatnonzero(masked), key=lambda i: (-confidence[i], i))
    return order[:count]


def static_rule(ids, found, confidence, mask_id: int, count: int):
    """The static low-confidence rule on one block: the `count` most
    confident masked positions take their candidates. Returns the new
    ids."""
    ids = np.array(ids)
    for i in _most_confident(confidence, ids == mask_id, count):
        ids[i] = found[i]
    return ids


def dynamic_rule(ids, found, confidence, mask_id: int, count: int,
                 threshold: float):
    """The dynamic low-confidence rule on one block: every masked position
    whose confidence passes `threshold` where those are at least `count`,
    else the `count` most confident."""
    ids = np.array(ids)
    masked = ids == mask_id
    passing = np.flatnonzero(masked & (np.asarray(confidence) > threshold))
    if len(passing) < count:
        passing = _most_confident(confidence, masked, count)
    for i in passing:
        ids[i] = found[i]
    return ids


def generate(params: Dict[str, Any], prompt: List[int], config: Dict[str, Any],
             max_new_tokens: int, denoising_steps: int,
             rule: str = "static", threshold: float = 0.9,
             forward=None):
    """The generator's loop of denoise and commit for ONE request. Returns
    (the tokens handed out, the forwards: per forward {"ids": the row's
    tokens as the forward read them, "at": the block's first position,
    "commit": bool, "logits": the block's [L, vocab]}). `forward(tokens) ->
    logits [s, vocab]` stands in for this module's `logits` where a test
    feeds made logits."""
    L, mask_id = int(config["block_length"]), int(config["mask_token_id"])
    if forward is None:
        forward = lambda tokens: logits(params, tokens, config)  # noqa: E731
    whole = len(prompt) - len(prompt) % L
    done, fixed = list(prompt[:whole]), list(prompt[whole:])
    handed: List[int] = []
    forwards = []
    while len(handed) < max_new_tokens:
        block = np.array(fixed + [mask_id] * (L - len(fixed)))
        counts = unmask_counts(L, denoising_steps)
        step = 0
        while (block == mask_id).any():
            out = np.asarray(forward(done + block.tolist()))[len(done):]
            forwards.append({"ids": done + block.tolist(), "at": len(done),
                             "commit": False, "logits": out})
            found, confidence = candidates(out, mask_id)
            count = counts[min(step, len(counts) - 1)]
            block = static_rule(block, found, confidence, mask_id, count) \
                if rule == "static" else dynamic_rule(
                    block, found, confidence, mask_id, count, threshold)
            step += 1
        handed += block.tolist()[len(fixed):]
        if len(handed) < max_new_tokens:
            # the commit: the block forwarded once more with its K/V kept
            out = np.asarray(forward(done + block.tolist()))[len(done):]
            forwards.append({"ids": done + block.tolist(), "at": len(done),
                             "commit": True, "logits": out})
        done += block.tolist()
        fixed = []
    return handed[:max_new_tokens], forwards
