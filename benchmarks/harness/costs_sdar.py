"""Parameters, bytes and operations of the SDAR-30B-A3B-Chat configuration
(a Qwen3-MoE decoder that generates by diffusion over blocks: GQA 32 : 4 at
128-wide heads, 128 softmax-routed SwiGLU experts, 8 a token, all held, an
untied head over 151,936 ids), from its config file's keys alone (the
published names): the table of the cut, what one block step has to move,
what one paged-attention call has to read, and what one prefill chunk has to
move and compute. Kept with the benchmark, as costs_lfm2.py is, so that no
PR that claims a gain can change the count. Every count is of bytes that
MUST move and operations that MUST run whatever implements them (each weight
once a step, and of the experts only those the step routed a position to;
each cached token's K and V once a ROW, not once a query of its block; the
logits written once in the model's type): a share of a roofline computed
from it can only be understated.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

WEIGHT_BYTES = 2   # bf16
CACHE_BYTES = 2    # the page pools' type
SCALAR_BYTES = 4   # norm scales are float32


def layer_params(c: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part. `*_scalars` are the float32 ones; the rest are
    bf16 matrices that multiply."""
    d, hd = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return {
        "attention": 2 * d * heads * hd + 2 * d * kv * hd,
        "attention_scalars": 2 * hd,
        "layer_norm_scalars": 2 * d,
        "router": d * c["num_experts"],
        "expert": 3 * d * c["moe_intermediate_size"],
        "embedding": c["vocab_size"] * d,
        "head": c["vocab_size"] * d,
        "final_norm_scalars": d}


def held(c: Dict[str, Any]) -> int:
    return (c.get("held_experts") or [0, c["num_experts"]])[1]


def kv_bytes_per_token(c: Dict[str, Any]) -> int:
    """One token's K and V in ONE layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * CACHE_BYTES


def table(c: Dict[str, Any]) -> Dict[str, float]:
    """The cut's table (ISSUE 58): parameters and bytes resident on the
    chip."""
    p, e, layers = layer_params(c), c["engine"], c["num_hidden_layers"]
    matrices = layers * (p["attention"] + p["router"]
                         + held(c) * p["expert"]) \
        + p["embedding"] + p["head"]
    scalars = layers * (p["layer_norm_scalars"] + p["attention_scalars"]) \
        + p["final_norm_scalars"]
    return {
        "embedding_params": p["embedding"],
        "head_params": p["head"],
        "attention_params": p["attention"] + p["attention_scalars"],
        "router_params": p["router"],
        "held_experts_params_per_layer": held(c) * p["expert"],
        "layer_params": p["attention"] + p["attention_scalars"]
        + p["layer_norm_scalars"] + p["router"] + held(c) * p["expert"],
        "weights_params": matrices + scalars,
        "weights_bytes": WEIGHT_BYTES * matrices + SCALAR_BYTES * scalars,
        "kv_bytes_per_token": layers * kv_bytes_per_token(c),
        "page_bytes": e["page_size"] * layers * kv_bytes_per_token(c),
        "pool_bytes": e["num_pages"] * e["page_size"] * layers
        * kv_bytes_per_token(c)}


def step_weight_bytes(c: Dict[str, Any],
                      hit_experts: Optional[float] = None,
                      head: bool = True) -> float:
    """Weights one call multiplies, once: every attention and router, the
    untied head (unless `head` is False), and of the held experts those
    `hit_experts` of ONE layer that the call routed at least one position
    to (mean over layers; None: all held). The embedding lookup is not
    counted."""
    p = layer_params(c)
    hit = held(c) if hit_experts is None else hit_experts
    return float(WEIGHT_BYTES * (
        c["num_hidden_layers"] * (p["attention"] + p["router"]
                                  + hit * p["expert"])
        + (p["head"] if head else 0)))


def expert_layer_bytes(c: Dict[str, Any], hit_experts: float,
                       pairs: float) -> float:
    """Bytes the routed experts of ONE layer have to move in one call: the
    three matrices of each of the `hit_experts` the call routed a position
    to, once, and each of the `pairs` routed (position, expert) pairs' row
    in and out in the model's type."""
    return float(WEIGHT_BYTES * (
        hit_experts * layer_params(c)["expert"]
        + 2.0 * pairs * c["hidden_size"]))


def paged_attention_bytes(c: Dict[str, Any], context_tokens: float,
                          rows: float) -> float:
    """Bytes ONE paged-attention call (one layer, one block step) has to
    read: the K and V of every committed token of every live row and of
    the row's open block, once a row (its block_length queries share
    them)."""
    return (float(context_tokens) + float(rows) * c["block_length"]) \
        * kv_bytes_per_token(c)


def block_step_bytes(c: Dict[str, Any], context_tokens: float, rows: float,
                     hit_experts: Optional[float] = None
                     ) -> Dict[str, float]:
    """`context_tokens` committed tokens the live rows hold together,
    `rows` rows live: each writes its block's K/V rows in every layer, and
    the logits of its block_length positions leave the head once, in the
    model's type."""
    layers, L = c["num_hidden_layers"], c["block_length"]
    weights = step_weight_bytes(c, hit_experts)
    cache = layers * (paged_attention_bytes(c, context_tokens, rows)
                      + float(rows) * L * kv_bytes_per_token(c))
    logits = float(rows) * L * c["vocab_size"] * WEIGHT_BYTES
    return {"weights": weights, "cache": cache, "logits": logits,
            "total": weights + cache + logits}


def chunk(c: Dict[str, Any], tokens: float, rows_read: float,
          hit_experts: Optional[float] = None) -> Dict[str, float]:
    """One prefill chunk of `tokens` tokens, the last of which attends
    `rows_read` cached rows (its own among them: the engine's
    `prefill_ctx_rows` a chunk). Under the block mask a query sees to its
    block's end, so the chunk's (query, key) pairs are tokens x (rows_read
    - (tokens - block_length) / 2). FLOPs: 2 a parameter a token through
    the attentions, the routers and the CHOSEN (token, expert) pairs, and
    q k^T and p v over the pairs (2 x 2 x heads x head_dim each); no head
    (a prompt samples nothing). Bytes: the weights once (of the experts
    those `hit_experts` of one layer that the chunk routed a token to, which
    the engine counts a chunk; None: all held. Seeded routers are far from
    balanced, and a sorted chunk reads no expert nobody chose), the rows
    attended read once and the chunk's own written."""
    p, layers = layer_params(c), c["num_hidden_layers"]
    pairs_held = c["num_experts_per_tok"] * held(c) / c["num_experts"]
    per_token = layers * (p["attention"] + p["router"]
                          + pairs_held * p["expert"])
    pairs = tokens * (rows_read - (tokens - c["block_length"]) / 2.0)
    attention = layers * pairs * 4.0 * c["num_attention_heads"] \
        * c["head_dim"]
    flops = 2.0 * tokens * per_token + attention
    weights = step_weight_bytes(c, hit_experts, head=False)
    cache = (rows_read + tokens) * layers * kv_bytes_per_token(c)
    return {"flops": flops, "attention_flops": attention,
            "weights": weights, "cache": cache, "bytes": weights + cache}
