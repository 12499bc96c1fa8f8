"""Parameters, bytes and operations of the LFM2-24B-A2B configuration (gated
short convolutions, grouped-query attention with 64-wide heads, 8 of 64
sigmoid-routed SwiGLU experts held), from its config file's keys alone (the
published names): the table of the cut, what one decode step has to move,
what one paged-attention call has to read, and what one prefill chunk has to
move and compute. Kept with the benchmark, as costs_xing_mhc.py is, so that
no PR that claims a gain can change the count. Every count is of bytes that
MUST move and operations that MUST run whatever implements them (the chosen
(token, expert) pairs on the experts held, not every held expert on every
token; heads at their own 64 lanes, not a padded row): a share of a roofline
computed from it can only be understated.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

WEIGHT_BYTES = 2   # bf16
CACHE_BYTES = 2    # the page pools' and the windows' type
SCALAR_BYTES = 4   # norm scales and the router's bias are float32


def head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def router_width(c: Dict[str, Any]) -> int:
    return c.get("published", {}).get("num_experts", c["num_experts"])


def layer_params(c: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part. `*_scalars` are the float32 ones; the rest are
    bf16 matrices that multiply."""
    d, hd = c["hidden_size"], head_dim(c)
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return {
        "conv": d * 3 * d + d * d + c["conv_L_cache"] * d,
        "attention": 2 * d * heads * hd + 2 * d * kv * hd,
        "attention_scalars": 2 * hd,
        "layer_norm_scalars": 2 * d,
        "dense_mlp": 3 * d * c["intermediate_size"],
        "router": d * router_width(c),
        "router_scalars": router_width(c),
        "expert": 3 * d * c["moe_intermediate_size"],
        "embedding": c["vocab_size"] * d,
        "final_norm_scalars": d}


def kinds(c: Dict[str, Any]) -> Dict[str, int]:
    types = c["layer_types"]
    dense = min(c["num_dense_layers"], len(types))
    return {"conv": types.count("conv"),
            "attention": types.count("full_attention"),
            "dense": dense, "moe": len(types) - dense}


def kv_bytes_per_token(c: Dict[str, Any]) -> int:
    """One token's K and V in ONE attending layer, as it has to move and
    as the packed pool holds it (no lane is padding)."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * CACHE_BYTES


def window_bytes_per_row(c: Dict[str, Any]) -> int:
    """One row's window in ONE conv layer."""
    return (c["conv_L_cache"] - 1) * c["hidden_size"] * CACHE_BYTES


def table(c: Dict[str, Any]) -> Dict[str, float]:
    """The cut's table (ISSUE 56): parameters and bytes resident on the
    chip."""
    p, k, e = layer_params(c), kinds(c), c["engine"]
    held = c["held_experts"][1]
    matrices = (k["conv"] * p["conv"] + k["attention"] * p["attention"]
                + k["dense"] * p["dense_mlp"]
                + k["moe"] * (p["router"] + held * p["expert"])
                + p["embedding"])
    scalars = (len(c["layer_types"]) * p["layer_norm_scalars"]
               + k["attention"] * p["attention_scalars"]
               + k["moe"] * p["router_scalars"] + p["final_norm_scalars"])
    return {
        "embedding_params": p["embedding"],
        "conv_mixer_params": p["conv"],
        "attention_params": p["attention"] + p["attention_scalars"],
        "dense_mlp_params": p["dense_mlp"],
        "held_experts_params_per_layer": held * p["expert"],
        "weights_params": matrices + scalars,
        "weights_bytes": WEIGHT_BYTES * matrices + SCALAR_BYTES * scalars,
        "kv_bytes_per_token": k["attention"] * kv_bytes_per_token(c),
        "page_bytes": e["page_size"] * k["attention"] * kv_bytes_per_token(c),
        "pool_bytes": e["num_pages"] * e["page_size"] * k["attention"]
        * kv_bytes_per_token(c),
        "window_bytes": e["max_batch"] * k["conv"] * window_bytes_per_row(c)}


def step_weight_bytes(c: Dict[str, Any],
                      hit_experts: Optional[float] = None,
                      head: bool = True) -> float:
    """Weights one call multiplies, once: every mixer, the dense layers,
    the routers, the tied head (the embedding read as a matrix; unless
    `head` is False), and of the held experts those `hit_experts` of ONE
    layer that the call routed at least one token to (mean over layers;
    None: all held). The embedding lookup itself is not counted."""
    p, k = layer_params(c), kinds(c)
    hit = c["held_experts"][1] if hit_experts is None else hit_experts
    return float(WEIGHT_BYTES * (
        k["conv"] * p["conv"] + k["attention"] * p["attention"]
        + k["dense"] * p["dense_mlp"]
        + k["moe"] * (p["router"] + hit * p["expert"])
        + (p["embedding"] if head else 0)))


def paged_attention_bytes(c: Dict[str, Any], context_tokens: float) -> float:
    """Bytes ONE paged-attention call (one layer, one decode step) has to
    read: the K and V of every cached token of every decoding row, at the
    rows' own lengths (`serve_cell_lfm2`'s `length_ticks`; the stock tick
    log rounds each row up to whole pages, 31.5 tokens a row too many)."""
    return float(context_tokens) * kv_bytes_per_token(c)


def decode_step_bytes(c: Dict[str, Any], context_tokens: float, rows: float,
                      hit_experts: Optional[float] = None
                      ) -> Dict[str, float]:
    """`context_tokens` cached tokens the decoding rows hold together,
    `rows` rows decoding (each reads and writes its window in every conv
    layer)."""
    k = kinds(c)
    weights = step_weight_bytes(c, hit_experts)
    cache = k["attention"] * paged_attention_bytes(c, context_tokens)
    windows = float(rows) * k["conv"] * 2 * window_bytes_per_row(c)
    return {"weights": weights, "cache": cache, "windows": windows,
            "total": weights + cache + windows}


def chunk(c: Dict[str, Any], tokens: float, rows_read: float
          ) -> Dict[str, float]:
    """One prefill chunk of `tokens` tokens, the last of which attends
    `rows_read` cached rows (its own among them: the engine's
    `prefill_ctx_rows` a chunk), so that the chunk's (query, key) pairs are
    tokens x (rows_read - (tokens - 1) / 2). FLOPs: 2 a parameter a token
    through the mixers, the dense layers, the routers and the CHOSEN
    (token, expert) pairs that fall on the experts held (k x held / width a
    token on a balanced router), the filter's taps, and q k^T and p v over
    the pairs (2 x 2 x heads x head_dim each); no head (a chunk that ends
    no prompt runs none). Bytes: the weights once, the rows attended read
    once and the chunk's own written."""
    p, k = layer_params(c), kinds(c)
    held = c["held_experts"][1]
    pairs_held = c["num_experts_per_tok"] * held / router_width(c)
    per_token = k["conv"] * p["conv"] + k["attention"] * p["attention"] \
        + k["dense"] * p["dense_mlp"] \
        + k["moe"] * (p["router"] + pairs_held * p["expert"])
    pairs = tokens * (rows_read - (tokens - 1) / 2.0)
    attention = k["attention"] * pairs * 4.0 \
        * c["num_attention_heads"] * head_dim(c)
    flops = 2.0 * tokens * per_token + attention
    weights = step_weight_bytes(c, None, head=False)
    cache = (rows_read + tokens) * k["attention"] * kv_bytes_per_token(c)
    return {"flops": flops, "attention_flops": attention,
            "weights": weights, "cache": cache, "bytes": weights + cache}
