"""Parameters, bytes and operations of the Sarvam-105B configuration (latent
attention beside routed SwiGLU experts), from its config file's keys alone
(the published names; `num_experts` is the experts HELD here,
`published.num_experts` the router's width): the table of the cut, what one
decode step has to move, and what one call of the latent decode kernel has
to move and compute. Kept with the benchmark, as costs.py and
costs_nemotron_h.py are, so that no PR that claims a gain can change the
count. Every count is of bytes that MUST move and operations that MUST run:
a share of a roofline computed from it can only be understated.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

WEIGHT_BYTES = 2   # bf16
CACHE_BYTES = 2    # the latent pool's type


def router_width(c: Dict[str, Any]) -> int:
    return c.get("published", {}).get("num_experts", c["num_experts"])


def layer_params(c: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that multiply, by part: a layer's attention, the dense
    layer's SwiGLU, an expert layer outside its routed experts (router and
    shared expert), one routed expert, the head. Norm scales are thousands
    of times smaller and left out."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    rank, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, v = c["qk_nope_head_dim"], c["v_head_dim"]
    return {
        "attention": d * heads * (nope + rope) + d * (rank + rope)
        + rank * heads * (nope + v) + heads * v * d,
        "dense_mlp": 3 * d * c["intermediate_size"],
        "moe_outside_experts": d * router_width(c)
        + 3 * d * c["moe_intermediate_size"] * c["num_shared_experts"],
        "expert": 3 * d * c["moe_intermediate_size"],
        "lm_head": c["vocab_size"] * d,
        "embedding": c["vocab_size"] * d}


def kinds(c: Dict[str, Any]) -> Dict[str, int]:
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    return {"dense": dense, "moe": c["num_hidden_layers"] - dense}


def latent_row_bytes(c: Dict[str, Any]) -> int:
    """One token's cached row in ONE layer as it had to move: `[c ;
    k_rope]`, key and value at once. (The pool holds it at whole 128-lane
    tiles, 640 for 576: the pad lanes are the device layout's.)"""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * CACHE_BYTES


def resident_row_bytes(c: Dict[str, Any]) -> int:
    lanes = -(-(c["kv_lora_rank"] + c["qk_rope_head_dim"]) // 128) * 128
    return lanes * CACHE_BYTES


def table(c: Dict[str, Any]) -> Dict[str, float]:
    """The cut's table (ISSUE 45): bytes resident on the chip."""
    p, n, e = layer_params(c), kinds(c), c["engine"]
    held = c["num_experts"]
    layers = c["num_hidden_layers"]
    weights = WEIGHT_BYTES * (
        layers * p["attention"] + n["dense"] * p["dense_mlp"]
        + n["moe"] * (p["moe_outside_experts"] + held * p["expert"])
        + p["lm_head"] + p["embedding"])
    return {
        "attention_bytes_per_layer": WEIGHT_BYTES * p["attention"],
        "dense_mlp_bytes": WEIGHT_BYTES * p["dense_mlp"],
        "moe_outside_experts_bytes": WEIGHT_BYTES * p["moe_outside_experts"],
        "held_experts_bytes_per_layer": WEIGHT_BYTES * held * p["expert"],
        "vocabulary_bytes": WEIGHT_BYTES * (p["lm_head"] + p["embedding"]),
        "weights_bytes": weights,
        "latent_bytes_per_token": layers * latent_row_bytes(c),
        "resident_bytes_per_token": layers * resident_row_bytes(c),
        "page_bytes": e["page_size"] * layers * resident_row_bytes(c),
        "pool_bytes": e["num_pages"] * e["page_size"] * layers
        * resident_row_bytes(c)}


def step_weight_bytes(c: Dict[str, Any],
                      hit_experts: Optional[float] = None) -> float:
    """Weights one decode step multiplies, once: every layer's attention,
    the dense layer, the routers and shared experts, the head, and of the
    routed experts those `hit_experts` of ONE layer that a step routed at
    least one token to (mean over layers and steps; None: all held). The
    embedding is a lookup and not counted."""
    p, n = layer_params(c), kinds(c)
    hit = c["num_experts"] if hit_experts is None else hit_experts
    return float(WEIGHT_BYTES * (
        c["num_hidden_layers"] * p["attention"]
        + n["dense"] * p["dense_mlp"]
        + n["moe"] * (p["moe_outside_experts"] + hit * p["expert"])
        + p["lm_head"]))


def decode_step_bytes(c: Dict[str, Any], pages: float, page_size: int,
                      hit_experts: Optional[float] = None
                      ) -> Dict[str, float]:
    """`pages` latent pages a step reads in ONE layer (counted a row:
    what this program's kernel reads; counted once: what had to move)."""
    weights = step_weight_bytes(c, hit_experts)
    cache = float(pages) * page_size * c["num_hidden_layers"] \
        * latent_row_bytes(c)
    return {"weights": weights, "cache": cache, "total": weights + cache}


def attention_call(c: Dict[str, Any], distinct_pages: float,
                   attended_tokens: float, page_size: int
                   ) -> Dict[str, float]:
    """ONE call of the latent decode kernel (one layer, one step): the
    bytes of the DISTINCT pages the decoding rows hold, each once (a page
    is key and value at once, and rows on one document share it), and the
    operations of the absorbed products over the rows' `attended_tokens`
    (summed over rows): heads x (576 for q . row + 512 for p . c) x 2."""
    rank = c["kv_lora_rank"]
    width = rank + c["qk_rope_head_dim"]
    return {"bytes": float(distinct_pages) * page_size
            * latent_row_bytes(c),
            "flops": float(attended_tokens) * c["num_attention_heads"]
            * (width + rank) * 2.0}
