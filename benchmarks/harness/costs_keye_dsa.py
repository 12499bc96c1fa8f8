"""Parameters, bytes and operations of the Keye-VL-2.0-30B-A3B configuration
(learned sparse attention beside softmax-routed SwiGLU experts), from its
config file's keys alone (the published names; `num_experts` is the experts
HELD here, `published.num_experts` the router's width): the table of the
cut, what one decode step has to move, and what the index scoring and the
gather-and-attend of one layer have to move and compute. Kept with the
benchmark, as costs_sarvam_mla.py is, so that no PR that claims a gain can
change the count. Every count is of the WORK (index keys scored, tokens
selected, rows written), whatever implements it, in bytes that MUST move
and operations that MUST run: a share of a roofline computed from it can
only be understated.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

WEIGHT_BYTES = 2   # bf16
CACHE_BYTES = 2    # the pools' type


def router_width(c: Dict[str, Any]) -> int:
    return c.get("published", {}).get("num_experts", c["num_experts"])


def layer_params(c: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that multiply, by part: a layer's attention (q, k, v, o),
    its indexer (qI, kI, w), its router, one routed expert, the head. Norm
    scales are thousands of times smaller and left out."""
    d, sa = c["hidden_size"], c["sa_config"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return {
        "attention": 2 * d * q + 2 * d * kv,
        "indexer": d * sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + d * sa["indexer_num_kv_heads"] * sa["indexer_head_dim"]
        + d * sa["indexer_num_heads"],
        "router": d * router_width(c),
        "expert": 3 * d * c["moe_intermediate_size"],
        "lm_head": c["vocab_size"] * d,
        "embedding": c["vocab_size"] * d}


def index_key_bytes(c: Dict[str, Any]) -> int:
    """One token's index key in ONE layer as it had to move. (The pool
    holds it at a whole 128-lane tile: the pad lanes are the device
    layout's.)"""
    sa = c["sa_config"]
    return sa["indexer_num_kv_heads"] * sa["indexer_head_dim"] * CACHE_BYTES


def kv_row_bytes(c: Dict[str, Any]) -> int:
    """One token's K and V in ONE layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * CACHE_BYTES


def resident_token_bytes(c: Dict[str, Any]) -> int:
    """One token in ONE layer as the pools hold it."""
    sa = c["sa_config"]
    lanes = -(-sa["indexer_num_kv_heads"] * sa["indexer_head_dim"]
              // 128) * 128
    return kv_row_bytes(c) + lanes * CACHE_BYTES


def table(c: Dict[str, Any]) -> Dict[str, float]:
    """The cut's table (ISSUE 49): bytes resident on the chip."""
    p, e = layer_params(c), c["engine"]
    layers, held = c["num_hidden_layers"], c["num_experts"]
    per_layer = p["attention"] + p["indexer"] + p["router"] \
        + held * p["expert"]
    return {
        "attention_params_per_layer": p["attention"],
        "indexer_params_per_layer": p["indexer"],
        "router_params_per_layer": p["router"],
        "expert_params": p["expert"],
        "embedding_and_head_params": p["lm_head"] + p["embedding"],
        "weights_params": layers * per_layer + p["lm_head"] + p["embedding"],
        "weights_bytes": WEIGHT_BYTES * (
            layers * per_layer + p["lm_head"] + p["embedding"]),
        "cache_bytes_per_token": layers * (
            kv_row_bytes(c) + index_key_bytes(c)),
        "resident_bytes_per_token": layers * resident_token_bytes(c),
        "page_bytes": e["page_size"] * layers * resident_token_bytes(c),
        "pool_bytes": e["num_pages"] * e["page_size"] * layers
        * resident_token_bytes(c)}


def step_weight_bytes(c: Dict[str, Any],
                      hit_experts: Optional[float] = None) -> float:
    """Weights one decode step multiplies, once: every layer's attention,
    indexer and router, the head, and of the routed experts those
    `hit_experts` of ONE layer that a step routed at least one token to
    (mean over layers and steps; None: all held). The embedding is a
    lookup and not counted."""
    p = layer_params(c)
    hit = c["num_experts"] if hit_experts is None else hit_experts
    return float(WEIGHT_BYTES * (
        c["num_hidden_layers"] * (p["attention"] + p["indexer"]
                                  + p["router"] + hit * p["expert"])
        + p["lm_head"]))


def decode_step_bytes(c: Dict[str, Any], scored: float, selected: float,
                      rows: float, hit_experts: Optional[float] = None
                      ) -> Dict[str, float]:
    """One decode step whose rows scored `scored` cached index keys,
    selected `selected` tokens and wrote `rows` new ones (each summed over
    the rows, in ONE layer; every layer does the same)."""
    layers = c["num_hidden_layers"]
    weights = step_weight_bytes(c, hit_experts)
    index = float(scored) * layers * index_key_bytes(c)
    gathered = float(selected) * layers * kv_row_bytes(c)
    written = float(rows) * layers * (kv_row_bytes(c) + index_key_bytes(c))
    return {"weights": weights, "index": index, "selected": gathered,
            "written": written, "cache": index + gathered,
            "total": weights + index + gathered + written}


def index_call(c: Dict[str, Any], scored: float, rows: float
               ) -> Dict[str, float]:
    """The indexer of ONE layer in one decode step (the scope
    `dsa/index`): the bytes of the `scored` index keys and of the
    indexer's three projections; the operations of the projections over
    `rows` tokens and of the index products, heads x head_dim x 2, and the
    ReLU and the weighted sum over the heads, over the scored keys."""
    sa, p = c["sa_config"], layer_params(c)
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {"bytes": float(scored) * index_key_bytes(c)
            + WEIGHT_BYTES * p["indexer"],
            "flops": float(scored) * heads * (2.0 * dim + 3.0)
            + 2.0 * float(rows) * p["indexer"]}


def attend_call(c: Dict[str, Any], selected: float) -> Dict[str, float]:
    """The gather-and-attend of ONE layer in one decode step (the scope
    `dsa/attend`): the bytes of the `selected` tokens' K and V rows, each
    once; the operations of q . k and p . v over them, heads x head_dim x
    2 each."""
    return {"bytes": float(selected) * kv_row_bytes(c),
            "flops": float(selected) * c["num_attention_heads"]
            * c["head_dim"] * 4.0}
