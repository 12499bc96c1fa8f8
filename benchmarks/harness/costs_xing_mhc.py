"""Parameters, bytes and operations of the Xing4.0-29B-A4B configuration (a
four-stream residual mixed by hyper-connections, latent attention with a
query latent, all 64 routed SwiGLU experts held), from its config file's
keys alone (the published names): the table of the cut, what one decode
step has to move, and what one prefill chunk has to move and compute. Kept
with the benchmark, as costs_sarvam_mla.py is, so that no PR that claims a
gain can change the count. Every count is of bytes that MUST move and
operations that MUST run whatever implements them (the chosen (token,
expert) pairs, not every held expert on every token; the cheaper of the
absorbed and the expanded attention products): a share of a roofline
computed from it can only be understated.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

WEIGHT_BYTES = 2   # bf16
CACHE_BYTES = 2    # the latent pool's type
STREAM_BYTES = 2   # the residual streams' type
SCALAR_BYTES = 4   # norm scales, biases and gains are float32


def layer_params(c: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part. `*_scalars` are the float32 ones (norm scales,
    the hyper-connections' biases and gains, the router's bias), thousands
    of times fewer; the rest are bf16 matrices that multiply."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    rank, q_rank = c["kv_lora_rank"], c["q_lora_rank"]
    rope, nope, v = (c["qk_rope_head_dim"], c["qk_nope_head_dim"],
                     c["v_head_dim"])
    n = c["hc_mult"]
    return {
        "attention": d * q_rank + q_rank * heads * (nope + rope)
        + d * (rank + rope) + rank * heads * (nope + v) + heads * v * d,
        "attention_scalars": q_rank + rank,
        # one connection: phi; a layer has two
        "connection": n * d * (n * n + 2 * n),
        "connection_scalars": n * n + 2 * n + 3,
        "layer_norm_scalars": 2 * d,
        "dense_mlp": 3 * d * c["intermediate_size"],
        "moe_outside_experts": d * c["n_routed_experts"]
        + 3 * d * c["moe_intermediate_size"] * c["n_shared_experts"],
        "moe_scalars": c["n_routed_experts"],
        "expert": 3 * d * c["moe_intermediate_size"],
        "lm_head": c["vocab_size"] * d,
        "embedding": c["vocab_size"] * d,
        "final_norm_scalars": d}


def kinds(c: Dict[str, Any]) -> Dict[str, int]:
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    return {"dense": dense, "moe": c["num_hidden_layers"] - dense}


def latent_row_bytes(c: Dict[str, Any]) -> int:
    """One token's cached row in ONE layer as it had to move: `[c ;
    k_rope]`, key and value at once."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * CACHE_BYTES


def resident_row_bytes(c: Dict[str, Any]) -> int:
    """... and as the pool holds it: whole 128-lane tiles (640 for 576)."""
    lanes = -(-(c["kv_lora_rank"] + c["qk_rope_head_dim"]) // 128) * 128
    return lanes * CACHE_BYTES


def table(c: Dict[str, Any]) -> Dict[str, float]:
    """The cut's table (ISSUE 52): parameters and bytes resident on the
    chip."""
    p, k, e = layer_params(c), kinds(c), c["engine"]
    held, layers = c["held_experts"][1], c["num_hidden_layers"]
    every_layer = p["attention"] + 2 * p["connection"]
    matrices = (layers * every_layer + k["dense"] * p["dense_mlp"]
                + k["moe"] * (p["moe_outside_experts"] + held * p["expert"])
                + p["lm_head"] + p["embedding"])
    scalars = (layers * (p["attention_scalars"] + 2 * p["connection_scalars"]
                         + p["layer_norm_scalars"])
               + k["moe"] * p["moe_scalars"] + p["final_norm_scalars"])
    return {
        "embedding_and_head_params": p["lm_head"] + p["embedding"],
        "attention_params_per_layer": p["attention"],
        "connections_params_per_layer": 2 * p["connection"],
        "dense_layer_params": every_layer + p["dense_mlp"],
        "expert_layer_params": every_layer + p["moe_outside_experts"]
        + held * p["expert"],
        "held_experts_params_per_layer": held * p["expert"],
        "weights_params": matrices + scalars,
        "weights_bytes": WEIGHT_BYTES * matrices + SCALAR_BYTES * scalars,
        "latent_bytes_per_token": layers * latent_row_bytes(c),
        "resident_bytes_per_token": layers * resident_row_bytes(c),
        "page_bytes": e["page_size"] * layers * resident_row_bytes(c),
        "pool_bytes": e["num_pages"] * e["page_size"] * layers
        * resident_row_bytes(c)}


def stream_bytes(c: Dict[str, Any], tokens: float) -> float:
    """The n-stream state of `tokens` tokens through every sublayer: each
    of the 2 x layers connections reads X once and writes X' once."""
    return float(tokens) * 2 * c["num_hidden_layers"] * 2 \
        * c["hc_mult"] * c["hidden_size"] * STREAM_BYTES


def step_weight_bytes(c: Dict[str, Any],
                      hit_experts: Optional[float] = None,
                      head: bool = True) -> float:
    """Weights one call multiplies, once: every layer's attention and two
    connections, the dense layer, the routers and shared experts, the head
    (unless `head` is False), and of the routed experts those
    `hit_experts` of ONE layer that the call routed at least one token to
    (mean over layers; None: all held). The embedding is a lookup and not
    counted."""
    p, k = layer_params(c), kinds(c)
    hit = c["held_experts"][1] if hit_experts is None else hit_experts
    return float(WEIGHT_BYTES * (
        c["num_hidden_layers"] * (p["attention"] + 2 * p["connection"])
        + k["dense"] * p["dense_mlp"]
        + k["moe"] * (p["moe_outside_experts"] + hit * p["expert"])
        + (p["lm_head"] if head else 0)))


def decode_step_bytes(c: Dict[str, Any], pages: float, page_size: int,
                      rows: float, hit_experts: Optional[float] = None
                      ) -> Dict[str, float]:
    """`pages` DISTINCT latent pages a step reads in ONE layer, `rows` rows
    decoding (their four streams through every sublayer)."""
    weights = step_weight_bytes(c, hit_experts)
    cache = float(pages) * page_size * c["num_hidden_layers"] \
        * latent_row_bytes(c)
    streams = stream_bytes(c, rows)
    return {"weights": weights, "cache": cache, "streams": streams,
            "total": weights + cache + streams}


def chunk(c: Dict[str, Any], tokens: float, rows_read: float
          ) -> Dict[str, float]:
    """One prefill chunk of `tokens` tokens, the last of which attends
    `rows_read` cached rows (its own among them: the engine's
    `prefill_ctx_rows` a chunk), so that the chunk's (query, row) pairs are
    tokens x (rows_read - (tokens - 1) / 2). Bytes: the weights once (every
    expert: `tokens` x k pairs over the router's width hit them all at the
    cell's chunks; no head: a chunk that ends no prompt runs none), the
    rows the chunk attends read once and its own written, the streams.
    FLOPs: 2 a parameter a token through the dense parts and the CHOSEN
    (token, expert) pairs, and the attention products in the cheaper of
    their two forms: absorbed (every (query, row, head) 2 x (576 + 512))
    or expanded (2 x (192 + 128), and each attended row's keys and values
    expanded once a chunk, 2 x 512 x heads x 256)."""
    p, k = layer_params(c), kinds(c)
    heads, rank = c["num_attention_heads"], c["kv_lora_rank"]
    rope, nope, v = (c["qk_rope_head_dim"], c["qk_nope_head_dim"],
                     c["v_head_dim"])
    layers = c["num_hidden_layers"]
    per_token = layers * (p["attention"] + 2 * p["connection"]) \
        + k["dense"] * p["dense_mlp"] \
        + k["moe"] * (p["moe_outside_experts"]
                      + c["num_experts_per_tok"] * p["expert"])
    pairs = tokens * (rows_read - (tokens - 1) / 2.0)
    absorbed = pairs * heads * (2 * rank + rope) * 2.0
    expanded = pairs * heads * (nope + rope + v) * 2.0 \
        + rows_read * rank * heads * (nope + v) * 2.0
    flops = 2.0 * tokens * per_token + layers * min(absorbed, expanded)
    weights = step_weight_bytes(c, None, head=False)
    cache = (rows_read + tokens) * layers * latent_row_bytes(c)
    streams = stream_bytes(c, tokens)
    return {"flops": flops, "weights": weights, "cache": cache,
            "streams": streams, "bytes": weights + cache + streams}
