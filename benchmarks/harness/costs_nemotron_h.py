"""Parameters and bytes of the Nemotron-H configuration with latent experts,
from its config file's keys alone (the published names; `n_routed_experts`
is the experts HELD here, `published.n_routed_experts` the router's width):
the table of the cut, and what one decode step has to move. Kept with the
benchmark, as costs.py and costs_hybrid.py are, so that no PR that claims a
gain can change the count. Every count is of bytes that MUST move: a share
of a roofline computed from it can only be understated.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

WEIGHT_BYTES = 2   # bf16
KV_BYTES = 2       # the page pool's type
STATE_BYTES = {"float32": 4, "bfloat16": 2}


def router_width(c: Dict[str, Any]) -> int:
    return c.get("published", {}).get("n_routed_experts",
                                      c["n_routed_experts"])


def layer_params(c: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that multiply, by part: one M layer, the * layer, one E
    layer outside its routed experts, one routed expert, the head. Norm
    scales, the convolution and the per-head scalars are thousands of
    times smaller and left out."""
    d = c["hidden_size"]
    d_ssm = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv_dim = d_ssm + 2 * c["n_groups"] * c["ssm_state_size"]
    latent = c["moe_latent_size"]
    return {
        "mamba": d * (d_ssm + conv_dim + c["mamba_num_heads"]) + d_ssm * d,
        "attention": 2 * d * c["num_attention_heads"] * c["head_dim"]
        + 2 * d * c["num_key_value_heads"] * c["head_dim"],
        "moe_outside_experts": d * router_width(c) + 2 * d * latent
        + 2 * d * c["moe_shared_expert_intermediate_size"],
        "expert": 2 * latent * c["moe_intermediate_size"],
        "lm_head": c["vocab_size"] * d,
        "embedding": c["vocab_size"] * d}


def kinds(c: Dict[str, Any]) -> Dict[str, int]:
    pattern = c["hybrid_override_pattern"]
    return {"mamba": pattern.count("M"), "attention": pattern.count("*"),
            "moe": pattern.count("E")}


def state_bytes_per_row(c: Dict[str, Any]) -> int:
    """One row's recurrent state in ONE M layer: the scan's state in the
    configuration's `state_dtype`, the convolution's window in bf16."""
    d_ssm = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv_dim = d_ssm + 2 * c["n_groups"] * c["ssm_state_size"]
    return d_ssm * c["ssm_state_size"] \
        * STATE_BYTES[c.get("state_dtype", "float32")] \
        + (c["conv_kernel"] - 1) * conv_dim * WEIGHT_BYTES


def kv_bytes_per_token(c: Dict[str, Any]) -> int:
    """One token's K and V in ONE * layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * KV_BYTES


def table(c: Dict[str, Any]) -> Dict[str, float]:
    """The cut's table (ISSUE 35, section 2): bytes resident on the chip."""
    p, n, e = layer_params(c), kinds(c), c["engine"]
    held = c["n_routed_experts"]
    weights = WEIGHT_BYTES * (
        n["mamba"] * p["mamba"] + n["attention"] * p["attention"]
        + n["moe"] * (p["moe_outside_experts"] + held * p["expert"])
        + p["lm_head"] + p["embedding"])
    staged_tokens = -(-e["max_len"] // e["page_size"]) * e["page_size"] \
        + e["prefill_buckets"][-1]
    return {
        "mamba_layer_bytes": WEIGHT_BYTES * p["mamba"],
        "attention_layer_bytes": WEIGHT_BYTES * p["attention"],
        "moe_outside_experts_bytes": WEIGHT_BYTES * p["moe_outside_experts"],
        "held_experts_bytes_per_layer": WEIGHT_BYTES * held * p["expert"],
        "vocabulary_bytes": WEIGHT_BYTES * (p["lm_head"] + p["embedding"]),
        "weights_bytes": weights,
        "moe_layers_share": n["moe"] * WEIGHT_BYTES * (
            p["moe_outside_experts"] + held * p["expert"]) / weights,
        "state_bytes_per_row": n["mamba"] * state_bytes_per_row(c),
        "state_pool_bytes": e["max_batch"] * n["mamba"]
        * state_bytes_per_row(c),
        "page_pool_bytes": e["num_pages"] * e["page_size"] * n["attention"]
        * kv_bytes_per_token(c),
        "staging_bytes_per_row": n["mamba"] * state_bytes_per_row(c)
        + n["attention"] * staged_tokens * kv_bytes_per_token(c)}


def decode_step_bytes(c: Dict[str, Any], rows: float, context_tokens: float,
                      hit_experts: Optional[float] = None
                      ) -> Dict[str, float]:
    """`rows` decoding rows whose contexts, each rounded up to whole
    pages, sum to `context_tokens`; `hit_experts`: held experts of ONE E
    layer that a step routed at least one token to, mean over layers and
    steps (None: all held). The embedding is a lookup and not counted."""
    p, n = layer_params(c), kinds(c)
    hit = c["n_routed_experts"] if hit_experts is None else hit_experts
    experts = WEIGHT_BYTES * n["moe"] * hit * p["expert"]
    dense = WEIGHT_BYTES * (
        n["mamba"] * p["mamba"] + n["attention"] * p["attention"]
        + n["moe"] * p["moe_outside_experts"] + p["lm_head"])
    state = 2.0 * rows * n["mamba"] * state_bytes_per_row(c)
    kv = float(context_tokens) * n["attention"] * kv_bytes_per_token(c)
    return {"experts": float(experts), "dense_weights": float(dense),
            "state": state, "kv": kv,
            "total": experts + dense + state + kv}


def expert_matmul_bytes(c: Dict[str, Any], hit_experts: float,
                        pairs: float) -> float:
    """What the routed experts of ONE E layer must move in one step: the
    two matrices of every expert that was hit, once, and each (token,
    expert) pair's latent row in and out in bf16 (a fused kernel keeps the
    hidden activations on the chip)."""
    p = layer_params(c)
    return WEIGHT_BYTES * hit_experts * p["expert"] \
        + pairs * 2 * c["moe_latent_size"] * WEIGHT_BYTES


def paged_attention_bytes(c: Dict[str, Any], context_tokens: float) -> float:
    """K and V pages ONE paged_attention call reads (one layer's)."""
    return float(context_tokens) * kv_bytes_per_token(c)
