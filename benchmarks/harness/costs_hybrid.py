"""Bytes one decode tick of a hybrid (state-space + attention) decoder has
to move, from its shapes alone: the weights that multiply (each once a
tick, whatever the batch), the recurrent state of every decoding row (read
and written), and the K/V pages of every decoding row's context. Their sum
over the published HBM bandwidth is the least time a tick can take; kept
with the benchmark, as costs.py is, so that no PR that claims a gain can
change the count. Keys are the published names of the source's config.json.
"""

from __future__ import annotations

from typing import Any, Dict

WEIGHT_BYTES = 2   # bf16
KV_BYTES = 2       # the page pool's type
STATE_BYTES = {"float32": 4, "bfloat16": 2}


def matmul_params(c: Dict[str, Any]) -> Dict[str, int]:
    """Parameters that multiply in a decode step, by part. The embedding is
    a lookup (one row a token) and the norms, the convolution and the
    per-head scalars are thousands of times smaller: left out, which can
    only understate a share of the roofline."""
    d = c["hidden_size"]
    hd = c["head_dim"]
    attention = 2 * d * c["num_attention_heads"] * hd \
        + 2 * d * c["num_key_value_heads"] * hd
    conv_dim = c["mamba_d_ssm"] + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    in_proj = c["mamba_d_ssm"] + conv_dim + c["mamba_n_heads"]
    mixer = d * in_proj + c["mamba_d_ssm"] * d
    mlp = 3 * d * c["intermediate_size"]
    return {"attention": attention, "mixer": mixer, "mlp": mlp,
            "lm_head": c["vocab_size"] * d}


def state_bytes_per_row(c: Dict[str, Any]) -> int:
    """One row's recurrent state in one layer: the scan's state in the
    configuration's `state_dtype`, the convolution's window in bf16."""
    ssm = c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"] \
        * STATE_BYTES[c.get("state_dtype", "float32")]
    conv_dim = c["mamba_d_ssm"] + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    return ssm + (c["mamba_d_conv"] - 1) * conv_dim * WEIGHT_BYTES


def decode_tick_bytes(c: Dict[str, Any], rows: float,
                      context_tokens: float) -> Dict[str, float]:
    """`rows` decoding rows whose contexts, each rounded up to whole pages,
    sum to `context_tokens`."""
    layers = c["num_hidden_layers"]
    p = matmul_params(c)
    weights = WEIGHT_BYTES * (
        layers * (p["attention"] + p["mixer"] + p["mlp"]) + p["lm_head"])
    state = 2.0 * rows * layers * state_bytes_per_row(c)   # read + written
    kv = float(context_tokens) * layers * 2 \
        * c["num_key_value_heads"] * c["head_dim"] * KV_BYTES
    return {"weights": float(weights), "state": state, "kv": kv,
            "total": weights + state + kv}


def window_tick_bytes(record: Dict[str, Any], began: float,
                      ended: float):
    """Mean bytes of the decode ticks the replica logged in [began, ended)
    (tick log rows: t0, t1, free pages, decoding rows, prefilling rows,
    page-rounded context tokens); None without such a tick."""
    ticks = [t for t in record["report"]["ticks"]
             if began <= t[0] < ended and t[3]]
    if not ticks:
        return None
    rows = sum(t[3] for t in ticks) / len(ticks)
    context = sum(t[5] for t in ticks) / len(ticks)
    return decode_tick_bytes(record["config"], rows, context)
