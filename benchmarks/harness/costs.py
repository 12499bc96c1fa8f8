"""Operations and bytes an algorithm needs, from its shapes alone. The
device's time for them comes from the trace; their quotient against the
peaks table is a roofline share. Kept with the benchmark so that no PR that
claims a gain can change the count.

FLOPs-per-token arithmetic copied from bench.py::main (6 N + the causal
attention term), with the causal half made explicit; see PERF.md's Open
questions for the original to delete."""

from __future__ import annotations

from typing import Any, Dict


def dense_decoder_params(c: Dict[str, Any]) -> Dict[str, int]:
    """Parameter counts of a dense decoder from its published keys."""
    d = c["hidden_size"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    attn = 2 * d * c["num_attention_heads"] * hd \
        + 2 * d * c["num_key_value_heads"] * hd
    mlp = 3 * d * c["intermediate_size"]
    layers = c["num_hidden_layers"] * (attn + mlp + 2 * d)
    embed = c["vocab_size"] * d
    head = 0 if c.get("tie_word_embeddings") else c["vocab_size"] * d
    return {"layers": layers, "embed": embed, "lm_head": head,
            "total": layers + embed + head + d}


def train_flops_per_token(c: Dict[str, Any], sequence: int) -> float:
    """Forward + backward FLOPs one token requires, recompute not counted:
    6 per parameter that multiplies (the embedding lookup multiplies
    nothing), plus causal attention: a token at position t attends t+1
    keys, (seq+1)/2 on average; QK^T and PV are 2 FLOPs x head_dim each per
    head per key, forward, and twice that backward."""
    p = dense_decoder_params(c)
    matmul_params = p["layers"] + p["lm_head"]
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    attention = 3 * (2 * 2 * c["num_attention_heads"] * hd) \
        * (sequence + 1) / 2 * c["num_hidden_layers"]
    return 6.0 * matmul_params + attention


FLASH_WORK = {"fwd": 1.0, "bwd_kv": 2.0, "bwd_q": 1.5}


def flash_flops(c: Dict[str, Any], batch: int, sequence: int,
                kind: str) -> float:
    """FLOPs one causal flash-attention kernel call over [batch, heads,
    seq, head_dim] requires. Forward: QK^T and PV, 2 x head_dim each per
    head per (query, visible key) pair. flash_bwd_kv has to form the
    scores, dP, dV and dK (twice the forward); flash_bwd_q the scores, dP
    and dQ (1.5 times)."""
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    pairs = batch * sequence * (sequence + 1) / 2
    return 4.0 * c["num_attention_heads"] * hd * pairs * FLASH_WORK[kind]


def paged_attention_bytes(c: Dict[str, Any], context_tokens: int,
                          page_size: int) -> float:
    """Bytes one paged-attention call (one layer, one decode tick) has to
    read: the K and V of every cached token of every active row, in whole
    pages, in the cache's 2-byte type. Queries and outputs are thousands of
    times smaller and are left out, which can only understate the share."""
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    pages = -(-context_tokens // page_size)
    return 2.0 * c["num_key_value_heads"] * hd * 2 * pages * page_size
