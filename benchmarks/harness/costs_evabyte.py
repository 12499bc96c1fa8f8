"""Bytes one decode step of an EvaByte decoder (EVA attention) has to move,
from its shapes alone: the weights that multiply (each once a step,
whatever the batch; of the head, the columns of the one prediction head
the engine samples) and the pages every decoding row HOLDS, whole: the
summaries of its closed windows and the K/V of its open one. A row's pages
are not its length over the page size (a row of 5000 bytes holds 73, not
313), so the count takes pages, which the cell's replica logs a tick.
Their sum over the published HBM bandwidth is the least time a step can
take; kept with the benchmark, as costs.py is, so that no PR that claims a
gain can change the count. Keys are the published names of the source's
config.json.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

WEIGHT_BYTES = 2   # bf16
KV_BYTES = 2       # the page pool's type


def head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def params(c: Dict[str, Any]) -> Dict[str, int]:
    """Parameter counts by part. `pooling`: phi and mu, a vector a head
    each; `norms`: two a layer and the final one."""
    d = c["hidden_size"]
    return {"attention": 4 * d * c["num_attention_heads"] * head_dim(c),
            "pooling": 2 * c["num_attention_heads"] * head_dim(c),
            "mlp": 3 * d * c["intermediate_size"],
            "norms_per_layer": 2 * d,
            "embedding": c["vocab_size"] * d,
            "head": d * c["num_pred_heads"] * c["vocab_size"],
            "head_sampled": d * c["vocab_size"],
            "final_norm": d}


def layer_params(c: Dict[str, Any]) -> int:
    p = params(c)
    return p["attention"] + p["pooling"] + p["mlp"] + p["norms_per_layer"]


def total_params(c: Dict[str, Any], layers: Optional[int] = None) -> int:
    p = params(c)
    layers = c["num_hidden_layers"] if layers is None else layers
    return layers * layer_params(c) + p["embedding"] + p["head"] \
        + p["final_norm"]


def page_bytes(c: Dict[str, Any], page_size: int, layers: int = 0) -> int:
    """One page (page_size rows of K and of V, every head) in `layers`
    layers (0: all the file has)."""
    return (layers or c["num_hidden_layers"]) * 2 * page_size \
        * c["num_key_value_heads"] * head_dim(c) * KV_BYTES


def pages_held(c: Dict[str, Any], length: int, page_size: int) -> int:
    """Pages a row of `length` positions holds: `window_size / chunk_size`
    summary rows a closed window, then the open window's positions."""
    rows = (length // c["window_size"]) \
        * (c["window_size"] // c["chunk_size"]) + length % c["window_size"]
    return -(-rows // page_size)


def decode_step_bytes(c: Dict[str, Any], pages: float, page_size: int
                      ) -> Dict[str, float]:
    """A decode step whose decoding rows hold `pages` pages together. The
    embedding is a lookup (a row a token), phi and mu are read by
    `compress_window` and not by a step, the norms are thousands of times
    smaller: left out, which can only understate a share of the roofline."""
    p = params(c)
    weights = WEIGHT_BYTES * (
        c["num_hidden_layers"] * (p["attention"] + p["mlp"])
        + p["head_sampled"])
    cache = float(pages) * page_bytes(c, page_size)
    return {"weights": float(weights), "cache": cache,
            "total": weights + cache}


def compress_bytes(c: Dict[str, Any], page_size: int) -> int:
    """What one `compress_window` call has to move: a window's pages read,
    its summaries' pages written, in every layer."""
    window = c["window_size"] // page_size
    kept = c["window_size"] // c["chunk_size"] // page_size
    return (window + kept) * page_bytes(c, page_size)


def window_pages(record: Dict[str, Any], began: float, ended: float
                 ) -> Optional[float]:
    """Mean pages the decoding rows held together over the decode ticks
    the replica logged in [began, ended) (`page_ticks` rows: t0, decoding
    rows, pages they hold); None without such a tick (a replica that logs
    none: another cell's, an older program's)."""
    ticks = [t for t in record["report"].get("page_ticks", ())
             if began <= t[0] < ended and t[1]]
    if not ticks:
        return None
    return sum(t[2] for t in ticks) / len(ticks)


def window_step_bytes(record: Dict[str, Any], began: float, ended: float
                      ) -> Optional[Dict[str, float]]:
    pages = window_pages(record, began, ended)
    if pages is None:
        return None
    return decode_step_bytes(record["config"], pages,
                             record["report"]["page_size"])
