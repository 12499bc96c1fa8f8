"""Builder of the EvaByte configurations (EVA attention: an exact window
beside one learned summary a chunk of everything before it): from a config
file's keys (the published names of the source's config.json) to the
program's EvaByteConfig and PagedEngineConfig. Beside builders.py."""

from __future__ import annotations

from typing import Any, Dict

from .builders import REHEARSE_ENGINE, REHEARSE_MODEL as DENSE_REHEARSE
from .builders import jax_seed

# toy widths for --rehearse (CPU), in the published ratios: one query a kv
# head, eight prediction heads, W / C = 16 (>= 8) with a window of two of
# the rehearsal engine's largest bucket and two of its pages of summaries,
# small enough that windows close in prefill and in decode within seconds;
# the vocabulary is the one serve_cell draws rehearsal ids from. Nothing
# measured.
REHEARSE_MODEL = {"vocab_size": DENSE_REHEARSE["vocab_size"],
                  "hidden_size": 64, "intermediate_size": 172,
                  "num_hidden_layers": 2, "num_attention_heads": 4,
                  "num_key_value_heads": 4, "window_size": 64,
                  "chunk_size": 4}


def model_keys(config: Dict[str, Any], rehearse: bool = False
               ) -> Dict[str, Any]:
    """The file's keys as run: with the rehearsal's toy widths laid over
    them where asked, and checked against what the program builds."""
    c = dict(config)
    if rehearse:
        c.update(REHEARSE_MODEL)
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("EVA attention is built with one query a kv head")
    if c["hidden_size"] % c["num_attention_heads"]:
        raise ValueError("hidden_size is not whole heads")
    for key, built in (("attention_class", "eva"), ("hidden_act", "silu"),
                       ("norm_add_unit_offset", True),
                       ("fp32_skip_add", True), ("fp32_logits", True),
                       ("mixedp_attn", True), ("attention_bias", False),
                       ("tie_word_embeddings", False),
                       ("rope_scaling", None)):
        if c[key] != built:
            raise ValueError(f"{key}={c[key]!r}: the program builds "
                             f"{built!r} only")
    return c


def evabyte_model(config: Dict[str, Any], rehearse: bool = False,
                  **overrides):
    """EvaByteConfig from published key names. `overrides`: fields of the
    program's config a control sets (`pool_dtype`)."""
    import jax.numpy as jnp  # dtype names only; opens no backend
    from ray_tpu.models.evabyte import EvaByteConfig
    c = model_keys(config, rehearse)
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    return EvaByteConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        num_pred_heads=c["num_pred_heads"],
        window_size=c["window_size"], chunk_size=c["chunk_size"],
        rope_theta=float(c["rope_theta"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        # no table is built from it: the angles are taken at the positions
        max_seq_len=c["max_position_embeddings"],
        dtype=dtype, param_dtype=dtype,
        # the CPU has no Pallas kernels: the rehearsal takes the jnp paths
        attention_impl="reference" if rehearse else "flash", **overrides)


def evabyte_engine(config: Dict[str, Any], seed: int,
                   rehearse: bool = False, **overrides):
    """PagedEngineConfig of an EvaByte serve configuration file."""
    from ray_tpu.llm.paged import PagedEngineConfig
    e = dict(config["engine"])
    if rehearse:
        e.update(REHEARSE_ENGINE)
    return PagedEngineConfig(
        model=evabyte_model(config, rehearse, **overrides),
        max_batch=e["max_batch"], max_len=e["max_len"],
        page_size=e["page_size"], num_pages=e["num_pages"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        temperature=0.0, eos_token=None, seed=jax_seed(seed),
        prefill_decode_ratio=e.get("prefill_decode_ratio", 1))
