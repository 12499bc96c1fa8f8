"""Builder of the SDAR-30B-A3B-Chat configurations (a Qwen3-MoE decoder that
generates by diffusion over blocks): from a config file's keys (the
published names of the source's config.json, and the generator's settings
the file lists under `assumed`) to the program's SdarConfig and
PagedEngineConfig. Beside builders.py and builders_lfm2.py."""

from __future__ import annotations

from typing import Any, Dict

from .builders import REHEARSE_ENGINE, REHEARSE_MODEL as DENSE_REHEARSE
from .builders import jax_seed

# toy widths for --rehearse (CPU), in the published ratios: 8 : 1 GQA is cut
# to 2 : 1, heads 16 wide, the expert width 3/8 of the hidden size, 2 experts
# a token of 8, all held; the vocabulary is the one serve_cell draws
# rehearsal ids from, the mask its last id. Nothing measured.
REHEARSE_MODEL = {"vocab_size": DENSE_REHEARSE["vocab_size"],
                  "hidden_size": 64, "num_hidden_layers": 2,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "head_dim": 16, "moe_intermediate_size": 24,
                  "num_experts": 8, "held_experts": [0, 8],
                  "num_experts_per_tok": 2,
                  "mask_token_id": DENSE_REHEARSE["vocab_size"] - 1}
# pages of 16 and buckets of 16 / 32: whole blocks of 4
REHEARSE_ENGINE_SDAR = dict(REHEARSE_ENGINE, page_size=16, max_len=320,
                            num_pages=256, max_batch=4)


def model_keys(config: Dict[str, Any], rehearse: bool = False
               ) -> Dict[str, Any]:
    """The file's keys as run (and as benchmarks/reference/sdar_ref.py
    reads them): with the rehearsal's toy widths laid over them where
    asked, and checked against each other."""
    c = dict(config)
    if rehearse:
        c.update(REHEARSE_MODEL)
    c.setdefault("held_experts", [0, c["num_experts"]])
    if c["decoder_sparse_step"] != 1 or c["mlp_only_layers"]:
        raise ValueError("only a stack whose every layer routes is built")
    if not c["norm_topk_prob"] or c["attention_bias"]:
        raise ValueError("only a router normalised over the chosen and "
                         "projections without bias are built")
    if c["rope_scaling"] or c["use_sliding_window"]:
        raise ValueError("only the plain rotary table over the whole "
                         "context is built")
    if c["tie_word_embeddings"]:
        raise ValueError("only an untied head is built")
    return c


reference_keys = model_keys


def sdar_model(config: Dict[str, Any], rehearse: bool = False,
               positions: int = 0):
    """SdarConfig from published key names. `positions`: how far the
    engine's padded positions may run (the rotary table's length)."""
    import jax.numpy as jnp  # dtype names only; opens no backend
    from ray_tpu.models.sdar import SdarConfig
    c = model_keys(config, rehearse)
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    return SdarConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        num_experts=c["num_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        held_experts=tuple(c["held_experts"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        max_seq_len=positions or c["max_position_embeddings"],
        block_length=int(c["block_length"]),
        mask_token_id=int(c["mask_token_id"]),
        denoising_steps=int(c.get("denoising_steps", c["block_length"])),
        remasking=c.get("remasking", "static"),
        confidence_threshold=float(c.get("confidence_threshold", 0.9)),
        embed_std=float(c.get("embedding_std", 0.02)),
        dtype=dtype, param_dtype=dtype,
        # the CPU has no Pallas kernels: the rehearsal takes the jnp paths
        attention_impl="reference" if rehearse else "flash")


def sdar_engine(config: Dict[str, Any], seed: int, rehearse: bool = False):
    """PagedEngineConfig of an SDAR serve configuration file."""
    from ray_tpu.llm.paged import PagedEngineConfig
    e = dict(config["engine"])
    if rehearse:
        e.update(REHEARSE_ENGINE_SDAR)
    return PagedEngineConfig(
        model=sdar_model(
            config, rehearse, e["max_len"] + e["prefill_buckets"][-1]),
        max_batch=e["max_batch"], max_len=e["max_len"],
        page_size=e["page_size"], num_pages=e["num_pages"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        temperature=0.0, eos_token=None, seed=jax_seed(seed),
        prefill_decode_ratio=e.get("prefill_decode_ratio", 1))
