"""Builder of the Xing4.0-29B-A4B configurations (a four-stream residual
mixed by hyper-connections around latent attention with a query latent and
routed SwiGLU experts, all held): from a config file's keys (the published
names of the source's config.json) to the program's XingMHCConfig and
PagedEngineConfig. Beside builders.py and builders_sarvam_mla.py."""

from __future__ import annotations

from typing import Any, Dict

from .builders import REHEARSE_ENGINE, REHEARSE_MODEL as DENSE_REHEARSE
from .builders import jax_seed

# toy widths for --rehearse (CPU), in the published ratios: the kv latent
# 4 x the nope width, rope half of it, v = nope, the query latent 1.5 x the
# kv latent, a leading dense layer 9 x the expert width, 4 experts a token
# of 64 -> 2 of 8, all held, four streams; the vocabulary is the one
# serve_cell draws rehearsal ids from. Nothing measured.
REHEARSE_MODEL = {"vocab_size": DENSE_REHEARSE["vocab_size"],
                  "hidden_size": 64, "intermediate_size": 144,
                  "num_hidden_layers": 3, "num_attention_heads": 4,
                  "q_lora_rank": 48, "kv_lora_rank": 32,
                  "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
                  "v_head_dim": 8, "moe_intermediate_size": 16,
                  "n_routed_experts": 8, "held_experts": [0, 8],
                  "num_experts_per_tok": 2}
# pages of 16 and buckets of 16 / 32, as the latent path's other cell
# rehearses (builders_sarvam_mla.py)
REHEARSE_ENGINE_MHC = dict(REHEARSE_ENGINE, page_size=16, max_len=320,
                           num_pages=256, max_batch=4)


def model_keys(config: Dict[str, Any], rehearse: bool = False
               ) -> Dict[str, Any]:
    """The file's keys as run: with the rehearsal's toy widths laid over
    them where asked, and checked against each other."""
    c = dict(config)
    if rehearse:
        c.update(REHEARSE_MODEL)
    first, count = c["held_experts"]
    if first < 0 or first + count > c["n_routed_experts"]:
        raise ValueError("held_experts lies outside the router's width")
    if c["rope_scaling"]["type"] != "yarn":
        raise ValueError("only the yarn table (the tree's deepseek_yarn) "
                         "is built")
    if c["scoring_func"] != "sigmoid" or c["n_group"] != 1 \
            or c["topk_group"] != 1 or not c["norm_topk_prob"]:
        raise ValueError("only one group of sigmoid scores, normalised "
                         "over the chosen, is built")
    if c["mhc_h_res_clamp_min"] > c["mhc_h_res_clamp_max"]:
        raise ValueError("mhc_h_res_clamp_min > mhc_h_res_clamp_max")
    return c


def xing_mhc_model(config: Dict[str, Any], rehearse: bool = False,
                   positions: int = 0):
    """XingMHCConfig from published key names. `positions`: how far the
    engine's padded positions may run."""
    import jax.numpy as jnp  # dtype names only; opens no backend
    from ray_tpu.models.xing_mhc import XingMHCConfig
    c = model_keys(config, rehearse)
    scaling = c["rope_scaling"]
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    return XingMHCConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        first_k_dense_replace=c["first_k_dense_replace"],
        num_experts=c["n_routed_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        num_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        held_experts=tuple(c["held_experts"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        rope_factor=float(scaling["factor"]),
        rope_original_max=int(scaling["original_max_position_embeddings"]),
        rope_beta_fast=float(scaling["beta_fast"]),
        rope_beta_slow=float(scaling["beta_slow"]),
        rope_mscale=float(scaling["mscale"]),
        rope_mscale_all_dim=float(scaling["mscale_all_dim"]),
        max_seq_len=positions or c["max_position_embeddings"],
        hc_mult=c["hc_mult"], hc_sinkhorn_iters=c["hc_sinkhorn_iters"],
        hc_eps=float(c["hc_eps"]),
        mhc_h_res_clamp=(float(c["mhc_h_res_clamp_min"]),
                         float(c["mhc_h_res_clamp_max"])),
        dtype=dtype, param_dtype=dtype,
        # the CPU has no Pallas kernels: the rehearsal takes the jnp paths
        attention_impl="reference" if rehearse else "flash")


def xing_mhc_engine(config: Dict[str, Any], seed: int,
                    rehearse: bool = False):
    """PagedEngineConfig of a Xing4.0 serve configuration file."""
    from ray_tpu.llm.paged import PagedEngineConfig
    e = dict(config["engine"])
    if rehearse:
        e.update(REHEARSE_ENGINE_MHC)
    return PagedEngineConfig(
        model=xing_mhc_model(
            config, rehearse, e["max_len"] + e["prefill_buckets"][-1]),
        max_batch=e["max_batch"], max_len=e["max_len"],
        page_size=e["page_size"], num_pages=e["num_pages"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        temperature=0.0, eos_token=None, seed=jax_seed(seed),
        prefill_decode_ratio=e.get("prefill_decode_ratio", 1))
