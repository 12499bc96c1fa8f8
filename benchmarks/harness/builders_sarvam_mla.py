"""Builder of the Sarvam-105B configurations (multi-head latent attention
beside routed SwiGLU experts and a shared one): from a config file's keys
(the published names of the source's config.json) to the program's
SarvamMLAConfig and PagedEngineConfig. Beside builders.py and
builders_nemotron_h.py."""

from __future__ import annotations

from typing import Any, Dict

from .builders import REHEARSE_ENGINE, REHEARSE_MODEL as DENSE_REHEARSE
from .builders import jax_seed

# toy widths for --rehearse (CPU), in the published ratios: rank 4 x the
# nope width, rope half of it, v = nope, a leading dense layer 8 x the
# expert width, 8 experts a token of 128 / 16 held -> 2 of 16 / 4 held; the
# vocabulary is the one serve_cell draws rehearsal ids from. Nothing
# measured.
REHEARSE_MODEL = {"vocab_size": DENSE_REHEARSE["vocab_size"],
                  "hidden_size": 64, "intermediate_size": 128,
                  "num_hidden_layers": 3, "num_attention_heads": 4,
                  "kv_lora_rank": 32, "qk_nope_head_dim": 8,
                  "qk_rope_head_dim": 4, "q_head_dim": 12, "v_head_dim": 8,
                  "head_dim": 36, "moe_intermediate_size": 16,
                  "num_experts": 4, "held_experts": [4, 4],
                  "published": {"num_experts": 16},
                  "num_experts_per_tok": 2}
# pages of 8 would make a 40-token document five radix nodes; the cell's
# page (64) is to its documents (8k-32k) as 8 is to 1k-4k, which a CPU
# rehearsal cannot prefill: 16-token pages, 12-25 nodes a document
REHEARSE_ENGINE_MLA = dict(REHEARSE_ENGINE, page_size=16, max_len=640,
                           num_pages=512, max_batch=4)


def model_keys(config: Dict[str, Any], rehearse: bool = False
               ) -> Dict[str, Any]:
    """The file's keys as run: with the rehearsal's toy widths laid over
    them where asked, and checked against each other."""
    c = dict(config)
    if rehearse:
        c.update(REHEARSE_MODEL)
    if c["num_experts"] != c["held_experts"][1]:
        raise ValueError("num_experts is the count of experts held: it "
                         "must equal held_experts[1]")
    if c["head_dim"] != c["kv_lora_rank"] + c["qk_rope_head_dim"]:
        raise ValueError("head_dim is the cached row: kv_lora_rank + "
                         "qk_rope_head_dim")
    if c["q_head_dim"] != c["qk_nope_head_dim"] + c["qk_rope_head_dim"]:
        raise ValueError("q_head_dim != qk_nope_head_dim + qk_rope_head_dim")
    if c["rope_scaling"]["type"] != "deepseek_yarn":
        raise ValueError("only the deepseek_yarn table is built")
    return c


def sarvam_mla_model(config: Dict[str, Any], rehearse: bool = False,
                     positions: int = 0):
    """SarvamMLAConfig from published key names. `positions`: how far the
    engine's padded positions may run."""
    import jax.numpy as jnp  # dtype names only; opens no backend
    from ray_tpu.models.sarvam_mla import SarvamMLAConfig
    c = model_keys(config, rehearse)
    scaling = c["rope_scaling"]
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    return SarvamMLAConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        first_k_dense_replace=c["first_k_dense_replace"],
        num_experts=c["published"]["num_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        num_shared_experts=c["num_shared_experts"],
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
        dtype=dtype, param_dtype=dtype,
        # the CPU has no Pallas kernels: the rehearsal takes the jnp paths
        attention_impl="reference" if rehearse else "flash")


def sarvam_mla_engine(config: Dict[str, Any], seed: int,
                      rehearse: bool = False):
    """PagedEngineConfig of a Sarvam-105B serve configuration file."""
    from ray_tpu.llm.paged import PagedEngineConfig
    e = dict(config["engine"])
    if rehearse:
        e.update(REHEARSE_ENGINE_MLA)
    return PagedEngineConfig(
        model=sarvam_mla_model(
            config, rehearse, e["max_len"] + e["prefill_buckets"][-1]),
        max_batch=e["max_batch"], max_len=e["max_len"],
        page_size=e["page_size"], num_pages=e["num_pages"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        temperature=0.0, eos_token=None, seed=jax_seed(seed),
        prefill_decode_ratio=e.get("prefill_decode_ratio", 1))
