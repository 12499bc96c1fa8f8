"""Builder of the Falcon-H1 configurations: from a config file's keys (the
published names of the source's config.json) to the program's
FalconH1Config and PagedEngineConfig. Beside builders.py, which builds the
dense decoder's."""

from __future__ import annotations

from typing import Any, Dict

from .builders import REHEARSE_ENGINE, jax_seed

# toy widths for --rehearse (CPU), in the published ratios: 5:1 GQA, two
# groups, heads x head_dim != hidden_size; nothing measured
REHEARSE_MODEL = {"vocab_size": 512, "hidden_size": 96,
                  "intermediate_size": 160, "num_hidden_layers": 2,
                  "num_attention_heads": 10, "num_key_value_heads": 2,
                  "head_dim": 16, "mamba_d_ssm": 128, "mamba_n_heads": 8,
                  "mamba_d_head": 16, "mamba_d_state": 24,
                  "mamba_chunk_size": 16}


def falcon_h1_model(config: Dict[str, Any], rehearse: bool = False,
                    rotary_table: int = 0):
    """FalconH1Config from published key names. `rotary_table`: positions
    the rotary table must cover (the engine's max_len and its padding); 0
    takes the published max_position_embeddings."""
    import jax.numpy as jnp  # dtype names only; opens no backend
    from ray_tpu.models.falcon_h1 import FalconH1Config
    c = dict(config)
    if rehearse:
        c.update(REHEARSE_MODEL)
    if c["mamba_d_ssm"] != c["mamba_n_heads"] * c["mamba_d_head"]:
        raise ValueError("mamba_d_ssm != mamba_n_heads * mamba_d_head")
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    return FalconH1Config(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        max_seq_len=rotary_table or c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        mamba_d_ssm=c["mamba_d_ssm"], mamba_n_heads=c["mamba_n_heads"],
        mamba_d_state=c["mamba_d_state"], mamba_n_groups=c["mamba_n_groups"],
        mamba_d_conv=c["mamba_d_conv"],
        mamba_chunk_size=c["mamba_chunk_size"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        lm_head_multiplier=float(c["lm_head_multiplier"]),
        attention_in_multiplier=float(c["attention_in_multiplier"]),
        attention_out_multiplier=float(c["attention_out_multiplier"]),
        key_multiplier=float(c["key_multiplier"]),
        ssm_in_multiplier=float(c["ssm_in_multiplier"]),
        ssm_out_multiplier=float(c["ssm_out_multiplier"]),
        ssm_multipliers=tuple(float(m) for m in c["ssm_multipliers"]),
        mlp_multipliers=tuple(float(m) for m in c["mlp_multipliers"]),
        dtype=dtype, param_dtype=dtype,
        state_dtype=jnp.dtype(c.get("state_dtype", "float32")),
        # the CPU has no Pallas kernels: the rehearsal takes the jnp paths
        attention_impl="reference" if rehearse else "flash")


def falcon_h1_engine(config: Dict[str, Any], seed: int,
                     rehearse: bool = False):
    """PagedEngineConfig of a Falcon-H1 serve configuration file."""
    from ray_tpu.llm.paged import PagedEngineConfig
    e = dict(config["engine"])
    if rehearse:
        e.update(REHEARSE_ENGINE)
    # the last prefill chunk is bucket-rounded: positions run to max_len
    # plus the largest bucket
    table = e["max_len"] + e["prefill_buckets"][-1]
    return PagedEngineConfig(
        model=falcon_h1_model(config, rehearse, rotary_table=table),
        max_batch=e["max_batch"], max_len=e["max_len"],
        page_size=e["page_size"], num_pages=e["num_pages"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        temperature=0.0, eos_token=None, seed=jax_seed(seed),
        prefill_decode_ratio=e.get("prefill_decode_ratio", 1))
