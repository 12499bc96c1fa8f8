"""Builder of the Nemotron-H configurations (layers that are each a Mamba-2
mixer, an attention or a latent mixture of experts): from a config file's
keys (the published names of the source's config.json) to the program's
NemotronHConfig and PagedEngineConfig. Beside builders.py and
builders_falcon_h1.py."""

from __future__ import annotations

from typing import Any, Dict

from .builders import REHEARSE_ENGINE, REHEARSE_MODEL as DENSE_REHEARSE
from .builders import jax_seed

# toy widths for --rehearse (CPU), in the published ratios: 16:1 grouping,
# 8 norm groups, latent < hidden, k > 1, every layer kind, a quarter of the
# experts held; the vocabulary is the one serve_cell draws rehearsal ids
# from. Nothing measured.
REHEARSE_MODEL = {"vocab_size": DENSE_REHEARSE["vocab_size"],
                  "hidden_size": 64, "hybrid_override_pattern": "ME*EM",
                  "num_hidden_layers": 5, "num_attention_heads": 16,
                  "num_key_value_heads": 1, "head_dim": 8,
                  "mamba_num_heads": 16, "mamba_head_dim": 8, "n_groups": 8,
                  "ssm_state_size": 16, "chunk_size": 16,
                  "n_routed_experts": 4, "held_experts": [4, 4],
                  "published": {"n_routed_experts": 16},
                  "num_experts_per_tok": 3, "moe_latent_size": 32,
                  "moe_intermediate_size": 48,
                  "moe_shared_expert_intermediate_size": 96}


def model_keys(config: Dict[str, Any], rehearse: bool = False
               ) -> Dict[str, Any]:
    """The file's keys as run: with the rehearsal's toy widths laid over
    them where asked, and checked against each other."""
    c = dict(config)
    if rehearse:
        c.update(REHEARSE_MODEL)
    if c["num_hidden_layers"] != len(c["hybrid_override_pattern"]):
        raise ValueError("num_hidden_layers != len(hybrid_override_pattern)")
    if c["expand"] * c["hidden_size"] \
            != c["mamba_num_heads"] * c["mamba_head_dim"]:
        raise ValueError("expand * hidden_size != mamba heads * head dim")
    if c["n_routed_experts"] != c["held_experts"][1]:
        raise ValueError("n_routed_experts is the count of experts held: "
                         "it must equal held_experts[1]")
    if (c["time_step_min"], c["time_step_max"]) != (0.001, 0.1) \
            or c["time_step_floor"] > c["time_step_min"]:
        raise ValueError("the shared mixer's initialiser draws the time "
                         "step log-uniform in [1e-3, 1e-1] and has no floor")
    return c


def nemotron_h_model(config: Dict[str, Any], rehearse: bool = False,
                     positions: int = 0):
    """NemotronHConfig from published key names. `positions`: how far the
    engine's padded positions may run (no table is built from it)."""
    import jax.numpy as jnp  # dtype names only; opens no backend
    from ray_tpu.models.nemotron_h import NemotronHConfig
    c = model_keys(config, rehearse)
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    return NemotronHConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        hybrid_override_pattern=c["hybrid_override_pattern"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rms_norm_eps=float(c["layer_norm_epsilon"]),
        mamba_num_heads=c["mamba_num_heads"],
        mamba_head_dim=c["mamba_head_dim"], n_groups=c["n_groups"],
        ssm_state_size=c["ssm_state_size"], conv_kernel=c["conv_kernel"],
        chunk_size=c["chunk_size"],
        n_routed_experts=c["published"]["n_routed_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_latent_size=c["moe_latent_size"],
        moe_intermediate_size=c["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=c[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        held_experts=tuple(c["held_experts"]),
        max_seq_len=positions or c["max_position_embeddings"],
        dtype=dtype, param_dtype=dtype,
        state_dtype=jnp.dtype(c.get("state_dtype", "float32")),
        # the CPU has no Pallas kernels: the rehearsal takes the jnp paths
        attention_impl="reference" if rehearse else "flash")


def nemotron_h_engine(config: Dict[str, Any], seed: int,
                      rehearse: bool = False):
    """PagedEngineConfig of a Nemotron-H serve configuration file."""
    from ray_tpu.llm.paged import PagedEngineConfig
    e = dict(config["engine"])
    if rehearse:
        e.update(REHEARSE_ENGINE)
    return PagedEngineConfig(
        model=nemotron_h_model(
            config, rehearse, e["max_len"] + e["prefill_buckets"][-1]),
        max_batch=e["max_batch"], max_len=e["max_len"],
        page_size=e["page_size"], num_pages=e["num_pages"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        temperature=0.0, eos_token=None, seed=jax_seed(seed),
        prefill_decode_ratio=e.get("prefill_decode_ratio", 1))
