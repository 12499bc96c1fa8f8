"""Builder of the LFM2-24B-A2B configurations (gated short convolutions with
attention every fourth layer, over sigmoid-routed SwiGLU experts): from a
config file's keys (the published names of the source's config.json) to the
program's Lfm2Config and PagedEngineConfig. Beside builders.py and
builders_xing_mhc.py."""

from __future__ import annotations

from typing import Any, Dict

from .builders import REHEARSE_ENGINE, REHEARSE_MODEL as DENSE_REHEARSE
from .builders import jax_seed

# toy widths for --rehearse (CPU), in the published ratios: two leading
# conv + dense layers, then one whole period and a conv behind it, 4 : 1
# GQA, heads 16 wide (two kv heads pack into a 32-lane row), the dense
# width ~6 x and the expert width 3/4 of the hidden size, 2 experts a token
# of 16 with 4 held as the cell holds an eighth; the vocabulary is the one
# serve_cell draws rehearsal ids from. Nothing measured.
REHEARSE_MODEL = {"vocab_size": DENSE_REHEARSE["vocab_size"],
                  "hidden_size": 64, "intermediate_size": 368,
                  "num_hidden_layers": 7,
                  "layer_types": ["conv", "conv", "full_attention", "conv",
                                  "conv", "conv", "full_attention"],
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "moe_intermediate_size": 48, "num_experts": 4,
                  "held_experts": [4, 4], "published": {"num_experts": 16},
                  "num_experts_per_tok": 2}
# pages of 16 and buckets of 16 / 32: a bucket is whole pages
REHEARSE_ENGINE_LFM2 = dict(REHEARSE_ENGINE, page_size=16, max_len=320,
                            num_pages=256, max_batch=4)


def model_keys(config: Dict[str, Any], rehearse: bool = False
               ) -> Dict[str, Any]:
    """The file's keys as run: with the rehearsal's toy widths laid over
    them where asked, and checked against each other. `num_experts` counts
    the experts HELD (`reduced`); the router's width is the published
    count."""
    c = dict(config)
    if rehearse:
        c.update(REHEARSE_MODEL)
    if c["num_hidden_layers"] != len(c["layer_types"]):
        raise ValueError("num_hidden_layers != len(layer_types)")
    if set(c["layer_types"]) - {"conv", "full_attention"}:
        raise ValueError("layer_types other than conv / full_attention")
    first, count = c["held_experts"]
    width = c.get("published", {}).get("num_experts", c["num_experts"])
    if count != c["num_experts"] or first < 0 or first + count > width:
        raise ValueError("held_experts does not say num_experts of the "
                         "router's width")
    if c["conv_bias"] or not c["norm_topk_prob"] or not c["use_expert_bias"]:
        raise ValueError("only a filter without bias and a biased router "
                         "normalised over the chosen are built")
    if c["rope_parameters"]["rope_type"] != "default":
        raise ValueError("only the default rotary table is built")
    return c


def reference_keys(config: Dict[str, Any], rehearse: bool = False
                   ) -> Dict[str, Any]:
    """... and as benchmarks/reference/lfm2_ref.py reads them: the router's
    width back under `num_experts`."""
    c = model_keys(config, rehearse)
    c["num_experts"] = c.get("published", {}).get("num_experts",
                                                  c["num_experts"])
    return c


def lfm2_model(config: Dict[str, Any], rehearse: bool = False,
               positions: int = 0):
    """Lfm2Config from published key names. `positions`: how far the
    engine's padded positions may run (the rotary table's length)."""
    import jax.numpy as jnp  # dtype names only; opens no backend
    from ray_tpu.models.lfm2 import Lfm2Config
    c = reference_keys(config, rehearse)
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    return Lfm2Config(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        layer_types=tuple(c["layer_types"]),
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        conv_L_cache=c["conv_L_cache"],
        num_dense_layers=c["num_dense_layers"],
        num_experts=c["num_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        held_experts=tuple(c["held_experts"]),
        norm_eps=float(c["norm_eps"]),
        rope_theta=float(c["rope_parameters"]["rope_theta"]),
        max_seq_len=positions or c["max_position_embeddings"],
        dtype=dtype, param_dtype=dtype,
        # the CPU has no Pallas kernels: the rehearsal takes the jnp paths
        attention_impl="reference" if rehearse else "flash")


def lfm2_engine(config: Dict[str, Any], seed: int, rehearse: bool = False):
    """PagedEngineConfig of an LFM2 serve configuration file."""
    from ray_tpu.llm.paged import PagedEngineConfig
    e = dict(config["engine"])
    if rehearse:
        e.update(REHEARSE_ENGINE_LFM2)
    return PagedEngineConfig(
        model=lfm2_model(
            config, rehearse, e["max_len"] + e["prefill_buckets"][-1]),
        max_batch=e["max_batch"], max_len=e["max_len"],
        page_size=e["page_size"], num_pages=e["num_pages"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        temperature=0.0, eos_token=None, seed=jax_seed(seed),
        prefill_decode_ratio=e.get("prefill_decode_ratio", 1))
