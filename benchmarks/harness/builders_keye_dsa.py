"""Builder of the Keye-VL-2.0-30B-A3B configurations (learned sparse
attention beside softmax-routed SwiGLU experts): from a config file's keys
(the published names of the source's config.json) to the program's
KeyeDSAConfig and PagedEngineConfig. Beside builders.py and
builders_sarvam_mla.py."""

from __future__ import annotations

from typing import Any, Dict

from .builders import REHEARSE_ENGINE, REHEARSE_MODEL as DENSE_REHEARSE
from .builders import jax_seed

# toy widths for --rehearse (CPU), in the published ratios: 8 heads to a kv
# head -> 2, the index key half a head, half as many index heads as heads,
# 8 experts a token of 128 / 16 held -> 2 of 16 / 4 held, and a selection
# (24) far under the rehearsal's documents (~190-520 tokens) as 2048 is
# under 16k-64k; the vocabulary is the one serve_cell draws rehearsal ids
# from. Nothing measured.
REHEARSE_MODEL = {
    "vocab_size": DENSE_REHEARSE["vocab_size"], "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 16,
    "num_experts": 4, "num_local_experts": 4, "held_experts": [4, 4],
    "published": {"num_experts": 16}, "num_experts_per_tok": 2,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 24}}
REHEARSE_ENGINE_DSA = dict(REHEARSE_ENGINE, page_size=16, max_len=640,
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
    if c["sa_config"]["indexer_num_kv_heads"] != 1:
        raise ValueError("one index key a token a layer is what is built")
    if c["mlp_only_layers"] or c["decoder_sparse_step"] != 1:
        raise ValueError("every layer is an expert layer in what is built")
    if c["rope_scaling"]["rope_type"] != "default":
        raise ValueError("only the default rotary table (mrope sections "
                         "over equal text positions) is built")
    return c


def keye_dsa_model(config: Dict[str, Any], rehearse: bool = False,
                   positions: int = 0):
    """KeyeDSAConfig from published key names. `positions`: how far the
    engine's padded positions may run."""
    import jax.numpy as jnp  # dtype names only; opens no backend
    from ray_tpu.models.keye_dsa import KeyeDSAConfig
    c = model_keys(config, rehearse)
    sa = c["sa_config"]
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    return KeyeDSAConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        num_experts=c["published"]["num_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        held_experts=tuple(c["held_experts"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        max_seq_len=positions or c["max_position_embeddings"],
        dtype=dtype, param_dtype=dtype,
        attention_impl="reference" if rehearse else "flash")


def keye_dsa_engine(config: Dict[str, Any], seed: int,
                    rehearse: bool = False):
    """PagedEngineConfig of a Keye-VL-2.0 serve configuration file."""
    from ray_tpu.llm.paged import PagedEngineConfig
    e = dict(config["engine"])
    if rehearse:
        e.update(REHEARSE_ENGINE_DSA)
    return PagedEngineConfig(
        model=keye_dsa_model(
            config, rehearse, e["max_len"] + e["prefill_buckets"][-1]),
        max_batch=e["max_batch"], max_len=e["max_len"],
        page_size=e["page_size"], num_pages=e["num_pages"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        temperature=0.0, eos_token=None, seed=jax_seed(seed),
        prefill_decode_ratio=e.get("prefill_decode_ratio", 1))
