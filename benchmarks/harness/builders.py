"""Builders the configuration files name: from a config file's keys (the
published names of the source's config.json) to the objects the program
takes. A configuration of another architecture brings a builder of its own
in a file of its own and names it under "builder"."""

from __future__ import annotations

from typing import Any, Dict

# toy widths for --rehearse (CPU): same keys, nothing measured
REHEARSE_MODEL = {"vocab_size": 512, "hidden_size": 64,
                  "intermediate_size": 128, "num_hidden_layers": 2,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "head_dim": 16}
REHEARSE_ENGINE = {"max_batch": 4, "max_len": 320, "page_size": 8,
                   "num_pages": 256, "prefill_buckets": [16, 32]}


def jax_seed(seed: int) -> int:
    """--seed may pass 2**31; a PRNGKey takes 32 signed bits."""
    return int(seed) % (2 ** 31 - 1)


def llama_model(config: Dict[str, Any], rehearse: bool = False):
    """LlamaConfig (the repo's dense decoder) from published key names."""
    import jax.numpy as jnp  # dtype names only; opens no backend
    from ray_tpu.models.llama import LlamaConfig
    c = dict(config)
    if rehearse:
        c.update(REHEARSE_MODEL)
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    return LlamaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim"),
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        dtype=dtype, param_dtype=dtype,
        remat=bool(c.get("trainer", {}).get("remat", True)),
        # the CPU has no Pallas kernels: the rehearsal takes the jnp paths
        use_flash=not rehearse,
        attention_impl="reference" if rehearse else "flash")


def llama_engine(config: Dict[str, Any], seed: int, rehearse: bool = False):
    """PagedEngineConfig of a serve configuration file."""
    from ray_tpu.llm.paged import PagedEngineConfig
    e = dict(config["engine"])
    if rehearse:
        e.update(REHEARSE_ENGINE)
    return PagedEngineConfig(
        model=llama_model(config, rehearse), max_batch=e["max_batch"],
        max_len=e["max_len"], page_size=e["page_size"],
        num_pages=e["num_pages"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        temperature=0.0, eos_token=None, seed=jax_seed(seed),
        prefill_decode_ratio=e.get("prefill_decode_ratio", 1))


def llama_train(config: Dict[str, Any], rehearse: bool = False):
    """What the train loop needs of a dense-decoder configuration: the flax
    module, the loss the step differentiates, the optimizer, and the plain
    reference's loss on the same weights."""
    import optax
    from ray_tpu.models import LlamaModel, cross_entropy_loss
    model_cfg = llama_model(config, rehearse)
    module = LlamaModel(model_cfg)
    trainer = config["trainer"]
    if trainer["optimizer"] != "adafactor":
        raise ValueError(f"optimizer {trainer['optimizer']!r} not built")
    tx = optax.chain(
        optax.clip_by_global_norm(float(trainer["clip_global_norm"])),
        optax.adafactor(learning_rate=float(trainer["learning_rate"])))

    def loss_fn(params, data):
        logits = module.apply({"params": params}, data["tokens"])
        return cross_entropy_loss(logits[:, :-1], data["tokens"][:, 1:])

    def reference_check(params, first_batch, first_loss):
        from .parity import train_loss
        return train_loss(params, first_batch, first_loss, model_cfg)

    return {"module": module, "model_cfg": model_cfg, "tx": tx,
            "loss_fn": loss_fn, "reference_check": reference_check,
            "vocab_size": model_cfg.vocab_size}
