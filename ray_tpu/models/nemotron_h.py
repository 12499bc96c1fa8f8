"""Hybrid decoder whose layers are each ONE of three mixers (the Nemotron-H
family with latent experts, `model_type: nemotron_h`; equations from the
published config's keys, which the field names below repeat).

    x0 = E[token]
    x  = x + mixer_kind(RMSNorm(x))        one pre-norm, one mixer a block
    logits = RMSNorm(x) W_head

`hybrid_override_pattern` gives each layer's kind, one character a layer:

  M  Mamba-2: `falcon_h1.MambaMixer` as it stands, with every multiplier 1
     (in_proj -> z | xBC | dt, causal depthwise conv + silu, the selective
     scan, gate, RMSNorm within each of `n_groups` groups, out_proj).
  *  attention: q, k, v projections, causal softmax(q k^T / sqrt(hd)) v
     with `num_attention_heads // num_key_value_heads` query heads a KV
     head, o_proj. NO positional embedding is applied, although the config
     carries `rope_theta`: the family's published description (arXiv:
     2504.03624, "no position embeddings") and forward pass; the scan
     layers carry order.
  E  latent mixture of experts: the router reads the full-width input in
     float32, s = sigmoid(u W_g); the `num_experts_per_tok` experts with
     the largest s + e_score_correction_bias are chosen and weigh
     routed_scaling_factor * s / sum of the chosen s. Experts live in a
     latent space: u_l = W_down u (hidden -> moe_latent_size, one
     projection a layer), E_i(u_l) = W2_i relu(W1_i u_l)^2 (not gated),
     out = W_up (sum_chosen w_i E_i(u_l)) + W2_s relu(W1_s u)^2, the shared
     expert on the full width.

`held_experts = (first, count)`: this chip's share of every E layer
(`moe.RoutedExperts`): the router keeps its width, the layer returns the
part of the sum its own experts give. The multi-token-prediction module
the config names (`num_nextn_predict_layers`) is a draft head for
speculative decoding and is not part of this model.

What a row carries between calls differs by layer kind: K/V pages (`*`),
the convolution's window and the scan state (`M`), nothing (`E`).
`layer_caches()` says which, `state_shapes()` / `init_state(rows)` make
the state of the scanning layers only, `init_counters()` the per-expert
accumulators an E layer carries through a decode step. Three paths, chosen
by `kv_caches` as in falcon_h1.py: None = the whole sequence from a zero
state; per-layer dicts = one paged decode token a row; per-layer tuples
(`(k, v)`, `(conv, ssm)` or `()`) = one chunk of a prefill whose first
`valid` tokens are real: the padded tail enters neither a scan state nor
an expert's count.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attend_cache
from ..ops.paged_attention import paged_attend
from .falcon_h1 import FalconH1Config, MambaMixer, _dense
from .llama import RMSNorm, _flash_on_mesh, _partitioned, write_token_rows
from .moe import RoutedExperts, relu2

KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    hybrid_override_pattern: str = "MEMEMEMEM*E"
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    rms_norm_eps: float = 1e-5
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    n_routed_experts: int = 512      # the router's width
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    # (first, count) of the routed experts this chip holds in every E layer
    held_experts: Tuple[int, int] = (0, 512)
    # the engine clamps padded positions to it; no rotary table is built
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    attention_impl: str = "flash"

    @property
    def num_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @property
    def head_dim_(self) -> int:
        return self.head_dim

    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(KINDS[c] for c in self.hybrid_override_pattern)

    def mixer_config(self) -> FalconH1Config:
        """The M layers' mixer as falcon_h1.MambaMixer reads it: the same
        equations with every multiplier 1. Its initialisers are Mamba-2's
        defaults, which are this family's published `time_step_min` 1e-3,
        `time_step_max` 0.1 (the floor 1e-4 lies under the range) and
        A = -U[1, 16]."""
        return FalconH1Config(
            hidden_size=self.hidden_size, rms_norm_eps=self.rms_norm_eps,
            mamba_d_ssm=self.mamba_num_heads * self.mamba_head_dim,
            mamba_n_heads=self.mamba_num_heads,
            mamba_d_state=self.ssm_state_size, mamba_n_groups=self.n_groups,
            mamba_d_conv=self.conv_kernel, mamba_chunk_size=self.chunk_size,
            dtype=self.dtype, param_dtype=self.param_dtype,
            state_dtype=self.state_dtype)

    # ---- what the paged engine asks of a model's configuration ----

    def module(self) -> "NemotronHModel":
        return NemotronHModel(self)

    def layer_caches(self) -> Tuple[Tuple[bool, bool, bool], ...]:
        """Per layer (keeps K/V pages, keeps recurrent state, carries
        expert counters through a decode step)."""
        return tuple((k == "attention", k == "mamba", k == "moe")
                     for k in self.layer_kinds())

    def state_shapes(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """What one row holds in one scanning (M) layer."""
        return self.mixer_config().state_shapes()

    def init_state(self, rows: int):
        """Zeroed (conv, ssm) for `rows` rows, one pair a scanning layer."""
        shapes = self.state_shapes()
        return [tuple(jnp.zeros((rows,) + shapes[k][0], shapes[k][1])
                      for k in ("conv", "ssm"))
                for kind in self.layer_kinds() if kind == "mamba"]

    def init_counters(self):
        """Per E layer, per held expert: (tokens routed to it, decode steps
        in which it had at least one), int32, on the device."""
        held = self.held_experts[1]
        return [(jnp.zeros((held,), jnp.int32), jnp.zeros((held,), jnp.int32))
                for kind in self.layer_kinds() if kind == "moe"]


class PositionlessAttention(nn.Module):
    """`cache` is None (whole sequence), a dict (paged decode) or a (k, v)
    pair of dense caches written at `cache_index` (a prefill chunk), as
    falcon_h1.HybridAttention; no rotary embedding, no multipliers."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, u, positions, cache=None, cache_index=None):
        cfg = self.config
        hd = cfg.head_dim
        q = _dense((cfg.num_heads, hd), ("embed", "heads", "head_dim"),
                   "q_proj", cfg)(u)
        k = _dense((cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                   "k_proj", cfg)(u)
        v = _dense((cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                   "v_proj", cfg)(u)
        q, k, v = (jnp.transpose(a, (0, 2, 1, 3)) for a in (q, k, v))
        new_cache = None
        if isinstance(cache, dict):
            kp, vp = cache["k"], cache["v"]
            tables, lengths = cache["block_tables"], cache["lengths"]
            rows = lambda a, pool: jnp.transpose(  # noqa: E731
                a[:, :, 0, :], (1, 0, 2)).astype(pool.dtype)
            kp = write_token_rows(kp, rows(k, kp), tables, lengths)
            vp = write_token_rows(vp, rows(v, vp), tables, lengths)
            out = paged_attend(q[:, :, 0, :], kp, vp, lengths, tables,
                               reference=cfg.attention_impl == "reference")
            out = out[:, :, None, :].astype(cfg.dtype)
            new_cache = (kp, vp)
        elif cache is not None:
            ck, cv = cache
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), cache_index, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), cache_index, axis=2)
            new_cache = (ck, cv)
            out = attend_cache(q, ck, cv, cache_index, positions)
        elif cfg.attention_impl == "reference":
            from ..ops.attention import attention_reference
            out = attention_reference(q, k, v, True)
        else:
            out = _flash_on_mesh(q, k, v)
        out = jnp.transpose(out, (0, 2, 1, 3))
        out = _dense(cfg.hidden_size, ("heads", "head_dim", "embed"),
                     "o_proj", cfg, axis=(-2, -1))(out)
        return out, new_cache


class LatentMoE(nn.Module):
    """An E layer. `mask` [batch, len] bool: the tokens that count (None:
    all). Returns (out, pairs [held]: tokens routed to each held expert)."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, u, mask=None):
        cfg = self.config
        with jax.named_scope("moe/latent"):
            latent = _dense(cfg.moe_latent_size, ("embed", "mlp"),
                            "latent_down", cfg)(u)
        routed, pairs = RoutedExperts(
            num_experts=cfg.n_routed_experts,
            experts_per_token=cfg.num_experts_per_tok,
            held=cfg.held_experts, mlp_dim=cfg.moe_intermediate_size,
            routed_scaling=cfg.routed_scaling_factor, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="routed")(u, latent, mask)
        with jax.named_scope("moe/latent"):
            routed = _dense(cfg.hidden_size, ("mlp", "embed"), "latent_up",
                            cfg)(routed.astype(cfg.dtype))
        with jax.named_scope("moe/shared"):
            hidden = _dense(cfg.moe_shared_expert_intermediate_size,
                            ("embed", "mlp"), "shared_up", cfg)(u)
            shared = _dense(cfg.hidden_size, ("mlp", "embed"),
                            "shared_down", cfg)(relu2(hidden))
        return routed + shared, pairs


class Block(nn.Module):
    config: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, cache=None, cache_index=None,
                 valid=None):
        cfg = self.config
        u = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        decoding = isinstance(cache, dict)
        new_cache = None
        if self.kind == "attention":
            mixed, new_cache = PositionlessAttention(cfg, name="attn")(
                u, positions, cache, cache_index)
        elif self.kind == "mamba":
            state = None if cache is None else \
                (cache["conv"], cache["ssm"]) if decoding else tuple(cache)
            mixed, new_cache = MambaMixer(cfg.mixer_config(), name="mamba")(
                u, state, cache["active"] if decoding else None, valid)
        else:
            mask = None
            if decoding:
                mask = cache["active"][:, None]
            elif valid is not None:
                mask = jnp.broadcast_to(
                    jnp.arange(x.shape[1]) < valid, x.shape[:2])
            mixed, pairs = LatentMoE(cfg, name="moe")(u, mask)
            if decoding:
                new_cache = (cache["pairs"] + pairs,
                             cache["steps"] + (pairs > 0).astype(jnp.int32))
            elif cache is not None:
                new_cache = ()
        return x + mixed.astype(x.dtype), new_cache


class NemotronHModel(nn.Module):
    """tokens -> logits; with `kv_caches`, (logits, per-layer tuples of
    what the layer's kind carries: (k, v), (conv, ssm), (pairs, steps) in
    paged decode and () in a prefill chunk for an E layer). `head=False`
    and the method `head` as `LlamaModel`'s: the final norm's output in
    place of the logits, and the head alone."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, tokens, positions=None, kv_caches=None,
                 cache_index=None, valid=None, head=True):
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None, :], tokens.shape)
        embed = self.param(
            "embed", _partitioned(nn.initializers.normal(0.02),
                                  ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        x = jnp.take(embed, tokens, axis=0).astype(cfg.dtype)
        new_caches = []
        for layer, kind in enumerate(cfg.layer_kinds()):
            cache = kv_caches[layer] if kv_caches is not None else None
            x, new_cache = Block(cfg, kind, name=f"layer_{layer}")(
                x, positions, cache, cache_index, valid)
            new_caches.append(new_cache)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="final_norm")(x)
        out = self.head(x) if head else x
        if kv_caches is not None:
            return out, new_caches
        return out

    @nn.compact
    def head(self, x):
        """Logits of the final norm's output `x` [batch, rows, hidden]."""
        cfg = self.config
        return _dense(cfg.vocab_size, ("embed", "vocab"), "lm_head", cfg)(x)
