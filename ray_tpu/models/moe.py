"""Mixture-of-Experts layer with expert parallelism.

TPU-native design (SURVEY §2d requires EP first-class; the reference
delegates it to vLLM engine kwargs — vllm_models.py:234): GShard/Switch
dense dispatch. Routing produces a dispatch mask [tokens, E, capacity] and
combine weights; einsums move tokens to per-expert buffers laid out on the
`expert` mesh axis (GSPMD lowers the dispatch/combine einsums to
all-to-alls over ICI), experts run batched on the MXU, outputs combine
back. Top-k routing with capacity dropping + load-balance aux loss
(Switch Transformer §2.2)."""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import ROW_TILE, grouped_experts, kernel_takes
from .llama import _partitioned


def _top_k_routing(logits, k: int):
    """Per-token top-k expert choice with renormalized weights."""
    weights = jax.nn.softmax(logits, axis=-1)  # [T, E]
    top_w, top_idx = jax.lax.top_k(weights, k)  # [T, k]
    top_w = top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9)
    return weights, top_w, top_idx


class MoELayer(nn.Module):
    """Drop-in FFN replacement: route tokens to num_experts expert MLPs.

    capacity = capacity_factor * tokens * k / num_experts per expert;
    overflow tokens are dropped (their combine weight is zero and the
    residual path carries them — standard Switch behavior)."""
    num_experts: int
    embed_dim: int
    mlp_dim: int
    num_experts_per_token: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32
    router_aux_weight: float = 0.01

    @nn.compact
    def __call__(self, x) -> Tuple[jax.Array, jax.Array]:
        # x: [batch, seq, embed] -> flatten tokens
        B, S, D = x.shape
        E, K = self.num_experts, self.num_experts_per_token
        T = B * S
        tokens = x.reshape(T, D)

        router_kernel = self.param(
            "router", _partitioned(nn.initializers.normal(0.02),
                                   ("embed", "expert")),
            (D, E), jnp.float32)
        logits = tokens.astype(jnp.float32) @ router_kernel  # [T, E]
        weights, top_w, top_idx = _top_k_routing(logits, K)

        capacity = max(1, int(self.capacity_factor * T * K / E))

        # Position of each (token, choice) in its expert's buffer: the
        # cumulative count of earlier assignments to the same expert.
        # one-hot: [T, K, E]
        assign = jax.nn.one_hot(top_idx, E, dtype=jnp.int32)
        flat_assign = assign.reshape(T * K, E)
        positions = (jnp.cumsum(flat_assign, axis=0) - 1).reshape(T, K, E)
        position_in_expert = (positions * assign).sum(-1)  # [T, K]
        kept = ((position_in_expert < capacity) &
                (assign.sum(-1) > 0)).astype(x.dtype)  # [T, K]

        # dispatch[t, e, c] = 1 where token t sits in slot c of expert e
        slot_onehot = jax.nn.one_hot(position_in_expert, capacity,
                                     dtype=x.dtype)  # [T, K, C]
        dispatch = jnp.einsum("tke,tkc->tec",
                              assign.astype(x.dtype) *
                              kept[..., None], slot_onehot)
        combine = jnp.einsum("tke,tkc->tec",
                             (assign.astype(x.dtype) *
                              (top_w * kept)[..., None]), slot_onehot)

        # To expert buffers: [E, C, D] (sharded on the expert mesh axis —
        # GSPMD turns this einsum into the all-to-all dispatch).
        expert_in = jnp.einsum("tec,td->ecd", dispatch, tokens)
        expert_in = nn.with_logical_constraint(
            expert_in, ("expert", None, "embed"))

        init = nn.initializers.normal(0.02)
        wi_gate = self.param("wi_gate",
                             _partitioned(init, ("expert", "embed", "mlp")),
                             (E, D, self.mlp_dim), self.dtype)
        wi_up = self.param("wi_up",
                           _partitioned(init, ("expert", "embed", "mlp")),
                           (E, D, self.mlp_dim), self.dtype)
        wo = self.param("wo",
                        _partitioned(init, ("expert", "mlp", "embed")),
                        (E, self.mlp_dim, D), self.dtype)
        h = jax.nn.silu(jnp.einsum("ecd,edm->ecm", expert_in, wi_gate)) * \
            jnp.einsum("ecd,edm->ecm", expert_in, wi_up)
        expert_out = jnp.einsum("ecm,emd->ecd", h, wo)
        expert_out = nn.with_logical_constraint(
            expert_out, ("expert", None, "embed"))

        out = jnp.einsum("tec,ecd->td", combine, expert_out)

        # Load-balance aux loss (Switch §2.2): E * sum_e f_e * P_e where
        # f_e = fraction of tokens routed (top-1) to e, P_e = mean router
        # probability for e.
        f = jnp.mean(jax.nn.one_hot(top_idx[:, 0], E, dtype=jnp.float32),
                     axis=0)
        p = jnp.mean(weights, axis=0)
        aux_loss = self.router_aux_weight * E * jnp.sum(f * p)

        return out.reshape(B, S, D), aux_loss


# ---------------------------------------------------------------------------
# A served expert layer: no capacity, no dropped pair, told which experts
# it holds
# ---------------------------------------------------------------------------

F32 = jnp.float32


def sigmoid_top_k(u, router, bias, k: int, scale: float):
    """The router of a sigmoid-scored mixture (DeepSeek-V3's, which the
    Nemotron-H family's expert layers repeat), in float32 whatever the
    model computes in: scores s = sigmoid(u W_g) over ALL experts; the k
    experts with the largest s + bias are chosen (the bias chooses and
    does not weigh); their weights are scale * s / (sum of the chosen s
    + 1e-20). The 1e-20 is DeepSeek-V3's constant, which Nemotron, Sarvam
    and Xing publish too; LFM2 publishes 1e-6 there and is served with
    this one all the same: four sigmoid scores sum to about 2, so the
    departure is 5e-7 of a weight, a hundredth of a bf16 rounding of the
    products the weight multiplies (settled in PR 56: one router for every
    family; the configuration file lists it under `assumed`, and the
    reference keeps the published 1e-6).
    u [T, d]; router [d, E]; bias [E]. Returns (chosen [T, k] int32,
    weights [T, k] float32, scores [T, E] float32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        u.astype(F32), router.astype(F32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias.astype(F32), k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), weights, scores


def softmax_top_k(u, router, k: int):
    """The router of a softmax-scored mixture with renormalised weights
    (`norm_topk_prob`), in float32 whatever the model computes in: the map
    `MoELayer` routes by (`_top_k_routing`), over logits u W_r taken at the
    highest precision. u [T, d]; router [d, E]. Returns (chosen [T, k]
    int32, weights [T, k] float32 that sum to 1, probabilities [T, E]
    float32)."""
    probs, weights, chosen = _top_k_routing(jnp.dot(
        u.astype(F32), router.astype(F32),
        precision=jax.lax.Precision.HIGHEST), k)
    return chosen.astype(jnp.int32), weights, probs


def relu2(h):
    return jnp.square(jax.nn.relu(h))


# Tokens a call from which the routed experts take the sorted form
# (`sorted_form`). One expert layer alone on a v5e, ms, bf16 matrices, float32
# sums, the seeded router on random tokens (PERF.md section 6, PR 53, step 0:
# `_scratch/probe53.py`, each form eight times in one program; "gmm" is the
# same sorted form through megablox's kernel):
#
#   shape (held of E, k, l x f)           T    dense  sorted  gmm
#   Xing      64 of  64,  4, 3584 x 1024  512  4.54   2.32    2.39
#     gated                               256  2.46   2.11    2.37
#                                         128  2.15   2.00    2.03
#                                          64  2.05   1.90    2.02
#   Keye      16 of 128,  8, 2048 x  768  512  0.52   0.41    0.42
#     gated                               256  0.26   0.29    0.33
#   Sarvam    16 of 128,  8, 4096 x 2048  256  1.52   1.34    1.35
#     gated                               128  1.22   1.20    1.19
#   Nemotron 128 of 512, 22, 1024 x 2688  256  2.03   2.08    2.12
#     not gated
#
# The dense form's FLOPs over its bytes is T; the chip's ridge is ~240. At
# 512 the sorted form wins at every width the repo has, by 2 x where every
# expert is held. At 256 it wins by a seventh and an eighth at Xing's and
# Sarvam's widths and loses at Keye's (a 151 MB read: the sort and the two
# gathers, ~0.1 ms, are a third of the layer) and at Nemotron's (22 choices
# a token: 5,632 pairs to sort and carry for 1,408 held); ISSUE 53 holds
# every program of 256 tokens and fewer to the parent's text, so the
# constant stands at the bucket above (ROADMAP S15: what is left).
SORTED_FROM_TOKENS = 512


def sorted_form(tokens: int, width: int, mlp_dim: int) -> bool:
    """Does `held_expert_sum` take the sorted form for `tokens` tokens a
    call and matrices [width, mlp_dim]? A function of static shapes alone,
    the same on every backend (the engine counts its chunks by it): from
    `SORTED_FROM_TOKENS` tokens, where the matrices are what the grouped
    kernels take (`ops.grouped_matmul.kernel_takes`: whole lane tiles)."""
    return tokens >= SORTED_FROM_TOKENS and kernel_takes(width, mlp_dim)


def sorted_buckets(params, buckets):
    """The token counts of `buckets` at which the routed experts of a model
    with these parameters take the sorted form, or None where the tree holds
    no routed experts (no `w_in` [held, l, f]). The engine counts its
    chunks by it: a chunk is one row of `bucket` tokens."""
    shapes = {leaf.shape[-2:] for path, leaf
              in jax.tree_util.tree_flatten_with_path(params)[0]
              if leaf.ndim == 3 and any(
                  getattr(key, "key", None) == "w_in" for key in path)}
    if not shapes:
        return None
    return frozenset(bucket for bucket in buckets
                     if any(sorted_form(bucket, *shape) for shape in shapes))


def held_expert_sum(x, chosen, weights, mask, w_in, w_out, first: int,
                    w_gate=None):
    """The part of sum_i w_i E_i(x) that the experts held here give, with
    E_i(x) = w_out[i] relu(w_in[i] x)^2, or, where `w_gate` [held, l, f] is
    given, the gated E_i(x) = w_out[i] (silu(w_gate[i] x) * w_in[i] x)
    (SwiGLU, three matrices an expert: `w_in` is then the up projection),
    for every token at once and with no capacity: every (token, choice)
    pair whose expert is held is computed, however the pairs fall.
    `w_gate=None` is static and leaves the two-matrix program as it was.

    x [T, l]; chosen, weights [T, k] (over all experts); mask [T] bool
    (False: the token is padding or an idle row, and counts nowhere);
    w_in [held, l, f], w_out [held, f, l]; the held experts are
    first .. first + held - 1. Returns (out [T, l] float32, pairs [held]
    int32: the tokens routed to each held expert).

    ONE sum, two schedules, chosen at trace time from T and the matrices'
    shapes (`sorted_form`; no option, no field of any config):

      * dense (`_dense_expert_sum`), a decode step and every chunk under
        `SORTED_FROM_TOKENS`: every held expert applied to every token.
        One read of the held matrices whatever the routing, and
        4 T held l f FLOPs that stay under that read's time while T is
        under the chip's ridge;
      * sorted (`_sorted_expert_sum`), the chunks from there up: the T x k
        pairs sorted by expert and multiplied group by group, each held
        expert's matrices read once and applied to the rows that chose it.

    Both are bf16 products with float32 sums over the same pairs; what the
    sorted form leaves out is the pairs nobody chose, whose weight in the
    dense form is an exact 0."""
    if sorted_form(x.shape[0], w_in.shape[1], w_in.shape[2]):
        return _sorted_expert_sum(x, chosen, weights, mask, w_in, w_out,
                                  first, w_gate)
    return _dense_expert_sum(x, chosen, weights, mask, w_in, w_out, first,
                             w_gate)


def _dense_expert_sum(x, chosen, weights, mask, w_in, w_out, first: int,
                      w_gate=None):
    """`held_expert_sum`, every held expert on every token: the pair
    weights, scattered to [T, held] (0 where the expert was not chosen),
    sum the results. No sort, no gather, one read of every held expert's
    matrices whatever the routing, which is what a step must read anyway
    once every held expert has a token (a balanced router at 96 rows leaves
    a held expert without one 1.5 % of the time). The cost grows with
    T x held, not with the pairs: with 64 gated experts 3584 x 1024 held, 4
    a token (PERF.md section 6, PR 52) a layer reads 1.41 GB, 1.72 ms at the
    chip's bandwidth; at 48 rows the form takes 2.0 ms a layer, within a
    sixth of that read; at 512 tokens 4.3 ms, the 721 GFLOP of T x held at
    85 % of the MXU's peak (the table above `SORTED_FROM_TOKENS`)."""
    held = w_in.shape[0]
    local = chosen - first
    mine = (local >= 0) & (local < held) & mask[:, None]
    taken = (local[..., None] == jnp.arange(held)) & mine[..., None]
    per_expert = jnp.where(taken, weights[..., None], 0.0).sum(1)  # [T, held]
    hidden = jnp.einsum("tl,elf->etf", x.astype(w_in.dtype), w_in,
                        preferred_element_type=F32)
    if w_gate is None:
        hidden = relu2(hidden)
    else:
        hidden = jax.nn.silu(jnp.einsum(
            "tl,elf->etf", x.astype(w_gate.dtype), w_gate,
            preferred_element_type=F32)) * hidden
    y = jnp.einsum("etf,efl->etl", hidden.astype(w_out.dtype), w_out,
                   preferred_element_type=F32)
    return jnp.einsum("te,etl->tl", per_expert, y), \
        taken.sum((0, 1)).astype(jnp.int32)


def _sorted_expert_sum(x, chosen, weights, mask, w_in, w_out, first: int,
                       w_gate=None):
    """`held_expert_sum`, the pairs sorted by expert: a pair's key is its
    expert's place among the held, or `held` where the expert is held
    elsewhere or the token masked, a last group that no product visits; a
    stable sort; the pairs' tokens gathered in that order [T k, l]; the
    grouped products (`ops.grouped_matmul`: gate and up from one pass over
    the rows, float32 sums, the hidden rows in the matrices' type before
    `w_out` as in the dense form); the inverse permutation; the k results of
    a token summed under their float32 weights. `pairs` is the groups'
    sizes. No capacity and no padding of a group: no pair is dropped at any
    imbalance. Rows past the last real group hold whatever the kernel found
    there: they are selected out, never multiplied by zero."""
    held = w_in.shape[0]
    tokens, k = chosen.shape
    local = chosen - first
    mine = (local >= 0) & (local < held) & mask[:, None]
    key = jnp.where(mine, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    pairs = (key[:, None] == jnp.arange(held)).sum(0).astype(jnp.int32)
    rows = x.astype(w_in.dtype)[order // k]
    # whole row tiles; the rows added lie behind the last group
    rows = jnp.pad(rows, ((0, -rows.shape[0] % ROW_TILE), (0, 0)))
    y = grouped_experts(rows, w_in, w_out, pairs, w_gate)
    y = y[jnp.argsort(order)].reshape(tokens, k, -1)
    return jnp.where(mine[..., None], y * weights[..., None], 0.0).sum(1), \
        pairs


class RoutedExperts(nn.Module):
    """The routed experts of one layer as ONE chip of an expert-parallel
    deployment holds them: the router keeps its width (`num_experts`) and
    its `experts_per_token`, the chip holds experts `held[0] ..
    held[0] + held[1] - 1`, and the layer returns the part of the weighted
    sum that those give. Nothing stands in for the absent chips or their
    exchange; the shares of all chips add up to the whole layer
    (tests/test_nemotron_h.py). No capacity: no pair is dropped at any
    imbalance (`held_expert_sum`, which applies every held expert to every
    token in a step and a small chunk and sorts the pairs into grouped
    products from `SORTED_FROM_TOKENS` tokens a call: one sum, the
    schedule read from the call's static shapes).

    `u` [.., d] is what the router reads, `x` [.., l] what the experts
    read and write (the same array unless the experts live in a latent
    space). Experts are not gated, E(x) = W2 relu(W1 x)^2, unless `gated`:
    E(x) = W_d (silu(W_g x) * W_u x), with a third matrix `w_gate` an
    expert (`w_in` is W_u, `w_out` W_d). `scoring` is the router's map:
    "sigmoid" (`sigmoid_top_k`, with its bias and `routed_scaling`) or
    "softmax" (`softmax_top_k`: no bias, weights that sum to 1).
    Returns (out [.., l] float32, pairs [held] int32)."""
    num_experts: int
    experts_per_token: int
    held: Tuple[int, int]
    mlp_dim: int
    routed_scaling: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    gated: bool = False
    scoring: str = "sigmoid"

    @nn.compact
    def __call__(self, u, x, mask=None):
        lead, d, width = u.shape[:-1], u.shape[-1], x.shape[-1]
        first, held = self.held
        router = self.param(
            "router", _partitioned(nn.initializers.lecun_normal(),
                                   ("embed", None)),
            (d, self.num_experts), self.param_dtype)
        bias = self.param(
            "e_score_correction_bias",
            _partitioned(nn.initializers.zeros, (None,)),
            (self.num_experts,), F32) if self.scoring == "sigmoid" else None
        w_in = self.param(
            "w_in", _partitioned(nn.initializers.lecun_normal(
                in_axis=-2, out_axis=-1, batch_axis=(0,)),
                ("expert", "embed", "mlp")),
            (held, width, self.mlp_dim), self.param_dtype)
        w_out = self.param(
            "w_out", _partitioned(nn.initializers.lecun_normal(
                in_axis=-2, out_axis=-1, batch_axis=(0,)),
                ("expert", "mlp", "embed")),
            (held, self.mlp_dim, width), self.param_dtype)
        w_gate = self.param(
            "w_gate", _partitioned(nn.initializers.lecun_normal(
                in_axis=-2, out_axis=-1, batch_axis=(0,)),
                ("expert", "embed", "mlp")),
            (held, width, self.mlp_dim), self.param_dtype) \
            if self.gated else None
        u = u.reshape(-1, d)
        x = x.reshape(-1, width)
        mask = jnp.ones((u.shape[0],), bool) if mask is None \
            else mask.reshape(-1)
        with jax.named_scope("moe/route"):
            if self.scoring == "sigmoid":
                chosen, weights, scores = sigmoid_top_k(
                    u, router, bias, self.experts_per_token,
                    self.routed_scaling)
            else:
                chosen, weights, scores = softmax_top_k(
                    u, router, self.experts_per_token)
        # readable by an apply with mutable=["routing"] (the parity check
        # certifies near-ties, the benchmark balances the bias); dropped
        # at trace time by every other
        self.sow("routing", "chosen", chosen.reshape(lead + (-1,)))
        self.sow("routing", "scores", scores.reshape(lead + (-1,)))
        with jax.named_scope("moe/experts"):
            out, pairs = held_expert_sum(x, chosen, weights, mask, w_in,
                                         w_out, first, w_gate)
        return out.reshape(lead + (width,)), pairs
