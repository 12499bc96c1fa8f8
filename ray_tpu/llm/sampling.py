"""Token sampling: temperature + top-k + nucleus (top-p), fully inside
jit (reference role: vLLM's sampler — the reference delegates serving
to vLLM, whose Sampler applies temperature/top_k/top_p per sequence;
here the same contract as ONE vectorized XLA program over the batch).

TPU notes: per-slot parameters arrive as [B] arrays so one compiled
program serves heterogeneous requests (no per-request recompiles).
The top-p mask needs a descending sort of the vocab, and that sort is
not cheap on the chip: 0.86 ms of a 13.4 ms decode step at 32 rows x
32,768 (PERF_LEDGER.jsonl, PR 42: `decode_step/sort`), 17.8 ms of
37.8 at 48 x 261,120 (PERF.md, PR 32). So `sample_tokens` branches ON
THE DEVICE, on the parameters it is handed, to the least work that
gives the same tokens (`sampler_tier`); a batch that asks for no
filter sorts nothing, one that asks for no sample draws nothing."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# what `sampler_tier` returns, by name (the engine's counters use them)
SAMPLER_TIERS = ("greedy", "plain", "filtered")


def sampler_tier(temperature, top_k, top_p):
    """The work a batch's [B] parameters ask of `sample_tokens`: 0, no
    row samples (an argmax); 1, some row samples and no SAMPLING row
    filters (a categorical draw, no sort); 2, some sampling row has a
    top-k or a nucleus. Written with operators and methods that numpy
    and jax arrays share: the device takes its branch by it, and the
    host counts, with the same arrays, the branch each step will take."""
    sampling = temperature > 0
    filtering = sampling & ((top_k > 0) | (top_p < 1.0))
    return sampling.any().astype("int32") + filtering.any().astype("int32")


def _greedy(rng, logits, temperature, top_k, top_p):
    return jnp.argmax(logits, axis=-1)


def _plain(rng, logits, temperature, top_k, top_p):
    """`_filtered` where no sampling row has a filter: both thresholds
    are NEG_INF there, the mask keeps every logit, and the draw under
    the same key is the same draw."""
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(rng, scaled)
    return jnp.where(temperature > 0, sampled, jnp.argmax(logits, axis=-1))


def _filtered(rng, logits, temperature, top_k, top_p):
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]

    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    V = scaled.shape[-1]

    # top-k threshold: value of the k-th largest entry (k<=0 -> -inf)
    k = jnp.clip(top_k.astype(jnp.int32), 0, V)
    k_idx = jnp.maximum(k - 1, 0)
    k_thresh = jnp.take_along_axis(sorted_desc, k_idx[:, None],
                                   axis=-1)[:, 0]
    k_thresh = jnp.where(k > 0, k_thresh, NEG_INF)

    # top-p threshold: smallest sorted value still inside the nucleus.
    # A position belongs to the nucleus while the mass of STRICTLY
    # higher-ranked tokens is < p (so the token crossing p is included,
    # matching the usual implementation).
    probs_sorted = jax.nn.softmax(sorted_desc, axis=-1)
    cum_before = jnp.cumsum(probs_sorted, axis=-1) - probs_sorted
    # clip away from 0: cum_before[0] == 0 < p keeps the top token even
    # for top_p=0 (every standard sampler keeps at least one token)
    in_nucleus = cum_before < jnp.clip(top_p, 1e-6, 1.0)[:, None]
    p_thresh = jnp.min(jnp.where(in_nucleus, sorted_desc, jnp.inf),
                       axis=-1)
    p_thresh = jnp.where(top_p >= 1.0, NEG_INF, p_thresh)

    thresh = jnp.maximum(k_thresh, p_thresh)
    masked = jnp.where(scaled >= thresh[:, None], scaled, NEG_INF)
    sampled = jax.random.categorical(rng, masked)
    return jnp.where(temperature > 0, sampled, greedy)


def sample_tokens(rng, logits, temperature, top_k, top_p):
    """One token per row.

    logits: [B, V] float32. temperature/top_k/top_p: [B] — per slot:
    temperature <= 0 means greedy (top_k/top_p ignored); top_k <= 0
    disables the k filter; top_p >= 1 disables the nucleus filter.
    Filters compose the standard way: restrict to the top-k set, then
    to the smallest prefix of the (sorted) distribution whose mass
    reaches top_p, renormalize implicitly via categorical.

    Every tier returns what `_filtered`, the whole contract, returns for
    the same arguments and key; the switch only leaves out work whose
    result no row of this batch reads."""
    return jax.lax.switch(sampler_tier(temperature, top_k, top_p),
                          (_greedy, _plain, _filtered),
                          rng, logits, temperature, top_k, top_p)


def sample_with_confidence(rng, logits, temperature, top_k, top_p):
    """`sample_tokens`, and beside each row's token its CONFIDENCE: the
    probability softmax(logits) gives it, float32 (of the plain
    distribution, whatever temperature and filters drew the token). What an
    unmasking rule ranks a block's candidates by (`unmask_block`).
    logits [R, V] float32. Returns (tokens [R] int32, confidence [R])."""
    tokens = sample_tokens(rng, logits, temperature, top_k, top_p)
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return tokens.astype(jnp.int32), \
        jnp.exp(picked - jax.nn.logsumexp(logits, axis=-1))


def unmask_count(block_length: int, denoising_steps: int, step: int) -> int:
    """Positions the static rule fixes in denoising forward `step` (from
    0) of a block: block_length // T, and one more in the first
    block_length % T forwards, T = `denoising_steps` held to 1 ..
    block_length (more forwards than positions would fix nothing)."""
    steps = max(1, min(denoising_steps, block_length))
    return block_length // steps + (step < block_length % steps)


def unmask_block(ids, candidates, confidence, mask_id, count, threshold):
    """One denoising forward's unmasking of every row's open block, by the
    low-confidence rules of generation by diffusion over blocks. ids [B, L]
    the block as the forward read it, `mask_id` where a position is still
    masked; candidates, confidence [B, L] the token each position would
    take and its probability; count [B] int32 the positions the STATIC rule
    fixes now (`unmask_count`); threshold [B] float32, the DYNAMIC rule's:
    every masked position whose confidence passes it is fixed where those
    are at least `count`, else the `count` most confident (a row on the
    static rule carries a threshold no probability passes, > 1). Ties go to
    the earlier position. A fixed position is never masked again. Returns
    (ids with the fixed positions filled [B, L], masks before [B], masks
    after [B]); a block that came without a mask comes back as it was (its
    forward is the block's commit)."""
    masked = ids == mask_id
    conf = jnp.where(masked, confidence, -1.0)
    # rank among the row's positions, most confident first
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (jnp.arange(ids.shape[1])[None, :] < jnp.arange(
            ids.shape[1])[:, None])[None])
    fixed = masked & (ahead.sum(-1) < count[:, None])
    passing = masked & (confidence > threshold[:, None])
    fixed = jnp.where((passing.sum(-1) >= count)[:, None], passing, fixed)
    out = jnp.where(fixed, candidates.astype(ids.dtype), ids)
    return out, masked.sum(-1).astype(jnp.int32), \
        (masked & ~fixed).sum(-1).astype(jnp.int32)
