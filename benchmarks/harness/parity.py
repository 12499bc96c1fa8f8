"""The comparisons that decide `correct` for the model's arithmetic: the
system against the plain reference (benchmarks/reference), same weights,
on the chip, outside the window. Logits are compared, never ids: with
random weights greedy decoding collapses to one token."""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

# bf16 keeps 8 bits of mantissa and the model multiplies through tens of
# layers; PR 21 measured a largest logit difference of 0.039 at logit
# std 1.0 for this code in bf16 against float32 (4 layers). The gate is
# relative to the spread of the logits: 0.12 of a standard deviation, three
# times that reading (this PR's chip runs at 16 layers: worst position
# 0.024-0.037), and still refuses what a wrong mask, a wrong rotary angle,
# a skipped layer or fp8-grade arithmetic gives (each moves logits by a
# whole standard deviation or more, at every position).
LOGIT_TOLERANCE_STD = 0.12
# ... at every position that is well conditioned. With random weights a
# deep stack collapses the positions onto each other (PR 21: logits of
# different positions correlate 0.85), and under some draws a few early
# positions are ill conditioned: a rounding's worth of change in their input
# moves their logits tens of times farther than it moves the others'. Under
# seed 23, and under no other seed of this PR's ~60 serve runs, position 18
# of the prompt differed by 1.01 standard deviations on the chip while the
# other 135 agreed to 0.03. Shown on the CPU with the same weights (PR 23,
# PERF.md section 6): the program's model in float32 agrees with the
# reference at every position to 5e-6; the program in bf16 by another
# rounding order (jnp attention) is off at positions 0, 1, 17, 18, 20, 21
# and 23, by up to 9 standard deviations; and the float32 reference ITSELF,
# its embedded tokens wobbled by 2**-9 relative, moves at just those
# positions 10-50 times the median, where under seed 11 nothing moves more
# than 3 times it past position 1. So it is rounding, and the reference can
# say where: the check wobbles the reference's input PROBES times and sets
# aside the positions that any probe moves more than ILL_CONDITIONED times
# the median position. The program never chooses them. Every other position
# is held to the tolerance without exception, the median position of
# prefill and of decode to half of it, and a draw that sets aside more than
# half of its positions proves too little and fails (seed 23 on the CPU:
# positions 0-27, 28 of 136; seed 11: positions 0 and 1).
PROBES = 4
PROBE_SIZE = 2.0 ** -9
ILL_CONDITIONED = 5.0
SET_ASIDE_AT_MOST = 0.5
# The train loss is a float32 mean over thousands of positions, so bf16
# rounding averages out: the five readings this PR kept from the chip, the
# system in bf16 against the float32 reference, are 1.1e-5, 9.0e-6, 5.5e-6
# and 4.5e-5 relative on one chip and 1.5e-7 on the 2x2 (root mean square
# 2.4e-5), and PR 21's smoke held one chip against the 2x2 to 7e-5. The
# gate is 2e-4: 4.4 times the worst reading, eight times their root mean
# square, so that none of a check's ~60 train runs is refused for rounding.
# With random weights the first loss sits near ln V + sigma^2 / 2 whatever
# attention computes: a wrong mask or rotary angle re-draws every
# position's loss and so moves the mean of 4094 of them by a random amount
# of about 2e-3 relative. This gate refuses about eleven such faults in
# twelve; a single mean cannot do better (comparing the forward pass
# position by position is under Open questions in PERF.md). Only the first
# FORWARD loss is compared: the backward pass and the optimiser are checked
# by nothing but "every loss is finite".
LOSS_TOLERANCE_REL = 2e-4


def _compare(got, want) -> Dict[str, Any]:
    """Rows are positions; each is judged against its own logit spread."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    ratio = np.abs(got - want).max(-1) / want.std(-1)
    return {"diff_over_std": [float(x) for x in ratio],
            "median": float(np.median(ratio)), "worst": float(ratio.max()),
            "worst_position": int(ratio.argmax()),
            "logit_std": float(want.std(-1).mean()),
            "argmax_agree": float((got.argmax(-1) == want.argmax(-1)).mean())}


def ill_conditioned(want, probes) -> np.ndarray:
    """Positions (rows of `want`) that some probe, the reference on a
    wobbled input, moves more than ILL_CONDITIONED times as far as it
    moves the median position."""
    want = np.asarray(want, np.float32)
    out = np.zeros(want.shape[0], bool)
    for probe in probes:
        moved = np.abs(np.asarray(probe, np.float32) - want).max(-1) \
            / want.std(-1)
        out |= moved > ILL_CONDITIONED * np.median(moved)
    return out


def _verdict(parts: Dict[str, Dict[str, Any]],
             set_aside: Dict[str, Any]) -> Dict[str, Any]:
    """`set_aside[part]`: which positions of that part are ill conditioned
    and so not held to the tolerance."""
    beyond, aside, total = [], [], 0
    for name, part in parts.items():
        skip = set_aside[name]
        for at, x in enumerate(part.pop("diff_over_std")):
            total += 1
            if skip[at]:
                aside.append((name, at, x))
            elif x > LOGIT_TOLERANCE_STD:
                beyond.append((name, at, x))
    out: Dict[str, Any] = dict(parts)
    out["beyond_tolerance"] = beyond
    out["set_aside"] = aside
    out["tolerance_std"] = LOGIT_TOLERANCE_STD
    out["ok"] = (not beyond and len(aside) <= SET_ASIDE_AT_MOST * total
                 and all(part["median"] <= LOGIT_TOLERANCE_STD / 2
                         for part in parts.values()))
    return out


def serve(engine, config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """A seeded 128-token prompt through the engine's own chunked prefill
    program, its page write, and 8 ticks of a paged decode program of the
    engine's shapes (the engine's own returns ids, not logits), against
    the reference's full forward pass over the same 136 tokens."""
    import jax
    import jax.numpy as jnp

    from ..reference import llama_ref
    from .builders import jax_seed

    cfg = engine.config
    model_cfg = cfg.model
    n_prompt, n_decode = 128, 8
    if engine.has_work():
        raise RuntimeError("parity needs an idle engine")
    rng = np.random.default_rng([jax_seed(seed), 77])
    prompt = rng.integers(1, model_cfg.vocab_size, size=n_prompt)
    chunk = min(64, cfg.prefill_buckets[-1])
    if chunk not in cfg.prefill_buckets:
        chunk = cfg.prefill_buckets[-1]

    with engine._mesh_scope():
        dense = engine._dense_zero_caches()
        rows = []
        for off in range(0, n_prompt, chunk):
            take = min(chunk, n_prompt - off)
            tokens = np.zeros((1, chunk), np.int32)
            tokens[0, :take] = prompt[off:off + take]
            positions = np.arange(off, off + chunk, dtype=np.int32)[None]
            lg, dense = engine._chunk_prefill(
                engine.params, jnp.asarray(tokens), jnp.asarray(positions),
                dense, jnp.asarray(off, jnp.int32))
            rows.append(np.asarray(lg[0, :take]))
        prefill_logits = np.concatenate(rows)

        ps = cfg.page_size
        n_pages = -(-(n_prompt + n_decode) // ps)
        short = n_pages - engine.pool.num_free()
        if short > 0 and engine.radix is not None:
            engine.radix.evict_pages(short)   # as admission does
        pages = [engine.pool.alloc() for _ in range(n_pages)]
        if any(p is None for p in pages):
            raise RuntimeError("no free pages for the parity prompt")
        try:
            engine._write_owned_pages(dense, pages, 0)
            del dense

            layers = model_cfg.num_layers

            def decode_logits(params, k_pages, v_pages, tables, lengths,
                              tokens):
                caches = [{"k": k_pages[i], "v": v_pages[i],
                           "block_tables": tables, "lengths": lengths}
                          for i in range(layers)]
                lg, new = engine.model.apply(
                    {"params": params}, tokens, positions=lengths[:, None],
                    kv_caches=caches, cache_index=None)
                return (lg[:, -1].astype(jnp.float32),
                        [c["k"] for c in new], [c["v"] for c in new])

            program = jax.jit(decode_logits, donate_argnums=(1, 2))
            B = cfg.max_batch
            tables = np.zeros((B, cfg.pages_per_seq), np.int32)
            tables[0, :n_pages] = pages
            fed = [int(prefill_logits[-1].argmax())]
            decode_rows = []
            for i in range(n_decode):
                lengths = np.zeros((B,), np.int32)
                lengths[0] = n_prompt + i
                tokens = np.zeros((B, 1), np.int32)
                tokens[0, 0] = fed[-1]
                lg, engine.k_pages, engine.v_pages = program(
                    engine.params, engine.k_pages, engine.v_pages,
                    jnp.asarray(tables), jnp.asarray(lengths),
                    jnp.asarray(tokens))
                decode_rows.append(np.asarray(lg[0]))
                fed.append(int(decode_rows[-1].argmax()))
        finally:
            for p in pages:
                if p is not None:
                    engine.pool.decref(p)

        sequence = np.concatenate([prompt, np.asarray(fed[:-1])])[None]
        reference = functools.partial(
            llama_ref.logits, engine.params, sequence, num_layers=layers,
            theta=float(model_cfg.rope_theta),
            eps=float(model_cfg.rms_norm_eps))
        want = np.asarray(reference()[0])
        wobble = (1, sequence.shape[1], model_cfg.hidden_size)
        probes = [np.asarray(reference(
            embed_scale=1.0 + PROBE_SIZE * jax.random.normal(
                jax.random.PRNGKey(k), wobble, jnp.float32))[0])
            for k in range(PROBES)]
    ill = ill_conditioned(want, probes)
    return _verdict({"prefill": _compare(prefill_logits, want[:n_prompt]),
                     "decode": _compare(np.stack(decode_rows),
                                        want[n_prompt:])},
                    {"prefill": ill[:n_prompt], "decode": ill[n_prompt:]})


def train_loss(params, first_batch, first_loss: float, model_cfg,
               rows_at_once: int = 1) -> Dict[str, Any]:
    """The first step's loss against the reference's on the same batch and
    the same (initial) weights."""
    from ..reference import llama_ref
    want = llama_ref.next_token_loss(
        params, first_batch, num_layers=model_cfg.num_layers,
        theta=float(model_cfg.rope_theta),
        eps=float(model_cfg.rms_norm_eps), rows_at_once=rows_at_once)
    rel = abs(first_loss - want) / abs(want)
    return {"loss": first_loss, "reference_loss": want, "rel_diff": rel,
            "tolerance_rel": LOSS_TOLERANCE_REL,
            "ok": rel <= LOSS_TOLERANCE_REL}
