"""The comparison that decides `correct` for a Falcon-H1 serve cell: the
engine's own prefill and paged decode, through its page pool and its
recurrent-state pool, against the plain float32 reference
(benchmarks/reference/falcon_h1_ref.py), same weights, on the chip, outside
the window. Two comparisons, and both must hold.

Logits, as parity.serve holds the dense decoder's and by the same code
(`_compare`, `ill_conditioned`, `_verdict`): parity.LOGIT_TOLERANCE_STD,
0.12 of a position's logit spread at every well-conditioned position and
half of it at the median one. This program computes in bf16 against a
float32 reference, and that limit is as wide as bf16 weights need: it
refuses what moves logits by a spread or more at every position (a padded
chunk tail leaking into the state, a state not handed from chunk to chunk
or not installed, a wrong decay, group or multiplier), and it cannot see
the recurrent state's own precision under the weights' rounding.

The state, which the configuration fixes at float32 (`state_dtype`): after
the prefill and the decode ticks, what the row's slot of the state pool
holds in every layer -- the scan state S [heads, head dim, d_state] and
the convolution's window -- against what the reference's token-by-token
recurrence holds after the same tokens. Per head, |S - S_ref| / |S_ref|
(Frobenius); a layer's reading is its worst head. The mixer's inputs are
bf16 activations, one rounding deep in the first layer and a stack of
them in the sixth, so a sound float32 state reads 0.004 of a head's norm
in the first layer and 0.012 in the last (the window 0.002 to 0.008),
whatever the ticks. STATE_TOLERANCE holds the first layer, where a bf16
state's own roundings stand clear of that; DEEP_TOLERANCE holds every
layer and every window to what bf16 inputs allow, and refuses a state
that was not handed on, not installed, or saw the padding.

The prompt is N_PROMPT = 300 tokens in CHUNK = 256-token chunks: the
bucket nearly every chunk of the cell's traffic runs in, two 128-token
chunks of the scan inside it, and a second chunk whose 212 padded
positions the mixer must not see (`valid`). N_DECODE = 128 ticks
then go through the pool: a state kept in bf16 is rounded once a tick,
and a head that forgets slowly keeps every one of those roundings, so its
error grows with the ticks (0.012 after 64, 0.014 to 0.016 after 128) where
the float32 state's does not.

STATE_TOLERANCE from two readings on the chip at the published widths
(PR 32; PERF.md section 6): the first layer's worst head with the state in
float32 0.0037 to 0.0045 over fifteen seeds, with the state in bf16
0.0138 to 0.0156 over four. 0.009 is twice the one and 0.65 of the other.
DEEP_TOLERANCE 0.03 is 2.4 times the largest reading of any layer or
window (0.0123).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

from .parity import (PROBE_SIZE, PROBES, _compare, _verdict,
                     ill_conditioned)

N_PROMPT, N_DECODE, CHUNK = 300, 128, 256
STATE_TOLERANCE = 0.009
DEEP_TOLERANCE = 0.03


def reference_keys(m) -> Dict[str, Any]:
    """The running FalconH1Config back under the published key names the
    reference reads (a rehearsal runs toy widths, not the file's)."""
    return {"num_attention_heads": m.num_heads,
            "num_key_value_heads": m.num_kv_heads, "head_dim": m.head_dim,
            "rope_theta": m.rope_theta, "rms_norm_eps": m.rms_norm_eps,
            "mamba_d_ssm": m.mamba_d_ssm, "mamba_n_heads": m.mamba_n_heads,
            "mamba_d_state": m.mamba_d_state,
            "mamba_n_groups": m.mamba_n_groups,
            "mamba_d_conv": m.mamba_d_conv,
            "attention_in_multiplier": m.attention_in_multiplier,
            "attention_out_multiplier": m.attention_out_multiplier,
            "key_multiplier": m.key_multiplier,
            "ssm_in_multiplier": m.ssm_in_multiplier,
            "ssm_out_multiplier": m.ssm_out_multiplier,
            "ssm_multipliers": list(m.ssm_multipliers),
            "mlp_multipliers": list(m.mlp_multipliers),
            "embedding_multiplier": m.embedding_multiplier,
            "lm_head_multiplier": m.lm_head_multiplier}


def engine_logits(engine, prompt, chunk: int, ticks: int, slot: int = 0):
    """`prompt` through the engine's own chunked prefill program in
    `chunk`-token chunks (a padded last chunk is told its real length),
    its page write and its state install into row `slot`, then `ticks`
    decode tokens through the page and state pools in a paged decode
    program of the engine's shapes (the engine's own returns ids, not
    logits), fed greedily. Returns the prefill's and the decode ticks'
    logits, the tokens fed to decode, and what row `slot` of the state
    pool holds after the last tick: per layer (window, S)."""
    import jax
    import jax.numpy as jnp

    cfg = engine.config
    layers = cfg.model.num_layers
    n_prompt = len(prompt)
    with engine._mesh_scope():
        staged = engine._dense_zero_caches()
        rows = []
        for off in range(0, n_prompt, chunk):
            take = min(chunk, n_prompt - off)
            tokens = np.zeros((1, chunk), np.int32)
            tokens[0, :take] = prompt[off:off + take]
            positions = np.arange(off, off + chunk, dtype=np.int32)[None]
            lg, staged = engine._chunk_prefill(
                engine.params, jnp.asarray(tokens), jnp.asarray(positions),
                staged, jnp.asarray(off, jnp.int32),
                jnp.asarray(take, jnp.int32))
            rows.append(np.asarray(lg[0, :take]))
        prefill_logits = np.concatenate(rows)

        n_pages = -(-(n_prompt + ticks) // cfg.page_size)
        pages = [engine.pool.alloc() for _ in range(n_pages)]
        if any(p is None for p in pages):
            raise RuntimeError("no free pages for the parity prompt")
        try:
            engine._write_owned_pages(staged["kv"], pages, 0)
            engine.state = engine._write_state(
                engine.state, staged["state"], jnp.asarray(slot, jnp.int32))
            del staged

            def decode_logits(params, k_pages, v_pages, state, active,
                              tables, lengths, tokens):
                caches = [{"k": k_pages[i], "v": v_pages[i],
                           "conv": state[i][0], "ssm": state[i][1],
                           "active": active, "block_tables": tables,
                           "lengths": lengths} for i in range(layers)]
                lg, new = engine.model.apply(
                    {"params": params}, tokens, positions=lengths[:, None],
                    kv_caches=caches, cache_index=None)
                return (lg[:, -1].astype(jnp.float32),
                        [c[0] for c in new], [c[1] for c in new],
                        [(c[2], c[3]) for c in new])

            program = jax.jit(decode_logits, donate_argnums=(1, 2, 3))
            B = cfg.max_batch
            tables = np.zeros((B, cfg.pages_per_seq), np.int32)
            tables[slot, :n_pages] = pages
            active = np.zeros((B,), bool)
            active[slot] = True
            fed = [int(prefill_logits[-1].argmax())]
            decode_rows = []
            for i in range(ticks):
                lengths = np.zeros((B,), np.int32)
                lengths[slot] = n_prompt + i
                tokens = np.zeros((B, 1), np.int32)
                tokens[slot, 0] = fed[-1]
                lg, engine.k_pages, engine.v_pages, engine.state = program(
                    engine.params, engine.k_pages, engine.v_pages,
                    engine.state, jnp.asarray(active), jnp.asarray(tables),
                    jnp.asarray(lengths), jnp.asarray(tokens))
                decode_rows.append(np.asarray(lg[slot]))
                fed.append(int(decode_rows[-1].argmax()))
            held = [tuple(np.asarray(pool[slot], np.float32)
                          for pool in pools) for pools in engine.state]
        finally:
            for p in pages:
                if p is not None:
                    engine.pool.decref(p)
    return prefill_logits, np.stack(decode_rows), fed[:-1], held


def state_errors(held, want) -> Dict[str, Any]:
    """The state pool's row against the reference's carried state, layer
    by layer: each head's |S - S_ref| / |S_ref| (Frobenius) and the
    window's, `held` and `want` per layer (window, S)."""
    heads, windows = [], []
    for (window, s), (window_ref, s_ref) in zip(held, want):
        s_ref = np.asarray(s_ref, np.float32)
        window_ref = np.asarray(window_ref, np.float32)
        across = tuple(range(1, s_ref.ndim))
        heads.append(np.sqrt(((s - s_ref) ** 2).sum(across)
                             / (s_ref ** 2).sum(across)))
        windows.append(float(np.linalg.norm(window - window_ref)
                             / np.linalg.norm(window_ref)))
    return {"worst_head": [float(h.max()) for h in heads],
            "window": windows}


def serve(engine, config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """A seeded 300-token prompt through `engine_logits` in 256-token
    chunks (the state crosses a chunk boundary, the second chunk is
    mostly padding) and 128 decode ticks, against the reference's full
    forward pass over the same 428 tokens: the logits, and the state."""
    import jax
    import jax.numpy as jnp

    from ..reference import falcon_h1_ref
    from .builders import jax_seed

    cfg = engine.config
    model_cfg = cfg.model
    if engine.has_work():
        raise RuntimeError("parity needs an idle engine")
    rng = np.random.default_rng([jax_seed(seed), 77])
    chunk = CHUNK if CHUNK in cfg.prefill_buckets \
        else cfg.prefill_buckets[-1]
    # a rehearsal's engine is shorter than the cell's
    n_prompt = min(N_PROMPT, cfg.max_len - N_DECODE - 60)
    prompt = rng.integers(1, model_cfg.vocab_size, size=n_prompt)
    prefill_logits, decode_logits, fed, held = engine_logits(
        engine, prompt, chunk, N_DECODE)

    sequence = np.concatenate([prompt, np.asarray(fed)])
    reference = functools.partial(
        falcon_h1_ref.logits, engine.params, sequence,
        reference_keys(model_cfg), num_layers=model_cfg.num_layers)
    want, carried = reference(states=True)
    want = np.asarray(want)
    wobble = (sequence.shape[0], model_cfg.hidden_size)
    probes = [np.asarray(reference(
        embed_scale=1.0 + PROBE_SIZE * jax.random.normal(
            jax.random.PRNGKey(k), wobble, jnp.float32)))
        for k in range(PROBES)]
    ill = ill_conditioned(want, probes)
    out = _verdict({"prefill": _compare(prefill_logits, want[:n_prompt]),
                    "decode": _compare(decode_logits, want[n_prompt:])},
                   {"prefill": ill[:n_prompt], "decode": ill[n_prompt:]})
    state = state_errors(held, carried)
    out["state"] = dict(state, tolerance=STATE_TOLERANCE,
                        deep_tolerance=DEEP_TOLERANCE)
    out["ok"] = bool(
        out["ok"] and state["worst_head"][0] <= STATE_TOLERANCE
        and max(state["worst_head"] + state["window"]) <= DEEP_TOLERANCE)
    return out
