"""The comparison that decides `correct` for a Nemotron-H serve cell: the
engine's own prefill and paged decode, through its page pool (one layer's)
and its recurrent-state pool (five layers'), against the plain float32
reference (benchmarks/reference/nemotron_h_ref.py), same weights, on the
chip, outside the window. Three comparisons, and all must hold.

Routing. An E layer's choice of 22 experts of 512 is discontinuous: the
program computes in bf16, the reference in float32, and where the 22nd and
the 23rd selection score lie within a rounding of each other they choose
differently, which moves that position's logits by far more than a
rounding (one expert of the ~5.5 held ones a token comes or goes). So the
reference is told which experts the program chose and FOLLOWS them, as it
already follows the program's greedy tokens, and the check certifies each
departure: along the followed path the reference ranks the experts by its
own float32 scores, and every expert the program took against that order,
or left, must lie within ROUTE_TIE of the reference's cut (midway between
its 22nd and 23rd score). A router that scores or chooses otherwise (no
bias, a wrong top-k, an input coarser than bf16) takes experts far from
the cut and is refused; one that weighs otherwise (softmax for sigmoid, no
renormalisation, no scaling) takes the same experts and is refused by the
logits. `routing_agree` is the share of (position, E layer) whose chosen
sets are the reference's own.

Logits, as parity.serve holds the dense decoder's and by the same code
(`_compare`, `ill_conditioned`, `_verdict`) and the same limit,
parity.LOGIT_TOLERANCE_STD: with the routes followed, what is left is
rounding, and the perturbed probes (which follow the same routes) set
aside the positions a float32 wobble moves far.

The state, which the configuration fixes at float32: after the prefill and
the decode ticks, what the row's slot of the state pool holds in every M
layer against what the reference's token-by-token recurrence holds, by
parity_falcon_h1.state_errors (per head |S - S_ref| / |S_ref|, a layer's
reading its worst head; the window's likewise). STATE_TOLERANCE holds the
first M layer, where a bf16 state's own roundings stand clear of the
float32 state's reading; DEEP_TOLERANCE every M layer and every window.

The prompt is N_PROMPT = 300 tokens: one CHUNK = 256-token chunk and a
tail of 44 in the bucket the engine's tick would pad it to (64: the state
crosses a chunk boundary, and the tail's 20 padded positions must enter
neither a state nor an expert's count), then N_DECODE = 128 ticks through
the pools. Over those ticks the E layers' accumulators must gain exactly
the pairs that the sown routes put on the held experts (`counters_match`).

What it does not compare: the decode ticks run in a jit of this check's own
(`decode_logits`: the engine's `_decode` returns ids, not logits) with one
row of the batch live; the engine's own program with many live rows is
held to the reference's greedy tokens on the CPU alone
(tests/test_nemotron_h.py).

The limits, each from two readings on the chip at the published widths,
all of the program as it stands (my chip runs, PR 35, seeds 3500000801-825
and 901-907: fourteen runs of the cell, and five of the controls, whose
other parts are sound; PERF.md section 6). The router's bias is zero, as
the cell runs it.

  limit                  sound (float32 state, float32 router)   control
  STATE_TOLERANCE 0.017  first M layer's worst head              state in bf16:
                         0.0059 to 0.0105, 17 readings           0.059, 0.090
  DEEP_TOLERANCE 0.04    largest of any M layer or window        state in bf16, a
                         0.0119 to 0.0226, 17 readings           run's largest:
                                                                 0.060, 0.109
  ROUTE_TIE 0.004        farthest expert taken or left against   router's input in
                         the reference's order, from the cut:    8-bit floats (e4m3):
                         0.0011 to 0.0018, 16 readings           0.0082, 0.0101

STATE_TOLERANCE is 1.6 times its largest sound reading and 0.29 of its
smallest control (128 heads 64 wide: a noisier worst than Falcon-H1's 32
of 128); DEEP_TOLERANCE 1.8 times and 0.67; ROUTE_TIE 2.2 times and 0.49
(with the 8-bit input the chosen sets agree at 48 % of (position, layer)
against 86 to 89 %). A bf16 state fails both state limits. A router kept
in bf16 (scores rounded to bf16 before the top-k) CANNOT be told from the
float32 one: it reads 0.0017, inside the sound range, because the bf16
stream under the router already moves the scores that far. The nearest
precision below that this check can refuse is the 8-bit input (PERF.md
section 7).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import numpy as np

from .parity import (PROBE_SIZE, PROBES, _compare, _verdict,
                     ill_conditioned)
from .parity_falcon_h1 import state_errors

N_PROMPT, N_DECODE, CHUNK = 300, 128, 256
STATE_TOLERANCE = 0.017
DEEP_TOLERANCE = 0.04
ROUTE_TIE = 0.004


def reference_keys(m) -> Dict[str, Any]:
    """The running NemotronHConfig back under the published key names the
    reference reads (a rehearsal runs toy widths, not the file's)."""
    return {"hybrid_override_pattern": m.hybrid_override_pattern,
            "num_attention_heads": m.num_heads,
            "num_key_value_heads": m.num_kv_heads, "head_dim": m.head_dim,
            "layer_norm_epsilon": m.rms_norm_eps,
            "mamba_num_heads": m.mamba_num_heads,
            "mamba_head_dim": m.mamba_head_dim, "n_groups": m.n_groups,
            "ssm_state_size": m.ssm_state_size,
            "conv_kernel": m.conv_kernel,
            "n_routed_experts": m.n_routed_experts,
            "num_experts_per_tok": m.num_experts_per_tok,
            "routed_scaling_factor": m.routed_scaling_factor,
            "held_experts": tuple(m.held_experts)}


def _routes_of(variables, kinds) -> List[Any]:
    """Per E layer, what `RoutedExperts` sowed: the chosen experts."""
    return [variables["routing"][f"layer_{i}"]["moe"]["routed"]["chosen"][0]
            for i, kind in enumerate(kinds) if kind == "moe"]


def engine_logits(engine, prompt, chunk: int, ticks: int, slot: int = 0):
    """`prompt` through the engine's own chunked prefill program in
    `chunk`-token chunks (a padded last chunk is told its real length),
    its page write and its state install into row `slot`, then `ticks`
    decode tokens through the page and state pools in a paged decode
    program of the engine's shapes (the engine's own returns ids, not
    logits), fed greedily. Returns the prefill's and the decode ticks'
    logits, the tokens fed to decode, what row `slot` of the state pool
    holds after the last tick (per M layer (window, S)), per E layer the
    experts the program chose at every position [positions, k], and per E
    layer what its (pairs, steps) accumulators gained over the ticks."""
    import jax
    import jax.numpy as jnp

    cfg = engine.config
    kinds = cfg.model.layer_kinds()
    n_prompt = len(prompt)
    apply = engine.model.apply

    def chunk_routes(params, tokens, positions, staged, offset, valid):
        (_, _), sown = apply(
            {"params": params}, tokens, positions=positions,
            kv_caches=engine._chunk_caches(staged), cache_index=offset,
            valid=valid, mutable=["routing"])
        return [r[0] for r in _routes_of(sown, kinds)]

    chunk_routes = jax.jit(chunk_routes)
    with engine._mesh_scope():
        staged = engine._dense_zero_caches()
        rows, routes = [], []
        for off in range(0, n_prompt, chunk):
            take = min(chunk, n_prompt - off)
            # the tail in the bucket the engine's tick would pad it to
            size = engine._bucket(take)
            tokens = np.zeros((1, size), np.int32)
            tokens[0, :take] = prompt[off:off + take]
            args = (engine.params, jnp.asarray(tokens),
                    jnp.asarray(np.arange(off, off + size,
                                          dtype=np.int32)[None]))
            tail = (jnp.asarray(off, jnp.int32), jnp.asarray(take, jnp.int32))
            # the routes first: the engine's program donates `staged`
            routes.append([np.asarray(r[:take])
                           for r in chunk_routes(*args, staged, *tail)])
            lg, staged = engine._chunk_prefill(*args, staged, *tail)
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

            def decode_logits(params, k_pages, v_pages, state, counters,
                              active, tables, lengths, tokens):
                (lg, new), sown = apply(
                    {"params": params}, tokens, positions=lengths[:, None],
                    kv_caches=engine._decode_caches(
                        k_pages, v_pages, state, counters, active, tables,
                        lengths),
                    cache_index=None, mutable=["routing"])
                nk, nv, nstate, ncount = engine._by_kind(new)
                return (lg[:, -1].astype(jnp.float32), nk, nv, nstate,
                        ncount, [r[:, 0] for r in _routes_of(sown, kinds)])

            program = jax.jit(decode_logits, donate_argnums=(1, 2, 3, 4))
            B = cfg.max_batch
            tables = np.zeros((B, cfg.pages_per_seq), np.int32)
            tables[slot, :n_pages] = pages
            active = np.zeros((B,), bool)
            active[slot] = True
            fed = [int(prefill_logits[-1].argmax())]
            decode_rows = []
            counted = jax.device_get(engine.counters)
            for i in range(ticks):
                lengths = np.zeros((B,), np.int32)
                lengths[slot] = n_prompt + i
                tokens = np.zeros((B, 1), np.int32)
                tokens[slot, 0] = fed[-1]
                (lg, engine.k_pages, engine.v_pages, engine.state,
                 engine.counters, chose) = program(
                    engine.params, engine.k_pages, engine.v_pages,
                    engine.state, engine.counters, jnp.asarray(active),
                    jnp.asarray(tables), jnp.asarray(lengths),
                    jnp.asarray(tokens))
                decode_rows.append(np.asarray(lg[slot]))
                routes.append([np.asarray(r[slot])[None] for r in chose])
                fed.append(int(decode_rows[-1].argmax()))
            held = [tuple(np.asarray(pool[slot], np.float32)
                          for pool in pools) for pools in engine.state]
            counted = [tuple(np.asarray(b) - np.asarray(a)
                             for a, b in zip(was, now)) for was, now
                       in zip(counted, jax.device_get(engine.counters))]
        finally:
            for p in pages:
                if p is not None:
                    engine.pool.decref(p)
    by_layer = [np.concatenate([part[j] for part in routes])
                for j in range(len(routes[0]))]
    return (prefill_logits, np.stack(decode_rows), fed[:-1], held, by_layer,
            counted)


def counters_match(counted, routes, held_experts) -> bool:
    """What the E layers' accumulators gained over the decode ticks
    (`counted`, per E layer (pairs, steps) [held]) against the routes the
    same ticks sowed (`routes`, per E layer [ticks, k]): an expert held
    here gains a pair for every tick that chose it and a step likewise
    (one row decodes), and the idle rows count nothing."""
    first, held = held_experts
    for (pairs, steps), chose in zip(counted, routes):
        local = np.asarray(chose) - first
        want = np.bincount(local[(local >= 0) & (local < held)],
                           minlength=held)
        if not (np.array_equal(pairs, want) and np.array_equal(steps, want)):
            return False
    return True


def routing_check(routes, selections, k: int) -> Dict[str, Any]:
    """The program's chosen experts (`routes`, per E layer [s, k]) against
    the reference's own order along the followed path (`selections`, per
    E layer [s, E] float32 scores): the share of (position, layer) whose
    sets agree, and how far from the reference's cut the farthest expert
    taken or left against its order lies."""
    agree, worst = [], 0.0
    for chose, selection in zip(routes, selections):
        selection = np.asarray(selection, np.float32)
        ranked = np.sort(selection, axis=-1)
        cut = (ranked[:, -k] + ranked[:, -k - 1]) / 2.0
        own = selection > cut[:, None]
        took = np.zeros_like(own)
        np.put_along_axis(took, np.asarray(chose), True, axis=-1)
        swapped = took ^ own
        agree.append(~swapped.any(-1))
        if swapped.any():
            worst = max(worst, float(
                np.abs(selection - cut[:, None])[swapped].max()))
        if (took.sum(-1) != k).any():
            worst = float("inf")     # a repeated or missing choice
    agree = np.stack(agree)
    return {"routing_agree": float(agree.mean()),
            "positions_all_agree": float(agree.all(0).mean()),
            "worst_tie": worst, "tie_tolerance": ROUTE_TIE}


def serve(engine, config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """A seeded 300-token prompt through `engine_logits` (a 256-token
    chunk and the tail in its bucket) and 128 decode ticks, against the reference's full forward pass
    over the same 428 tokens along the same routes: the routing, the
    logits, and the state."""
    import jax
    import jax.numpy as jnp

    from ..reference import nemotron_h_ref
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
    prefill_logits, decode_logits, fed, held, routes, counted = \
        engine_logits(engine, prompt, chunk, N_DECODE)

    sequence = np.concatenate([prompt, np.asarray(fed)])
    reference = functools.partial(
        nemotron_h_ref.logits, engine.params, sequence,
        reference_keys(model_cfg), routes=routes)
    want, details = reference(details=True)
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
    out["routing"] = routing_check(routes, details["selection"],
                                   model_cfg.num_experts_per_tok)
    out["routing"]["counters_match"] = counters_match(
        counted, [r[n_prompt:] for r in routes], model_cfg.held_experts)
    state = state_errors(held, details["states"])
    out["state"] = dict(state, tolerance=STATE_TOLERANCE,
                        deep_tolerance=DEEP_TOLERANCE)
    out["ok"] = bool(
        out["ok"] and out["routing"]["worst_tie"] <= ROUTE_TIE
        and out["routing"]["counters_match"]
        and state["worst_head"][0] <= STATE_TOLERANCE
        and max(state["worst_head"] + state["window"]) <= DEEP_TOLERANCE)
    return out
