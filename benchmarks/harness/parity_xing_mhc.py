"""The comparison that decides `correct` for the Xing4.0 serve cell, at the
cell's own sizes: PROMPTS = four fresh prompts of 4,611, 1,089, 577 and 130
tokens (the cell's are 2k-8k; one past 4k and past the rotary table's 4,096;
they end 3, 65, 65 and 130 tokens past a chunk's edge, so their tails take
the 32, 128, 128 and 256 buckets and cross page edges of 64), each prefilled
from nothing in the tick's chunks of 512 into its own pages, then N_DECODE =
32 tokens decoded for all four TOGETHER, four live rows of the engine's 48
with dead rows between, against the plain float32 reference
(benchmarks/reference/xing_mhc_ref.py: no cache, attention expanded, the
expert sum dense a block of experts at a time), same weights, on the chip,
outside the window.

Every chunk and every decode step runs TWICE, as parity_sarvam_mla's do (its
`run_chunks` and `run_decode` drive both): through the engine's timed
program (`_chunk_prefill`, `_decode`), then through the check's own jit of
the same `model.apply` on the same arguments, which also returns what the
timed programs keep to themselves: logits at every row, the experts each
token chose, what every router read, and what every hyper-connection read
(the four streams) and gave (its 24 coefficients). Routing is
discontinuous and two compilations of one model round differently, so the
reference follows the routes of the very execution whose logits it reads.

1. Logits, by parity.py's code (`_compare`, `ill_conditioned`) at this
   check's limits: the last LOGIT_ROWS rows of every prompt and every decode
   step of every row, against the reference's forward pass over each prompt
   and the tokens it was fed, the four sequences one after another as one
   array (its `branch` argument: a token attends its own sequence).
2. Routing, by parity_nemotron_h.routing_check: every expert the program
   took against the reference's own float32 order lies within ROUTE_TIE of
   the reference's cut. And the router ALONE (`router_float32`): what the
   compared rows' routers read, through float64 on the host, against the
   experts they chose: the share of routings whose sets agree, at least
   ROUTER_AGREE. The stream's bf16 noise hides a router computed in bf16
   from the comparison with the reference; this one has no stream in it.
3. The hyper-connections ALONE (`coefficients`): the streams every
   connection of the compared rows read, through float64 on the host (the
   norm, phi, the gains and biases, the sigmoids, 20 Sinkhorn iterations),
   against the 24 numbers the program gave: the largest difference of any
   coefficient, at most COEFFICIENTS. The streams are the program's own
   (bf16, exact in float64; the chain reads them as stored), so nothing
   but the chain's arithmetic is in it. Beside it `doubly_stochastic`:
   how far the program's H_res rows and columns are from summing to 1.
4. The timed programs against the check's, as parity_sarvam_mla's 6: the
   share of (row, step) at which the token the timed `_decode` sampled is
   the argmax of the check's logits, at least TIMED_AGREE; the row of
   logits the timed chunk returns against the check's row, the median chunk
   within TIMED_MEDIAN of a spread. The reference is compared with the
   check's programs, so this is what ties the timed path to a checked
   answer; `mismatched_*` say what a timed program that answered from
   another row's or another chunk's logits reads.
5. `stream_error`, judged by nothing: the streams every connection read
   against the reference's, by connection (`stream_errors`): where the
   logits' distance comes from.

Controls that must fail, each through the same comparison as the program:
  latent_rows_8bit  every page the rows hold through e4m3 and back, in
                 place, then CONTROL_STEPS decode steps again through the
                 check's program on the tokens the rows were fed
                 (parity_sarvam_mla's control): by the logits' limits
  bf16_chain     the chain from z to the three H computed in bfloat16 (every
                 operation's result rounded) from the same streams: by
                 `coefficients`
  sinkhorn_19    the float64 chain with 19 iterations in place of 20: by
                 `coefficients` (the config's b_res = 4 I leaves H_res 0.95
                 on the diagonal, whose iterations converge by 0.87 each:
                 the twentieth still moves an entry by ~1e-4)
  bf16_router    the float64 scores of the same router inputs rounded to
                 bf16 (logits, then their sigmoid), ranked: by
                 `router_float32` (not judged under ROUTER_CONTROL_AT_LEAST
                 routings)

The limits, each between two readings on the chip at the published widths,
six layers (my chip runs, PR 52: the final form's runs, the check alone and
inside the cell, seeds 5200000342-345, -402-405, -511-513, -521-527, -531-535; the upper
readings from -511 on; PERF.md section 6):

  limit                 the program               what must fail
  LOGIT_WORST 0.40      worst position 0.202-     8-bit rows: 4.17-5.34
                        0.274 of a spread
  LOGIT_MEDIAN 0.25     a part's median 0.156-    8-bit rows: 2.17-2.95
                        0.167
  ROUTE_TIE 0.04        0.014-0.027               (Sarvam's: its 8-bit rows
                                                  0.10-0.22)
  ROUTER_AGREE 0.999    1.0 of 4,170 routings     bf16 router 0.947-0.952
  COEFFICIENTS 1.5e-5   2.3e-6-2.6e-6             19 iterations 1.3e-4-
                                                  1.8e-4, bf16 chain 1.2e-2-
                                                  1.4e-2
  TIMED_AGREE 0.9       0.969-1.0 (128 tokens a   another row's logits: 0.0
                        run)
  TIMED_MEDIAN 0.05     0.0156-0.0174 (worst      another chunk's row:
                        chunk 0.0313)             6.23-6.62

The logit limits are 1.5 times the largest reading and about a tenth of
the control's smallest. The sound median is twice Sarvam-105B's
0.08 on the same attention path and depth, and `stream_error` says where it
comes from: the streams' sum stands 0.83 % off the reference's after layer
0's attention (Sarvam's one stream 0.65 %), 1.20 % after its SwiGLU (0.94 %),
then 1.87, 2.38, 2.80, 3.16 % after each expert layer but the last (1.4,
1.7, 1.9, 2.0 %); of each layer's growth 0.29-0.43 % stands at the expert
sublayer and 0.05-0.24 % at the attention. Per unit of what it adds to the
streams neither is the noisier (crudely 0.3-0.6 % against 0.5-0.7 %): the
expert sublayer's output is 0.50-1.27 of the streams it is added to, the
attention's 0.16-0.36, because every chosen expert of a token is held here
(four routed outputs, scaled by 2, beside the shared one) where Sarvam's
cell holds one chosen expert in eight. The 8-bit rows read six times
Sarvam's (0.34-0.56): these rows attend 130-4.6k cached tokens where
Sarvam's attend 16k, so less of the rounding averages out.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict

import numpy as np

from .parity import (PROBE_SIZE, SET_ASIDE_AT_MOST, _compare,
                     ill_conditioned)
from .parity_nemotron_h import routing_check
from .parity_sarvam_mla import (_alloc, _peak_bytes, _table, run_chunks,
                                run_decode)

PROMPTS, N_DECODE, CONTROL_STEPS = (4611, 1089, 577, 130), 32, 16
LOGIT_ROWS = 192        # of each prompt, its last rows
PROBES = 2
# of a position's logit spread: every well-conditioned position, and the
# median position of each part
LOGIT_WORST, LOGIT_MEDIAN = 0.40, 0.25
ROUTE_TIE = 0.04
ROUTER_AGREE = 0.999
ROUTER_CONTROL_AT_LEAST = 2000
COEFFICIENTS = 1.5e-5
TIMED_AGREE, TIMED_MEDIAN = 0.9, 0.05
# what the check's programs hand out beside logits and routes
READ = ("streams", "coefficients", "router_inputs")


def reference_keys(m) -> Dict[str, Any]:
    """The running XingMHCConfig back under the published key names the
    reference reads (a rehearsal runs toy widths, not the file's)."""
    return {"num_hidden_layers": m.num_layers,
            "num_attention_heads": m.num_heads,
            "kv_lora_rank": m.kv_lora_rank,
            "qk_nope_head_dim": m.qk_nope_head_dim,
            "qk_rope_head_dim": m.qk_rope_head_dim,
            "v_head_dim": m.v_head_dim, "rms_norm_eps": m.rms_norm_eps,
            "rope_theta": m.rope_theta,
            "rope_scaling": {
                "type": "yarn", "factor": m.rope_factor,
                "original_max_position_embeddings": m.rope_original_max,
                "beta_fast": m.rope_beta_fast, "beta_slow": m.rope_beta_slow,
                "mscale": m.rope_mscale,
                "mscale_all_dim": m.rope_mscale_all_dim},
            "first_k_dense_replace": m.first_k_dense_replace,
            "n_routed_experts": m.num_experts,
            "num_experts_per_tok": m.num_experts_per_tok,
            "routed_scaling_factor": m.routed_scaling_factor,
            "held_experts": tuple(m.held_experts),
            "hc_mult": m.hc_mult, "hc_sinkhorn_iters": m.hc_sinkhorn_iters,
            "hc_eps": m.hc_eps,
            "mhc_h_res_clamp_min": m.mhc_h_res_clamp[0],
            "mhc_h_res_clamp_max": m.mhc_h_res_clamp[1]}


def spans(cfg) -> Dict[str, Any]:
    """The check's lengths on this engine: the cell's where they fit, else
    the same shape at the engine's own bucket and batch (a prompt of six
    chunks and a part, one of a chunk and a part, one of a part; a live
    row fewer than the batch)."""
    top = cfg.prefill_buckets[-1]
    if (cfg.max_len >= max(PROMPTS) + N_DECODE + 2 and top == 512
            and cfg.max_batch >= len(PROMPTS)):
        return {"prompts": PROMPTS, "ticks": N_DECODE, "rows": LOGIT_ROWS,
                "control": CONTROL_STEPS}
    rows = max(1, min(cfg.max_batch - 1, 3))
    return {"prompts": (6 * top + top // 6 + 1, top + top // 2 + 1,
                        top // 2 + 3)[:rows],
            "ticks": top // 2, "rows": top, "control": top // 4}


def _sown(variables, cfg):
    """Of one apply: per expert layer the experts `RoutedExperts` sowed
    [batch, positions, k]; and {"streams": [batch, positions, connections,
    n, d], "coefficients": [batch, positions, connections, 24],
    "router_inputs": [batch, positions, expert layers, d]}, the
    connections in the order the model runs them."""
    import jax.numpy as jnp
    routing, seen = variables["routing"], variables["intermediates"]
    layers = [f"layer_{i}" for i in range(cfg.num_layers)]
    experts = [name for i, name in enumerate(layers) if cfg.expert_layer(i)]
    routes = [routing[name]["moe"]["routed"]["chosen"][0]
              for name in experts]
    connections = [seen[name][hc] for name in layers
                   for hc in ("attn_hc", "mlp_hc")]
    # sown [n, batch, positions, d] and [24, batch, positions]
    return routes, {
        "streams": jnp.stack([jnp.moveaxis(c["streams"][0], 0, 2)
                              for c in connections], 2),
        "coefficients": jnp.stack(
            [jnp.moveaxis(c["coefficients"][0], 0, 2)
             for c in connections], 2),
        "router_inputs": jnp.stack(
            [seen[name]["router_input"][0] for name in experts], 2)}


class Programs:
    """The check's own jits of the engine's model, on the arguments the
    engine's timed programs take and in the form
    `parity_sarvam_mla.run_chunks` / `run_decode` call them, returning what
    the timed programs keep to themselves."""
    read = READ

    def __init__(self, engine):
        import jax
        import jax.numpy as jnp
        self.engine = engine
        cfg = engine.config.model
        module = engine.model
        kinds = cfg.layer_caches()
        f32 = jnp.float32
        seen = ["routing", "intermediates"]

        def chunk(params, tokens, positions, pools, offset, table, valid):
            (hidden, new), sown = module.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=[{"pool": pool, "table": table} for pool in pools],
                cache_index=offset, valid=valid, head=False, mutable=seen)
            routes, gave = _sown(sown, cfg)
            return (hidden[0], [kept[0] for kept in new],
                    [r[0] for r in routes],
                    {name: a[0] for name, a in gave.items()})

        def decode(params, pools, active, tables, lengths, tokens):
            counters = iter(cfg.init_counters())
            caches = []
            for pool, (_, _, counts) in zip(pools, kinds):
                cache = {"pool": pool, "active": active,
                         "block_tables": tables, "lengths": lengths}
                if counts:
                    cache["pairs"], cache["steps"] = next(counters)
                caches.append(cache)
            (logits, new), sown = module.apply(
                {"params": params}, tokens[:, None],
                positions=lengths[:, None], kv_caches=caches,
                cache_index=None, mutable=seen)
            routes, gave = _sown(sown, cfg)
            return (logits[:, -1].astype(f32), [kept[0] for kept in new],
                    [r[:, 0] for r in routes],
                    {name: a[:, 0] for name, a in gave.items()})

        # the pools donated and handed back, as the engine's own programs
        # take them: a program that only read them would copy every pool.
        # Compiled as the timed programs are, so that the two are one
        # arithmetic: with `xla_allow_excess_precision` off here alone a
        # tenth of the routings fall otherwise and the two programs'
        # logits stand a whole spread apart (my chip runs, PR 52). The
        # streams stand in the model's type behind a barrier where they are
        # written (`hyper_connect`), so what a chain hands out is what it
        # read
        self.chunk = jax.jit(chunk, donate_argnums=(3,))
        self.decode = jax.jit(decode, donate_argnums=(1,))
        self.head = jax.jit(lambda params, hidden: module.apply(
            {"params": params}, hidden[None], method="head")[0].astype(f32))
        # whole pages out of every pool and back in place (the control)
        self.gather = jax.jit(lambda pools, ids: [p[0][ids] for p in pools])
        self.scatter = jax.jit(
            lambda pools, ids, rows: [p.at[0, ids].set(r)
                                      for p, r in zip(pools, rows)],
            donate_argnums=(0,))


# ---------------------------------------------------------------------------
# the chain and the router alone, on the host
# ---------------------------------------------------------------------------

def chain_float64(streams, hc, cfg, iters=None) -> np.ndarray:
    """The 24 coefficients [rows, 24] of the streams [rows, n, d] one
    connection read, through float64 (the module docstring of
    benchmarks/reference/xing_mhc_ref.py has the equations)."""
    n = cfg.hc_mult
    x = np.asarray(streams, np.float64).reshape(len(streams), -1)
    z = x / np.sqrt((x * x).mean(-1, keepdims=True) + cfg.rms_norm_eps)
    gains = np.asarray(hc["gains"], np.float64)
    raw = (z @ np.asarray(hc["phi"], np.float64).reshape(x.shape[1], -1)) \
        * np.repeat(gains, [n, n, n * n]) + np.asarray(hc["bias"], np.float64)
    sigmoid = lambda a: 1.0 / (1.0 + np.exp(-a))  # noqa: E731
    m = np.exp(np.clip(raw[:, 2 * n:], *cfg.mhc_h_res_clamp)).reshape(
        -1, n, n)
    for _ in range(cfg.hc_sinkhorn_iters if iters is None else iters):
        m = m / (m.sum(-1, keepdims=True) + cfg.hc_eps)
        m = m / (m.sum(-2, keepdims=True) + cfg.hc_eps)
    return np.concatenate([sigmoid(raw[:, :n]), 2.0 * sigmoid(raw[:, n:2 * n]),
                           m.reshape(len(m), -1)], -1)


def chain_bfloat16(streams, hc, cfg) -> np.ndarray:
    """The same chain with every operation's result rounded to bfloat16."""
    import jax
    import jax.numpy as jnp
    n, low = cfg.hc_mult, jnp.bfloat16

    @jax.jit
    def chain(x, phi, gains, bias):
        x = x.reshape(x.shape[0], -1).astype(low)
        z = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + low(cfg.rms_norm_eps))
        raw = jnp.dot(z, phi.reshape(x.shape[1], -1).astype(low)) \
            * jnp.repeat(gains.astype(low), np.array([n, n, n * n])) \
            + bias.astype(low)
        m = jnp.exp(jnp.clip(raw[:, 2 * n:], *cfg.mhc_h_res_clamp)).reshape(
            -1, n, n)
        for _ in range(cfg.hc_sinkhorn_iters):
            m = m / (m.sum(-1, keepdims=True) + low(cfg.hc_eps))
            m = m / (m.sum(-2, keepdims=True) + low(cfg.hc_eps))
        return jnp.concatenate(
            [jax.nn.sigmoid(raw[:, :n]), 2 * jax.nn.sigmoid(raw[:, n:2 * n]),
             m.reshape(m.shape[0], -1)], -1).astype(jnp.float32)
    return np.asarray(chain(jnp.asarray(streams), hc["phi"], hc["gains"],
                            hc["bias"]), np.float64)


def chains_alone(streams, gave, params, cfg) -> Dict[str, float]:
    """`streams` [rows, connections, n, d] and the coefficients the
    program `gave` [rows, connections, 24]: the largest difference from
    the float64 chain of the program's, of the bf16 chain's and of 19
    iterations'; and how far the program's H_res is from doubly
    stochastic."""
    n = cfg.hc_mult
    names = [(f"layer_{i}", hc) for i in range(cfg.num_layers)
             for hc in ("attn_hc", "mlp_hc")]
    worst = {"program": 0.0, "bf16_chain": 0.0, "sinkhorn_19": 0.0}
    by_connection = []
    for at, (layer, hc) in enumerate(names):
        p = params[layer][hc]
        x = streams[:, at]
        want = chain_float64(x, p, cfg)
        for name, got in (
                ("program", np.asarray(gave[:, at], np.float64)),
                ("bf16_chain", chain_bfloat16(x, p, cfg)),
                ("sinkhorn_19", chain_float64(
                    x, p, cfg, cfg.hc_sinkhorn_iters - 1))):
            worst[name] = max(worst[name], float(np.abs(got - want).max()))
            if name == "program":
                by_connection.append(float(np.abs(got - want).max()))
    res = np.asarray(gave[..., 2 * n:], np.float64).reshape(
        gave.shape[:2] + (n, n))
    worst["doubly_stochastic"] = float(max(
        np.abs(res.sum(-1) - 1).max(), np.abs(res.sum(-2) - 1).max()))
    worst["program_by_connection"] = by_connection
    worst["rows"] = int(gave.shape[0])
    worst["at_most"] = COEFFICIENTS
    return worst


def router_alone(inputs, routes, params, cfg) -> Dict[str, float]:
    """`inputs` [rows, expert layers, d] the routers read and the experts
    they chose (`routes`: per expert layer [rows, k]): through float64
    (sigmoid(u W) + bias), `routing_check`'s share of routings that agree
    with that order, of the program's choice and of a router in bf16
    (logits rounded to bf16, their sigmoid rounded to bf16) on the same
    inputs."""
    import jax.numpy as jnp
    low = lambda a: np.asarray(jnp.asarray(  # noqa: E731
        a, jnp.bfloat16).astype(jnp.float32), np.float64)
    k = cfg.num_experts_per_tok
    layers = [f"layer_{i}" for i in range(cfg.num_layers)
              if cfg.expert_layer(i)]
    selections, low_routes = [], []
    for at, name in enumerate(layers):
        m = params[name]["moe"]["routed"]
        bias = np.asarray(m["e_score_correction_bias"], np.float64)
        logits = np.asarray(inputs[:, at], np.float64) \
            @ np.asarray(m["router"], np.float64)
        selections.append(1.0 / (1.0 + np.exp(-logits)) + bias)
        rounded = low(1.0 / (1.0 + np.exp(-low(logits)))) + bias
        low_routes.append(np.argsort(-rounded, axis=-1, kind="stable")[:, :k])
    mine = routing_check(routes, selections, k)
    lower = routing_check(low_routes, selections, k)
    return {"program": mine["routing_agree"],
            "program_worst_tie": mine["worst_tie"],
            "bf16_router": lower["routing_agree"],
            "routings": int(sum(len(r) for r in routes)),
            "agree_at_least": ROUTER_AGREE}


def logit_verdict(parts, set_aside) -> Dict[str, Any]:
    """Comparison 1 on one run's logits (the program's, or a control's):
    `parts` {part: `_compare`'s}, `set_aside[part]` the positions that are
    ill conditioned and so not held to LOGIT_WORST. `passed`: the two
    limits apart."""
    beyond, aside, total = [], [], 0
    for name, part in parts.items():
        for at, x in enumerate(part.pop("diff_over_std")):
            total += 1
            if set_aside[name][at]:
                aside.append((name, at, x))
            elif x > LOGIT_WORST:
                beyond.append((name, at, x))
    out: Dict[str, Any] = dict(parts)
    out.update(beyond_tolerance=beyond[:32], beyond=len(beyond),
               set_aside=len(aside), tolerance_std=LOGIT_WORST,
               median_tolerance_std=LOGIT_MEDIAN)
    out["passed"] = {
        "logits": not beyond and len(aside) <= SET_ASIDE_AT_MOST * total,
        "logit_median": all(part["median"] <= LOGIT_MEDIAN
                            for part in parts.values())}
    return out


def stream_errors(streams, reference) -> Dict[str, Any]:
    """`streams` [rows, connections, n, d] as the program's connections
    read them and the `reference`'s (per connection [rows, n, d]): by
    connection, in the order they run (a layer's attention's, then its
    MLP's: the first reads n copies of the embedding), the median row's
    |sum_j X[j] - ref| / |ref| (the sum is what the head reads, and what
    one residual stream would be) and |X - ref| / |ref| over the n streams
    apart; and how large each sublayer's output stands beside the streams it
    was added to, |sum' - sum| / |sum| in the reference (H_res being doubly
    stochastic, the sum moves by sum_i H_post[i] y alone)."""
    norm = lambda a: np.sqrt((a * a).reshape(len(a), -1).sum(-1))  # noqa
    of_sum, apart, step = [], [], []
    sums = [np.asarray(x, np.float64).sum(1) for x in reference]
    for at, want in enumerate(reference):
        have = np.asarray(streams[:, at], np.float64)
        want = np.asarray(want, np.float64)
        of_sum.append(float(np.median(
            norm(have.sum(1) - sums[at]) / norm(sums[at]))))
        apart.append(float(np.median(norm(have - want) / norm(want))))
        if at:
            step.append(float(np.median(
                norm(sums[at] - sums[at - 1]) / norm(sums[at - 1]))))
    return {"of_sum": of_sum, "of_streams": apart,
            "sublayer_over_stream": step}


# ---------------------------------------------------------------------------

def serve(engine, config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from ..reference import xing_mhc_ref
    from .builders import jax_seed

    cfg = engine.config
    model_cfg = cfg.model
    ps, B = cfg.page_size, cfg.max_batch
    if engine.has_work():
        raise RuntimeError("parity needs an idle engine")
    n = spans(cfg)
    rng = np.random.default_rng([jax_seed(seed), 77])
    prompts = [rng.integers(1, model_cfg.vocab_size, size=size).tolist()
               for size in n["prompts"]]
    R, ticks = len(prompts), n["ticks"]
    # rows compared of each prompt: its last ones
    starts = [max(len(p) - n["rows"], 0) for p in prompts]
    # live rows apart from one another in the batch, dead ones between
    slots = [(1 + r * B // R) % B for r in range(R)]
    programs = Programs(engine)
    out: Dict[str, Any] = {"prompts": list(n["prompts"]),
                           "decode_steps": ticks, "live_rows": R}
    began = time.monotonic()
    held = []
    with engine._mesh_scope():
        try:
            pages = []
            for prompt in prompts:
                pages.append(_alloc(engine, -(-(len(prompt) + ticks) // ps)))
                held += pages[-1]
            chunks = [run_chunks(programs, prompt, _table(engine, own), 0,
                                 first)
                      for prompt, own, first in zip(prompts, pages, starts)]
            decoded = run_decode(
                programs, slots, [_table(engine, own) for own in pages],
                [len(p) for p in prompts],
                first_tokens=[int(c["logits"][-1].argmax()) for c in chunks],
                ticks=ticks)
            # -- the control, over the same pages and the same tokens (as
            # parity_sarvam_mla's): every page of the rows through 8-bit
            # floats and back, in place, the first steps again through the
            # check's program, and what the pages held put back
            ids = jnp.asarray(sorted({p for own in pages for p in own}))
            kept = programs.gather(engine.k_pages, ids)
            engine.k_pages = programs.scatter(
                engine.k_pages, ids,
                [rows.astype(jnp.float8_e4m3fn).astype(rows.dtype)
                 for rows in kept])
            eight = run_decode(
                programs, slots, [_table(engine, own) for own in pages],
                [len(p) for p in prompts],
                fed=decoded["fed"][:, :n["control"]].T)
            engine.k_pages = programs.scatter(engine.k_pages, ids, kept)
            del kept
        finally:
            for page in held:
                engine.pool.decref(page)
        out["peak_bytes"] = {"programs": _peak_bytes()}
        out["seconds"] = {"programs": round(time.monotonic() - began, 1)}

        # -- the reference: every prompt and the tokens its row was fed, one
        # after another as ONE array (its `branch`: a token attends its own
        # sequence), along the routes the check's programs took
        keys = reference_keys(model_cfg)
        tails = [np.concatenate([p, f]).astype(np.int64)
                 for p, f in zip(prompts, decoded["fed"])]
        edges = np.cumsum([0] + [len(t) for t in tails])
        tokens = np.concatenate(tails)
        layers = range(len(decoded["routes"]))
        routes = [np.concatenate(
            [part for r in range(R)
             for part in (chunks[r]["routes"][j], decoded["routes"][j][r])])
            for j in layers]
        wanted = np.concatenate([np.arange(edges[r] + starts[r], edges[r + 1])
                                 for r in range(R)])
        reference = functools.partial(
            xing_mhc_ref.logits, engine.params, tokens, keys,
            positions=np.concatenate([np.arange(len(t)) for t in tails]),
            branch=np.concatenate([np.full(len(t), r + 1)
                                   for r, t in enumerate(tails)]),
            routes=routes, rows=wanted)
        want, details = reference(details=True)
        want = np.asarray(want)
        wobble = (len(tokens), model_cfg.hidden_size)
        probes = [np.asarray(reference(
            embed_scale=1.0 + PROBE_SIZE * jax.random.normal(
                jax.random.PRNGKey(k), wobble, jnp.float32)))
            for k in range(PROBES)]
        selection = [np.asarray(s) for s in details["selection"]]
    ill = ill_conditioned(want, probes)
    out["peak_bytes"]["reference"] = _peak_bytes()
    out["seconds"]["reference"] = round(
        time.monotonic() - began - out["seconds"]["programs"], 1)

    # -- 1. logits: each prompt's compared rows, then each row's steps
    # (rows of `wanted`: sequence r's compared prompt rows, then its steps)
    asked = [len(p) - s for p, s in zip(prompts, starts)]
    at = np.cumsum([0] + [a + ticks for a in asked])
    prompt_rows = np.concatenate([np.arange(at[r], at[r] + asked[r])
                                  for r in range(R)])
    step_rows = np.concatenate([np.arange(at[r] + asked[r], at[r + 1])
                                for r in range(R)])
    control_rows = np.concatenate(
        [np.arange(at[r] + asked[r], at[r] + asked[r] + n["control"])
         for r in range(R)])
    flat = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731
    verdict = logit_verdict(
        {"prefill": _compare(
            np.concatenate([c["logits"] for c in chunks]), want[prompt_rows]),
         "decode": _compare(flat(decoded["logits"]), want[step_rows])},
        {"prefill": ill[prompt_rows], "decode": ill[step_rows]})
    logits_ok = verdict.pop("passed")
    out.update(verdict)
    # the control through the same comparison: it must fail it
    control = logit_verdict(
        {"decode": _compare(flat(eight["logits"]), want[control_rows])},
        {"decode": ill[control_rows]})
    control.pop("beyond_tolerance")
    control["ok"] = all(control["passed"].values())

    # -- 2. routing against the reference's order, every position
    k = model_cfg.num_experts_per_tok
    routing = routing_check(routes, selection, k)
    routing["tie_tolerance"] = ROUTE_TIE
    out["routing"] = routing

    # -- 2, 3. the router and the chain alone, at the compared rows
    seen = {name: np.concatenate(
        [c["attended"][name] for c in chunks]
        + [flat(decoded["attended"][name])]) for name in READ}
    chose = [np.concatenate(
        [c["routes"][j][s:] for c, s in zip(chunks, starts)]
        + [flat(decoded["routes"][j])]) for j in layers]
    router = router_alone(seen["router_inputs"], chose, engine.params,
                          model_cfg)
    chain = chains_alone(seen["streams"], seen["coefficients"],
                         engine.params, model_cfg)
    out["router_float32"] = router
    out["coefficients"] = chain
    # where the logits' distance comes from: the streams every connection
    # read against the reference's, in the program's order of rows
    out["stream_error"] = stream_errors(
        seen["streams"], [x[np.concatenate([prompt_rows, step_rows])]
                          for x in details["streams"]])

    # -- 4. the timed programs against the check's
    argmax = decoded["logits"].argmax(-1)
    pairs = [pair for c in chunks for pair in c["timed"]]
    apart = lambda a, b: float(np.abs(a - b).max() / b.std())  # noqa: E731
    timed = [apart(mine, its) for mine, its in pairs]
    out["timed"] = {
        "decode_agree": float((decoded["sampled"] == argmax).mean()),
        "decode_agree_at_least": TIMED_AGREE,
        "chunk_median": float(np.median(timed)),
        "chunk_worst": float(np.max(timed)), "chunks": len(timed),
        "chunk_median_at_most": TIMED_MEDIAN,
        # what a timed program that answered from another row's or
        # another chunk's logits would read
        "mismatched_decode_agree": float(
            (decoded["sampled"] == np.roll(argmax, 1, 0)).mean()),
        "mismatched_chunk_median": float(np.median(
            [apart(pairs[i][0], pairs[i - 1][1])
             for i in range(len(pairs))]))}

    judged = router["routings"] >= ROUTER_CONTROL_AT_LEAST
    out["controls"] = {
        "bf16_chain": {"coefficients": chain["bf16_chain"],
                       "ok": chain["bf16_chain"] <= COEFFICIENTS},
        "sinkhorn_19": {"coefficients": chain["sinkhorn_19"],
                        "ok": chain["sinkhorn_19"] <= COEFFICIENTS},
        "bf16_router": {"routing_agree": router["bf16_router"],
                        "ok": (router["bf16_router"] >= ROUTER_AGREE)
                        if judged else None},
        "latent_rows_8bit": control}
    finite = all(np.isfinite(a).all() for a in
                 (decoded["logits"], seen["coefficients"]))
    out["failed"] = [what for what, good in (
        ("logits", finite and logits_ok["logits"]),
        ("logit_median", logits_ok["logit_median"]),
        ("routing", routing["worst_tie"] <= ROUTE_TIE),
        ("router_float32", router["program"] >= ROUTER_AGREE),
        ("coefficients", chain["program"] <= COEFFICIENTS),
        ("timed_programs",
         out["timed"]["decode_agree"] >= TIMED_AGREE
         and out["timed"]["chunk_median"] <= TIMED_MEDIAN),
        ("control_passed", not any(c["ok"] for c in out["controls"].values()
                                   if c["ok"] is not None))) if not good]
    out["latent_kernel"] = engine.stats().get("latent_kernel")
    out["ok"] = not out["failed"]
    return out


def main() -> int:
    """`python3 -m benchmarks.harness.parity_xing_mhc [--seed N]
    [--rehearse]`: the check alone, on an engine built from the cell's
    configuration file. Prints the verdict as one JSON line, with the
    seconds it took and the device's peak memory; the builder's tool for
    the readings behind the limits, not part of any run."""
    import argparse
    import json
    import os
    import time

    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from . import builders_xing_mhc, spec
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    config = spec.load_json(os.path.join(
        root, "benchmarks", "configs", "xing4.0-29b-a4b-serve.json"))
    import jax

    from ray_tpu.llm.paged import PagedLLMEngine
    engine = PagedLLMEngine(builders_xing_mhc.xing_mhc_engine(
        config, args.seed, args.rehearse))
    began = time.monotonic()
    out = serve(engine, config, args.seed)
    memory = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"seed": args.seed,
                      "seconds_in_all": round(time.monotonic() - began, 1),
                      "peak_bytes_in_use": memory.get("peak_bytes_in_use"),
                      "bytes_limit": memory.get("bytes_limit"), **out}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
