"""The comparison that decides `correct` for the Keye-VL-2.0 serve cell, at
the cell's own sizes: a document of N_DOCUMENT = 17,173 tokens (the cell's
are 17.1k-62.8k, mean 35.4k: the SHORTEST, because the float32 reference of
19k tokens in blocks is what fits in the 2.5 GB the engine leaves free)
asked len(QUESTIONS) = 8 times with questions of 64-256 tokens, then
N_DECODE = 32 tokens decoded for all eight asks TOGETHER, eight live rows of
the engine's 48 on ONE document's pages, against the plain float32 reference
(benchmarks/reference/keye_dsa_ref.py: no cache, every query against every
position), same weights, on the chip, outside the window.

The first ask (document + question) is prefilled from nothing in the tick's
chunks of 512 through the row's table of 1036 pages; its whole pages go into
the radix as a finished prompt's do. Each later ask must get the document's
268 whole pages back from `_match_prefix`; they go into its table IN PLACE
(K, V and index pages alike: one id) and its chunks compute positions 17,152
on, scoring and attending the shared pages where they lie. A decode step
then scores 17.4k index keys a row, selects 2048 and gathers them.

Every chunk and every decode step runs TWICE, as parity_sarvam_mla's do:
through the engine's timed program (`_chunk_prefill`, `_decode`), then
through the check's own jit of the same `model.apply` on the same
arguments, which also returns what the timed programs keep to themselves:
logits at every row, the experts each token chose, the positions each query
selected in every layer, and what two layers' attention gave in front of
W_o. Selection and routing are discontinuous and two compilations of one
model round differently, so the reference follows the routes AND the
selection of the very execution whose logits it reads (its `routes`,
`selection`); its own scores and selection come back beside and are
compared with the program's apart (3).

1. Logits, by parity.py's code (`_compare`, `ill_conditioned`): every
   question row of the first ask, of the later asks, and every decode step
   of every row, against the reference's forward pass over the document and
   the eight continuations as one array (`branch`).
2. Routing, by parity_nemotron_h.routing_check over the reference's
   log-probabilities: every expert the program took lies within ROUTE_TIE
   of the reference's cut. And the router ALONE (`router_float32`): what
   every question row's and decode step's router read, through float64 on
   the host, against the experts it chose: the share of routings whose
   sets agree, at least ROUTER_AGREE. The stream's bf16 noise (~0.02 in a
   logit) hides a router computed in bf16 (~0.004) from the comparison with
   the reference, and this one has no stream in it.
3. The indexer, in layer 0 (whose input is the embedding itself) and in the
   LAST layer: the decode steps' scores against the reference's, relative
   to the spread of a row's scores (`index_scores`); and the selection at
   the question rows (the chunk's threshold form) and the decode steps (the
   exact top-k): the share of the reference's 2048 that the program chose,
   and every disagreement (a token one chose and the other did not)
   confined to tokens whose REFERENCE score lies within SELECT_TIE of a
   spread of the reference's 2048-th.
4. What the attention gave in front of W_o with the program's selection fed
   to the reference, in layer 0 and the last layer, at the question rows
   and the decode steps: per head |o - o_ref| / |o_ref|, the worst head of
   the worst row.
5. The cached rows, layer 0 and the last: what the K, V and index pools
   hold for every position of every row against the reference's.
6. The timed programs against the check's, as parity_sarvam_mla's 6.

Controls that must fail, each through the same comparison as the program:
  index_8bit       the index keys of the rows' pages through e4m3 and back,
                   in place, then CONTROL_STEPS decode steps (8-bit operands
                   of the index products): by `index_scores` in layer 0
  bf16_router      the float64 logits of the question rows' and decode
                   steps' router inputs rounded to bf16, their softmax
                   rounded to bf16, ranked: by `router_float32` (not judged
                   under ROUTER_CONTROL_AT_LEAST routings: a rehearsal's 350
                   at toy widths flip none one run in twelve)
  misplaced_token  the program's selection of a decode row with ONE token
                   swapped for the candidate the reference scores lowest:
                   by the selection's margin
  misplaced_row    the index rows read one position off: by `cached`

The limits, each from two readings on the chip at the published widths, six
layers (my chip runs, PR 49: two runs of the check alone and four of the
cell, seeds 4900000103/-104, -201, -302..-304). The check's own programs are
compiled with `xla_allow_excess_precision` off (`Programs`).

  limit                     the program           the control
  LOGIT_WORST 0.10          worst position        (no control reads the logits)
                            0.022-0.031 of a
                            spread
  LOGIT_MEDIAN 0.06         median 0.018-0.023
  ROUTE_TIE 0.04            0.015-0.018 in        (a router in bf16 reads
                            log-probability       0.005-0.009 here: under the
                                                  stream's own noise)
  ROUTER_AGREE 0.999        1.0 (0 of ~9,000      bf16_router 0.973-0.985
                            routings flipped)
  index scores, layer 0     0.031-0.038 of a      index_8bit 0.185-0.222
   0.08                     row's spread
  index scores, last 0.30   0.034-0.136           0.68-0.70
  selection margin, layer   0.061-0.069 of a      misplaced_token 6.9-14.4
   0 0.20 / last 0.60       spread / 0.058-0.23   / 8.8-12.3
  selection share, layer 0  0.992-0.994 /         (what 8-bit keys select was
   0.98 / last 0.95         0.984-0.992           not read apart)
  attended 0.04             0.008-0.013 layer 0,  (no control; the selection
                            0.001-0.002 last      is fed, so what is left is
                                                  bf16 products over 2,048
                                                  tokens)
  cached rows, layer 0      0.0036-0.0047         misplaced_row 1.99-2.06
   0.012
  cached rows, last 0.06    0.0074-0.033          0.46-0.72
  TIMED_AGREE 0.8           0.969-1.0 (256 tokens a run)
  TIMED_MEDIAN 0.15         0.031-0.039 (worst chunk 0.35)

Each limit is 2-3 times the largest reading and under half the control's
smallest, but the logits' and the attended values', which no control reads:
they are 3 times the largest reading. The index scores of layer 0 round
twice (u -> qI, kI in bf16, the products' float32 sums) and cancel: w has
both signs, so a row's scores are a difference of sums several times their
spread. The last layer's carry the stream's ~2 % besides.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import numpy as np

from .parity import (PROBE_SIZE, SET_ASIDE_AT_MOST, _compare,
                     ill_conditioned)
from .parity_nemotron_h import routing_check
from .parity_sarvam_mla import _alloc, _peak_bytes, _table, row_errors

N_DOCUMENT, N_DECODE, CONTROL_STEPS = 17173, 32, 8
QUESTIONS = (64, 91, 119, 146, 174, 201, 229, 256)
PROBES = 2
LOGIT_WORST, LOGIT_MEDIAN = 0.10, 0.06
ROUTE_TIE = 0.04
ROUTER_AGREE = 0.999    # of the routings, the router on its own input
# routings under which a bf16 router may flip none (1 in ~200 flips at toy
# widths, 1 in 40 at the published ones): the control is then not judged
ROUTER_CONTROL_AT_LEAST = 2000
INDEX_SCORES = {"first": 0.08, "last": 0.30}
SELECT_TIE = {"first": 0.20, "last": 0.60}
SELECT_SHARE = {"first": 0.98, "last": 0.95}
ATTENDED = {"first": 0.04, "last": 0.04}
CACHED = {"first": 0.012, "last": 0.06}
TIMED_AGREE, TIMED_MEDIAN = 0.8, 0.15


def reference_keys(m) -> Dict[str, Any]:
    """The running KeyeDSAConfig back under the published key names the
    reference reads (a rehearsal runs toy widths, not the file's)."""
    return {"num_hidden_layers": m.num_layers,
            "num_attention_heads": m.num_heads,
            "num_key_value_heads": m.num_kv_heads, "head_dim": m.head_dim,
            "rms_norm_eps": m.rms_norm_eps, "rope_theta": m.rope_theta,
            "sa_config": {"indexer_num_heads": m.index_heads,
                          "indexer_head_dim": m.index_head_dim,
                          "indexer_num_kv_heads": 1, "topk": m.index_topk},
            "num_experts": m.num_experts,
            "num_experts_per_tok": m.num_experts_per_tok,
            "held_experts": tuple(m.held_experts)}


def spans(cfg) -> Dict[str, Any]:
    """The check's lengths on this engine: the cell's where they fit, else
    the same shape at the engine's own bucket, page and batch."""
    top = cfg.prefill_buckets[-1]
    if (cfg.max_len >= N_DOCUMENT + max(QUESTIONS) + N_DECODE + 2
            and cfg.max_batch >= len(QUESTIONS)):
        return {"document": N_DOCUMENT, "questions": QUESTIONS,
                "ticks": N_DECODE, "control": CONTROL_STEPS}
    rows = max(1, min(cfg.max_batch - 1, 3))
    return {"document": 6 * top + top // 6 + 1,
            "questions": tuple(top // 2 + 3 + r * (top // 2 + 1)
                               for r in range(rows)),
            "ticks": top // 2, "control": top // 4}


class Programs:
    """The check's own jits of the engine's model, on the arguments the
    engine's timed programs take, returning what those keep to themselves;
    and the rows of some pages read and written in place."""

    def __init__(self, engine):
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops import sparse_attention as sa
        self.engine = engine
        cfg = engine.config.model
        module = engine.model
        self.read = read = (0, cfg.num_layers - 1)
        layers = range(cfg.num_layers)
        topk, f32 = cfg.index_topk, jnp.float32
        sown = ["routing", "intermediates"]

        def routes_of(variables):
            return [variables["routing"][f"layer_{i}"]["moe"]["chosen"][0]
                    for i in layers]

        def chunk(params, tokens, positions, pools, offset, table, valid):
            (hidden, new), seen = module.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=[{"k": k, "v": v, "index": ix, "table": table}
                           for k, v, ix in zip(*pools)],
                cache_index=offset, valid=valid, head=False, mutable=sown)
            attn = lambda i: seen["intermediates"][  # noqa: E731
                f"layer_{i}"]["attn"]
            chosen = []
            for i in layers:
                u = attn(i)["candidates"][0]
                chosen.append(sa.positions_of(
                    sa.kept(u, sa.threshold_of(u, topk)), min(topk,
                                                              u.shape[1])))
            return (hidden[0], tuple([kept[j] for kept in new]
                                     for j in range(3)),
                    [r[0] for r in routes_of(seen)], chosen,
                    {i: attn(i)["attended"][0][0].astype(f32) for i in read},
                    [seen["intermediates"][f"layer_{i}"]["router_input"][0][
                        0].astype(f32) for i in layers])

        def decode(params, pools, active, tables, lengths, tokens):
            caches = [{"k": k, "v": v, "index": ix, "active": active,
                       "block_tables": tables, "lengths": lengths,
                       "pairs": pairs, "steps": steps}
                      for k, v, ix, (pairs, steps) in zip(
                          *pools, cfg.init_counters())]
            (logits, new), seen = module.apply(
                {"params": params}, tokens[:, None],
                positions=lengths[:, None], kv_caches=caches,
                cache_index=None, mutable=sown)
            attn = lambda i: seen["intermediates"][  # noqa: E731
                f"layer_{i}"]["attn"]
            chosen = []
            for i in layers:
                at, count = attn(i)["selected"][0]
                live = jnp.arange(at.shape[1])[None, :] < count[:, None]
                chosen.append(jnp.where(live, at, -1))
            return (logits[:, -1].astype(f32),
                    tuple([kept[j] for kept in new] for j in range(3)),
                    [r[:, 0] for r in routes_of(seen)], chosen,
                    {i: attn(i)["attended"][0][:, 0].astype(f32)
                     for i in read},
                    {i: attn(i)["index_scores"][0] for i in read},
                    [seen["intermediates"][f"layer_{i}"]["router_input"][0][
                        :, 0].astype(f32) for i in layers])

        # the pools donated and handed back, as the engine's own programs
        # take them: a program that only read them would copy every pool.
        # Every rounding the model states is made: the compiler may keep a
        # float32 value where the model casts to bf16 and back (its
        # default), and the router would then read another number than the
        # `router_input` it hands out
        as_stated = {"xla_allow_excess_precision": False}
        self.chunk = jax.jit(chunk, donate_argnums=(3,),
                             compiler_options=as_stated)
        self.decode = jax.jit(decode, donate_argnums=(1,),
                              compiler_options=as_stated)
        self.head = jax.jit(lambda params, hidden: module.apply(
            {"params": params}, hidden[None], method="head")[0].astype(f32))
        self.gather = jax.jit(lambda pools, ids: [p[0][ids] for p in pools])
        self.scatter = jax.jit(
            lambda pools, ids, rows: [p.at[0, ids].set(r)
                                      for p, r in zip(pools, rows)],
            donate_argnums=(0,))

    def held_rows(self, pages, first: int, upto: int):
        """What the pools of the layers read hold for positions `first` ..
        `upto` - 1 of the row whose pages are `pages`: {layer: (k, v,
        index) each [positions, width] float32}."""
        import jax.numpy as jnp
        engine = self.engine
        ps = engine.config.page_size
        ids = jnp.asarray(pages[first // ps:-(-upto // ps)], jnp.int32)
        lanes = engine.config.model.index_head_dim
        out = {}
        for i in self.read:
            rows = self.gather([engine.k_pages[i], engine.v_pages[i],
                                engine.index_pages[i]], ids)
            out[i] = tuple(np.asarray(r.astype(jnp.float32)).reshape(
                -1, r.shape[-1])[first % ps:first % ps + upto - first]
                for r in rows)
            out[i] = out[i][:2] + (out[i][2][:, :lanes],)
        return out


def run_chunks(programs: Programs, prompt, table, start: int,
               logits_from: int) -> Dict[str, Any]:
    """`prompt[start:]` into the row whose pages `table` names, every chunk
    through the engine's timed program and then through the check's. From
    the check's: the logits and the attended values of the positions
    `logits_from` on, the routes and the selected positions (every layer)
    of every position computed; from both, at each chunk's last real row,
    the timed program's logits and the check's (`timed`: pairs)."""
    import jax.numpy as jnp
    engine = programs.engine
    cfg = engine.config
    logits, routes, timed, chosen, read = [], [], [], [], []
    attended = {i: [] for i in programs.read}
    off = start
    while off < len(prompt):
        rem = len(prompt) - off
        size = engine._bucket(min(rem, cfg.prefill_buckets[-1]))
        take = min(rem, size)
        tokens = np.zeros((1, size), np.int32)
        tokens[0, :take] = prompt[off:off + take]
        args = (engine.params, jnp.asarray(tokens), jnp.asarray(
            np.arange(off, off + size, dtype=np.int32)[None]))
        tail = (jnp.asarray(off, jnp.int32), jnp.asarray(table),
                jnp.asarray(take, jnp.int32))
        last, engine._row_pools = engine._chunk_prefill(
            *args, engine._row_pools, *tail,
            jnp.asarray(take - 1, jnp.int32))
        hidden, engine._row_pools, chose, picked, gave, inputs = \
            programs.chunk(*args, engine._row_pools, *tail)
        routes.append([np.asarray(r[:take]) for r in chose])
        # (a query selects among positions <= its own: slots behind its
        # count hold the table's width)
        chosen.append([np.asarray(p[:take]) for p in picked])
        first = max(logits_from - off, 0)
        rows = np.asarray(programs.head(
            engine.params,
            hidden if first < take else hidden[take - 1:take]))
        rows = rows[first:take] if first < take else rows
        timed.append((np.asarray(last[0]), rows[-1]))
        if first < take:
            logits.append(rows)
            read.append([np.asarray(u[first:take]) for u in inputs])
            for i in programs.read:
                attended[i].append(np.asarray(gave[i][first:take]))
        off += take
    return {"logits": np.concatenate(logits) if logits else None,
            "attended": {i: np.concatenate(a) if a else None
                         for i, a in attended.items()},
            "chosen": [np.concatenate([part[j] for part in chosen])
                       for j in range(len(chosen[0]))],
            "router_inputs": [np.concatenate([part[j] for part in read])
                              for j in range(len(read[0]))] if read else None,
            "routes": [np.concatenate([part[j] for part in routes])
                       for j in range(len(routes[0]))],
            "timed": timed}


def run_decode(programs: Programs, slots, tables, starts, fed=None,
               first_tokens=None, ticks: int = 0) -> Dict[str, Any]:
    """Decode steps with the rows `slots` of the engine's batch live, row r
    on the pages `tables[r]`, its first token at position `starts[r]`.
    With `fed` [ticks, rows] those tokens through the check's program alone
    (a control); else `ticks` steps fed greedily from `first_tokens`, each
    through the engine's timed `_decode`, whose tokens are the next step's,
    and then through the check's. Per live row and step: logits, routes,
    selected positions (-1 behind a row's count), attended values, index
    scores, the tokens fed, and what the timed program sampled."""
    import jax
    import jax.numpy as jnp
    engine = programs.engine
    B = engine.config.max_batch
    block_tables = np.zeros((B, engine.config.pages_per_seq), np.int32)
    active = np.zeros((B,), bool)
    for slot, table in zip(slots, tables):
        block_tables[slot] = table
        active[slot] = True
    greedy = (jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
              jnp.ones((B,), jnp.float32))
    steps = ticks if fed is None else len(fed)
    now = np.asarray(first_tokens if fed is None else fed[0], np.int32)
    logits, routes, chosen, sampled, tokens_fed, read = \
        [], [], [], [], [], []
    attended = {i: [] for i in programs.read}
    scores = {i: [] for i in programs.read}
    most = max(starts) + steps
    for i in range(steps):
        lengths = np.zeros((B,), np.int32)
        lengths[slots] = np.asarray(starts) + i
        tokens = np.zeros((B,), np.int32)
        tokens[slots] = now
        args = (jnp.asarray(active), jnp.asarray(block_tables),
                jnp.asarray(lengths), jnp.asarray(tokens))
        if fed is None:
            engine._rng, key = jax.random.split(engine._rng)
            ids, engine._row_pools, engine.counters = engine._decode(
                engine.params, engine._row_pools, *args, key, *greedy,
                engine.counters)
        lg, engine._row_pools, chose, picked, gave, scored, inputs = \
            programs.decode(engine.params, engine._row_pools, *args)
        live = jnp.asarray(slots)
        tokens_fed.append(now)
        logits.append(np.asarray(lg[live]))
        routes.append([np.asarray(r[live]) for r in chose])
        chosen.append([np.asarray(p[live]) for p in picked])
        read.append([np.asarray(u[live]) for u in inputs])
        for layer in programs.read:
            attended[layer].append(np.asarray(gave[layer][live]))
            scores[layer].append(np.asarray(scored[layer][live][:, :most]))
        if fed is None:
            now = np.asarray(ids)[slots]
            sampled.append(now)
        elif i + 1 < steps:
            now = np.asarray(fed[i + 1], np.int32)
    by_row = lambda parts: np.stack(parts, 1)  # noqa: E731
    per_layer = lambda parts: [  # noqa: E731
        by_row([part[j] for part in parts]) for j in range(len(parts[0]))]
    return {"logits": by_row(logits), "routes": per_layer(routes),
            "chosen": per_layer(chosen), "router_inputs": per_layer(read),
            "attended": {i: by_row(a) for i, a in attended.items()},
            "index_scores": {i: by_row(s) for i, s in scores.items()},
            "fed": by_row(tokens_fed),
            "sampled": by_row(sampled) if sampled else None}


def selection_check(chose, ref_scores, ref_selected) -> Dict[str, float]:
    """The program's selection of some queries (`chose` [queries, s] bool
    over the reference's array) against the reference's own (`ref_selected`)
    by the reference's scores [queries, s]: the smallest share of the
    reference's set that the program chose, and the farthest from the
    reference's cut (its lowest selected score), in spreads of the query's
    selected-or-candidate scores, that a token one chose and the other did
    not lies."""
    share, margin = 1.0, 0.0
    for took, scores, own in zip(chose, ref_scores, ref_selected):
        if not own.any():
            continue
        share = min(share, float((took & own).sum() / own.sum()))
        swapped = took ^ own
        if swapped.any():
            cut = scores[own].min()
            spread = scores[own | took].std() + 1e-30
            margin = max(margin, float(
                np.abs(scores[swapped] - cut).max() / spread))
    return {"share": share, "margin": margin}


def router_alone(inputs, routers, routes, k: int) -> Dict[str, float]:
    """Per layer the router's inputs [n, hidden] through float64
    (`inputs` x `routers` [hidden, E], softmax), the experts the program
    chose of them [n, k], and what a router in bf16 would have chosen of
    the same inputs (logits rounded to bf16, their softmax rounded to
    bf16): `routing_check`'s share of routings that agree with the
    float64 order, and its worst tie in log-probability, of each."""
    import jax.numpy as jnp
    low = lambda a: np.asarray(jnp.asarray(  # noqa: E731
        a, jnp.bfloat16).astype(jnp.float32), np.float64)
    log_probs, low_routes = [], []
    for u, w in zip(inputs, routers):
        logits = np.asarray(u, np.float64) @ np.asarray(w, np.float64)
        logits -= logits.max(-1, keepdims=True)
        log_probs.append(logits - np.log(np.exp(logits).sum(
            -1, keepdims=True)))
        rounded = np.exp(low(logits))
        rounded = low(rounded / rounded.sum(-1, keepdims=True))
        low_routes.append(np.argsort(-rounded, axis=-1, kind="stable")[:, :k])
    mine = routing_check(routes, log_probs, k)
    lower = routing_check(low_routes, log_probs, k)
    return {"program": mine["routing_agree"],
            "program_worst_tie": mine["worst_tie"],
            "bf16_router": lower["routing_agree"],
            "bf16_router_worst_tie": lower["worst_tie"],
            "routings": int(sum(len(r) for r in routes)),
            "agree_at_least": ROUTER_AGREE}


def score_errors(got, want, lengths) -> float:
    """Worst row of |I - I_ref| over the row's candidates, in spreads of
    the reference's scores of that row."""
    return max(float(np.abs(g[:n] - w[:n]).max() / w[:n].std())
               for g, w, n in zip(got, want, lengths))


def judge(logit_parts, set_aside, routing, router, scores, selection,
          attended, cached) -> Dict[str, Any]:
    """Comparisons 1-5 on one run's numbers (the program's, or a
    control's); `scores`, `attended`, `cached`: {"first" | "last": worst},
    `selection`: {"first" | "last": `selection_check`'s}."""
    beyond, aside, total = [], [], 0
    for name, part in logit_parts.items():
        for at, x in enumerate(part.pop("diff_over_std")):
            total += 1
            if set_aside[name][at]:
                aside.append((name, at, x))
            elif x > LOGIT_WORST:
                beyond.append((name, at, x))
    out: Dict[str, Any] = dict(logit_parts)
    out.update(beyond_tolerance=beyond[:32], beyond=len(beyond),
               set_aside=len(aside), tolerance_std=LOGIT_WORST,
               median_tolerance_std=LOGIT_MEDIAN)
    routing["tie_tolerance"] = ROUTE_TIE
    out["routing"] = routing
    out["router_float32"] = router
    out["index_scores"] = dict(scores, tolerance=INDEX_SCORES)
    out["selection"] = dict(selection, tie_tolerance=SELECT_TIE,
                            share_at_least=SELECT_SHARE)
    out["attended"] = dict(attended, tolerance=ATTENDED)
    out["cached"] = dict(cached, tolerance=CACHED)
    within = lambda got, limits: all(  # noqa: E731
        got[k] <= limits[k] for k in limits if k in got)
    out["failed"] = [what for what, good in (
        ("logits", not beyond and len(aside) <= SET_ASIDE_AT_MOST * total),
        ("logit_median", all(part["median"] <= LOGIT_MEDIAN
                             for part in logit_parts.values())),
        ("routing", routing["worst_tie"] <= ROUTE_TIE),
        ("router_float32", router["program"] >= ROUTER_AGREE),
        ("index_scores", within(scores, INDEX_SCORES)),
        ("selection", all(
            selection[k]["margin"] <= SELECT_TIE[k]
            and selection[k]["share"] >= SELECT_SHARE[k]
            for k in selection)),
        ("attended", within(attended, ATTENDED)),
        ("cached", within(cached, CACHED))) if not good]
    out["ok"] = not out["failed"]
    return out


def serve(engine, config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from ..reference import keye_dsa_ref
    from .builders import jax_seed

    cfg = engine.config
    model_cfg = cfg.model
    ps, B, L = cfg.page_size, cfg.max_batch, model_cfg.num_layers
    if engine.has_work():
        raise RuntimeError("parity needs an idle engine")
    n = spans(cfg)
    rng = np.random.default_rng([jax_seed(seed), 77])
    draw = lambda size: rng.integers(  # noqa: E731
        1, model_cfg.vocab_size, size=size).tolist()
    document = draw(n["document"])
    asks = [document + draw(size) for size in n["questions"]]
    R, ticks = len(asks), n["ticks"]
    D = len(document) // ps * ps
    slots = [(1 + r * B // R) % B for r in range(R)]
    programs = Programs(engine)
    first_layer, last_layer = programs.read
    names = (("first", first_layer), ("last", last_layer))
    out: Dict[str, Any] = {"document": len(document), "shared_span": D,
                           "questions": list(n["questions"]),
                           "decode_steps": ticks, "live_rows": R}
    held: List[int] = []
    with engine._mesh_scope():
        try:
            room = lambda ask: -(-(len(ask) + ticks) // ps)  # noqa: E731
            pages = [_alloc(engine, room(asks[0]))]
            held += pages[0]
            starts = [0]
            chunks = [run_chunks(programs, asks[0],
                                 _table(engine, pages[0]), 0, D)]
            engine._register_prefix(asks[0], pages[0])
            shared_pages = []
            for ask in asks[1:]:
                shared = engine._match_prefix(ask)
                held += shared
                shared_pages.append(len(shared))
                own = _alloc(engine, room(ask) - len(shared))
                held += own
                pages.append(shared + own)
                starts.append(len(shared) * ps)
                chunks.append(run_chunks(
                    programs, ask, _table(engine, pages[-1]), starts[-1], D))
            out["shared_pages"] = shared_pages
            out["shared_pages_expected"] = D // ps
            tables = [_table(engine, p) for p in pages]
            decoded = run_decode(
                programs, slots, tables, [len(a) for a in asks],
                first_tokens=[int(c["logits"][-1].argmax()) for c in chunks],
                ticks=ticks)
            in_pool = [programs.held_rows(p, s, len(a) + ticks)
                       for p, s, a in zip(pages, starts, asks)]
            # -- the control: the rows' index keys through 8-bit floats and
            # back, in place, over the same pages and the same tokens
            ids = jnp.asarray(sorted({p for row in pages for p in row}))
            kept = programs.gather(engine.index_pages, ids)
            engine.index_pages = programs.scatter(
                engine.index_pages, ids,
                [rows.astype(jnp.float8_e4m3fn).astype(rows.dtype)
                 for rows in kept])
            eight = run_decode(
                programs, slots, tables, [len(a) for a in asks],
                fed=decoded["fed"][:, :n["control"]].T)
            engine.index_pages = programs.scatter(engine.index_pages, ids,
                                                  kept)
            del kept
        finally:
            for page in held:
                engine.pool.decref(page)
        out["peak_bytes"] = {"programs": _peak_bytes()}

        # -- the reference: the shared span, then each ask's own part, as
        # one array
        tails = [np.concatenate([a[D:], f]).astype(np.int64)
                 for a, f in zip(asks, decoded["fed"])]
        edges = np.cumsum([D] + [len(t) for t in tails])
        tokens = np.concatenate([np.asarray(document[:D])] + tails)
        S = len(tokens)
        positions = np.concatenate(
            [np.arange(D)] + [D + np.arange(len(t)) for t in tails])
        branch = np.concatenate(
            [np.zeros(D, np.int64)]
            + [np.full(len(t), r + 1) for r, t in enumerate(tails)])
        routes = [np.concatenate(
            [chunks[0]["routes"][j][:D]]
            + [part for c, s, r in zip(chunks, starts, range(R))
               for part in (c["routes"][j][D - s:], decoded["routes"][j][r])])
            for j in range(L)]
        wanted = np.arange(D, S)

        def in_array(picked, r):
            """A row's selected positions (its own sequence's; -1 or past
            its context where a slot is empty) as indices of the array."""
            picked = np.asarray(picked)
            real = (picked >= 0) & (picked < D + len(tails[r]))
            return np.where(
                real, np.where(picked < D, picked, edges[r] + picked - D), -1)

        # the program's selection, every layer, every token of the array:
        # the document's span (the first ask's chunks), then ask r's
        # question rows (the chunk's form) and its decode steps
        def fed_selection(of_decode):
            return {j: np.concatenate(
                [in_array(chunks[0]["chosen"][j][:D], 0)]
                + [part for r, (c, s) in enumerate(zip(chunks, starts))
                   for part in (in_array(c["chosen"][j][D - s:], r),
                                in_array(of_decode["chosen"][j][r], r))])
                for j in range(L)}
        selection = fed_selection(decoded)

        def as_masks(layer, rows):
            """The fed selection of some of `wanted`, as masks [rows, S]."""
            at = selection[layer][wanted[rows]]
            masks = np.zeros((len(rows), S + 1), bool)
            masks[np.arange(len(rows))[:, None], at] = True   # -1: column S
            return masks[:, :S]
        reference = functools.partial(
            keye_dsa_ref.logits, engine.params, tokens,
            reference_keys(model_cfg), positions=positions, branch=branch,
            routes=routes, rows=wanted)
        want, details = reference(selection=selection, details=programs.read)
        want = np.asarray(want)
        wobble = (S, model_cfg.hidden_size)
        probes = [np.asarray(reference(
            selection=selection,
            embed_scale=1.0 + PROBE_SIZE * jax.random.normal(
                jax.random.PRNGKey(k), wobble, jnp.float32)))
            for k in range(PROBES)]
        ref = {i: {k: (tuple(np.asarray(a) for a in v) if k == "cached"
                       else np.asarray(v)) for k, v in details[i].items()}
               for i in programs.read}
        log_probs = [np.log(np.asarray(p) + 1e-30) for p in details["probs"]]
    ill = ill_conditioned(want, probes)
    out["peak_bytes"]["reference"] = _peak_bytes()

    # rows of `wanted` by part: ask r's question, ask r's decode steps
    question = [np.arange(edges[r], edges[r] + len(asks[r]) - D) - D
                for r in range(R)]
    steps = [np.arange(edges[r + 1] - ticks, edges[r + 1]) - D
             for r in range(R)]
    every_step = np.concatenate(steps)
    every_question = np.concatenate(question)
    got = {"first_ask": chunks[0]["logits"],
           "decode": decoded["logits"].reshape(-1, want.shape[-1])}
    rows_of = {"first_ask": question[0], "decode": every_step}
    if R > 1:
        got["later_asks"] = np.concatenate([c["logits"] for c in chunks[1:]])
        rows_of["later_asks"] = np.concatenate(question[1:])
    flat = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731

    def step_scores(of_decode, count):
        """The decode steps' scores against the reference's, over each
        step's candidates (the row's own sequence: the document's span,
        then its own part)."""
        worst = {}
        for name, layer in names:
            errors = []
            for r in range(R):
                for i in range(count):
                    at = steps[r][i]
                    n_own = len(asks[r]) - D + i + 1
                    mine = of_decode["index_scores"][layer][r, i]
                    theirs = ref[layer]["index_scores"][at]
                    errors.append(score_errors(
                        [np.concatenate([mine[:D], mine[D:D + n_own]])],
                        [np.concatenate([theirs[:D], theirs[
                            edges[r]:edges[r] + n_own]])], [D + n_own]))
            worst[name] = max(errors)
        return worst

    def selections(rows):
        return {name: selection_check(
            as_masks(layer, rows), ref[layer]["index_scores"][rows],
            ref[layer]["selected"][rows]) for name, layer in names}

    def attended_errors(pairs):
        return {name: max(row_errors(gave[layer],
                                     ref[layer]["attended"][rows])
                          for gave, rows in pairs)
                for name, layer in names}

    def cached_errors(shift=0):
        """Worst row of what the pools hold (K, V, index key) against the
        reference's rows of the same tokens; `shift`: read the index rows
        that many positions off (the control)."""
        worst = {}
        for name, layer in names:
            errors = []
            for r, (rows, start) in enumerate(zip(in_pool, starts)):
                for kind, have in enumerate(rows[layer]):
                    full = ref[layer]["cached"][kind]
                    theirs = np.concatenate(
                        [full[start:D], full[edges[r]:edges[r + 1]]])
                    theirs = theirs[:len(have)]
                    if shift and kind == 2:
                        have, theirs = have[shift:], theirs[:-shift]
                    errors.append(row_errors(have, theirs))
            worst[name] = max(errors)
        return worst

    everything = np.arange(len(wanted))
    verdict = judge(
        {name: _compare(got[name], want[rows_of[name]]) for name in got},
        {name: ill[rows_of[name]] for name in got},
        routing_check(routes, log_probs, model_cfg.num_experts_per_tok),
        # (the question rows' and the decode steps', every layer)
        router_alone(
            [np.concatenate([c["router_inputs"][j] for c in chunks]
                            + [flat(decoded["router_inputs"][j])])
             for j in range(L)],
            [engine.params[f"layer_{i}"]["moe"]["router"] for i in range(L)],
            [np.concatenate([c["routes"][j][D - s:]
                             for c, s in zip(chunks, starts)]
                            + [flat(decoded["routes"][j])])
             for j in range(L)],
            model_cfg.num_experts_per_tok),
        step_scores(decoded, ticks), selections(everything),
        attended_errors(
            [({i: np.concatenate([c["attended"][i] for c in chunks])
               for i in programs.read}, every_question),
             ({i: flat(a) for i, a in decoded["attended"].items()},
              every_step)]),
        cached_errors())
    out.update(verdict)

    # -- the controls, each through the comparison it must fail
    c = n["control"]
    control_steps = np.concatenate([s[:c] for s in steps])
    eight_scores = step_scores(eight, c)
    low_agree = out["router_float32"]["bf16_router"]
    swapped = {layer: as_masks(layer, control_steps) for _, layer in names}
    at = wanted[control_steps]
    # a query's candidates: no later in the array, of the document or of
    # its own part
    allowed = (np.arange(S)[None, :] <= at[:, None]) & (
        (branch[None, :] == 0) | (branch[None, :] == branch[at][:, None]))
    for _, layer in names:
        for took, scores, own, may in zip(
                swapped[layer], ref[layer]["index_scores"][control_steps],
                ref[layer]["selected"][control_steps], allowed):
            # one selected token out, the candidate scored lowest in
            left = np.flatnonzero(may & ~took & ~own)
            if len(left):
                took[np.flatnonzero(took)[0]] = False
                took[left[np.argmin(scores[left])]] = True
    out["controls"] = {
        "index_8bit": {"index_scores": eight_scores,
                       "ok": eight_scores["first"] <= INDEX_SCORES["first"]},
        "bf16_router": {"routing_agree": low_agree, "ok": (
            low_agree >= ROUTER_AGREE
            if out["router_float32"]["routings"] >= ROUTER_CONTROL_AT_LEAST
            else None)},
        "misplaced_token": {
            name: selection_check(
                swapped[layer], ref[layer]["index_scores"][control_steps],
                ref[layer]["selected"][control_steps])["margin"]
            for name, layer in names},
        "misplaced_row": cached_errors(shift=1)}
    controls = out["controls"]
    controls["misplaced_token"]["ok"] = \
        controls["misplaced_token"]["first"] <= SELECT_TIE["first"]
    controls["misplaced_row"]["ok"] = all(
        controls["misplaced_row"][k] <= CACHED[k] for k in CACHED)

    # -- the timed programs against the check's
    argmax = decoded["logits"].argmax(-1)
    pairs = [pair for c_ in chunks for pair in c_["timed"]]
    apart = lambda a, b: float(np.abs(a - b).max() / b.std())  # noqa: E731
    timed = [apart(mine, its) for mine, its in pairs]
    out["timed"] = {
        "decode_agree": float((decoded["sampled"] == argmax).mean()),
        "decode_agree_at_least": TIMED_AGREE,
        "chunk_median": float(np.median(timed)),
        "chunk_worst": float(np.max(timed)), "chunks": len(timed),
        "chunk_median_at_most": TIMED_MEDIAN}
    out["sparse_kernel"] = engine.stats().get("sparse_kernel")
    if out["shared_pages"] != [out["shared_pages_expected"]] * (R - 1):
        out["failed"].append("shared_pages")
    out["failed"] += [f"control_passed:{name}"
                      for name, control in controls.items()
                      if control["ok"] is True]
    if not (out["timed"]["decode_agree"] >= TIMED_AGREE
            and out["timed"]["chunk_median"] <= TIMED_MEDIAN):
        out["failed"].append("timed_programs")
    out["ok"] = not out["failed"]
    return out


def main() -> int:
    """`python3 -m benchmarks.harness.parity_keye_dsa [--seed N]
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
    from . import builders_keye_dsa, spec
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    config = spec.load_json(os.path.join(
        root, "benchmarks", "configs", "keye-vl-2.0-30b-a3b-serve.json"))
    for name, value in config.get("program_settings", {}).items():
        os.environ["RTPU_" + name.upper()] = str(value)
    import jax

    from ray_tpu.llm.paged import PagedLLMEngine
    engine = PagedLLMEngine(builders_keye_dsa.keye_dsa_engine(
        config, args.seed, args.rehearse))
    began = time.monotonic()
    out = serve(engine, config, args.seed)
    memory = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"seed": args.seed,
                      "seconds": round(time.monotonic() - began, 1),
                      "peak_bytes_in_use": memory.get("peak_bytes_in_use"),
                      "bytes_limit": memory.get("bytes_limit"), **out},
                     default=float), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
