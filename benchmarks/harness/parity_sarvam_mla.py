"""The comparison that decides `correct` for a Sarvam-105B serve cell, at the
cell's own sizes: a document of N_DOCUMENT = 16,421 tokens (the cell's are
8k-32k, mean 17.7k; four times past the rotary table's 4,096) asked
len(QUESTIONS) = 8 times with questions of 64-256 tokens, then N_DECODE = 64
tokens decoded for all eight asks TOGETHER, eight live rows of the engine's
48 on ONE document's pages, against the plain float32 reference
(benchmarks/reference/sarvam_mla_ref.py: expanded form, no cache), same
weights, on the chip, outside the window.

The first ask (document + question) is prefilled from nothing, 65 chunks of
256 through the row's table of 524 pages; its whole pages go into the radix
as a finished prompt's do. Each later ask must get the document's 256 whole
pages back from `_match_prefix`; they go into its table IN PLACE and its
chunks compute positions 16,384 on, attending the shared pages where they
lie. A decode step then attends 16.5k cached rows a row: 11 blocks of the
latent kernel (24 pages = 1,536 tokens a block at these shapes), so the
slot flip, the prefetch of the next block and of the next row's first, the
running softmax across blocks, dead rows between live ones and rows that
share pages are all in what is compared.

Every chunk and every decode step runs TWICE: through the engine's timed
program (`_chunk_prefill`, `_decode`), then through the check's own jit of
the same `model.apply` on the same arguments, which also returns what the
timed programs keep to themselves: logits at every row, the experts each
token chose, and what two layers' attention gave in front of W_o. A routed
layer's choice flips on a rounding at a near tie and two compilations of
one model round differently, so the reference must follow the routes of
the very execution whose logits it reads: the check's program is the one
compared with the reference, it runs second (the pages hold ITS rows), and
the timed programs are tied to it (6 below). The tokens fed are the ones
the timed `_decode` sampled.

1. Logits, by parity.py's code (`_compare`, `ill_conditioned`) at this
   check's limits: every question row of the first ask, of the later asks,
   and every decode step of every row, against the reference's forward
   pass over the document and the eight continuations as one array (its
   `branch` argument: each continuation attends the document and itself).
2. Routing, by parity_nemotron_h.routing_check: every expert the program
   took against the reference's own float32 order lies within ROUTE_TIE of
   the reference's cut.
3. The cached rows themselves, in layer 0 (whose input is the embedding
   itself: W_kva, the norm, the rotary table, ONE rounding) and in the
   LAST layer (everything before it besides): what the pools hold for
   every position of every row, the document's 16k included, against the
   reference's `[c ; k_rope]`; per row |row - ref| / |ref|, the worst row.
4. What the absorbed attention gave in front of W_o, in layer 0 and in the
   last layer, at the question rows (the chunk program's loop over blocks
   of pages) and at the decode steps (the kernel): per head |o - o_ref| /
   |o_ref| against the reference's EXPANDED products, the worst head of
   the worst row.
5. The control, "the latent row stored in an 8-bit float" (every page the
   rows hold through e4m3 and back, in place, then CONTROL_STEPS decode
   steps), goes through 1-4 as the program does and must come out NOT
   correct; else the check has no teeth and says so (`control.ok`).
6. The timed programs: the share of (row, step) at which the token the
   timed `_decode` sampled is the argmax of the check's logits, at least
   TIMED_AGREE; and the row of logits the timed chunk returns (`last`)
   against the check's row, the median chunk within TIMED_MEDIAN of a
   spread. They are the same model lowered twice and differ where a near
   tie broke differently, so both are held by a share or a median, which
   a fault in the timed executable alone would still fail.

The limits, each from two readings on the chip at the published widths, six
layers (my chip runs, PR 45: seven runs of this form of the check alone
and thirteen of the cell, seeds 4500000211-326, the last-layer numbers from
the eighteen since a row's own part begins at the shared span's end; PERF.md
section 6). The router's bias is zero, as the cell runs it.

  limit                    the program               8-bit latent rows
  LOGIT_WORST 0.30         worst position 0.097-     worst 1.77-2.59
                           0.135 of a spread
  LOGIT_MEDIAN 0.18        median 0.078-0.083        0.340-0.556
  ROUTE_TIE 0.04           0.010-0.019               0.099-0.222
  cached rows, layer 0     0.0036-0.0038             0.0305-0.0317
   0.012
  cached rows, last layer  0.0319-0.0350             0.0427-0.0451 (under
   0.06                                              the limit: below)
  attended, layer 0  0.05  0.0204-0.0260             0.093-0.142
  attended, last layer     0.052-0.096               0.238-0.538
   0.15
  TIMED_AGREE 0.8          1.0 (512 tokens a run)    (no 8-bit reading; a
                                                     timed program that
                                                     answered from another
                                                     row's logits: 0.000-
                                                     0.002)
  TIMED_MEDIAN 0.15        0.0466-0.0469 (worst      (from another chunk's
                           chunk 0.68-1.39)          row: 5.6-5.8)

The logits are the check's own limits and 2.5 times parity.py's (0.12,
median 0.06): six bf16 layers of THIS model leave the final stream 2.1 %
off the float32 reference's (my chip run, PR 45: 0.65 % after layer 0's
attention, whose input is exact, 0.94 % after its SwiGLU, then 1.4, 1.7,
1.9, 2.0, 2.1 % by layer) and the largest of 32,768 logit differences is
~4 times that of a spread at EVERY position, hardly moving with the seed.
A latent layer rounds twice more than a K/V layer on the way to a key (h ->
c~ -> c -> k, v) and its softmax is 1.87 times sharper (m^2). LOGIT_WORST
is 2.2 times the largest reading and 0.17 of the control's smallest;
LOGIT_MEDIAN 2.2 times and 0.53; ROUTE_TIE 2.1 times and 0.40; layer 0's
rows 3.2 times and 0.39; layer 0's attention 1.9 times and 0.54; the last
layer's attention (the worst of 64 heads at 1.8k rows: a heavy tail, mean
reading 0.073) 1.6 times and 0.63. The LAST layer's cached rows carry
the stream's 2-3 % themselves, which is what an 8-bit row's rounding is
(3 %): that comparison cannot tell the control (0.044 against 0.034) and
its limit is not set by it. It holds what the others do not see at depth,
a row in the wrong page or at the wrong position, which reads 1.47-1.51
(`misplaced_by_one`, every run: the same rows against the reference's a
position off); 0.06 is 1.7 times the largest reading. ISSUE 45's second
control, "absorbed products accumulated in bf16", is not run: it cannot
be told from float32 accumulation on this chip (it read 0.022-0.028
against 0.0195-0.0215 in this check's first form, PERF.md section 7: the
MXU accumulates in float32 either way and `preferred_element_type`
rounds each product's RESULT once, beside operands that are bf16 already),
and the knob it needed reached into the model's configuration.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import numpy as np

from .parity import (PROBE_SIZE, SET_ASIDE_AT_MOST, _compare,
                     ill_conditioned)
from .parity_nemotron_h import routing_check

N_DOCUMENT, N_DECODE, CONTROL_STEPS = 16421, 64, 16
QUESTIONS = (64, 91, 119, 146, 174, 201, 229, 256)
PROBES = 2
# of a position's logit spread: every well-conditioned position, and the
# median position of each part (the module's docstring has the readings)
LOGIT_WORST, LOGIT_MEDIAN = 0.30, 0.18
ROUTE_TIE = 0.04
LATENT_ROW_TOLERANCE = {"first": 0.012, "last": 0.06}
ATTENDED_TOLERANCE = {"first": 0.05, "last": 0.15}
TIMED_AGREE, TIMED_MEDIAN = 0.8, 0.15


def reference_keys(m) -> Dict[str, Any]:
    """The running SarvamMLAConfig back under the published key names the
    reference reads (a rehearsal runs toy widths, not the file's)."""
    return {"num_hidden_layers": m.num_layers,
            "num_attention_heads": m.num_heads,
            "kv_lora_rank": m.kv_lora_rank,
            "qk_nope_head_dim": m.qk_nope_head_dim,
            "qk_rope_head_dim": m.qk_rope_head_dim,
            "v_head_dim": m.v_head_dim, "rms_norm_eps": m.rms_norm_eps,
            "rope_theta": m.rope_theta,
            "rope_scaling": {
                "type": "deepseek_yarn", "factor": m.rope_factor,
                "original_max_position_embeddings": m.rope_original_max,
                "beta_fast": m.rope_beta_fast, "beta_slow": m.rope_beta_slow,
                "mscale": m.rope_mscale,
                "mscale_all_dim": m.rope_mscale_all_dim},
            "first_k_dense_replace": m.first_k_dense_replace,
            "num_experts": m.num_experts,
            "num_experts_per_tok": m.num_experts_per_tok,
            "routed_scaling_factor": m.routed_scaling_factor,
            "held_experts": tuple(m.held_experts)}


def spans(cfg) -> Dict[str, Any]:
    """The check's lengths on this engine: the cell's where they fit, else
    the same shape at the engine's own bucket, page and batch (a document
    of six chunks and a part, questions from half a chunk to a chunk and a
    part, a live row fewer than the batch)."""
    top = cfg.prefill_buckets[-1]
    if (cfg.max_len >= N_DOCUMENT + max(QUESTIONS) + N_DECODE + 2
            and top == 256 and cfg.max_batch >= len(QUESTIONS)):
        return {"document": N_DOCUMENT, "questions": QUESTIONS,
                "ticks": N_DECODE, "control": CONTROL_STEPS}
    rows = max(1, min(cfg.max_batch - 1, 3))
    return {"document": 6 * top + top // 6 + 1,
            "questions": tuple(top // 2 + 3 + r * (top // 2 + 1)
                               for r in range(rows)),
            "ticks": top, "control": top // 4}


def _sown(variables, cfg, read):
    """Per expert layer the experts `RoutedExperts` sowed [batch,
    positions, k], and of the layers `read` what their attention sowed in
    front of W_o [batch, positions, heads, v]."""
    routing = variables["routing"]
    routes = [routing[f"layer_{i}"]["moe"]["routed"]["chosen"][0]
              for i in range(cfg.num_layers) if cfg.expert_layer(i)]
    attended = {i: variables["intermediates"][f"layer_{i}"]["attn"][
        "attended"][0] for i in read}
    return routes, attended


class Programs:
    """The check's own jits of the engine's model, on the arguments the
    engine's timed programs take, returning what those keep to themselves
    (the module's docstring); and the rows of some pages read and written
    in place."""

    def __init__(self, engine):
        import jax
        import jax.numpy as jnp
        self.engine = engine
        cfg = engine.config.model
        module = engine.model
        self.read = read = (0, cfg.num_layers - 1)
        kinds = cfg.layer_caches()
        f32 = jnp.float32

        def chunk(params, tokens, positions, pools, offset, table, valid):
            (hidden, new), sown = module.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=[{"pool": pool, "table": table} for pool in pools],
                cache_index=offset, valid=valid, head=False,
                mutable=["routing", "intermediates"])
            routes, attended = _sown(sown, cfg, read)
            return (hidden[0], [kept[0] for kept in new],
                    [r[0] for r in routes],
                    {i: a[0].astype(f32) for i, a in attended.items()})

        def decode(params, pools, active, tables, lengths, tokens):
            # what a latent layer is handed in a paged decode step (the
            # model's contract; the counters start from zero and are not
            # kept)
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
                cache_index=None, mutable=["routing", "intermediates"])
            routes, attended = _sown(sown, cfg, read)
            return (logits[:, -1].astype(f32), [kept[0] for kept in new],
                    [r[:, 0] for r in routes],
                    {i: a[:, 0].astype(f32) for i, a in attended.items()})

        # the pools donated and handed back, as the engine's own programs
        # take them: a program that only read them would copy every pool
        self.chunk = jax.jit(chunk, donate_argnums=(3,))
        self.decode = jax.jit(decode, donate_argnums=(1,))
        self.head = jax.jit(lambda params, hidden: module.apply(
            {"params": params}, hidden[None], method="head")[0].astype(f32))
        self.gather = jax.jit(lambda pools, ids: [p[0][ids] for p in pools])
        self.scatter = jax.jit(
            lambda pools, ids, rows: [p.at[0, ids].set(r)
                                      for p, r in zip(pools, rows)],
            donate_argnums=(0,))

    def held_rows(self, pages, first: int, upto: int, lanes: int):
        """What the pools of the layers read hold for positions `first` ..
        `upto` - 1 of the row whose pages are `pages`: {layer: [positions,
        lanes] float32}."""
        import jax.numpy as jnp
        engine = self.engine
        ps = engine.config.page_size
        ids = jnp.asarray(pages[first // ps:-(-upto // ps)], jnp.int32)
        pools = [engine.k_pages[i] for i in self.read]
        return {i: np.asarray(rows.astype(jnp.float32)).reshape(
            -1, rows.shape[-1])[first % ps:first % ps + upto - first, :lanes]
            for i, rows in zip(self.read, self.gather(pools, ids))}


def run_chunks(programs: Programs, prompt, table, start: int,
               logits_from: int) -> Dict[str, Any]:
    """`prompt[start:]` into the row whose pages `table` names, every
    chunk through the engine's timed program and then through the check's.
    From the check's: the logits and the attended values of the positions
    `logits_from` on, the routes of every position computed. From both, at
    each chunk's last real row: the timed program's logits and the
    check's (`timed`: pairs)."""
    import jax.numpy as jnp
    engine = programs.engine
    cfg = engine.config
    logits, routes, timed = [], [], []
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
        last, engine.k_pages = engine._chunk_prefill(
            *args, engine.k_pages, *tail, jnp.asarray(take - 1, jnp.int32))
        hidden, engine.k_pages, chose, gave = programs.chunk(
            *args, engine.k_pages, *tail)
        routes.append([np.asarray(r[:take]) for r in chose])
        first = max(logits_from - off, 0)
        # the head over the whole chunk where rows of it are read, else
        # over its last real row alone (a shape a bucket, and one)
        rows = np.asarray(programs.head(
            engine.params,
            hidden if first < take else hidden[take - 1:take]))
        rows = rows[first:take] if first < take else rows
        timed.append((np.asarray(last[0]), rows[-1]))
        if first < take:
            logits.append(rows)
            for i in programs.read:
                attended[i].append(np.asarray(gave[i][first:take]))
        off += take
    return {"logits": np.concatenate(logits) if logits else None,
            "attended": {i: np.concatenate(a) if a else None
                         for i, a in attended.items()},
            "routes": [np.concatenate([part[j] for part in routes])
                       for j in range(len(routes[0]))],
            "timed": timed}


def run_decode(programs: Programs, slots, tables, starts, fed=None,
               first_tokens=None, ticks: int = 0) -> Dict[str, Any]:
    """Decode steps with the rows `slots` of the engine's batch live, row
    r on the pages `tables[r]`, its first token at position `starts[r]`.
    With `fed` [ticks, rows] those tokens through the check's program
    alone (the control); else `ticks` steps fed greedily from
    `first_tokens`, each through the engine's timed `_decode`, whose
    tokens are the next step's, and then through the check's. Per live
    row and step: logits, routes, attended values, the tokens fed, and
    what the timed program sampled."""
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
    logits, routes, sampled, tokens_fed = [], [], [], []
    attended = {i: [] for i in programs.read}
    for i in range(steps):
        lengths = np.zeros((B,), np.int32)
        lengths[slots] = np.asarray(starts) + i
        tokens = np.zeros((B,), np.int32)
        tokens[slots] = now
        args = (jnp.asarray(active), jnp.asarray(block_tables),
                jnp.asarray(lengths), jnp.asarray(tokens))
        if fed is None:
            engine._rng, key = jax.random.split(engine._rng)
            ids, engine.k_pages, engine.counters = engine._decode(
                engine.params, engine.k_pages, *args, key, *greedy,
                engine.counters)
        lg, engine.k_pages, chose, gave = programs.decode(
            engine.params, engine.k_pages, *args)
        tokens_fed.append(now)
        logits.append(np.asarray(lg)[slots])
        routes.append([np.asarray(r)[slots] for r in chose])
        for layer in programs.read:
            attended[layer].append(np.asarray(gave[layer])[slots])
        if fed is None:
            now = np.asarray(ids)[slots]
            sampled.append(now)
        elif i + 1 < steps:
            now = np.asarray(fed[i + 1], np.int32)
    # [rows, steps, ...]: a row's steps follow one another in the array
    # the reference takes
    by_row = lambda parts: np.stack(parts, 1)  # noqa: E731
    return {"logits": by_row(logits),
            "routes": [by_row([part[j] for part in routes])
                       for j in range(len(routes[0]))],
            "attended": {i: by_row(a) for i, a in attended.items()},
            "fed": by_row(tokens_fed),
            "sampled": by_row(sampled) if sampled else None}


def row_errors(held, want) -> float:
    """Worst row's |row - ref| / |ref| (rows are positions; or heads of
    positions, over the value lanes)."""
    held, want = np.asarray(held, np.float32), np.asarray(want, np.float32)
    return float((np.linalg.norm(held - want, axis=-1)
                  / np.linalg.norm(want, axis=-1)).max())


def _alloc(engine, n: int) -> List[int]:
    short = n - engine.pool.num_free()
    if short > 0:
        engine.radix.evict_pages(short)      # as admission does
    pages = [engine.pool.alloc() for _ in range(n)]
    if any(p is None for p in pages):
        for p in pages:
            if p is not None:
                engine.pool.decref(p)
        raise RuntimeError("no free pages for the parity prompt")
    return pages


def _peak_bytes():
    """The most the device has held so far (None where it does not say)."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def _table(engine, pages) -> np.ndarray:
    table = np.zeros((engine.config.pages_per_seq,), np.int32)
    table[:len(pages)] = pages
    return table


def judge(logit_parts, set_aside, routing, latent, attended
          ) -> Dict[str, Any]:
    """Comparisons 1-4 on one run's numbers (the program's, or the
    control's): parity._verdict at this check's limits over `logit_parts`
    ({part: `_compare`'s}; `set_aside[part]`: the positions that are ill
    conditioned and so not held to LOGIT_WORST), `routing`
    (`routing_check`'s), `latent` and `attended` ({"first" | "last":
    worst error}, layer 0 and the last layer)."""
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
    out["latent_rows"] = dict(latent, tolerance=LATENT_ROW_TOLERANCE)
    out["attended"] = dict(attended, tolerance=ATTENDED_TOLERANCE)
    out["failed"] = [what for what, good in (
        ("logits", not beyond and len(aside) <= SET_ASIDE_AT_MOST * total),
        ("logit_median", all(part["median"] <= LOGIT_MEDIAN
                             for part in logit_parts.values())),
        ("routing", routing["worst_tie"] <= ROUTE_TIE),
        ("latent_rows", all(latent[k] <= LATENT_ROW_TOLERANCE[k]
                            for k in LATENT_ROW_TOLERANCE)),
        ("attended", all(attended[k] <= ATTENDED_TOLERANCE[k]
                         for k in attended))) if not good]
    out["ok"] = not out["failed"]
    return out


def serve(engine, config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from ..reference import sarvam_mla_ref
    from .builders import jax_seed

    cfg = engine.config
    model_cfg = cfg.model
    ps, B = cfg.page_size, cfg.max_batch
    if engine.has_work():
        raise RuntimeError("parity needs an idle engine")
    n = spans(cfg)
    rng = np.random.default_rng([jax_seed(seed), 77])
    draw = lambda size: rng.integers(  # noqa: E731
        1, model_cfg.vocab_size, size=size).tolist()
    document = draw(n["document"])
    asks = [document + draw(size) for size in n["questions"]]
    R, ticks = len(asks), n["ticks"]
    # the span later asks share in place: the document's whole pages. Its
    # last part page is every ask's own, computed by each for itself
    D = len(document) // ps * ps
    # live rows apart from one another in the batch, dead ones between
    slots = [(1 + r * B // R) % B for r in range(R)]
    programs = Programs(engine)
    first_layer, last_layer = programs.read
    width = model_cfg.latent_dim
    out: Dict[str, Any] = {"document": len(document), "shared_span": D,
                           "questions": list(n["questions"]),
                           "decode_steps": ticks, "live_rows": R}
    held: List[int] = []
    with engine._mesh_scope():
        try:
            # -- the first ask from nothing, then into the radix
            room = lambda ask: -(-(len(ask) + ticks) // ps)  # noqa: E731
            pages = [_alloc(engine, room(asks[0]))]
            held += pages[0]
            starts = [0]
            chunks = [run_chunks(programs, asks[0],
                                 _table(engine, pages[0]), 0, D)]
            engine._register_prefix(asks[0], pages[0])
            # -- the later asks map the document's pages where they lie
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
            out["tail_computed"] = [len(a) - s
                                    for a, s in zip(asks, starts)][1:]
            # -- all asks decode together, greedily, through both programs
            tables = [_table(engine, p) for p in pages]
            decoded = run_decode(
                programs, slots, tables, [len(a) for a in asks],
                first_tokens=[int(c["logits"][-1].argmax()) for c in chunks],
                ticks=ticks)
            # what the pools hold: the first ask's row whole, the others'
            # from where their own pages begin
            in_pool = [programs.held_rows(p, s, len(a) + ticks, width)
                       for p, s, a in zip(pages, starts, asks)]
            # -- the control, over the same pages and the same tokens:
            # every page of the rows through 8-bit floats and back, in
            # place, and what they held put back afterwards
            ids = jnp.asarray(sorted({p for row in pages for p in row}))
            kept = programs.gather(engine.k_pages, ids)
            engine.k_pages = programs.scatter(
                engine.k_pages, ids,
                [rows.astype(jnp.float8_e4m3fn).astype(rows.dtype)
                 for rows in kept])
            eight_pool = [programs.held_rows(p, s, len(a), width)
                          for p, s, a in zip(pages, starts, asks)]
            eight = run_decode(
                programs, slots, tables, [len(a) for a in asks],
                fed=decoded["fed"][:, :n["control"]].T)
            engine.k_pages = programs.scatter(engine.k_pages, ids, kept)
            del kept
        finally:
            for page in held:
                engine.pool.decref(page)
        out["peak_bytes"] = {"programs": _peak_bytes()}

        # -- the reference: the shared span, then each ask's own part (the
        # document's last part page, the question, the tokens it was
        # fed), as one array
        tails = [np.concatenate([a[D:], f]).astype(np.int64)
                 for a, f in zip(asks, decoded["fed"])]
        edges = np.cumsum([D] + [len(t) for t in tails])
        tokens = np.concatenate([np.asarray(document[:D])] + tails)
        positions = np.concatenate(
            [np.arange(D)] + [D + np.arange(len(t)) for t in tails])
        branch = np.concatenate(
            [np.zeros(D, np.int64)]
            + [np.full(len(t), r + 1) for r, t in enumerate(tails)])
        # the routes the check's program took: the first ask's over the
        # shared span, each ask's over its own part, each row's decode
        # steps
        routes = [np.concatenate(
            [chunks[0]["routes"][j][:D]]
            + [part for c, s, r in zip(chunks, starts, range(R))
               for part in (c["routes"][j][D - s:], decoded["routes"][j][r])])
            for j in range(len(decoded["routes"]))]
        wanted = np.arange(D, len(tokens))
        reference = functools.partial(
            sarvam_mla_ref.logits, engine.params, tokens,
            reference_keys(model_cfg), positions=positions, branch=branch,
            routes=routes, rows=wanted)
        want, details = reference(details=programs.read)
        want = np.asarray(want)
        wobble = (len(tokens), model_cfg.hidden_size)
        probes = [np.asarray(reference(
            embed_scale=1.0 + PROBE_SIZE * jax.random.normal(
                jax.random.PRNGKey(k), wobble, jnp.float32)))
            for k in range(PROBES)]
        latent_ref = {i: np.asarray(details["latent"][i])
                      for i in programs.read}
        attended_ref = {i: np.asarray(details["attended"][i])
                        for i in programs.read}
        selection = [np.asarray(s) for s in details["selection"]]
    ill = ill_conditioned(want, probes)
    out["peak_bytes"]["reference"] = _peak_bytes()

    # rows of `wanted` by part: ask r's question, ask r's decode steps
    question = [np.arange(edges[r], edges[r] + len(asks[r]) - D) - D
                for r in range(R)]
    steps = [np.arange(edges[r + 1] - ticks, edges[r + 1]) - D
             for r in range(R)]
    later = np.concatenate(question[1:]) if R > 1 else question[0][:0]
    every_step = np.concatenate(steps)
    every_question = np.concatenate(question)
    got = {"first_ask": chunks[0]["logits"],
           "decode": decoded["logits"].reshape(-1, want.shape[-1])}
    rows_of = {"first_ask": question[0], "decode": every_step}
    if R > 1:
        got["later_asks"] = np.concatenate([c["logits"] for c in chunks[1:]])
        rows_of["later_asks"] = later

    def latent_errors(pools):
        """Worst row of what `pools` (a row's `held_rows`, from its
        `starts`) hold, against the reference's rows of the same tokens:
        the document's where the position lies in it, else the ask's own."""
        worst = {}
        ref0 = np.concatenate([latent_ref[last_layer][:D],
                               latent_ref[last_layer][edges[0]:edges[1]]])
        for name, layer in (("first", first_layer), ("last", last_layer)):
            errors = []
            for r, (rows, start) in enumerate(zip(pools, starts)):
                have = rows[layer]
                ref = np.concatenate(
                    [latent_ref[layer][start:D],
                     latent_ref[layer][edges[r]:edges[r + 1]]])[:len(have)]
                errors.append(row_errors(have, ref))
            worst[name] = max(errors)
        # what rows written one position off would read (the first ask's)
        worst["misplaced_by_one"] = row_errors(
            pools[0][last_layer][1:len(ref0)],
            ref0[:len(pools[0][last_layer]) - 1])
        return worst

    def attended_errors(pairs):
        """Worst head over `pairs` of (what the program's attention gave
        {layer: [rows, heads, v]}, the rows of `wanted` they are)."""
        return {name: max(row_errors(gave[layer], attended_ref[layer][rows])
                          for gave, rows in pairs)
                for name, layer in (("first", first_layer),
                                    ("last", last_layer))}

    flat = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731
    verdict = judge(
        {name: _compare(got[name], want[rows_of[name]]) for name in got},
        {name: ill[rows_of[name]] for name in got},
        routing_check(routes, selection, model_cfg.num_experts_per_tok),
        latent_errors(in_pool),
        attended_errors(
            [({i: np.concatenate([c["attended"][i] for c in chunks])
               for i in programs.read}, every_question),
             ({i: flat(a) for i, a in decoded["attended"].items()},
              every_step)]))
    out.update(verdict)

    # -- the control through the same comparisons: it must fail them
    c = n["control"]
    control_steps = np.concatenate([s[:c] for s in steps])
    control = judge(
        {"decode": _compare(flat(eight["logits"]), want[control_steps])},
        {"decode": ill[control_steps]},
        routing_check([flat(r) for r in eight["routes"]],
                      [s[D:][control_steps] for s in selection],
                      model_cfg.num_experts_per_tok),
        latent_errors(eight_pool),
        attended_errors([({i: flat(a) for i, a in eight["attended"].items()},
                          control_steps)]))
    control.pop("beyond_tolerance")
    out["control_8bit_rows"] = control

    # -- the timed programs against the check's
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
    out["latent_kernel"] = engine.stats().get("latent_kernel")
    if out["shared_pages"] != [out["shared_pages_expected"]] * (R - 1):
        out["failed"].append("shared_pages")
    if control["ok"]:
        out["failed"].append("control_passed")
    if not (out["timed"]["decode_agree"] >= TIMED_AGREE
            and out["timed"]["chunk_median"] <= TIMED_MEDIAN):
        out["failed"].append("timed_programs")
    out["ok"] = not out["failed"]
    return out


def main() -> int:
    """`python3 -m benchmarks.harness.parity_sarvam_mla [--seed N]
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
    from . import builders_sarvam_mla, spec
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    config = spec.load_json(os.path.join(
        root, "benchmarks", "configs", "sarvam-105b-serve.json"))
    # the program's knobs the cell sets, as cluster.start_cluster sets them
    for name, value in config.get("program_settings", {}).items():
        os.environ["RTPU_" + name.upper()] = str(value)
    import jax

    from ray_tpu.llm.paged import PagedLLMEngine
    engine = PagedLLMEngine(builders_sarvam_mla.sarvam_mla_engine(
        config, args.seed, args.rehearse))
    began = time.monotonic()
    out = serve(engine, config, args.seed)
    memory = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"seed": args.seed,
                      "seconds": round(time.monotonic() - began, 1),
                      "peak_bytes_in_use": memory.get("peak_bytes_in_use"),
                      "bytes_limit": memory.get("bytes_limit"), **out}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
