"""The comparison that decides `correct` for the SDAR serve cell: the
engine's TIMED programs, driven by its own scheduler (`submit`, `step`: the
block step at the batch's 128 rows, the chunks written into the rows' pages),
against the plain float32 reference (benchmarks/reference/sdar_ref.py), same
weights, on the chip, outside the window, at the published widths and the
timed programs' shapes. Logits, not tokens.

What runs. 32 requests into an idle engine. First FILLERS = 27 rows that
fill the batch: prompts drawn log-uniform over the traffic's 256-2048
tokens, denoising steps 1 / 2 / 4 in turn, FILLER_NEW = 46 new tokens
(twelve blocks; the last is cut). Then the five rows the reference judges,
which so decode among some twenty live rows: prompts of PROMPTS = 2048 /
701 / 330 / 64 / 7 seeded tokens (the traffic's longest, four chunks of
512; 701 % 4 = 1, 330 % 4 = 2, 7 % 4 = 3: three rows open their first block
with fixed prompt tokens; 701 is a chunk of 512 and one of 188 in the 256
bucket: the last whole block of it and of the 2048 comes from a chunk that
is not the row's first), STEPS = 2 / 4 / 2 / 1 / 2 a row, MAX_NEW = 14
tokens (four blocks; the last is cut), the static rule. The engine's
`_decode` and `_chunk_prefill` are wrapped for the duration (`Spy`): every
call runs FIRST through this check's own jit of the same `model.apply` on
the same arguments (`Programs`: it also hands out what the timed programs
keep to themselves, logits of the live rows' block positions, the experts
each layer chose, what each router read, and the report ITS application of
the rule leaves), then through the timed program, whose results the engine
keeps. Both take the pools donated and write the same places; what each
WROTE there is read back after it (`Programs.read`), and the timed
program's rows are the ones that stay.

The reference is fed the ENGINE'S OWN block ids at every forward it checks:
the row's committed tokens (the prompt's whole blocks, then the ids of every
commit forward so far) and the block as the forward read it, masks and all.
It follows the check's routes (a routed layer's choice flips on a rounding
at a near tie), in one full forward without a cache, and is compared at the
block's positions:

  logits   parity._compare: every checked position's largest |difference|
           over its logit spread; the median under LOGIT_MEDIAN, every
           position under LOGIT_WORST. Checked of every judged row: (a) its
           first denoising forward (masks in the block; (c) with the
           prompt's tail fixed in it), its first commit, and (b) every
           forward of its third block (two blocks committed: committed K/V
           is what is attended; not of the 2048-token row); and (d) of
           every prompt, the logits at its last whole block as the check's
           chunk computed them.
  rule     for EVERY forward of EVERY row, the fillers' too: the
           reference's own rule (`sdar_ref.static_rule` on
           `sdar_ref.candidates` of the check's logits, with the count the
           engine uploaded) against the ids the check's program left:
           positions and ids agree exactly.
  router   the experts the check chose against the float64 softmax of what
           its router read (`router_input`), over every block forward of
           every row and the judged rows' chunks: the share of routings
           whose sets agree at least ROUTER_AGREE.
  timed    the TIMED programs against the check's. The share of
           row-forwards, commits among them, at which the timed step's
           report (the block's ids as the rule left them, the masks found
           and left: a commit's ids come back unchanged) is the check's,
           at least TIMED_AGREE (two compilations of one model break a
           near tie of two candidates differently now and then); and what
           the timed programs WROTE, the K and V rows of every live row's
           block after every step and of every chunk's tokens after every
           chunk, in every layer, against what the check's program had
           written there: |timed - check| / |check| a place, the median
           under TIMED_APART.

The controls go through the same verdict and must FAIL it (`controls`; `ok`
of each judged one must be false):

  reference_8bit   the reference with the operands of every product
                   rounded to 8 bits (5 of exponent, 2 of mantissa: the
                   nearest precision below the configuration's bfloat16),
                   at every position the sound verdict judges of the SHORT
                   rows (prompts under four pages: 64 and 7 tokens): by the
                   logits' limits;
  causal_inside    the reference with a causal mask inside the block, on
                   each short row's first denoising forward: by the logits'
                   limits;
  commit_left_out  the reference fed, for every committed answer block, the
                   ids its LAST DENOISING forward read (the K/V that forward
                   wrote, which a commit replaces), on the short rows'
                   forwards of the third block: by the logits' limits;
  router_bf16      the float64 logits of the router inputs rounded to bf16,
                   their softmax rounded to bf16, ranked: by ROUTER_AGREE
                   (not judged under ROUTER_CONTROL_AT_LEAST routings: a
                   rehearsal's few hundred at toy widths may flip none);
  timed_count_less_one   the TIMED step itself, called once more in front
                   of every sound call and told one position fewer to fix a
                   row (what a scheduler's count off by one, or a program
                   that applied it so, would hand out): its reports against
                   the check's, by TIMED_AGREE;
  timed_wrote_mismatched what a timed program that wrote ANOTHER row's (a
                   chunk: another position's) K/V into a place reads: by
                   TIMED_APART;
  bf16_reference   read beside them (the short rows' positions) and NOT
                   judged: the reference with every product, sum, norm and
                   softmax in bfloat16. The program
                   is bfloat16 itself, so this stands 1.5-2 x the program's
                   own reading and no limit with room on both sides lies
                   between (ISSUE 58 asked for it; PERF.md section 7).

A FAULTY PROGRAM in the timed step's place (`--fault projections_8bit`: the
engine's own step on weights whose attention projections are rounded to 8
bits; the check alone, not the cell) gives `timed` its upper readings on
the chip.

The limits, each from two readings on the chip, stand beside the constants
below (PERF.md section 6, PR 58).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .parity import _compare

# the rows the reference judges (prompts, denoising steps, new tokens) ...
PROMPTS, STEPS, MAX_NEW = (2048, 701, 330, 64, 7), (2, 4, 2, 1, 2), 14
# ... and the rows that fill the batch beside them: FILLERS prompts drawn
# log-uniform over the traffic's FILLER_PROMPT tokens, steps 1 / 2 / 4 in
# turn, FILLER_NEW new tokens (the last block is cut), submitted FIRST, so
# that the judged rows decode among them
FILLERS, FILLER_PROMPT, FILLER_NEW = 27, (256, 2048), 46
# a rehearsal's engine is shorter and four rows wide: buckets of 16 / 32,
# 45 = 32 + 13
REHEARSE_PROMPTS, REHEARSE_STEPS = (45, 22, 16, 7), (4, 2, 1, 2)
REHEARSE_FILLERS, REHEARSE_FILLER_PROMPT, REHEARSE_FILLER_NEW = 3, (16, 45), 22
# the reference's forwards are padded to whole PAD_TO tokens (positions in
# blocks behind every real one, which no real query sees): a handful of
# compiled shapes in place of one a forward
PAD_TO = 256
# every forward of a row's third block is judged where its prompt is no
# longer than this
THIRD_BLOCK_UP_TO = 1024
# The limits, each between two readings on the chip at the published
# widths, six layers (my chip runs, PR 58, in this form of the check: the
# check alone, sound and with the FAULTY program in the timed step's place,
# seeds 5800003001 / -002, and the cell eight times from the final tree's
# archive, seeds 5800003101 and -201..207; with the embedding of the first
# hand-in, N(0, 0.02), the check alone twice and the cell six times, seeds
# 5800002001 / -002 and -101..106, and twenty runs of the check's first
# form; PERF.md section 6):
#   limit               the program            what must fail
#   LOGIT_MEDIAN 0.09   median 0.0259-0.0304   reference_8bit 0.738-0.888
#                       of a spread, 112       (0.90-1.17 at N(0, 0.02));
#                       positions a run        causal_inside 1.22-2.09;
#                       (0.022-0.030 before)   commit_left_out 1.66-2.24
#                                              (bf16_reference 0.0615-0.0706,
#                                              unjudged: 2 x the program)
#   LOGIT_WORST 0.25    worst 0.037-0.072      reference_8bit 0.913-1.121;
#                       (0.035-0.086 before)   causal_inside 2.16-3.54;
#                                              commit_left_out 1.78-3.0; the
#                                              faulty program 0.29 (0.36)
#   ROUTER_AGREE 0.999  1.0 (0 of ~45,600      router_bf16 0.968-0.972
#                       routings a run)        (0.959-0.970 before)
#   TIMED_AGREE 0.95    1.0 (1,110-1,121 row-  the faulty program 0.593
#                       forwards a run, ~320   (0.603); timed_count_less_one
#                       commits; 16 runs)      0.286-0.287
#   TIMED_APART 0.02    block steps 0.0 at     the faulty program's block
#                       EVERY place (the two   steps 0.057 at the median,
#                       compilations write     p99 0.15 (0.137 / 0.45);
#                       the same bits);        mismatched 1.36-1.38 (another
#                       chunks 0.0 at the      row's, another position's)
#                       median, p99 0.006-
#                       0.007, worst 0.14-0.28
# LOGIT_MEDIAN is 3 x the program's largest reading and an eighth of the 8-bit
# reference's smallest; LOGIT_WORST 2.9 x the program's largest and under
# 0.3 of the 8-bit reference's smallest. TIMED_AGREE lets one row-forward in
# twenty differ (two compilations of one model may break a near tie
# otherwise; none did in ~18,000) and stands 0.35 over the faulty program.
# TIMED_APART is judged at the median place: 3 x the chunks' p99 and a third
# of the faulty program's median.
LOGIT_MEDIAN = 0.09
LOGIT_WORST = 0.25
# a rehearsal's engine computes in float32, as the reference does: the
# program reads 2e-6 / 5e-6 there and the bf16 reference 0.045 / 0.10
FLOAT32_MEDIAN, FLOAT32_WORST = 2e-4, 1e-3
ROUTER_AGREE = 0.999
ROUTER_CONTROL_AT_LEAST = 2000
TIMED_AGREE = 0.95
TIMED_APART = 0.02


def _sown(variables, model_cfg, name) -> List[Any]:
    if name == "chosen":
        return [variables["routing"][f"layer_{i}"]["moe"]["chosen"][0]
                for i in range(model_cfg.num_layers)]
    return [variables["intermediates"][f"layer_{i}"][name][0]
            for i in range(model_cfg.num_layers)]


def rounded_projections(params):
    """The weights with every layer's four attention projections rounded to
    8 bits (5 of exponent, 2 of mantissa, as the reference's `bits8`): what
    the FAULTY timed program of `Spy(fault="projections_8bit")` multiplies
    by."""
    import jax

    def maybe(path, leaf):
        names = [getattr(k, "key", "") for k in path]
        if any(n in ("q_proj", "k_proj", "v_proj", "o_proj") for n in names):
            return jax.lax.reduce_precision(leaf, exponent_bits=5,
                                            mantissa_bits=2)
        return leaf
    return jax.tree_util.tree_map_with_path(maybe, params)


class Programs:
    """This check's jits beside the engine's own: a block step and a chunk
    that also return logits, routes and router inputs (both take the pools
    donated and hand them back: a pool that is not donated would be copied
    whole to be updated); `read`, the K/V rows the pools hold at given
    positions of given rows; `apart`, how far two such readings stand from
    each other; and `faulty`, the engine's own block step on weights whose
    attention projections are rounded to 8 bits."""

    def __init__(self, engine):
        import jax
        import jax.numpy as jnp

        from ray_tpu.llm.paged import chunk_logits
        from ray_tpu.llm.sampling import (sample_with_confidence,
                                          unmask_block)

        model_cfg = engine.config.model
        L, mask_id = model_cfg.block_length, model_cfg.mask_token_id
        page_size = engine.config.page_size
        apply = engine.model.apply

        def step(params, k_pages, v_pages, live, tables, lengths, report,
                 opened, fresh, count, threshold, rows):
            """`engine._decode`'s arguments up to the threshold, greedy;
            `rows` [n]: the slots whose logits are wanted. Counters start
            from zero and are not kept. Hands out the report as the
            engine's step forms it."""
            ids = jnp.where(opened[:, None], fresh, report[:, :L])
            (hidden, new), sown = apply(
                {"params": params}, ids,
                positions=lengths[:, None] + jnp.arange(L),
                kv_caches=engine._block_caches(
                    k_pages, v_pages, model_cfg.init_counters(), live,
                    tables, lengths),
                cache_index=None, head=False,
                mutable=["routing", "intermediates"])
            logits = chunk_logits(
                engine.model, params,
                hidden.reshape(1, -1, hidden.shape[-1]), None)[0]
            masked = jnp.where(
                jnp.arange(logits.shape[-1]) == mask_id, -1e30, logits)
            zeros = jnp.zeros((masked.shape[0],), jnp.float32)
            found, confidence = sample_with_confidence(
                jax.random.PRNGKey(0), masked, zeros,
                zeros.astype(jnp.int32), zeros + 1.0)
            out, before, after = unmask_block(
                ids, found.reshape(ids.shape),
                confidence.reshape(ids.shape), mask_id, count, threshold)
            out = jnp.where(live[:, None], out, ids)
            told = jnp.concatenate(
                [out, before[:, None], after[:, None]], 1).astype(jnp.int32)
            nk, nv, _ = engine._by_kind(new)
            return (ids[rows], told[rows],
                    logits.reshape(ids.shape + (-1,))[rows],
                    [r[rows] for r in _sown(sown, model_cfg, "chosen")],
                    [u[rows] for u in _sown(sown, model_cfg,
                                            "router_input")], nk, nv)

        self.step = jax.jit(step, donate_argnums=(1, 2))

        def chunk(params, tokens, positions, pools, offset, table, valid):
            """The chunk's routes and router inputs at every row, and the
            logits of its last whole block of real tokens. `pools`: (k
            pools, v pools)."""
            (hidden, new), sown = apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=[(k, v, table) for k, v in zip(*pools)],
                cache_index=offset, valid=valid, head=False,
                mutable=["routing", "intermediates"])
            last = jax.lax.dynamic_slice_in_dim(
                hidden, jnp.maximum(valid - L, 0), L, axis=1)
            return (chunk_logits(engine.model, params, last, None)[0],
                    [r[0] for r in _sown(sown, model_cfg, "chosen")],
                    [u[0] for u in _sown(sown, model_cfg, "router_input")],
                    engine._by_kind(new)[:2])

        self.chunk = jax.jit(chunk, donate_argnums=(3,))

        def read(k_pages, v_pages, tables, at):
            """[2, layers, rows, n, kv heads x head_dim]: the K and V rows
            the pools [kv heads, pages, page_size, head_dim] hold at
            positions `at` [rows, n] of the rows whose block tables are
            `tables` [rows, pages a row]."""
            pages = jnp.take_along_axis(tables, at // page_size, axis=1)
            rows = lambda pool: jnp.moveaxis(  # noqa: E731
                pool[:, pages, at % page_size], 0, 2).reshape(
                    at.shape + (-1,))
            return jnp.stack([jnp.stack([rows(k) for k in k_pages]),
                              jnp.stack([rows(v) for v in v_pages])])

        self.read = jax.jit(read)

        def apart(check, timed, count, axis):
            """|timed - check| / |check| a place [2, layers, rows, n], and
            the same against ANOTHER place's check (the next of the first
            `count` along `axis`): what a program that wrote another row's
            or another position's K/V would read."""
            f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
            check, timed = f32(check), f32(timed)
            size = jnp.linalg.norm(check, axis=-1) + 1e-30
            other = jnp.take(
                check, (jnp.arange(check.shape[axis]) + 1) % count, axis=axis)
            return (jnp.linalg.norm(timed - check, axis=-1) / size,
                    jnp.linalg.norm(timed - other, axis=-1) / size)

        self.apart = jax.jit(apart, static_argnames=("axis",))
        timed_step = engine._decode    # before a `Spy` stands in its place
        self.faulty = jax.jit(
            lambda params, *rest: timed_step(
                rounded_projections(params), *rest),
            donate_argnums=(1, 2, 15))


class Apart:
    """What the timed programs WROTE against what the check's wrote into the
    same places (`Programs.apart`), gathered over a drive."""

    def __init__(self):
        self.off: List[np.ndarray] = []
        self.mismatched: List[np.ndarray] = []

    def add(self, off, mismatched, rows, n) -> None:
        self.off.append(np.asarray(off)[:, :, :rows, :n].ravel())
        if max(rows, n) > 1:
            self.mismatched.append(
                np.asarray(mismatched)[:, :, :rows, :n].ravel())

    def summary(self) -> Dict[str, Any]:
        if not self.off:
            return {"places": 0, "median": 0.0, "p99": 0.0, "worst": 0.0,
                    "mismatched": None}
        off = np.concatenate(self.off)
        return {"places": int(off.size), "median": float(np.median(off)),
                "p99": float(np.quantile(off, 0.99)),
                "worst": float(off.max()),
                "mismatched": float(np.median(np.concatenate(
                    self.mismatched))) if self.mismatched else None}


class Spy:
    """The engine's two programs wrapped for the check's duration: every
    call first through `Programs`, then through the timed program, and
    what the two WROTE read back after each (`wrote`). `rule(ids, logits,
    count)`: what the reference's rule leaves of a block; with it every
    row-forward is judged on the spot (`ruled`) and only the rows in `keep`
    hold on to their logits (2.4 MB a forward at the published widths).
    `fault`: "count_less_one" runs the timed step ONCE MORE in front of the
    sound call, told one position fewer to fix a row (`fault_report`; the
    K/V it writes are the sound call's); "projections_8bit" puts
    `Programs.faulty` in the timed step's place."""

    def __init__(self, engine, programs: Programs, width: int = 4,
                 rule: Optional[Callable] = None, keep=None,
                 fault: Optional[str] = None):
        self.engine, self.programs = engine, programs
        # the most rows live at once: the check's program hands out the
        # logits of that many slots, whatever are live (one shape)
        self.width = width
        self.rule, self.keep, self.fault = rule, keep, fault
        self.timed_step, self.timed_chunk = \
            engine._decode, engine._chunk_prefill
        self.forwards: Dict[str, List[Dict[str, Any]]] = {}
        self.chunks: Dict[str, List[Dict[str, Any]]] = {}
        self.wrote = {"block_step": Apart(), "chunk": Apart()}
        self.most_live = 0
        self.chunk_of = None      # the request whose chunk runs next
        engine._decode, engine._chunk_prefill = self.step, self.chunk
        prefill = engine._prefill_chunk

        def noted(seq):
            self.chunk_of = seq.request.request_id
            return prefill(seq)
        self._prefill, engine._prefill_chunk = prefill, noted

    def restore(self):
        engine = self.engine
        engine._decode, engine._chunk_prefill = \
            self.timed_step, self.timed_chunk
        engine._prefill_chunk = self._prefill

    def _kept(self, rid) -> bool:
        return self.keep is None or rid in self.keep

    def step(self, params, k_pages, v_pages, live, tables, lengths, report,
             opened, fresh, count, threshold, *rest):
        import jax.numpy as jnp
        engine, programs = self.engine, self.programs
        model = engine.config.model
        L = model.block_length
        slots = np.flatnonzero(np.asarray(live))
        self.most_live = max(self.most_live, len(slots))
        rows = np.zeros((self.width,), np.int32)
        rows[:len(slots)] = slots
        ids, told, logits, routes, inputs, k_pages, v_pages = \
            programs.step(params, k_pages, v_pages, live, tables,
                          lengths, report, opened, fresh, count,
                          threshold, rows)
        at = jnp.asarray(lengths)[rows][:, None] + jnp.arange(L)
        mine = jnp.asarray(tables)[rows]
        wrote = programs.read(k_pages, v_pages, mine, at)
        args = (live, tables, lengths, report, opened, fresh)
        faulty = None
        if self.fault == "count_less_one":
            faulty, k_pages, v_pages, _ = self.timed_step(
                params, k_pages, v_pages, *args,
                jnp.maximum(jnp.asarray(count) - 1, 0), threshold,
                *rest[:-1], model.init_counters())
            faulty = np.asarray(faulty)
        timed_step = programs.faulty if self.fault == "projections_8bit" \
            else self.timed_step
        result = timed_step(params, k_pages, v_pages, *args, count,
                            threshold, *rest)
        off, mismatched = programs.apart(
            wrote, programs.read(result[1], result[2], mine, at),
            max(1, len(slots)), axis=2)
        self.wrote["block_step"].add(off, mismatched, len(slots), L)
        timed = np.asarray(result[0])
        ids, told, logits = (np.asarray(a) for a in (ids, told, logits))
        routes = [np.asarray(r) for r in routes]
        inputs = [np.asarray(u) for u in inputs]
        counts, begins = np.asarray(count), np.asarray(lengths)
        for n, slot in enumerate(slots):
            rid = engine.seqs[slot].request.request_id
            entry = {
                "ids": ids[n], "out": told[n, :L], "report": told[n],
                "routes": [r[n] for r in routes],
                "inputs": [u[n] for u in inputs],
                "timed": timed[slot, :L], "timed_report": timed[slot],
                "count": int(counts[slot]), "at": int(begins[slot]),
                "live": len(slots)}
            if faulty is not None:
                entry["fault_report"] = faulty[slot]
            if self.rule is not None:
                entry["ruled"] = self.rule(ids[n], logits[n], entry["count"])
            if self._kept(rid):
                entry["logits"] = logits[n]
            self.forwards.setdefault(rid, []).append(entry)
        return result

    def chunk(self, params, tokens, positions, pools, offset, table, valid):
        import jax.numpy as jnp
        programs = self.programs
        k_pages, v_pages, counters = pools
        logits, routes, inputs, (k_pages, v_pages) = programs.chunk(
            params, tokens, positions, (k_pages, v_pages), offset, table,
            valid)
        take, bucket = int(valid), np.asarray(tokens).shape[1]
        at = (jnp.asarray(offset) + jnp.arange(bucket))[None]
        mine = jnp.asarray(table)[None]
        wrote = programs.read(k_pages, v_pages, mine, at)
        if self._kept(self.chunk_of):
            self.chunks.setdefault(self.chunk_of, []).append({
                "at": int(offset), "take": take, "bucket": bucket,
                "logits": np.asarray(logits),
                "routes": [np.asarray(r)[:take] for r in routes],
                "inputs": [np.asarray(u)[:take] for u in inputs]})
        result = self.timed_chunk(params, tokens, positions,
                                  (k_pages, v_pages, counters), offset,
                                  table, valid)
        off, mismatched = programs.apart(
            wrote, programs.read(result[1][0], result[1][1], mine, at),
            take, axis=3)
        self.wrote["chunk"].add(off, mismatched, 1, take)
        return result


def drive(engine, requests) -> Dict[str, List[int]]:
    """The requests [(id, prompt, denoising steps, new tokens)] through the
    engine's own scheduler, to their ends."""
    from ray_tpu.llm.paged import GenerationRequest
    done: Dict[str, List[int]] = {}
    for rid, prompt, t, new in requests:
        engine.submit(GenerationRequest(
            prompt_tokens=prompt, max_new_tokens=new, request_id=rid,
            denoising_steps=t, remasking="static"))
    limit = time.monotonic() + 1200.0
    while engine.has_work():
        if time.monotonic() > limit:
            raise TimeoutError("the parity check's requests did not end")
        for request, tokens in engine.step():
            done[request.request_id] = tokens
    return done


def router_check(routes, inputs, router, k: int, bf16: bool = False
                 ) -> Dict[str, Any]:
    """The chosen experts [n, k] against the float64 softmax of what the
    router read [n, d] (`router` [d, E]): the share of routings whose sets
    agree. `bf16`: the control's choice, from logits and probabilities
    rounded to bfloat16, in place of `routes`."""
    import jax.numpy as jnp
    z = np.asarray(inputs, np.float64) @ np.asarray(router, np.float64)
    z -= z.max(-1, keepdims=True)
    probs = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    own = np.sort(np.argsort(-probs, axis=-1, kind="stable")[:, :k], -1)
    if bf16:
        rounded = lambda a: np.asarray(  # noqa: E731
            jnp.asarray(a, jnp.bfloat16).astype(jnp.float32), np.float64)
        zb = rounded(np.asarray(inputs, np.float64)
                     @ np.asarray(router, np.float64))
        zb -= zb.max(-1, keepdims=True)
        pb = rounded(np.exp(zb) / np.exp(zb).sum(-1, keepdims=True))
        routes = np.argsort(-pb, axis=-1, kind="stable")[:, :k]
    agree = (np.sort(np.asarray(routes), -1) == own).all(-1)
    return {"routings": int(agree.size), "agree": float(agree.mean())}


def judge(parts: Dict[str, Dict[str, Any]], limits) -> Dict[str, Any]:
    """The logits' verdict over named comparisons (`_compare`'s), by
    `limits` (median, worst)."""
    ratios = np.concatenate([np.asarray(p["diff_over_std"], np.float64)
                             for p in parts.values()])
    out = {"positions": int(ratios.size),
           "median": float(np.median(ratios)), "worst": float(ratios.max()),
           "limits": {"median": limits[0], "worst": limits[1]}}
    out["ok"] = bool(out["median"] <= limits[0]
                     and out["worst"] <= limits[1])
    return out


def serve(engine, config: Dict[str, Any], seed: int,
          fault: Optional[str] = "count_less_one") -> Dict[str, Any]:
    """`fault`: `Spy`'s. The cell runs "count_less_one" (a control of every
    run, beside the sound timed step); "projections_8bit" puts a faulty
    program in the timed step's place, and the verdict then has to read
    `ok` false (`python3 -m benchmarks.harness.parity_sdar --fault ...`)."""
    import jax

    from ..reference import sdar_ref
    from .builders import jax_seed
    from .builders_sdar import reference_keys

    began = time.monotonic()
    cfg = engine.config
    model_cfg = cfg.model
    if engine.has_work():
        raise RuntimeError("parity needs an idle engine")
    L, mask_id = model_cfg.block_length, model_cfg.mask_token_id
    rehearse = cfg.max_len < PROMPTS[0] + MAX_NEW + L
    keys = reference_keys(config, rehearse)
    rng = np.random.default_rng([jax_seed(seed), 58])
    sizes, steps, fillers, span, filler_new = \
        (REHEARSE_PROMPTS, REHEARSE_STEPS, REHEARSE_FILLERS,
         REHEARSE_FILLER_PROMPT, REHEARSE_FILLER_NEW) if rehearse \
        else (PROMPTS, STEPS, FILLERS, FILLER_PROMPT, FILLER_NEW)

    def ids_of(n):
        drawn = rng.integers(1, model_cfg.vocab_size - 1, size=n)
        return (drawn + (drawn >= mask_id)).tolist()    # never the mask
    prompts = [ids_of(n) for n in sizes]
    judged = [f"parity-{n}" for n in range(len(prompts))]
    lengths = np.exp(rng.uniform(np.log(span[0]), np.log(span[1]),
                                 size=fillers)).astype(int)
    requests = [(f"filler-{n}", ids_of(int(size)), (1, 2, 4)[n % 3],
                 filler_new) for n, size in enumerate(lengths)]
    requests += [(rid, prompt, t, MAX_NEW)
                 for rid, prompt, t in zip(judged, prompts, steps)]

    def rule(ids, logits, count):
        """The reference's own rule on the check's logits: the ids it
        leaves (a commit: the ids as they stand)."""
        if not (ids == mask_id).any():
            return ids
        found, confidence = sdar_ref.candidates(logits, mask_id)
        return sdar_ref.static_rule(ids, found, confidence, mask_id, count)

    before = engine.stats()
    spy = Spy(engine, Programs(engine),
              width=min(cfg.max_batch, len(requests)), rule=rule,
              keep=set(judged), fault=fault)
    try:
        handed = drive(engine, requests)
    finally:
        spy.restore()
    params = engine.params
    router = [np.asarray(params[f"layer_{i}"]["moe"]["router"], np.float32)
              for i in range(model_cfg.num_layers)]
    k = model_cfg.num_experts_per_tok

    def reference(tokens, routes, rows, **control):
        """`sdar_ref.logits` at `rows`, the forward padded to whole PAD_TO
        tokens (ids 0 routed anywhere, in blocks behind every real one)."""
        pad = -len(tokens) % PAD_TO
        routes = [np.concatenate([r, np.tile(np.arange(k), (pad, 1))])
                  for r in routes]
        return sdar_ref.logits(params, list(tokens) + [0] * pad, keys,
                               routes=routes, rows=rows, **control)

    lower = {"bf16_reference": {"dtype": jax.numpy.bfloat16},
             "reference_8bit": {"bits8": True}}
    parts: Dict[str, Dict[str, Any]] = {}
    controls = {name: {} for name in (*lower, "causal_inside",
                                      "commit_left_out")}
    routed = {"routes": [[] for _ in router], "inputs": [[] for _ in router]}

    def judged_forward(name, got, tokens, routes, rows, short):
        """Against the float32 reference; a SHORT row's (under four pages:
        one compiled shape of each lower precision, not four) against the
        lower-precision ones too."""
        parts[name] = _compare(got, reference(tokens, routes, rows))
        for control, how in lower.items() if short else ():
            controls[control][name] = _compare(
                got, reference(tokens, routes, rows, **how))

    for rid, prompt in zip(judged, prompts):
        whole = len(prompt) - len(prompt) % L
        short = whole < 4 * cfg.page_size
        done = list(prompt[:whole])
        chunks = sorted(spy.chunks.get(rid, []), key=lambda c: c["at"])
        path = [np.concatenate([c["routes"][i] for c in chunks])
                if chunks else np.zeros((0, k), np.int32)
                for i in range(len(router))]
        if chunks:
            # (d) the prompt's last whole block, as its chunk computed it
            judged_forward(f"{rid}/prompt", chunks[-1]["logits"], done, path,
                           list(range(whole - L, whole)), short)
            for i in range(len(router)):
                routed["routes"][i] += [c["routes"][i] for c in chunks]
                routed["inputs"][i] += [c["inputs"][i] for c in chunks]
        # what a missing commit would have left of each committed block
        uncommitted = list(done)
        last_read = None
        first_denoise = first_commit = True
        for f, forward in enumerate(spy.forwards[rid]):
            ids, at = forward["ids"], forward["at"]
            assert at == len(done), (rid, f, at, len(done))
            commit = not (ids == mask_id).any()
            tokens = done + ids.tolist()
            routes = [np.concatenate([path[i], forward["routes"][i]])
                      for i in range(len(router))]
            rows = list(range(at, at + L))
            blocks_done = (at - whole) // L
            # (b) of the longest row the first block alone: seven full
            # forwards over 2,048 tokens would be half the check's time
            check = (commit and first_commit) \
                or (not commit and first_denoise) \
                or (blocks_done == 2 and whole <= THIRD_BLOCK_UP_TO)
            if check:
                name = f"{rid}/{f}"
                judged_forward(name, forward["logits"], tokens, routes, rows,
                               short)
                if short and not commit and first_denoise:
                    controls["causal_inside"][name] = _compare(
                        forward["logits"], reference(
                            tokens, routes, rows, causal_inside=True))
                if blocks_done == 2 and short:
                    controls["commit_left_out"][name] = _compare(
                        forward["logits"], reference(
                            uncommitted + ids.tolist(), routes, rows))
            if commit:
                first_commit = False
                done += ids.tolist()
                uncommitted += last_read
                path = routes
            else:
                first_denoise = False
                last_read = ids.tolist()
    # every row-forward of every row, the fillers' too: the rule by the
    # reference's own function, the timed program's report against the
    # check's (a commit's too: its ids unchanged, no mask found or left),
    # the faulty call's against the check's
    rule_off, forwards, same, faulty_same, commits = [], 0, 0, 0, 0
    for rid, rows in spy.forwards.items():
        for f, forward in enumerate(rows):
            forwards += 1
            commits += not (forward["ids"] == mask_id).any()
            if not (forward["ruled"] == forward["out"]).all():
                rule_off.append((rid, f, forward["ids"].tolist(),
                                 np.asarray(forward["ruled"]).tolist(),
                                 forward["out"].tolist()))
            same += bool((forward["timed_report"]
                          == forward["report"]).all())
            if "fault_report" in forward:
                faulty_same += bool((forward["fault_report"]
                                     == forward["report"]).all())
            for i in range(len(router)):
                routed["routes"][i].append(forward["routes"][i])
                routed["inputs"][i].append(forward["inputs"][i])
    limits = (FLOAT32_MEDIAN, FLOAT32_WORST) \
        if np.dtype(model_cfg.dtype) == np.float32 \
        else (LOGIT_MEDIAN, LOGIT_WORST)
    sound = judge(parts, limits)
    rows_of = lambda name, i: np.concatenate(routed[name][i])  # noqa: E731
    routers = [router_check(rows_of("routes", i), rows_of("inputs", i),
                            router[i], k) for i in range(len(router))]
    control = [router_check(None, rows_of("inputs", i), router[i], k,
                            bf16=True) for i in range(len(router))]
    share = lambda rows: float(  # noqa: E731
        sum(r["agree"] * r["routings"] for r in rows)
        / sum(r["routings"] for r in rows))
    routings = sum(r["routings"] for r in routers)
    out_controls = {name: dict(judge(found, limits), forwards=len(found))
                    for name, found in controls.items()}
    # ISSUE 58 asked that a bfloat16 REFERENCE fail; it stands 1.5-2 x the
    # program's own reading (the program IS bfloat16), so it is read beside
    # the verdict and judges nothing (PERF.md sections 6 and 7)
    out_controls["bf16_reference"]["judged"] = False
    out_controls["router_bf16"] = {
        "agree": share(control), "routings": routings,
        "judged": routings >= ROUTER_CONTROL_AT_LEAST,
        "ok": share(control) >= ROUTER_AGREE}
    wrote = {name: apart.summary() for name, apart in spy.wrote.items()}
    timed = {"forwards": forwards, "commits": commits,
             "agree": same / max(1, forwards), "limit": TIMED_AGREE,
             "wrote": wrote, "wrote_apart_at_most": TIMED_APART,
             "most_rows_live": spy.most_live, "fault": fault}
    timed["ok"] = bool(
        timed["agree"] >= TIMED_AGREE
        and all(w["median"] <= TIMED_APART for w in wrote.values()))
    if fault == "count_less_one":
        # the timed program told one position fewer a row: its reports
        # against the check's, through the same limit
        agree = faulty_same / max(1, forwards)
        out_controls["timed_count_less_one"] = {
            "agree": agree, "forwards": forwards,
            "ok": agree >= TIMED_AGREE}
    # what a timed program that wrote another row's (another position's)
    # K/V reads, through TIMED_APART
    out_controls["timed_wrote_mismatched"] = {
        "median": {name: w["mismatched"] for name, w in wrote.items()},
        "judged": all(w["mismatched"] is not None for w in wrote.values()),
        "ok": any(w["mismatched"] is not None
                  and w["mismatched"] <= TIMED_APART
                  for w in wrote.values())}
    passed = [name for name, c in out_controls.items()
              if c.get("judged", True) and c["ok"]]
    after = engine.stats()
    out = {
        "logits": sound,
        "by_forward": {name: {"median": p["median"], "worst": p["worst"],
                              "argmax_agree": p["argmax_agree"]}
                       for name, p in parts.items()},
        "rule": {"forwards": forwards, "off": rule_off[:4]},
        "router": {"agree": share(routers), "routings": routings,
                   "limit": ROUTER_AGREE},
        "timed": timed,
        "controls": out_controls,
        "controls_that_passed": passed,
        "handed_out": {rid: len(tokens) for rid, tokens in handed.items()},
        "forwards": after["block_forwards"] - before["block_forwards"],
        "commits": after["commit_forwards"] - before["commit_forwards"],
        "seconds": round(time.monotonic() - began, 1),
        "peak_bytes": _peak_bytes(),
    }
    out["ok"] = bool(
        sound["ok"] and not rule_off and not passed
        and share(routers) >= ROUTER_AGREE and timed["ok"]
        and all(len(handed.get(rid, ())) == new
                for rid, _, _, new in requests))
    return out


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main() -> None:
    """The check alone, on a fresh engine of the cell's configuration:
    `python3 -m benchmarks.harness.parity_sdar [--rehearse] [--fault F]
    --seed N`."""
    import argparse
    import json
    import os

    from . import spec
    from .builders_sdar import sdar_engine
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--fault", default="count_less_one",
                        choices=["count_less_one", "projections_8bit"])
    args = parser.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    config = spec.load_json(os.path.join(
        root, "benchmarks", "configs", "sdar-30b-a3b-chat-serve.json"))
    from ray_tpu.llm.paged import PagedLLMEngine
    engine = PagedLLMEngine(sdar_engine(config, args.seed, args.rehearse))
    out = serve(engine, config, args.seed, fault=args.fault)
    out.pop("by_forward")
    print(json.dumps(out, default=str))


if __name__ == "__main__":
    main()
