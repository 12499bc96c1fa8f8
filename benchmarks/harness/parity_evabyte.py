"""The comparison that decides `correct` for an EvaByte serve cell: the
engine's own prefill chunks over the page pool, its `compress_window` and
paged decode through the same pool, against the plain float32 reference
(benchmarks/reference/evabyte_ref.py), same weights, on the chip, outside
the window. Four comparisons, and all must hold.

The prompt is N_PROMPT = 3900 bytes in CHUNK = 256-byte chunks (the bucket
nearly every chunk of the cell's traffic runs in; the last is padded): the
first window (2048) closes in prefill and 1852 positions stand open. Then
N_DECODE = 256 ticks go through the pool, fed greedily, and the second
window closes after the tick that writes position 4095 (tick 196 of 256).
So positions before and after a close made in prefill, and before and
after one made in decode, all lie inside the compared span; a smaller
engine (the rehearsal's, the CPU test's) takes the same shape at its own
window.

1. Logits of prediction head 0 (the head the engine samples), as
   parity.serve holds the dense decoder's and by the same code (`_compare`,
   `ill_conditioned`, `_verdict`): parity.LOGIT_TOLERANCE_STD, 0.12 of a
   position's logit spread at every well-conditioned position and half of
   it at the median one. That limit is as wide as bf16 weights need. It
   refuses what moves logits by a spread at every position (a wrong mask,
   a chunk written to the wrong page, a window not compressed); it cannot
   see 128 summaries among ~2000 exact rows, which may move a logit less
   than bf16 does. Hence 2 and 3.

2. The summary pages themselves: after each close, what the row's first
   pages of that window hold in every layer, against the reference's k~
   and v~ of the same chunks. Per head, |k~ - k~_ref| / |k~_ref| over the
   window's summaries (Frobenius), the same for v~; a layer's reading is
   its worst head of either. DEEP_TOLERANCE holds every layer to what
   bf16 K/V rows under a stack of bf16 activations allow, and refuses a
   window pooled from the wrong pages, in the wrong order or with the
   wrong phi (each an error of the summaries' own size). It cannot see
   the pooling's precision: the rounding of the K/V rows themselves
   (0.003 in layer 0, 0.005 in layer 1, 0.002 deeper) covers it. Hence 3.

3. The pooling alone, which the configuration fixes at float32 with the
   summaries stored in the pool's bf16: layer 0's summary pages against
   the reference's pooling (float32, `highest`) of the very rows the
   window's pages held before the close. What is read is then the
   pooling's own arithmetic and the ONE rounding of its result, a
   statistic of 32 heads x 128 summaries x 128 numbers that hardly
   moves with the seed. POOLING_TOLERANCE lies between pooling in
   float32 and pooling in bf16 (`--pool-dtype bfloat16` below: the
   control "a lower precision than the file states").

4. Layer 0's attention output (in front of W_o) at the first position
   after each close, where the query sees itself and 128 summaries and
   nothing else, so the summaries are all of it: per head |o - o_ref| /
   |o_ref|, worst head. ATTENDED_TOLERANCE refuses the control "summaries
   left out" (the reference with the sum over c dropped, read in every
   run as `control_no_summaries`: there o is v_i alone).

Readings on the chip at the published widths, 8 layers (my chip runs,
PR 42: nine seeds with the pooling in float32, eight of them with the
pooling's own reading; three with it in bf16, two of them with it;
PERF.md section 6):

  limit                   the program          the control
  LOGIT_TOLERANCE_STD     worst 0.009-0.089,   (a wrong mask or page: a
   0.12 (median 0.06)     median 0.0061-0.0069  spread or more)
  DEEP_TOLERANCE 0.02     any layer <= 0.0055   (wrong pages or phi: ~1)
  POOLING_TOLERANCE       0.001694-0.001736    pooled in bf16: 0.00296-
   0.002                                        0.00306 (its other close
                                                0.00217-0.00221)
  ATTENDED_TOLERANCE      0.0039-0.0085        summaries left out:
   0.05                                         13.9-17.8

POOLING_TOLERANCE is 1.15 times the largest float32 reading and 0.66 of
the bf16 control's; DEEP_TOLERANCE 3.6 times the largest reading of any
layer; ATTENDED_TOLERANCE 5.9 times the largest reading and 1/280 of the
control's smallest.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import numpy as np

from .parity import (PROBE_SIZE, PROBES, _compare, _verdict,
                     ill_conditioned)

N_PROMPT, N_DECODE, CHUNK = 3900, 256, 256

POOLING_TOLERANCE = 0.002
DEEP_TOLERANCE = 0.02
ATTENDED_TOLERANCE = 0.05


def reference_keys(m) -> Dict[str, Any]:
    """The running EvaByteConfig back under the published key names the
    reference reads (a rehearsal runs toy widths, not the file's)."""
    return {"num_attention_heads": m.num_heads,
            "num_key_value_heads": m.num_kv_heads,
            "hidden_size": m.num_heads * m.head_dim,
            "window_size": m.window_size, "chunk_size": m.chunk_size,
            "rope_theta": m.rope_theta, "rms_norm_eps": m.rms_norm_eps}


def spans(cfg) -> Dict[str, int]:
    """(prompt, ticks, chunk) of the check on this engine: the cell's
    where it fits, else the same shape at the engine's own window (one
    close in prefill before a padded last chunk, one three quarters
    through the decode ticks)."""
    window = cfg.model.window_size
    chunk = CHUNK if CHUNK in cfg.prefill_buckets \
        else cfg.prefill_buckets[-1]
    if cfg.max_len >= N_PROMPT + N_DECODE + 2 and N_PROMPT > window:
        return {"prompt": N_PROMPT, "ticks": N_DECODE, "chunk": chunk}
    ticks = window // 2
    return {"prompt": 2 * window - 3 * ticks // 4 - cfg.page_size // 2,
            "ticks": ticks, "chunk": chunk}


def engine_run(engine, prompt, chunk: int, ticks: int, slot: int = 0
               ) -> Dict[str, Any]:
    """`prompt` through the engine's own chunk program over the page pool
    in `chunk`-byte chunks (pages taken as the chunks come to them, the
    row's table handed in), its `compress_window` wherever a window
    fills, then `ticks` decode tokens through the pool in a paged decode
    program of the engine's shapes (the engine's own returns ids, not
    logits), fed greedily. Returns the prefill's and the decode ticks'
    logits (head 0), the tokens fed to decode, per close what the row's
    summary pages hold in every layer, and layer 0's attention output at
    the first position after each close."""
    import jax
    import jax.numpy as jnp

    cfg = engine.config
    model_cfg = cfg.model
    layers, ps = model_cfg.num_layers, cfg.page_size
    window = model_cfg.window_size
    kept = model_cfg.window_summaries // ps
    n_prompt = len(prompt)
    pages: List[int] = []
    closes: List[Dict[str, Any]] = []
    attended: Dict[int, np.ndarray] = {}

    def grow(rows: int):
        while len(pages) * ps < rows:
            page = engine.pool.alloc()
            if page is None:
                raise RuntimeError("no free pages for the parity prompt")
            pages.append(page)

    def table():
        out = np.zeros((cfg.pages_per_seq,), np.int32)
        out[:len(pages)] = pages
        return out

    def close(at: int):
        """The row's open window is full: the engine's own program, then
        what its first pages hold."""
        base = len(pages) - window // ps
        full = np.asarray(pages[base:])
        # layer 0's window as the pool holds it, [heads, window, d]
        before = tuple(
            np.asarray(pool[:, full], np.float32).reshape(
                pool.shape[0], -1, pool.shape[-1])
            for pool in (engine.k_pages[0], engine.v_pages[0]))
        engine.k_pages, engine.v_pages = engine._compress_window(
            engine.params, engine.k_pages, engine.v_pages,
            np.asarray(pages[base:], np.int32))
        held = np.asarray(pages[base:base + kept])
        rows = lambda pool: np.asarray(  # noqa: E731
            pool[:, held], np.float32).reshape(
                pool.shape[0], -1, pool.shape[-1])
        closes.append({"position": at, "window": before, "summaries": [
            (rows(k), rows(v))
            for k, v in zip(engine.k_pages, engine.v_pages)]})
        for page in pages[base + kept:]:
            engine.pool.decref(page)
        del pages[base + kept:]

    def layer0(intermediates):
        return intermediates["intermediates"]["layer_0"]["attn"][
            "attended"][0]

    def chunk_attended(params, tokens, positions, pools, offset, tbl):
        """The engine's chunk body once more, for layer 0's attention
        output (the engine's program returns logits only)."""
        k_pages, v_pages = pools
        (_, new), inter = engine.model.apply(
            {"params": params}, tokens, positions=positions,
            kv_caches=[{"k": k, "v": v, "table": tbl}
                       for k, v in zip(k_pages, v_pages)],
            cache_index=offset, head=False, mutable=["intermediates"])
        return layer0(inter), ([c["k"] for c in new], [c["v"] for c in new])

    def decode_logits(params, k_pages, v_pages, tables, lengths, tokens):
        caches = [{"k": k_pages[i], "v": v_pages[i],
                   "block_tables": tables, "lengths": lengths}
                  for i in range(layers)]
        (lg, new), inter = engine.model.apply(
            {"params": params}, tokens, positions=lengths[:, None],
            kv_caches=caches, cache_index=None, mutable=["intermediates"])
        return (lg[:, -1].astype(jnp.float32), layer0(inter),
                [c["k"] for c in new], [c["v"] for c in new])

    try:
        with engine._mesh_scope():
            rows = []
            for off in range(0, n_prompt, chunk):
                take = min(chunk, n_prompt - off)
                tokens = np.zeros((1, chunk), np.int32)
                tokens[0, :take] = prompt[off:off + take]
                positions = np.arange(off, off + chunk,
                                      dtype=np.int32)[None]
                grow(model_cfg.cache_rows(off) + take)
                args = (jnp.asarray(tokens), jnp.asarray(positions))
                lg, (engine.k_pages, engine.v_pages) = \
                    engine._chunk_prefill(
                        engine.params, *args,
                        (engine.k_pages, engine.v_pages),
                        jnp.asarray(off, jnp.int32), table())
                rows.append(np.asarray(lg[0, :take]))
                if off and model_cfg.window_closes(off):
                    # the first position after a close: the same rows are
                    # written again, the same pages attended
                    got, (engine.k_pages, engine.v_pages) = jax.jit(
                        chunk_attended, donate_argnums=(3,))(
                            engine.params, *args,
                            (engine.k_pages, engine.v_pages),
                            jnp.asarray(off, jnp.int32), table())
                    attended[off] = np.asarray(got[0, 0], np.float32)
                if model_cfg.window_closes(off + take):
                    close(off + take)
            prefill_logits = np.concatenate(rows)

            program = jax.jit(decode_logits, donate_argnums=(1, 2))
            B = cfg.max_batch
            fed = [int(prefill_logits[-1].argmax())]
            decode_rows = []
            for i in range(ticks):
                at = n_prompt + i
                grow(model_cfg.cache_rows(at) + 1)
                tables = np.zeros((B, cfg.pages_per_seq), np.int32)
                tables[slot] = table()
                lengths = np.zeros((B,), np.int32)
                lengths[slot] = at
                tokens = np.zeros((B, 1), np.int32)
                tokens[slot, 0] = fed[-1]
                lg, got, engine.k_pages, engine.v_pages = program(
                    engine.params, engine.k_pages, engine.v_pages,
                    jnp.asarray(tables), jnp.asarray(lengths),
                    jnp.asarray(tokens))
                decode_rows.append(np.asarray(lg[slot]))
                fed.append(int(decode_rows[-1].argmax()))
                if at and model_cfg.window_closes(at):
                    attended[at] = np.asarray(got[slot, 0], np.float32)
                if model_cfg.window_closes(at + 1):
                    close(at + 1)
    finally:
        for page in pages:
            engine.pool.decref(page)
    return {"prefill_logits": prefill_logits,
            "decode_logits": np.stack(decode_rows), "fed": fed[:-1],
            "closes": closes, "attended": attended}


def _worst_head(got, want) -> float:
    """got, want [heads, rows, d]: the worst head's |got - want| /
    |want| (Frobenius over rows and d)."""
    want = np.asarray(want, np.float32)
    err = np.sqrt(((got - want) ** 2).sum((-2, -1))
                  / (want ** 2).sum((-2, -1)))
    return float(err.max())


def summary_errors(closes, details, window: int, chunk: int
                   ) -> List[List[float]]:
    """Per close, per layer: the summary pages against the reference's
    k~, v~ of the chunks of the window that closed (worst head of
    either)."""
    per = window // chunk
    out = []
    for made in closes:
        first = (made["position"] // window - 1) * per
        layers = []
        for (k, v), ref in zip(made["summaries"], details):
            ref_k, ref_v = (np.transpose(np.asarray(
                a[first:first + per], np.float32), (1, 0, 2))
                for a in ref["summaries"])
            layers.append(max(_worst_head(k, ref_k), _worst_head(v, ref_v)))
        out.append(layers)
    return out


def pooling_errors(closes, attn, keys) -> List[float]:
    """Per close: layer 0's summary pages against the reference's pooling
    (float32, `evabyte_ref.summaries`) of the very rows the window's pages
    held before the close, so that what is read is the pooling's own
    arithmetic and the one rounding of its result, with no rounding of
    the K/V rows in it. `attn`: layer 0's attention weights (phi, mu)."""
    import jax
    import jax.numpy as jnp

    from ..reference import evabyte_ref
    sh = evabyte_ref.shape_of(keys)
    out = []
    for made in closes:
        k, v = (jnp.transpose(jnp.asarray(a), (1, 0, 2))
                for a in made["window"])
        with jax.default_matmul_precision("highest"):
            ref_k, ref_v, _ = evabyte_ref.summaries(
                k, v, attn["phi"], attn["mu"], sh)
        got_k, got_v = made["summaries"][0]
        out.append(max(
            _worst_head(got_k, np.transpose(np.asarray(ref_k), (1, 0, 2))),
            _worst_head(got_v, np.transpose(np.asarray(ref_v), (1, 0, 2)))))
    return out


def attended_errors(attended, reference_o) -> Dict[int, float]:
    """position -> worst head's |o - o_ref| / |o_ref| of layer 0's
    attention output there (`reference_o` [s, heads, d])."""
    return {int(at): _worst_head(
        got[:, None, :], np.asarray(reference_o[at], np.float32)[:, None, :])
        for at, got in attended.items()}


def serve(engine, config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """A seeded prompt through `engine_run` (a window closes in prefill,
    one in decode), against the reference's full forward pass over the
    same bytes: head 0's logits, the summary pages, and layer 0's
    attention output at the first position after each close."""
    import jax
    import jax.numpy as jnp

    from ..reference import evabyte_ref
    from .builders import jax_seed

    cfg = engine.config
    model_cfg = cfg.model
    if engine.has_work():
        raise RuntimeError("parity needs an idle engine")
    span = spans(cfg)
    n_prompt = span["prompt"]
    rng = np.random.default_rng([jax_seed(seed), 77])
    prompt = rng.integers(1, model_cfg.vocab_size, size=n_prompt)
    run = engine_run(engine, prompt, span["chunk"], span["ticks"])

    sequence = np.concatenate([prompt, np.asarray(run["fed"])])
    keys = reference_keys(model_cfg)
    reference = functools.partial(
        evabyte_ref.logits, engine.params, sequence, keys,
        num_layers=model_cfg.num_layers)
    want, details = reference(details=True)
    want = np.asarray(want[:, 0])          # the head the engine samples
    wobble = (sequence.shape[0], model_cfg.hidden_size)
    probes = [np.asarray(reference(
        embed_scale=1.0 + PROBE_SIZE * jax.random.normal(
            jax.random.PRNGKey(k), wobble, jnp.float32))[:, 0])
        for k in range(PROBES)]
    ill = ill_conditioned(want, probes)
    out = _verdict(
        {"prefill": _compare(run["prefill_logits"], want[:n_prompt]),
         "decode": _compare(run["decode_logits"], want[n_prompt:])},
        {"prefill": ill[:n_prompt], "decode": ill[n_prompt:]})

    summaries = summary_errors(run["closes"], details,
                               model_cfg.window_size, model_cfg.chunk_size)
    pooling = pooling_errors(run["closes"],
                             engine.params["layer_0"]["attn"], keys)
    attended = attended_errors(run["attended"], details[0]["attended"])
    # the control the third limit must refuse: one layer of the reference
    # with the sum over c dropped
    _, bare = evabyte_ref.logits(
        engine.params, sequence, keys, num_layers=1, details=True,
        with_summaries=False)
    control = attended_errors(
        {at: np.asarray(bare[0]["attended"][at], np.float32)
         for at in run["attended"]}, details[0]["attended"])
    out["closes"] = [made["position"] for made in run["closes"]]
    # neither ln(chunk_size) (a mean-pool) nor 0 (one position), or the
    # cell measures something simpler than the mechanism
    out["pool_entropy"] = {
        "by_layer": [float(d["pool_entropy"]) for d in details],
        "mean_pool": float(np.log(model_cfg.chunk_size))}
    out["summaries"] = {"by_close_and_layer": summaries,
                        "deep_tolerance": DEEP_TOLERANCE,
                        "pooling_by_close": pooling,
                        "pooling_tolerance": POOLING_TOLERANCE}
    out["attended"] = {"by_position": attended,
                       "control_no_summaries": control,
                       "tolerance": ATTENDED_TOLERANCE}
    out["ok"] = bool(
        out["ok"] and len(summaries) == 2 and len(attended) == 2
        and all(max(layers) <= DEEP_TOLERANCE for layers in summaries)
        and max(pooling) <= POOLING_TOLERANCE
        and max(attended.values()) <= ATTENDED_TOLERANCE)
    return out


def main() -> int:
    """`python3 -m benchmarks.harness.parity_evabyte [--seed N]
    [--pool-dtype bfloat16] [--rehearse]`: the check alone, on an engine
    built from the cell's configuration file, with the control that pools
    in a lower precision than the file states where asked. Prints the
    verdict as one JSON line; the builder's tool for the readings behind
    the limits, not part of any run."""
    import argparse
    import json
    import os

    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--pool-dtype", default=None)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax.numpy as jnp

    from ray_tpu.llm.paged import PagedLLMEngine

    from . import builders_evabyte, spec
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    config = spec.load_json(os.path.join(
        root, "benchmarks", "configs", "evabyte-6.5b-serve.json"))
    over = {"pool_dtype": jnp.dtype(args.pool_dtype)} \
        if args.pool_dtype else {}
    engine = PagedLLMEngine(builders_evabyte.evabyte_engine(
        config, args.seed, args.rehearse, **over))
    out = serve(engine, config, args.seed)
    out.pop("set_aside", None)
    print(json.dumps({"pool_dtype": args.pool_dtype or "float32",
                      "seed": args.seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
