"""The decode tick's kept arrays (`ray_tpu/llm/staging.py`): what a step is
told about its rows lives between visits on the host and on the device, and
only what changed is written or sent. Over one scripted run an engine kind
(admissions into freed rows, page crossings, a sampled request, a cancel, a
preemption and its resume) every step's arguments, AS THE PROGRAM RECEIVES
THEM, equal what the loop this replaced would have built from `engine.seqs`
(kept here as `loop_arrays`); the tokens equal those of a second engine whose
steps are handed that loop's fresh arrays; the uploads a step are the arrays
that changed; and the rows-attended accounts, now vector sums, equal their
per-row form to the integer. CPU, float32, toy widths."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu.llm import GenerationRequest  # noqa: E402
from ray_tpu.llm.paged import PagedEngineConfig, PagedLLMEngine  # noqa: E402
from ray_tpu.llm.staging import KEPT, StagedRows  # noqa: E402


def _dense(params=None):
    from ray_tpu.models.llama import LlamaConfig
    model = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                        num_layers=2, num_heads=4, num_kv_heads=4,
                        max_seq_len=256, remat=False, use_flash=False,
                        attention_impl="reference")
    return PagedLLMEngine(PagedEngineConfig(
        model=model, max_batch=3, max_len=160, page_size=8, num_pages=96,
        prefill_buckets=(16, 32)), params=params)


def _recurrent(params=None):
    from test_lfm2 import tiny_engine
    return tiny_engine(params=params)


def _latent(params=None):
    from test_sarvam_mla import tiny_engine
    return tiny_engine(params=params)


def _indexed(params=None):
    from test_keye_dsa import tiny_engine
    return tiny_engine(params=params)


def _windowed(params=None):
    from test_evabyte import tiny_engine
    return tiny_engine(params=params)


def _blockwise(params=None):
    from test_sdar import tiny_engine
    return tiny_engine(params=params, batch=3)


ENGINES = {"dense": _dense, "recurrent": _recurrent, "latent": _latent,
           "indexed": _indexed, "windowed": _windowed,
           "blockwise": _blockwise}


def loop_arrays(engine, active):
    """The arrays of a step over the rows `active` as the deleted loop built
    them, anew, from `engine.seqs`; read at the program's call, when a row's
    length already counts the token this step computes."""
    cfg = engine.config
    B = cfg.max_batch
    out = {"tables": np.zeros((B, cfg.pages_per_seq), np.int32),
           "lengths": np.zeros((B,), np.int32),
           "live": np.zeros((B,), bool),
           "temps": np.zeros((B,), np.float32),
           "top_ks": np.zeros((B,), np.int32),
           "top_ps": np.ones((B,), np.float32)}
    for i in active:
        seq = engine.seqs[i]
        out["tables"][i, :len(seq.pages)] = seq.pages
        # a block step is told where the open block starts
        out["lengths"][i] = seq.flight[-1][0] if engine.kind == "blockwise" \
            else seq.length - 1
        out["live"][i] = True
        out["temps"][i], out["top_ks"][i], out["top_ps"][i] = \
            engine._sampling(seq.request)
    return out


def rows_attended(engine, active):
    """The per-row form of the accounts `_decode_tick` keeps of what a step
    attends, as the deleted loop summed them a row at a time."""
    model = engine.config.model
    out = dict.fromkeys(
        ("summary_rows", "window_rows", "latent_rows_attended",
         "latent_pages_rowwise", "index_rows_scanned", "index_pages_rowwise",
         "sparse_rows_selected", "sparse_rows_context"), 0)
    for i in active:
        seq = engine.seqs[i]
        length = seq.length - 1
        if engine.kind == "windowed":
            summary, window = model.attended_rows(length)
            out["summary_rows"] += summary
            out["window_rows"] += window
        if engine.kind == "latent":
            out["latent_rows_attended"] += length + 1
            out["latent_pages_rowwise"] += len(seq.pages)
        if engine.kind == "indexed":
            out["index_rows_scanned"] += length + 1
            out["index_pages_rowwise"] += len(seq.pages)
            out["sparse_rows_context"] += length + 1
            out["sparse_rows_selected"] += min(length + 1, model.index_topk)
    return out


class Spy:
    """Stands where the tick calls `_ensure_decode_pages` and `_decode`:
    keeps the rows the step is over and, at each call of the program, what
    it was handed beside what `loop_arrays` gives. With `substitute` the
    program is handed the loop's arrays instead, freshly uploaded: the
    engine then runs as it did before the arrays were kept."""

    def __init__(self, engine, substitute=False):
        self.engine, self.substitute = engine, substitute
        self.steps = []          # per step: dict of what was seen
        self.account = dict.fromkeys(rows_attended(engine, ()), 0)
        self._active = []
        grow, decode = engine._ensure_decode_pages, engine._decode

        def ensure(active):
            self._active = grow(active)
            return self._active

        def call(*args):
            return decode(*self.seen(list(args)))

        engine._ensure_decode_pages, engine._decode = ensure, call

    def places(self, args):
        """Where in the program's arguments each staged array stands: the
        table is the first [rows, pages_per_seq] int32, the lengths follow
        it, the rows that decode stand before it where the program takes
        them, and the triple follows the key."""
        cfg = self.engine.config
        shape = (cfg.max_batch, cfg.pages_per_seq)
        is_array = lambda a: isinstance(a, (jax.Array, np.ndarray))  # noqa
        tables = next(n for n, a in enumerate(args) if is_array(a)
                      and a.shape == shape and a.dtype == np.int32)
        key = next(n for n, a in enumerate(args) if is_array(a)
                   and a.dtype == np.uint32)
        at = {"tables": tables, "lengths": tables + 1, "temps": key + 1,
              "top_ks": key + 2, "top_ps": key + 3}
        if is_array(args[tables - 1]) and args[tables - 1].dtype == bool:
            at["live"] = tables - 1
        return at

    def seen(self, args):
        engine = self.engine
        at = self.places(args)
        want = loop_arrays(engine, self._active)
        got = {name: np.asarray(args[n]) for name, n in at.items()}
        for name, n in at.items():
            assert got[name].dtype == want[name].dtype, name
            if self.substitute:
                args[n] = jnp.asarray(want[name])
        stage = engine._stage
        self.steps.append({
            "active": list(self._active), "got": got, "want": want,
            "uploads": stage.uploads, "steps": stage.steps,
            "kept": {name: getattr(stage, name).copy()
                     for name in KEPT + ("lengths", "held")},
            "held": [len(engine.seqs[i].pages) for i in self._active],
            # rows that hold pages while they are out of the step
            "holding": [i for i, s in enumerate(engine.seqs) if s.pages
                        and i not in self._active]})
        for name, value in rows_attended(engine, self._active).items():
            self.account[name] += value
        self.steps[-1]["account"] = dict(self.account)
        counts = engine._ahead_counts()      # a kind reports its own alone
        self.steps[-1]["engine_account"] = {
            name: counts.get(name, 0) for name in self.account}
        return args


def prompt_of(engine, seed, n):
    vocab = engine.config.model.vocab_size
    return np.random.default_rng(seed).integers(1, vocab - 8, size=n).tolist()


def scripted_run(engine):
    """Five requests on three rows: prompts of 5 to 27 tokens, answers long
    enough to cross pages (pages of 8, or 4), one request sampled with a
    top-k and a nucleus; the second row's request is cancelled while it
    decodes, the youngest decoding row is preempted a few visits later and
    resumes in the next free row. Returns {request id: tokens or None}."""
    results = {}

    def done(request, result):
        results[request.request_id] = result

    def submit(rid, n, new, **sampling):
        request = GenerationRequest(
            prompt_tokens=prompt_of(engine, 100 + n, n), max_new_tokens=new,
            request_id=rid, **sampling)
        engine.submit(request, done_callback=done)

    submit("a", 11, 26)
    submit("b", 27, 30)
    submit("c", 5, 18, temperature=0.8, top_k=12, top_p=0.9)
    submit("d", 16, 12)
    submit("e", 9, 21, temperature=0.7)
    visits = cancelled = preempted = 0
    while engine.has_work():
        engine.step()
        visits += 1
        decoding = [i for i, s in enumerate(engine.seqs)
                    if s.request is not None and s.phase == "decode"]
        if not cancelled and visits >= 8 and "b" in engine._by_id \
                and engine._by_id["b"].phase == "decode":
            cancelled = engine.cancel("b")
        decoding = [i for i in decoding if not engine.seqs[i].cancelled]
        if cancelled and not preempted and visits >= 14 and decoding:
            victim = max(decoding, key=lambda i: engine.seqs[i].admit_at)
            engine._preempt(victim, reason="page_pressure")
            preempted = 1
        assert visits < 2000, "the scripted run does not end"
    assert cancelled and preempted
    assert results["b"] is None and len(results) == 5
    return results


@pytest.fixture(scope="module", params=list(ENGINES))
def run(request):
    """One scripted run an engine kind with the kept arrays, and one with
    every step handed the deleted loop's arrays instead."""
    build = ENGINES[request.param]
    engine = build()
    spy = Spy(engine)
    tokens = scripted_run(engine)
    fresh = build(params=engine.params)
    Spy(fresh, substitute=True)
    return engine, spy, tokens, scripted_run(fresh)


def test_every_step_is_handed_the_arrays_the_loop_built(run):
    engine, spy, _, _ = run
    assert len(spy.steps) > 30
    for n, step in enumerate(spy.steps):
        for name, want in step["want"].items():
            if name in step["got"]:
                np.testing.assert_array_equal(
                    step["got"][name], want, err_msg=f"step {n}: {name}")
        # the host's copies too, `live` whether the program takes it or not
        for name in KEPT:
            np.testing.assert_array_equal(step["kept"][name],
                                          step["want"][name],
                                          err_msg=f"step {n}: kept {name}")
        np.testing.assert_array_equal(
            step["kept"]["held"][step["active"]], step["held"])


def test_rows_out_of_the_step_read_what_a_fresh_array_held(run):
    engine, spy, _, _ = run
    B = engine.config.max_batch
    idle_rows = 0
    for step in spy.steps:
        out = sorted(set(range(B)).difference(step["active"]))
        idle_rows += len(out)
        got = step["got"]
        assert not got["tables"][out].any()
        assert not got["lengths"][out].any()
        assert not got["temps"][out].any() and not got["top_ks"][out].any()
        assert (got["top_ps"][out] == 1.0).all()
        if "live" in got:
            assert not got["live"][out].any()
    # the script leaves rows out of steps: finished, cancelled, prefilling;
    # some of them hold pages meanwhile (a prompt being prefilled, a row
    # whose last token is in flight)
    assert idle_rows > 10
    assert any(step["holding"] for step in spy.steps)


def test_tokens_are_those_of_an_engine_handed_the_loops_arrays(run):
    _, _, tokens, fresh_tokens = run
    assert tokens == fresh_tokens
    assert sum(t is not None for t in tokens.values()) == 4
    assert all(len(t) > 0 for t in tokens.values() if t is not None)


def test_a_step_is_sent_the_arrays_that_changed(run):
    engine, spy, _, _ = run
    kept = [name for name in KEPT if name in spy.steps[0]["got"]]
    before, uploads, steps = None, 0, 0
    quiet = full = 0
    for n, step in enumerate(spy.steps):
        sent = step["uploads"] - uploads
        assert step["steps"] == steps == n       # counted after the call
        uploads, steps = step["uploads"], step["steps"] + 1
        changed = [name for name in kept if before is None
                   or not np.array_equal(before[name], step["want"][name])]
        # lengths always; every array whose content moved; none but those
        # the step's rows could have moved
        assert 1 + len(changed) <= sent <= 1 + len(kept), (n, sent, changed)
        if not changed:
            assert sent == 1, (n, sent)
            quiet += 1
        full += len(changed) == len(kept)
        before = step["want"]
    assert spy.steps[0]["uploads"] == 1 + len(kept) and full >= 1
    # many steps move nothing but lengths (on pages of four positions
    # and three rows, some row crosses a page in most)
    assert quiet > len(spy.steps) // 3
    stats = engine.stats()
    assert stats["stage_steps"] == len(spy.steps)
    assert stats["stage_uploads"] == engine._stage.uploads
    assert stats["stage_uploads"] / stats["stage_steps"] < 2.5
    row = engine._ahead_counts()
    assert row["stage_steps"] == stats["stage_steps"]
    assert row["stage_uploads"] == stats["stage_uploads"]


def test_the_vector_accounts_equal_their_per_row_form(run):
    engine, spy, _, _ = run
    for n, step in enumerate(spy.steps):
        # the engine's sums when step n's program is called hold step n's
        assert step["engine_account"] == step["account"], n
    last = spy.steps[-1]["account"]
    if engine.kind == "windowed":
        assert last["window_rows"] > 0 and last["summary_rows"] > 0
    if engine.kind == "latent":
        assert last["latent_rows_attended"] > last["latent_pages_rowwise"] > 0
    if engine.kind == "indexed":
        assert last["index_rows_scanned"] == last["sparse_rows_context"] > 0
        assert 0 < last["sparse_rows_selected"] <= last["sparse_rows_context"]
    if engine.kind not in ("windowed", "latent", "indexed"):
        assert not any(last.values())


def test_staged_rows_alone():
    """`StagedRows` against rows made by hand: a row written whole, a page
    appended as one element, a table rewritten after `stale`, a slot that
    changes hands inside the step's rows, a row cleared when it leaves."""
    class Row:
        def __init__(self, pages, length, request):
            self.pages, self.length, self.request = pages, length, request

    triple = lambda request: request                      # noqa: E731
    stage = StagedRows(4, 6)
    a, b = Row([5, 6], 9, (0.0, 0, 1.0)), Row([7], 3, (0.5, 4, 0.9))
    seqs = [a, None, b, None]
    stage.sync([0, 2], seqs, triple)
    assert stage.tables.tolist() == [[5, 6, 0, 0, 0, 0], [0] * 6,
                                     [7, 0, 0, 0, 0, 0], [0] * 6]
    assert stage.lengths.tolist() == [9, 0, 3, 0] and stage.tier == 2
    assert stage.live.tolist() == [True, False, True, False]
    for name in ("tables", "lengths", "live", "temps", "top_ks", "top_ps"):
        stage.send(name)
    stage.sent(advance=True)
    assert (stage.uploads, stage.steps) == (6, 1)
    assert stage.lengths.tolist() == [10, 0, 4, 0]
    # nothing moved: the same device arrays again, lengths anew
    was = {name: stage.send(name) for name in KEPT}
    stage.sync([0, 2], seqs, triple)
    assert all(stage.send(name) is was[name] for name in KEPT)
    assert stage.uploads == 6
    assert np.asarray(stage.send("lengths")).tolist() == [10, 0, 4, 0]
    # a page appended: one element, the table alone is sent
    a.pages.append(8)
    stage.sync([0, 2], seqs, triple)
    assert stage.tables[0].tolist() == [5, 6, 8, 0, 0, 0]
    assert stage.send("tables") is not was["tables"]
    assert stage.send("live") is was["live"]
    assert stage.send("temps") is was["temps"]
    assert np.asarray(was["tables"])[0].tolist() == [5, 6, 0, 0, 0, 0]
    # pages given back and one taken: whole again after `stale`
    a.pages[:] = [5, 9]
    stage.stale(0)
    stage.sync([0, 2], seqs, triple)
    assert stage.tables[0].tolist() == [5, 9, 0, 0, 0, 0]
    assert stage.held.tolist() == [2, 0, 1, 0]
    # the slot changes hands while it stays among the step's rows
    c = Row([2, 3, 4], 17, (0.0, 0, 1.0))
    seqs[2] = c
    stage.sync([0, 2], seqs, triple)
    assert stage.tables[2].tolist() == [2, 3, 4, 0, 0, 0]
    assert stage.lengths[2] == 17 and stage.tier == 0
    assert stage.top_ps.tolist() == [1.0] * 4
    # a row leaves: zeros again, and the rows that decode are sent
    stage.send("live")
    sent = stage.uploads
    stage.sync([2], seqs, triple)
    assert not stage.tables[0].any() and stage.lengths[0] == 0
    assert stage.held.tolist() == [0, 0, 3, 0]
    assert np.asarray(stage.send("live")).tolist() == [False, False, True,
                                                      False]
    assert stage.uploads == sent + 1
