"""The generator and the arithmetic, with no program and no clock."""

import collections
import itertools
import json
import os

import pytest

from benchmarks.harness import arith, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")


def load(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat-closed64", "docqa-open"])
def test_same_multiset_of_lengths_under_every_seed(name):
    spec = load(name)
    n = spec["cycle"] * 3

    def sizes(seed):
        rows = list(itertools.islice(traffic.requests(spec, seed, 32768), n))
        fresh = [len(r.prompt) - r.document_tokens for r in rows]
        return rows, (collections.Counter(fresh),
                      collections.Counter(r.max_new for r in rows),
                      collections.Counter(zip(fresh, (r.max_new
                                                      for r in rows))))

    rows_a, a = sizes(1)
    rows_b, b = sizes(2 ** 31 + 12345)   # the driver's seeds are large
    assert a == b      # lengths, and which prompt goes with which answer
    assert [r.prompt for r in rows_a] != [r.prompt for r in rows_b]


def test_fixed_schedule_is_one_poisson_realisation_for_every_seed():
    spec = load("docqa-open")
    n = 600

    def stream(spec, seed):
        return list(itertools.islice(traffic.requests(spec, seed, 32768), n))

    a, b = stream(spec, 1), stream(spec, 2 ** 31 + 12345)
    # the file fixes arrivals and the order of sizes; the seed draws ids
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [(len(r.prompt), r.max_new) for r in a] \
        == [(len(r.prompt), r.max_new) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    # independent exponential gaps at the file's rate: mean 1/rate,
    # coefficient of variation 1, bursts (three arrivals inside 0.2 of a
    # mean gap) and lulls (a gap over four means) both present
    due = [0.0] + [r.due_s for r in a]
    gaps = sorted(y - x for x, y in zip(due, due[1:]))
    mean = sum(gaps) / n
    assert mean == pytest.approx(1 / spec["rate_per_s"], rel=0.1)
    cv = (sum((g - mean) ** 2 for g in gaps) / n) ** 0.5 / mean
    assert 0.9 < cv < 1.1
    assert gaps[-1] > 4 * mean
    assert any(due[i + 2] - due[i] < 0.2 * mean for i in range(n - 1))
    # without schedule_seed the seed draws the schedule, as issue 23 wrote
    free = {k: v for k, v in spec.items() if k != "schedule_seed"}
    assert [r.due_s for r in stream(free, 1)] \
        != [r.due_s for r in stream(free, 2)]
    # the same realisation at another rate is the same schedule, scaled
    fast = dict(spec, rate_per_s=2 * spec["rate_per_s"])
    assert [r.due_s for r in stream(fast, 1)] == pytest.approx(
        [r.due_s / 2 for r in a])


def ratios(n, **others):
    out = [0.03] * n
    for at, x in others.items():
        out[int(at[1:])] = x
    return {"diff_over_std": out, "median": 0.03, "worst": max(out)}


def flags(n, *set_aside):
    return [at in set_aside for at in range(n)]


@pytest.mark.parametrize("prefill, decode, aside, ok", [
    ({}, {}, (), True),
    ({"p18": 1.01}, {}, (18,), True),     # seed 23's reading on the chip
    ({"p18": 0.13}, {}, (), False),       # off where nothing excuses it
    ({"p18": 9.0, "p21": 1.5}, {}, (17, 18, 21), True),
    ({"p18": 9.0, "p64": 0.5}, {}, (18,), False),
    ({}, {"p0": 0.5}, (18,), False),
    ({}, {}, tuple(range(70)), False),    # too much set aside: no proof
])
def test_parity_gate_excuses_only_ill_conditioned_positions(prefill, decode,
                                                            aside, ok):
    from benchmarks.harness import parity
    verdict = parity._verdict(
        {"prefill": ratios(128, **prefill), "decode": ratios(8, **decode)},
        {"prefill": flags(128, *aside), "decode": flags(8)})
    assert verdict["ok"] is ok


def test_parity_gate_holds_the_median():
    from benchmarks.harness import parity
    part = ratios(128)
    part["median"] = 0.07
    assert not parity._verdict(
        {"prefill": part, "decode": ratios(8)},
        {"prefill": flags(128), "decode": flags(8)})["ok"]


def test_ill_conditioned_positions_are_the_ones_a_probe_moves_far():
    import numpy as np
    from benchmarks.harness import parity
    rng = np.random.default_rng(0)
    want = rng.standard_normal((20, 50)).astype(np.float32)

    def probe(seed, far):
        move = 1e-3 * np.random.default_rng(seed).standard_normal((20, 50))
        for at in far:
            move[at] *= 30
        return want + move.astype(np.float32)

    ill = parity.ill_conditioned(want, [probe(1, (3,)), probe(2, (3, 7))])
    assert list(np.flatnonzero(ill)) == [3, 7]
    assert not parity.ill_conditioned(want, [probe(3, ())]).any()


def test_documents_are_asked_three_times_and_interleave():
    spec = load("docqa-open")
    rows = list(itertools.islice(traffic.requests(spec, 5, 32768), 240))
    steady = rows[24:]
    hits = [bool(r.shared_tokens) for r in steady]
    assert 0.6 < sum(hits) / len(hits) < 0.72
    # never three first asks in a row once the stream is rolling
    assert all(any(hits[i:i + 3]) for i in range(len(hits) - 3))
    assert traffic.longest(spec) <= 2304 - 2
    # a later ask repeats its document's tokens exactly
    first = {}
    for r in rows:
        key = tuple(r.prompt[:64])
        if r.shared_tokens:
            assert r.shared_tokens == r.document_tokens
            if key in first:
                assert r.prompt[:r.shared_tokens] \
                    == first[key][:r.shared_tokens]
        else:
            first[key] = r.prompt
    same = [r for r in rows if r.shared_tokens]
    assert same and all(r.shared_tokens >= 1024 * 0.99 for r in same)


def test_train_batches_repeat_per_seed_and_step():
    spec = load("pretrain-2x2048")
    a = traffic.train_batch(spec, 2 ** 31 + 5, 3, 64000)
    assert a.shape == (2, 2048) and a.max() < 64000
    assert (a == traffic.train_batch(spec, 2 ** 31 + 5, 3, 64000)).all()
    assert (a != traffic.train_batch(spec, 2 ** 31 + 5, 4, 64000)).any()


def row(chunks, expected=None, due=None, sent=0.0, done=None, error=None):
    got = sum(n for _, n in chunks)
    return {"due": due, "sent": sent, "chunks": chunks,
            "expected": got if expected is None else expected,
            "done": chunks[-1][0] if done is None and chunks else done,
            "error": error}


def test_tpot_averages_inside_a_request_with_several_tokens_a_chunk():
    # first chunk at 1.0 s with 1 token, then 3 + 2 tokens: 5 tokens after
    # the first chunk over 0.5 s = 100 ms a token
    r = row([(1.0, 1), (1.2, 3), (1.5, 2)])
    assert arith.tpot_ms(r) == pytest.approx(100.0)
    assert arith.tpot_ms(row([(1.0, 4)])) is None
    rows = [r, row([(2.0, 1), (2.2, 1)]),            # 200 ms
            row([(3.0, 1), (3.1, 1)], expected=9),   # short: failed
            row([(9.0, 1), (9.9, 1)])]               # ended after window
    samples = arith.tpot_samples(rows, 0.0, 5.0)
    assert sorted(samples) == [pytest.approx(100.0), pytest.approx(200.0),
                               arith.WORST_MS]
    assert arith.percentile(samples, 90) > 200.0


def test_ttft_counts_from_due_and_failed_is_worst():
    rows = [row([(1.25, 1), (1.3, 1)], due=1.0, sent=1.05),
            row([], due=2.0, sent=2.0, done=2.5, error="503"),
            row([], due=3.0, sent=3.0, done=None, expected=4),  # no token
            row([(7.0, 1)], due=6.5, sent=6.5)]                 # due later
    samples = arith.ttft_samples(rows, 0.0, 5.0)
    assert samples[0] == pytest.approx(250.0)
    assert samples[1:] == [arith.WORST_MS, arith.WORST_MS]
    assert arith.lateness_samples(rows, 0.0, 5.0) == [
        pytest.approx(50.0), 0.0, 0.0]


def test_tokens_in_window_counts_unfinished_requests_too():
    rows = [row([(0.5, 2), (1.5, 3), (2.5, 4)], expected=20, done=None)]
    rows[0]["done"] = None
    assert arith.tokens_in_window(rows, 1.0, 3.0) == 7
    assert arith.gap_samples(rows, 1.0, 3.0) == [
        pytest.approx(1000.0 / 3), pytest.approx(250.0)]


def test_spread_is_the_contracts():
    values = [100.0, 101.0, 99.0, 100.5, 99.5, 100.2]
    import statistics
    q = statistics.quantiles(values, n=4)
    assert arith.spread(values) == pytest.approx(
        (q[2] - q[0]) / statistics.median(values))
