"""One run of a serving cell: cluster, replica, warm-up, the cell's own
traffic through the real HTTP proxy, a window of --seconds, the program's
records of that window, parity, tear-down. Returns the run's record; the
metric readers (benchmarks/metrics) take their numbers from it."""

from __future__ import annotations

import os
import shutil
import time
from typing import Any, Dict, List

import numpy as np

from . import client, cluster, spec, traffic as traffic_mod
from .cluster import BenchFailure, say

REPLICA_WAIT_S = 1100.0


def warm_prompts(cell: spec.Cell, vocab: int, seed: int,
                 rehearse: bool) -> List[List[List[int]]]:
    """Prompts that call every prefill bucket once, then (where the traffic
    shares prefixes) one that hits the radix, so gather_pages runs too."""
    from .builders import REHEARSE_ENGINE
    engine = dict(cell.config["engine"])
    if rehearse:
        engine.update(REHEARSE_ENGINE)
    buckets = list(engine["prefill_buckets"])
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 99])
    top = buckets[-1]
    first = [rng.integers(1, vocab, size=top + b - 3).tolist()
             for b in buckets]
    rounds = [first]
    if cell.traffic.get("sharing"):
        page = engine["page_size"]
        keep = (top // page) * page
        rounds.append([first[0][:keep]
                       + rng.integers(1, vocab, size=9).tolist()])
    return rounds


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        rehearse: bool, started: float) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import serve

    from .builders import REHEARSE_MODEL
    from .replica import BenchLLMServer

    config, traffic = cell.config, dict(cell.traffic)
    vocab = REHEARSE_MODEL["vocab_size"] if rehearse \
        else config["vocab_size"]
    if rehearse:
        traffic.update(traffic.get("rehearse", {}))
    longest = traffic_mod.longest(traffic)
    if not rehearse and longest > config["engine"]["max_len"] - 2:
        raise BenchFailure(
            f"the traffic's longest request ({longest} tokens) does not "
            f"fit max_len {config['engine']['max_len']}")
    record: Dict[str, Any] = {"kind": traffic["kind"], "traffic": traffic,
                              "config": config, "seconds": seconds,
                              "rehearse": rehearse}
    trace_dir = os.path.join(cell.root, "chiprun_out", "bench_trace",
                             cell.name)
    cluster.start_cluster(cell.chips, rehearse,
                          config.get("program_settings"))
    load = None
    try:
        t = time.monotonic()
        app = serve.deployment(
            BenchLLMServer, name="bench", num_replicas=1,
            max_ongoing_requests=1024,
            ray_actor_options=cluster.actor_options(cell.chips, rehearse)
        ).bind(config, seed, rehearse)
        handle = serve.run(app, name="bench", route_prefix="/llm",
                           wait_for_ready_timeout_s=REPLICA_WAIT_S)
        ask = lambda method, *a, timeout=600.0: getattr(  # noqa: E731
            handle, method).remote(*a).result(timeout_s=timeout)
        device = ask("bench_device")
        cluster.check_device(device, cell.chips, rehearse)
        say(f"bench: replica ready in {time.monotonic() - t:.1f}s on "
            f"{device}")
        warm_s = ask("bench_warm",
                     warm_prompts(cell, vocab, seed, rehearse),
                     timeout=REPLICA_WAIT_S)
        say(f"bench: programs warm in {warm_s:.1f}s")

        load = client.Load(serve.get_http_address(), traffic,
                           traffic_mod.requests(traffic, seed, vocab),
                           vocab)
        load.start()
        time.sleep(load.ramped())
        opened = ask("bench_mark", True)
        t0 = time.monotonic()
        record["setup_s"] = t0 - started
        say(f"bench: window opens, setup_s={record['setup_s']:.1f}")
        traced_for = min(4.0, seconds / 3.0)
        if traced:
            time.sleep(max(0.0, seconds / 2.0 - traced_for / 2.0))
            shutil.rmtree(trace_dir, ignore_errors=True)
            ask("bench_trace_start", trace_dir)
            time.sleep(traced_for)
            keep = os.path.join(cell.root, "chiprun_out",
                                f"trace_events_{cell.name}.json.gz")
            record["trace"] = ask("bench_trace_stop", trace_dir, keep)
            shutil.rmtree(trace_dir, ignore_errors=True)
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        t1 = t0 + seconds
        closed = ask("bench_mark", False)
        load.no_more_requests()
        if traffic["kind"] == "open":
            wait_first_tokens(load.rows, t0, t1,
                              float(traffic.get("drain_s", 10.0)))
        load.stop()
        record.update(t0=t0, t1=t1, rows=load.rows, opened=opened,
                      closed=closed, device=device)
        if traffic["kind"] == "open":
            say_open_loop(record)
        # what was abandoned is cancelled by the proxy; then the pool must
        # balance and the engine must be idle for the parity check
        limit = time.monotonic() + 60.0
        while time.monotonic() < limit and not ask("bench_idle"):
            time.sleep(0.2)
        record["report"] = ask("bench_report", t0, t1)
        if traffic["kind"] == "open":
            say_sharing(record)
        record["parity"] = ask("bench_parity", timeout=REPLICA_WAIT_S)
        say(f"bench: parity {record['parity']}")
        serve.shutdown()
    finally:
        if load is not None:
            load.stop()
        ray_tpu.shutdown()
    cluster.wait_pid_gone(device["pid"], "replica")
    judge(record)
    return record


def wait_first_tokens(rows, t0: float, t1: float, drain_s: float) -> None:
    """Requests due in the window may still owe their first token when it
    closes: the tail is the tail of all of them, so wait (a while)."""
    limit = time.monotonic() + drain_s
    while time.monotonic() < limit and any(
            r["due"] is not None and t0 <= r["due"] < t1
            and not r["chunks"] and not r["error"] for r in rows):
        time.sleep(0.05)


def say_open_loop(record: Dict[str, Any]) -> None:
    """What a rate sweep reads (stderr): does the backlog grow?"""
    from . import arith, readers
    rows, t0, t1 = record["rows"], record["t0"], record["t1"]
    ttft = arith.ttft_samples(rows, t0, t1)
    say(f"bench: rate {record['traffic']['rate_per_s']}/s: requests sent "
        f"and not yet finished at the window's middle "
        f"{backlog(rows, (t0 + t1) / 2)}, at its end {backlog(rows, t1)}; "
        f"ttft p50 {arith.percentile(ttft, 50):.0f} p90 "
        f"{arith.percentile(ttft, 90):.0f} ms over {len(ttft)}; radix hits "
        f"{readers.stat_delta(record, 'prefix_hits'):.0f} misses "
        f"{readers.stat_delta(record, 'prefix_misses'):.0f}, radix "
        f"entries {record['closed']['stats']['prefix_entries']}")


def say_sharing(record: Dict[str, Any]) -> None:
    """Of the prompt tokens an earlier ask had already sent, the share the
    engine really took from its radix (reqtrace ADMITTED: shared_pages)."""
    page = record["report"]["page_size"]
    admitted = {rid: args for rid, event, _ts, args
                in record["report"]["events"] if event == "ADMITTED"}
    could = got = 0
    for row in record["rows"]:
        if row["id"] in admitted and row["shared_tokens"]:
            could += row["shared_tokens"]
            got += admitted[row["id"]].get("shared_pages", 0) * page
    if could:
        say(f"bench: of {could} prompt tokens an earlier ask had sent, "
            f"{100.0 * got / could:.1f}% came from the radix")


def backlog(rows, at: float) -> int:
    """Requests sent by `at` and not finished by then."""
    return sum(1 for r in rows if r["sent"] is not None and r["sent"] <= at
               and (r["done"] is None or r["done"] > at))


def judge(record: Dict[str, Any]) -> None:
    """attempted / failed / correct of a serving run."""
    from . import arith
    t0, t1 = record["t0"], record["t1"]
    rows = [r for r in record["rows"]
            if r["sent"] is not None and r["sent"] < t1
            and (r["done"] is None or r["done"] >= t0)]
    bad = [r for r in rows if arith.failed(r)]
    final = record["report"]["final"]
    window_compiles = record["closed"]["compile"].get("compiles", 0) \
        - record["opened"]["compile"].get("compiles", 0)
    record["compiles_in_window"] = window_compiles
    reasons = []
    if bad:
        reasons.append(f"{len(bad)} requests failed, e.g. {bad[0]['error']}")
    if final["stats"]["leaked_pages"]:
        reasons.append(f"{final['stats']['leaked_pages']} leaked pages")
    if window_compiles:
        reasons.append(f"{window_compiles} compiles inside the window")
    if not record["parity"]["ok"]:
        reasons.append(f"parity failed: {record['parity']}")
    if not any(r["done"] is not None and t0 <= r["done"] < t1
               for r in rows):
        reasons.append("no request finished inside the window")
    record["attempted"] = len(rows)
    record["failed"] = len(bad)
    record["correct"] = not reasons
    record["reasons"] = reasons
