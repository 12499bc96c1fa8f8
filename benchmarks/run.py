"""One command, one cell, one run:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

A new process each time. It stays off JAX (the replica or the train worker
holds the chip), starts the cluster, loads, warms up, measures for
--seconds, prints one JSON object as the last line of stdout, tears down
and exits. A run that finds no TPU fails: nothing falls back to the CPU.
`--rehearse` runs the same control flow at toy sizes on the CPU for the
harness's own tests and prints no result line.
"""

from __future__ import annotations

import time

STARTED = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# stdout carries the result line only; whatever else prints here (relayed
# worker logs) goes to stderr
OUT = sys.stdout
sys.stdout = sys.stderr


def result_line(cell, record, traced: bool) -> dict:
    from benchmarks.harness import readers, trace as trace_mod
    metrics = {}
    for metric in cell.metrics(traced):
        try:
            value = cell.reader(metric["name"])(record)
        except KeyError:
            if not record["rehearse"]:
                raise
            value = None   # the CPU has no row in the peaks table
        if value is not None:
            metrics[metric["name"]] = {"value": float(value),
                                       "unit": metric["unit"]}
    device = {k: record["device"][k] for k in ("platform", "kind", "count")}
    device["memory_peak_bytes"] = readers.memory_peak_bytes(record) or 0
    line = {"correct": bool(record["correct"]),
            "attempted": record["attempted"], "failed": record["failed"],
            "metrics": metrics, "device": device}
    reduced = readers.trace_of(record)
    if traced and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = trace_mod.breakdown(reduced)
    if record["reasons"]:
        line["reasons"] = record["reasons"]
    return line


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU, toy sizes, same control flow; no result")
    parser.add_argument("--set", action="append", default=[],
                        metavar="KEY=JSON", help="override a key of the "
                        "traffic file for an exploratory run (the rate "
                        "sweep); the driver never passes it")
    parser.add_argument("--root", default=ROOT,
                        help="checkout whose BENCHMARK.json names the cell")
    args = parser.parse_args()

    from benchmarks.harness import cluster, spec
    cell = spec.Cell(args.root, args.workload)
    for item in args.set:
        key, _, value = item.partition("=")
        cell.traffic[key] = json.loads(value)
    seconds = args.seconds if args.seconds is not None \
        else float(cell.benchmark["run_seconds"])
    cache = cluster.prepare_environment(ROOT, args.rehearse)
    cluster.say(f"bench: {cell.name} seed={args.seed} seconds={seconds} "
                f"trace={args.trace} cache={cache}")
    record = cell.driver()(cell, args.seed, seconds, bool(args.trace),
                           args.rehearse, STARTED)
    line = result_line(cell, record, bool(args.trace))
    if args.rehearse:
        cluster.say("bench: rehearsal (never a result): " + json.dumps(line))
        return 3
    print(json.dumps(line), file=OUT, flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException as e:  # noqa: BLE001 — report, exit non-zero
        import traceback
        traceback.print_exc()
        print(f"bench: FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        code = 1
    OUT.flush()
    sys.stderr.flush()
    # every cluster was shut down and every worker waited for above; leave
    # without waiting on whatever daemon threads the runtime still holds
    os._exit(code)
