"""From the profiler's .xplane.pb to numbers: device busy and idle, device
time per jitted program and per named kernel, idle gaps by the host span
they fall in, exposed collective time. Kept with the benchmark so that
every PR computes the same number in the same way.

The reduction works on a plain structure, so it can be checked against a
small recorded trace (fixtures/) with no profiler at hand:

  {"planes": [{"name": str, "lines": [{"name": str,
               "events": [[name, start_ns, duration_ns], ...]}]}]}
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter", "send", "recv")
# host spans the benchmark's replica and train loop write
OUTER_SPANS = ("engine_step", "train_step")


def load_xplane(path: str) -> Dict[str, Any]:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def start(directory: str) -> None:
    """Start the profiler with host spans (TraceAnnotation) on and the
    Python tracer off: spans, not every frame."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=options)


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def save(trace: Dict[str, Any], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def load(path: str) -> Dict[str, Any]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def cut(trace: Dict[str, Any], t0: int, t1: int,
        keep_host: Iterable[str] = ()) -> Dict[str, Any]:
    """The events that start in [t0, t1): all of the device planes', and
    of the host planes' only those whose name starts with a kept prefix."""
    keep_host = tuple(keep_host)
    planes = []
    for plane in trace["planes"]:
        device = is_device(plane["name"])
        lines = []
        for line in plane["lines"]:
            # an op's name without its HLO text ("%copy.3 = bf16[...")
            events = [[e[0].split(" = ")[0], e[1], e[2]]
                      for e in line["events"] if t0 <= e[1] < t1
                      and (device or e[0].startswith(keep_host))]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


# -- intervals ---------------------------------------------------------------

def is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (merged) intervals a that no (merged) interval of b
    covers."""
    out = []
    j = 0
    for start, end in a:
        at = start
        while j < len(b) and b[j][1] <= at:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < end:
            out.append((at, end))
    return out


def _line(plane: Dict[str, Any], name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def clean(name: str) -> str:
    """'jit_decode_step(123)' -> 'decode_step'; 'fusion.42' -> 'fusion';
    '%copy.3 = ...' -> 'copy'."""
    name = name.strip().lstrip("%").split(" ", 1)[0]
    name = re.sub(r"\(.*\)$", "", name)
    name = re.sub(r"^(jit|pjit)_+", "", name)
    name = re.sub(r"[.\-_]\d+$", "", name)
    return re.sub(r"\.\d+$", "", name)


def is_collective(op: str) -> bool:
    return op.startswith(COLLECTIVES)


# -- the reduction -------------------------------------------------------------

def device_planes(trace: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [p for p in trace["planes"] if is_device(p["name"])
            and _line(p, "XLA Ops")]


def host_spans(trace: Dict[str, Any]) -> List[list]:
    """The benchmark's own spans (engine_step, train_step, dispatch:*,
    data_wait, fetch_loss), wherever the host wrote them."""
    out = []
    for plane in trace["planes"]:
        if is_device(plane["name"]):
            continue
        for line in plane["lines"]:
            for e in line["events"]:
                if e[0] in OUTER_SPANS or e[0].startswith(
                        ("dispatch:", "data_wait", "fetch_loss")):
                    out.append(e)
    out.sort(key=lambda e: e[1])
    return out


def reduce(trace: Dict[str, Any]) -> Dict[str, Any]:
    planes = device_planes(trace)
    if not planes:
        return {}
    starts = [e[1] for p in planes for e in _line(p, "XLA Ops")]
    ends = [e[1] + e[2] for p in planes for e in _line(p, "XLA Ops")]
    t0, t1 = min(starts), max(ends)
    window = t1 - t0
    busy = []
    exposed = []
    programs: Dict[str, List[int]] = {}
    ops: Dict[str, int] = {}
    op_calls: Dict[str, int] = {}
    inside: Dict[str, int] = {}
    for plane in planes:
        events = _line(plane, "XLA Ops")
        modules = sorted(_line(plane, "XLA Modules"), key=lambda e: e[1])
        module_starts = [m[1] for m in modules]
        compute, collective = [], []
        for name, start, dur in events:
            op = clean(name)
            (collective if is_collective(op) else compute).append(
                (start, start + dur))
            ops[op] = ops.get(op, 0) + dur
            op_calls[op] = op_calls.get(op, 0) + 1
            i = bisect.bisect_right(module_starts, start) - 1
            if i >= 0 and start < modules[i][1] + modules[i][2]:
                key = clean(modules[i][0]) + "/" + op
                inside[key] = inside.get(key, 0) + dur
        compute = union(compute)
        busy.append(total(union(compute + collective)))
        exposed.append(total(subtract(union(collective), compute)))
        for name, start, dur in modules:
            programs.setdefault(clean(name), []).append(dur)
    n = len(planes)
    # idle gaps of the first device, by what the host was doing
    first = planes[0]
    gaps = subtract([(t0, t1)], union(
        (s, s + d) for _, s, d in _line(first, "XLA Ops")))
    spans = host_spans(trace)
    outer = [(s, s + d) for nm, s, d in spans if nm in OUTER_SPANS]
    outer_name = {s: nm for nm, s, d in spans if nm in OUTER_SPANS}
    inner = [(s, s + d, nm) for nm, s, d in spans if nm not in OUTER_SPANS]
    idle: Dict[str, int] = {}
    for a, b in gaps:
        for (x, y), label in _label_gap(a, b, outer, outer_name, inner):
            idle[label] = idle.get(label, 0) + (y - x)
    return {
        "devices": n,
        "window_s": window / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "collective_exposed_s": sum(exposed) / n / 1e9,
        "programs": {k: {"calls": len(v) // n or len(v),
                         "total_s": sum(v) / n / 1e9,
                         "median_ms": sorted(v)[len(v) // 2] / 1e6}
                     for k, v in programs.items()},
        "ops": {k: {"calls": op_calls[k] // n or op_calls[k],
                    "total_s": v / n / 1e9} for k, v in ops.items()},
        "inside": {k: v / n / 1e9 for k, v in inside.items()},
        "idle": {k: v / 1e9 for k, v in idle.items()},
        "host_spans": len(spans),
    }


def _label_gap(a: int, b: int, outer, outer_name, inner):
    """Split the idle gap [a, b) by the host spans over it."""
    pieces = []
    covered = []
    for s, e, name in inner:
        if e <= a or s >= b:
            continue
        x, y = max(a, s), min(b, e)
        covered.append((x, y))
        pieces.append(((x, y), _outer_of(x, outer, outer_name) + name))
    rest = subtract([(a, b)], union(covered))
    for x, y in rest:
        at = x
        for s, e in outer:
            if e <= at or s >= y:
                continue
            if s > at:
                pieces.append(((at, s), "outside_any_span"))
            top = min(y, e)
            pieces.append(((max(at, s), top), outer_name[s] + "/host"))
            at = top
        if at < y:
            pieces.append(((at, y), "outside_any_span"))
    return pieces


def _outer_of(at: int, outer, outer_name) -> str:
    for s, e in outer:
        if s <= at < e:
            return outer_name[s] + "/"
    return ""


def breakdown(reduced: Dict[str, Any]) -> Dict[str, list]:
    """The contract's `breakdown`: device time by jitted program, then the
    largest operations inside each; idle gaps by host span."""
    programs = sorted(((k, v["total_s"])
                       for k, v in reduced.get("programs", {}).items()),
                      key=lambda kv: -kv[1])[:4]
    inside = sorted(reduced.get("inside", {}).items(),
                    key=lambda kv: -kv[1])[:10 - len(programs)]
    idle = sorted(((k, v) for k, v in reduced.get("idle", {}).items()
                   if v >= 1e-4), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in programs + inside][:10],
            "idle_gaps": [[k, v] for k, v in idle][:10]}


def reduce_directory(directory: str,
                     keep_events: Optional[str] = None) -> Dict[str, Any]:
    """Reduce the newest trace under `directory`; optionally keep a cut of
    its events (a second and a half from the middle) as a fixture."""
    trace = load_xplane(find_xplane(directory))
    reduced = reduce(trace)
    reduced["plane_names"] = [
        [p["name"], [[ln["name"], len(ln["events"])] for ln in p["lines"]]]
        for p in trace["planes"]]
    if keep_events and device_planes(trace):
        ops = _line(device_planes(trace)[0], "XLA Ops")
        middle = (ops[0][1] + ops[-1][1]) // 2
        os.makedirs(os.path.dirname(keep_events), exist_ok=True)
        save(cut(trace, middle - 200_000_000, middle + 200_000_000,
                 keep_host=OUTER_SPANS + ("dispatch:", "data_wait",
                                          "fetch_loss", "PjitFunction")),
             keep_events)
    return reduced
