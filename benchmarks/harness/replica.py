"""The benchmark's own replica: LLMServer as the program ships it, plus what
a measurement needs from inside the process that holds the chip — marks of
the program's counters at the window's edges, a per-tick log, the profiler,
host spans around the calls into each layer, and the parity check against
the plain reference. It changes nothing the engine computes or schedules."""

from __future__ import annotations

import asyncio
import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.llm.serving import LLMServer

from . import spec

PROGRAMS = ("_decode", "_chunk_prefill", "_dense_zero_caches",
            "_write_pages", "_gather_pages")


class _Dispatch:
    """A jitted program with a host span around each call (its other
    attributes, such as .lower, pass through)."""

    def __init__(self, fn, name: str):
        self._fn = fn
        self._name = name

    def __call__(self, *args, **kwargs):
        import jax
        with jax.profiler.TraceAnnotation(self._name):
            return self._fn(*args, **kwargs)

    def __getattr__(self, item):
        return getattr(self._fn, item)


class BenchLLMServer(LLMServer):
    def __init__(self, config: Dict[str, Any], seed: int,
                 rehearse: bool = False):
        self._bench_config = config
        self._bench_seed = seed
        self._rehearse = rehearse
        engine_config = spec.resolve(config["builder"])(
            config, seed, rehearse)
        super().__init__(engine_config)
        self._ticks: List[tuple] = []
        self._logging = False
        self._jobs: collections.deque = collections.deque()
        self._jobs_lock = threading.Lock()
        self._instrument()

    # -- spans and the per-tick log -----------------------------------------

    def _instrument(self) -> None:
        import jax
        engine = self._engine
        for name in PROGRAMS:
            setattr(engine, name, _Dispatch(
                getattr(engine, name), "dispatch:" + name.lstrip("_")))
        step = engine.step
        page = engine.config.page_size

        def traced_step():
            self._run_jobs()
            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation("engine_step"):
                out = step()
            if self._logging:
                decode = prefill = context = 0
                for s in engine.seqs:
                    if s.request is not None:
                        if s.phase == "decode":
                            decode += 1
                            context += -(-s.length // page) * page
                        else:
                            prefill += 1
                self._ticks.append((t0, time.monotonic(),
                                    engine.pool.num_free(), decode,
                                    prefill, context))
            return out

        engine.step = traced_step

    def _run_jobs(self) -> None:
        while True:
            with self._jobs_lock:
                if not self._jobs:
                    return
                job = self._jobs.popleft()
            job()

    def _between_steps(self, fn):
        """Run fn where no engine step runs: on the stepping thread before
        its next step, or here at once if the engine is idle."""
        done = threading.Event()
        box: Dict[str, Any] = {}

        def job():
            try:
                box["value"] = fn()
            except Exception as e:  # noqa: BLE001 — re-raised below
                box["error"] = e
            done.set()

        with self._jobs_lock:
            self._jobs.append(job)
        while not done.wait(0.25):
            if not self._engine.has_work():
                with self._jobs_lock:
                    mine = job in self._jobs
                    if mine:
                        self._jobs.remove(job)
                if mine:
                    job()
        if "error" in box:
            raise box["error"]
        return box["value"]

    async def _off_loop(self, fn, *args):
        return await asyncio.get_running_loop().run_in_executor(
            None, fn, *args)

    # -- what the driver calls ------------------------------------------------

    async def bench_device(self) -> Dict[str, Any]:
        def probe():
            import jax
            devices = jax.devices()
            return {"pid": os.getpid(), "platform": devices[0].platform,
                    "kind": devices[0].device_kind, "count": len(devices)}
        return await self._off_loop(probe)

    async def bench_idle(self) -> bool:
        return not self._engine.has_work()

    async def bench_warm(self, prompts: List[List[List[int]]]) -> float:
        """Every program the cell's traffic will call, before any request:
        each round of prompts runs to its end (a later round can hit the
        radix entries an earlier one left)."""
        def warm():
            t0 = time.monotonic()
            for round_ in prompts:
                self._engine.generate(round_, max_new_tokens=3)
            return time.monotonic() - t0
        return await self._off_loop(warm)

    def _mark(self) -> Dict[str, Any]:
        from ray_tpu._internal import accel
        from ray_tpu.llm import reqtrace
        engine = self._engine
        stats = engine.stats()
        return {"t": time.monotonic(),
                "stats": {k: stats[k] for k in (
                    "steps", "tokens_generated", "prefix_hits",
                    "prefix_misses", "preemptions", "free_pages",
                    "leaked_pages", "active", "pending",
                    "prefix_entries")},
                "steps": accel.step_summary(),
                "compile": accel.compile_summary(),
                "events": len(reqtrace.events())}

    async def bench_mark(self, logging: Optional[bool] = None):
        def mark():
            out = self._between_steps(self._mark)
            if logging is not None:
                self._logging = logging
            return out
        return await self._off_loop(mark)

    async def bench_report(self, t0: float, t1: float) -> Dict[str, Any]:
        """The program's own records of the window [t0, t1)."""
        def report():
            import jax
            from ray_tpu.llm import reqtrace
            events = [[rid, event, ts, args]
                      for rid, event, ts, args in reqtrace.events()
                      if t0 <= ts < t1 + 30.0]
            cfg = self._engine.config
            return {"ticks": [t for t in self._ticks if t0 <= t[0] < t1],
                    "events": events,
                    "max_batch": cfg.max_batch,
                    "num_pages": cfg.num_pages,
                    "page_size": cfg.page_size,
                    "memory": [d.memory_stats() for d in jax.devices()],
                    "final": self._between_steps(self._mark)}
        return await self._off_loop(report)

    async def bench_trace_start(self, directory: str) -> None:
        def start():
            from . import trace
            trace.start(directory)
            self._trace_began = time.monotonic()
        return await self._off_loop(start)

    async def bench_trace_stop(self, directory: str,
                               keep_events: Optional[str] = None):
        def stop():
            import jax
            from . import trace
            jax.profiler.stop_trace()
            ended = time.monotonic()
            reduced = trace.reduce_directory(directory, keep_events)
            reduced["host_began"] = self._trace_began
            reduced["host_ended"] = ended
            return reduced
        return await self._off_loop(stop)

    async def bench_parity(self) -> Dict[str, Any]:
        from . import parity
        return await self._off_loop(
            lambda: self._between_steps(
                lambda: parity.serve(self._engine, self._bench_config,
                                     self._bench_seed)))
