"""The load generator: streamed HTTP requests through the serve proxy, from
one thread with one event loop. Every token-bearing chunk is stamped on
arrival; the numbers are worked out afterwards from that log (arith.py).

Copied in spirit from perf_workloads._sat_stream_once (a raw socket, chunked
ndjson), which reads the whole body before it looks at it and so cannot time
a chunk."""

from __future__ import annotations

import asyncio
import json
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

from .traffic import Request


async def stream_one(host: str, port: int, request: Request,
                     row: Dict[str, Any], vocab: int,
                     route: str = "/llm") -> None:
    """One streamed generation; fills `row` (see arith.py) as it goes."""
    body = json.dumps({"prompt_tokens": request.prompt,
                       "max_new_tokens": request.max_new,
                       "stream": True, "temperature": 0.0}).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        row["sent"] = time.monotonic()
        writer.write((f"POST {route} HTTP/1.1\r\nHost: bench\r\n"
                      f"X-RTPU-Request-Id: {row['id']}\r\n"
                      f"Content-Length: {len(body)}\r\n"
                      "Connection: close\r\n\r\n").encode() + body)
        await writer.drain()
        status = await reader.readline()
        if b" 200" not in status:
            rest = await reader.read(300)
            raise RuntimeError(f"{status!r} {rest[:200]!r}")
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        while True:
            size = await reader.readline()
            if not size:
                raise RuntimeError("stream cut before its last chunk")
            n = int(size.strip() or b"0", 16)
            if n == 0:
                break
            data = await reader.readexactly(n + 2)
            at = time.monotonic()
            got = 0
            for line in data.splitlines():
                if not line.strip():
                    continue
                record = json.loads(line)
                tokens = record.get("tokens", [])
                got += len(tokens)
                if any(not 0 <= t < vocab for t in tokens):
                    row["error"] = f"token id out of range in {tokens}"
                if record.get("error"):
                    row["error"] = str(record["error"])
            if got:
                row["chunks"].append((at, got))
        row["done"] = time.monotonic()
    except asyncio.CancelledError:
        raise
    except Exception as e:  # noqa: BLE001 — a failed request is a result
        row["error"] = f"{type(e).__name__}: {e}"
        row["done"] = time.monotonic()
    finally:
        if writer is not None:
            writer.close()


class Load:
    """Runs a traffic file's closed or open loop on a thread of its own.
    `rows` grows as requests are sent; read it after `stop()`."""

    def __init__(self, address: str, traffic: Dict[str, Any],
                 stream: Iterator[Request], vocab: int, tag: str = "r"):
        host, port = address.replace("http://", "").rsplit(":", 1)
        self.host, self.port = host, int(port)
        self.traffic = traffic
        self.stream = stream
        self.vocab = vocab
        self.tag = tag
        self.rows: List[Dict[str, Any]] = []
        self.started_at: Optional[float] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._no_more = False
        self._thread = threading.Thread(target=self._main, daemon=True,
                                        name="bench-load")
        self._tasks: set = set()
        self._ready = threading.Event()
        self._finished = threading.Event()

    # -- control, from the driver's thread ---------------------------------

    def start(self) -> None:
        self._thread.start()
        self._ready.wait()

    def ramped(self) -> float:
        """Seconds after start() at which the load is whole."""
        if self.traffic["kind"] == "closed":
            return (int(self.traffic["clients"])
                    * float(self.traffic.get("client_start_gap_s", 0.1))
                    + float(self.traffic.get("settle_s", 4.0)))
        return float(self.traffic.get("lead_in_s", 8.0))

    def no_more_requests(self) -> None:
        self._no_more = True

    def stop(self) -> None:
        """Abandon what is in flight (the proxy cancels on disconnect)."""
        self._no_more = True
        loop = self._loop
        if loop is not None and not self._finished.is_set():
            loop.call_soon_threadsafe(self._cancel_all)
        self._thread.join(timeout=30)

    def _cancel_all(self) -> None:
        for task in list(self._tasks):
            task.cancel()

    # -- the loop -----------------------------------------------------------

    def _main(self) -> None:
        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)
        try:
            self._loop.run_until_complete(self._run())
        finally:
            self._finished.set()
            self._loop.close()

    def _row(self, request: Request, due: Optional[float]) -> Dict[str, Any]:
        row = {"id": f"{self.tag}{request.index}", "index": request.index,
               "due": due, "sent": None, "chunks": [], "done": None,
               "error": None, "expected": request.max_new,
               "prompt_tokens": len(request.prompt),
               "shared_tokens": request.shared_tokens}
        self.rows.append(row)
        return row

    async def _run(self) -> None:
        self.started_at = time.monotonic()
        self._ready.set()
        runner = self._closed if self.traffic["kind"] == "closed" \
            else self._open
        try:
            await runner()
        except asyncio.CancelledError:
            pass
        await asyncio.gather(*self._tasks, return_exceptions=True)

    def _spawn(self, coro) -> None:
        task = asyncio.ensure_future(coro)
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)

    async def _closed(self) -> None:
        gap = float(self.traffic.get("client_start_gap_s", 0.1))

        async def caller():
            while not self._no_more:
                request = next(self.stream)
                row = self._row(request, None)
                await stream_one(self.host, self.port, request, row,
                                 self.vocab)

        # callers start one by one: 64 at once would admit 32 prefills
        # together, each with a dense cache of its own
        for _ in range(int(self.traffic["clients"])):
            self._spawn(caller())
            await asyncio.sleep(gap)
        while self._tasks and not self._no_more:
            await asyncio.sleep(0.05)

    async def _open(self) -> None:
        while not self._no_more:
            request = next(self.stream)
            due = self.started_at + request.due_s
            wait = due - time.monotonic()
            if wait > 0:
                await asyncio.sleep(wait)
            if self._no_more:
                break
            row = self._row(request, due)
            self._spawn(stream_one(self.host, self.port, request, row,
                                   self.vocab))
