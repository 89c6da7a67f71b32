"""The load generator: one thread, one asyncio loop, streamed HTTP. JAX-free.

It plays a traffic mix (traffic.py) against ``POST /api/generate`` and
keeps one record per request, all on this process's monotonic clock:
when the request was due, when it was sent, when each streamed chunk was
read and how many tokens it carried. Under the benchmark tokenizer
(serve_cell.py) one streamed character is one token.

A run has a ramp, a window and a drain. Sessions are due from the start
of the ramp to the end of the window; afterwards nothing new is started
and the requests that are out are awaited. Which requests count is
metrics.py's business: this module only records.

Copied in shape from p2p_llm_chat_tpu/loadgen/driver.py (a schedule
fixed before the run, fired open loop, lag recorded), with threads
replaced by one event loop so that 64 streams do not fight over the
interpreter lock and the host's cores.
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field
from typing import Optional

from . import traffic as traffic_mod

REQUEST_TIMEOUT_S = 120.0


@dataclass
class Record:
    """What the client saw of one request. Times are seconds on the
    client's monotonic clock, relative to the run's start."""

    session: int
    turn: int
    due_t: float
    prompt_bytes: int
    num_predict: int
    trace_id: str = ""
    send_t: Optional[float] = None
    status: int = 0                 # HTTP status; 0 = no answer
    error: str = ""
    chunk_t: list = field(default_factory=list)      # read time per chunk
    chunk_tokens: list = field(default_factory=list)  # tokens per chunk
    end_t: Optional[float] = None   # the done record was read
    final: dict = field(default_factory=dict)   # the done record's counts
    text: str = ""                  # kept only when asked (probes)

    @property
    def ok(self) -> bool:
        return (self.status == 200 and not self.error
                and self.end_t is not None and bool(self.chunk_t))

    @property
    def tokens(self) -> int:
        return sum(self.chunk_tokens)


async def stream_generate(host: str, port: int, rec: Record, body: dict,
                          t0: float, keep_text: bool = False) -> None:
    """Send one streamed request and fill ``rec`` as the chunks arrive."""
    payload = json.dumps(body).encode()
    head = ["POST /api/generate HTTP/1.1", f"Host: {host}:{port}",
            "Content-Type: application/json",
            f"Content-Length: {len(payload)}", "Connection: close"]
    if rec.trace_id:
        head.append(f"X-Graft-Trace: {rec.trace_id};s=1")
    writer = None
    try:
        rec.send_t = time.monotonic() - t0
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + payload)
        await writer.drain()
        status_line = await reader.readline()
        parts = status_line.split()
        rec.status = int(parts[1]) if len(parts) >= 2 else 0
        while True:                     # headers
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
        if rec.status != 200:
            rec.error = (await reader.read(400)).decode("utf-8", "replace")
            return
        # Chunked NDJSON: size lines and blank lines sit between the
        # records; a record is the line that starts with a brace.
        while True:
            line = await reader.readline()
            if not line:
                if rec.end_t is None:
                    rec.error = "stream ended without a done record"
                return
            if line[:1] != b"{":
                continue
            now = time.monotonic() - t0
            obj = json.loads(line)
            if "error" in obj:
                rec.error = str(obj["error"])
                return
            if obj.get("done"):
                rec.end_t = now
                rec.final = {k: obj.get(k) for k in (
                    "prompt_eval_count", "prompt_eval_duration",
                    "eval_count", "eval_duration", "total_duration")}
                return
            delta = obj.get("response", "")
            if delta:
                rec.chunk_t.append(now)
                rec.chunk_tokens.append(len(delta))
                if keep_text:
                    rec.text += delta
    except (OSError, ValueError, asyncio.IncompleteReadError) as e:
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()
            try:
                await writer.wait_closed()
            except OSError:
                pass


def request_body(turn: traffic_mod.Turn, traffic: dict) -> dict:
    opts = dict(traffic.get("options", {}))
    opts["num_predict"] = turn.num_predict
    return {"prompt": turn.prompt, "stream": True, "options": opts}


class Run:
    """One run of a traffic mix. ``records`` grows as requests are
    started; read it after :meth:`play` returns."""

    def __init__(self, host: str, port: int, traffic: dict, seed: int,
                 ramp_s: float, window_s: float, traced: bool = False,
                 drain_s: float = 90.0) -> None:
        self.host, self.port = host, port
        self.traffic, self.seed = traffic, seed
        self.ramp_s, self.window_s = ramp_s, window_s
        self.traced = traced
        self.drain_s = drain_s
        self.records: list = []
        self.t0 = 0.0               # monotonic time of the run's start
        self.drained = True         # every started request ended in time

    @property
    def stop_t(self) -> float:
        return self.ramp_s + self.window_s

    async def _session(self, index: int, due_t: float) -> None:
        sess = traffic_mod.make_session(self.traffic, self.seed, index)
        for k, turn in enumerate(sess.turns):
            if k:
                due_t = (time.monotonic() - self.t0) + turn.think_s
                if due_t >= self.stop_t:
                    return
                await asyncio.sleep(turn.think_s)
            rec = Record(session=index, turn=k, due_t=due_t,
                         prompt_bytes=len(turn.prompt),
                         num_predict=turn.num_predict,
                         trace_id=(f"{self.seed & 0xffffffff:08x}"
                                   f"{index:016x}{k:08x}"
                                   if self.traced else ""))
            self.records.append(rec)
            try:
                await asyncio.wait_for(
                    stream_generate(self.host, self.port, rec,
                                    request_body(turn, self.traffic),
                                    self.t0),
                    timeout=REQUEST_TIMEOUT_S)
            except asyncio.TimeoutError:
                rec.error = f"no end after {REQUEST_TIMEOUT_S:.0f} s"
            if not rec.ok:
                # A dead server must not turn a closed loop into a spin.
                await asyncio.sleep(0.05)
                return

    async def _open_loop(self) -> list:
        times = traffic_mod.arrival_times(self.traffic, self.seed,
                                          self.stop_t)
        tasks = []
        for i, due in enumerate(times):
            delay = self.t0 + due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(self._session(i, due)))
        return tasks

    async def _closed_loop(self) -> list:
        counter = iter(range(1 << 62))

        async def client() -> None:
            while True:
                now = time.monotonic() - self.t0
                if now >= self.stop_t:
                    return
                await self._session(next(counter), now)

        return [asyncio.ensure_future(client())
                for _ in range(int(self.traffic["clients"]))]

    async def _play(self) -> None:
        self.t0 = time.monotonic()
        loop = self.traffic["loop"]
        if loop == "open":
            tasks = await self._open_loop()
        elif loop == "closed":
            tasks = await self._closed_loop()
        else:
            raise ValueError(f"unknown loop kind {loop!r}")
        if tasks:
            deadline = self.t0 + self.stop_t + self.drain_s
            _, pending = await asyncio.wait(
                tasks, timeout=max(0.1, deadline - time.monotonic()))
            for t in pending:
                self.drained = False
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)

    def play(self) -> list:
        asyncio.run(self._play())
        return self.records


def send_alone(host: str, port: int, prompt: str, num_predict: int,
               options: Optional[dict] = None) -> Record:
    """One request with nothing else in flight (the probes)."""
    rec = Record(session=-1, turn=0, due_t=0.0, prompt_bytes=len(prompt),
                 num_predict=num_predict)
    body = {"prompt": prompt, "stream": True,
            "options": {**(options or {}), "num_predict": num_predict}}

    async def go() -> None:
        await asyncio.wait_for(
            stream_generate(host, port, rec, body, time.monotonic(),
                            keep_text=True), timeout=REQUEST_TIMEOUT_S)
    asyncio.run(go())
    return rec
