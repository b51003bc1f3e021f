"""Open-loop load from one thread: an asyncio client that sends each request
when it is due, whatever the earlier ones are doing, and timestamps every
streamed token as it reaches the client."""

from __future__ import annotations

import asyncio
import json
import threading
import time


class LoadGen:
    def __init__(self, base: str, model_id: str, block: int):
        self.base, self.model_id, self.block = base, model_id, block
        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(target=self._loop.run_forever,
                                        name="bench-client", daemon=True)
        self._thread.start()
        self._session = self._call(self._open())

    async def _open(self):
        import aiohttp
        return aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None))

    def _call(self, coro, timeout=None):
        return asyncio.run_coroutine_threadsafe(coro, self._loop).result(
            timeout)

    def close(self):
        self._call(self._session.close(), 30)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(30)

    # -- one request --------------------------------------------------------

    async def _send(self, req, first_token: asyncio.Event | None = None):
        body = json.dumps({
            "model_id": self.model_id, "input": [req.prompt],
            "block_size": self.block, "max_new_tokens": req.max_new,
            "temperature": 0.0, "stream": True}).encode()
        req.sent_at = time.monotonic()
        try:
            async with self._session.post(
                    self.base + "/generate/", data=body,
                    headers={"Content-Type": "application/json"}) as resp:
                req.status = resp.status
                if resp.status != 200:
                    req.error = (await resp.text())[:200]
                    return
                async for line in resp.content:
                    now = time.monotonic()
                    text = line.strip()
                    if not text:
                        continue
                    if not text.lstrip(b"-").isdigit():
                        req.error = text.decode(errors="replace")[:200]
                        break
                    req.tokens.append(int(text))
                    req.token_at.append(now)
                    if first_token is not None:
                        first_token.set()
        except Exception as exc:  # noqa: BLE001 — a failed request is a sample
            req.status = req.status or -1
            req.error = f"{type(exc).__name__}: {exc}"[:200]
        finally:
            if first_token is not None:
                first_token.set()

    # -- a schedule ---------------------------------------------------------

    async def _run(self, requests, t0: float):
        async def at_due(req):
            req.due_at = t0 + req.due
            delay = req.due_at - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            await self._send(req)
        await asyncio.gather(*(at_due(r) for r in requests))

    def start(self, requests, t0: float):
        """Begin sending ``requests`` (due relative to ``t0``); returns a
        future that completes when every one has ended."""
        return asyncio.run_coroutine_threadsafe(self._run(requests, t0),
                                                self._loop)

    # -- a warm-up wave -----------------------------------------------------

    async def _wave(self, first, then, wait):
        events = [asyncio.Event() for _ in first]
        now = time.monotonic()
        for r in first + then:
            r.due_at = now
        tasks = [asyncio.ensure_future(self._send(r, e))
                 for r, e in zip(first, events)]
        if wait == "first_token":
            for e in events:
                await e.wait()
        elif first:
            await asyncio.sleep(float(wait) / 1000.0)
        tasks += [asyncio.ensure_future(self._send(r)) for r in then]
        await asyncio.gather(*tasks)

    def wave(self, first, then, wait, timeout: float = 1100):
        """Send ``first`` together, wait (for each one's first token, or
        ``wait`` milliseconds), send ``then`` together, wait for all."""
        self._call(self._wave(first, then, wait), timeout)
