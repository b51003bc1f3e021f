"""The service in this process, and a plain HTTP client for it.

A copy of ``chip_smoke.py::Service`` (the yardstick keeps its own: later PRs
may change the program's file, not this one).  ``serve/app.py::main`` minus
``web.run_app``'s blocking loop: the aiohttp app runs on its own event-loop
thread and the caller is the client — one process, one owner of the chip.
Requests go over real HTTP on a loopback port.
"""

from __future__ import annotations

import asyncio
import json
import socket
import threading
import urllib.error
import urllib.request


class ServiceError(RuntimeError):
    """A request to the service did not answer as the benchmark needs."""


class Service:
    def __init__(self):
        from aiohttp import web
        from penroz_tpu.parallel import dist
        from penroz_tpu.serve import app as app_mod
        app_mod._configure_logging()
        app_mod._configure_compile_cache()
        dist.initialize()
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self._loop = asyncio.new_event_loop()
        self._runner = web.AppRunner(app_mod.create_app())
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self._loop)
            self._loop.run_until_complete(self._runner.setup())
            self._loop.run_until_complete(
                web.TCPSite(self._runner, "127.0.0.1", self.port).start())
            started.set()
            self._loop.run_forever()

        self._thread = threading.Thread(target=run, name="bench-server",
                                        daemon=True)
        self._thread.start()
        if not started.wait(60):
            raise ServiceError("server did not start within 60 s")
        self.base = f"http://127.0.0.1:{self.port}"

    def stop(self):
        from penroz_tpu.serve import decode_scheduler
        decode_scheduler.reset()
        fut = asyncio.run_coroutine_threadsafe(self._runner.cleanup(),
                                               self._loop)
        fut.result(timeout=120)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=30)

    def call(self, method: str, path: str, body=None, timeout: float = 900):
        """(status, parsed JSON or text)."""
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path, data=data, method=method,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                status, raw = resp.status, resp.read()
        except urllib.error.HTTPError as e:
            status, raw = e.code, e.read()
        text = raw.decode(errors="replace")
        try:
            return status, json.loads(text)
        except ValueError:
            return status, text

    def ok(self, method: str, path: str, body=None, expect=200, **kw):
        status, payload = self.call(method, path, body, **kw)
        if status != expect:
            raise ServiceError(f"{method} {path} -> {status} (wanted "
                               f"{expect}): {str(payload)[:500]}")
        return payload
