"""REST API service (aiohttp) — same 15-route surface as the reference
FastAPI app (main.py:310-496), same semantics:

- per-id asyncio locks with 409 on conflict for /import/, /dataset/ download
  and /train/;
- 202 + background task for /dataset/ download and /train/;
- gzip request-body decompression middleware;
- KeyError→404, ValueError→400, validation→422, anything else→500;
- /generate/ streaming one token per line.

TPU-specific design: /train/ runs in a worker thread of this process rather
than forking a DDP process tree (main.py:461-464) — a single process owns the
TPU runtime and per-chip parallelism lives inside the compiled program.
Training still checkpoints through /dev/shm, so /progress/ polls observe it
exactly as they do in the reference.
"""

from __future__ import annotations

import asyncio
import gzip
import json
import logging
import os
import re
from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np
import pydantic
from aiohttp import web

from penroz_tpu.data.loaders import Downloader, Loader
from penroz_tpu.data.tokenizers import Tokenizer
from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import NeuralNetworkModel
from penroz_tpu.serve import schemas
from penroz_tpu.utils import tracing

log = logging.getLogger(__name__)

STATIC_DIR = os.path.join(os.path.dirname(__file__), "static")
TEMPLATES_DIR = os.path.join(os.path.dirname(__file__), "templates")

# Heavy work (training, HF import, dataset download) runs here; one at a time
# per resource via the locks below, globally bounded by the pool.
_EXECUTOR = ThreadPoolExecutor(max_workers=4, thread_name_prefix="penroz-work")

dataset_locks: Dict[str, asyncio.Lock] = {}
model_locks: Dict[str, asyncio.Lock] = {}


def _json(content, status: int = 200) -> web.Response:
    return web.json_response(content, status=status)


@web.middleware
async def request_id_middleware(request: web.Request, handler):
    """Every request gets an id (the client's sane ``X-Request-Id`` is
    honored for cross-system correlation): echoed in the response header,
    carried in error bodies (error_middleware), bound into log records
    via the tracing contextvar, and — for generation requests — the key
    of the ``GET /trace/{request_id}`` lifecycle span tree."""
    rid = tracing.new_request_id(request.headers.get("X-Request-Id"))
    request["request_id"] = rid
    token = tracing.bind(rid)
    try:
        response = await handler(request)
    except web.HTTPException as exc:
        exc.headers.setdefault("X-Request-Id", rid)
        raise
    finally:
        tracing.unbind(token)
    if not response.prepared:
        response.headers.setdefault("X-Request-Id", rid)
    return response


@web.middleware
async def gzip_middleware(request: web.Request, handler):
    # aiohttp inflates gzip request bodies itself; only decompress when the
    # payload still carries the gzip magic (e.g. proxies that skip inflation).
    if request.headers.get("Content-Encoding", "").lower() == "gzip":
        body = await request.read()
        log.info("Retrieved gzip encoded request body")
        if body[:2] == b"\x1f\x8b":
            request._read_bytes = gzip.decompress(body)
            log.info("Decompressed gzip encoded body")
    return await handler(request)


@web.middleware
async def error_middleware(request: web.Request, handler):
    # Error bodies name the request id so a client-side failure report can
    # be joined against server logs and GET /trace/{request_id}.
    rid = request.get("request_id")
    try:
        return await handler(request)
    except web.HTTPException:
        raise
    except pydantic.ValidationError as e:
        return _json({"detail": json.loads(e.json()), "request_id": rid},
                     status=422)
    except KeyError as e:
        return _json({"detail": f"Not found error occurred: {e}",
                      "request_id": rid}, status=404)
    except ValueError as e:
        return _json({"detail": f"Value error occurred: {e}",
                      "request_id": rid}, status=400)
    except Exception as e:  # noqa: BLE001
        log.error("An error occurred: %s", e)
        return _json({"detail": "Please refer to server logs",
                      "request_id": rid}, status=500)


async def _parse(request: web.Request, model_cls):
    try:
        payload = await request.json()
    except json.JSONDecodeError:
        raise web.HTTPUnprocessableEntity(
            text=json.dumps({"detail": "Invalid JSON body"}),
            content_type="application/json")
    return model_cls.model_validate(payload)


def _query_param(request: web.Request, name: str) -> str:
    value = request.query.get(name)
    if value is None:
        raise web.HTTPUnprocessableEntity(
            text=json.dumps({"detail": f"Missing query parameter {name}"}),
            content_type="application/json")
    return value


async def _run_blocking(fn, *args):
    return await asyncio.get_running_loop().run_in_executor(_EXECUTOR, fn, *args)


# ---------------------------------------------------------------------------
# Handlers
# ---------------------------------------------------------------------------

async def redirect_to_dashboard(request: web.Request):
    raise web.HTTPFound("/dashboard")


async def dashboard(request: web.Request):
    with open(os.path.join(TEMPLATES_DIR, "dashboard.html")) as f:
        return web.Response(text=f.read(), content_type="text/html")


async def create_model(request: web.Request):
    body = await _parse(request, schemas.CreateModelRequest)
    log.info("Requesting creation of model %s", body.model_id)
    model = NeuralNetworkModel(body.model_id, Mapper(body.layers, body.optimizer))
    model.serialize()
    return _json({"message": f"Model {body.model_id} created and saved successfully"})


async def import_from_huggingface(request: web.Request):
    body = await _parse(request, schemas.ImportModelRequest)
    model_id = body.model_id
    log.info("Requesting import of HuggingFace model %s as %s",
             body.hf_repo_id, model_id)
    lock = model_locks.setdefault(model_id, asyncio.Lock())
    if lock.locked():
        return _json({"detail": f"Operation already in progress for model {model_id}."},
                     status=409)
    async with lock:
        await _run_blocking(NeuralNetworkModel.from_huggingface, model_id,
                            body.hf_repo_id, body.revision, body.device)
    return _json({
        "model_id": model_id,
        "status": "imported",
        "message": f"Model imported from HuggingFace ({body.hf_repo_id}) "
                   f"and ready for use",
    })


async def list_dataset(request: web.Request):
    dataset_id = _query_param(request, "dataset_id")
    log.info("Requesting list of files for dataset %s", dataset_id)
    # "download" is additive (None when no download ran this process):
    # clients polling after a 202 can see "downloading" / "complete" /
    # terminal "failed" + error instead of tailing server logs.
    return _json({"files": Loader(dataset_id).list(),
                  "download": download_status.get(dataset_id)})


# Terminal download outcomes per dataset id, surfaced through GET /dataset/
# — the background task must not swallow failures into the log where no
# client can see them (PR 3 satellite).
download_status: Dict[str, dict] = {}


async def download_dataset(request: web.Request):
    body = await _parse(request, schemas.DownloadDatasetRequest)
    dataset_id = body.dataset_id
    log.info("Requesting download of dataset %s", dataset_id)
    lock = dataset_locks.setdefault(dataset_id, asyncio.Lock())
    if lock.locked():
        return _json({"detail": f"Downloading dataset {dataset_id} already in progress."},
                     status=409)
    downloader = Downloader(dataset_id, body.shard_size, body.encoding)
    attempts = max(1, int(os.environ.get("PENROZ_DOWNLOAD_RETRIES", "3")))
    backoff_s = float(os.environ.get("PENROZ_DOWNLOAD_BACKOFF_S", "1.0"))

    async def download():
        async with lock:
            status = download_status[dataset_id] = {
                "state": "downloading", "attempts": 0, "error": None}
            for attempt in range(1, attempts + 1):
                status["attempts"] = attempt
                try:
                    await _run_blocking(downloader.download, body.path,
                                        body.name, body.split)
                except Exception as e:  # noqa: BLE001
                    log.exception("Dataset %s download attempt %d/%d failed",
                                  dataset_id, attempt, attempts)
                    status["error"] = f"{type(e).__name__}: {e}"
                    if attempt < attempts:
                        await asyncio.sleep(backoff_s * 2 ** (attempt - 1))
                else:
                    status["state"] = "complete"
                    status["error"] = None
                    return
            status["state"] = "failed"
            log.error("Dataset %s download failed terminally after %d "
                      "attempt(s)", dataset_id, attempts)

    asyncio.get_running_loop().create_task(download())
    return _json({"message": f"Downloading Dataset {dataset_id} asynchronously."},
                 status=202)


async def delete_dataset(request: web.Request):
    dataset_id = _query_param(request, "dataset_id")
    log.info("Requesting deletion of dataset %s", dataset_id)
    Loader(dataset_id).delete()
    return web.Response(status=204)


async def tokenize_text(request: web.Request):
    body = await _parse(request, schemas.TokenizeTextRequest)
    log.info("Requesting tokenization of text %s", body.text)
    tokens = Tokenizer(body.encoding).tokenize(body.text)
    return _json({"encoding": body.encoding, "tokens": tokens})


async def compute_model_output(request: web.Request):
    body = await _parse(request, schemas.OutputRequest)
    log.info("Requesting output for model %s", body.model_id)
    model = await _run_blocking(NeuralNetworkModel.deserialize, body.model_id)
    output, cost = await _run_blocking(model.compute_output, body.input,
                                       body.target)
    return _json({"output": output, "cost": cost})


async def evaluate_model(request: web.Request):
    body = await _parse(request, schemas.EvaluateRequest)
    log.info("Requesting evaluation of model %s", body.model_id)
    model = await _run_blocking(NeuralNetworkModel.deserialize, body.model_id)
    cost = await _run_blocking(
        lambda: model.evaluate_model(body.dataset_id, body.target_dataset_id,
                                     body.shard, body.epochs, body.batch_size,
                                     body.block_size, body.step_size))
    return _json({"cost": cost})


def _shed_response(exc) -> web.Response:
    """Map scheduler shed exceptions to their HTTP statuses: queue full /
    tenant quota exceeded → 429 + Retry-After, deadline exceeded → 504,
    circuit open → 503 + Retry-After (fault-tolerance contract,
    serve/decode_scheduler.py).  Retry-After is load-aware: queue depth ×
    recent tick time for queue sheds, bucket refill time for quota sheds,
    remaining cooldown for breaker sheds."""
    from penroz_tpu.serve import decode_scheduler
    retry = str(int(getattr(exc, "retry_after", 1) or 1))
    if isinstance(exc, decode_scheduler.QueueFullError):
        return web.json_response({"detail": f"Server overloaded: {exc}"},
                                 status=429, headers={"Retry-After": retry})
    if isinstance(exc, decode_scheduler.TenantQuotaExceeded):
        return web.json_response({"detail": f"Tenant quota exceeded: {exc}"},
                                 status=429, headers={"Retry-After": retry})
    if isinstance(exc, decode_scheduler.DeadlineExceeded):
        return _json({"detail": f"Deadline exceeded: {exc}"}, status=504)
    assert isinstance(exc, decode_scheduler.CircuitOpenError), exc
    return web.json_response({"detail": f"Service unavailable: {exc}"},
                             status=503, headers={"Retry-After": retry})


async def _resolve_adapter(adapter_id: str, model_id: str):
    """Pin the adapter's registry entry (loading it off the event loop on
    a miss).  Returns the entry, or a ready 409 Response while another
    request's load is in flight.  Unknown/corrupt adapters raise
    ValueError (→ 400 naming the adapter via the error middleware) — never
    a KeyError 500."""
    from penroz_tpu.serve import adapters
    try:
        return await _run_blocking(adapters.REGISTRY.acquire, adapter_id,
                                   model_id)
    except adapters.AdapterLoadingError as exc:
        return _json({"detail": f"Conflict: {exc}"}, status=409)


async def _try_scheduler_generate(request: web.Request, body, adapter=None):
    """Serve /generate/ through the continuous-batching scheduler when
    enabled and eligible; returns a Response or None (→ legacy path).
    The whole point: K concurrent requests share one batch-K decode step
    per token instead of K batch-1 programs (serve/decode_scheduler.py).

    Overload/failure mapping: queue-full → 429, deadline → 504, open
    circuit breaker → 503 (or the legacy path when
    PENROZ_SCHED_FALLBACK=1 — degraded service beats none).  A client
    disconnect cancels this handler (non-streaming) or fails the stream
    write; both set ``req.cancelled`` so the abandoned row frees its KV
    slot and prefix pins at the next step boundary."""
    from penroz_tpu.serve import decode_scheduler
    if not decode_scheduler.enabled():
        return None
    prompt = NeuralNetworkModel._prompt_tokens(body.input)
    if not decode_scheduler.eligible(prompt, body.block_size,
                                     body.max_new_tokens):
        return None
    # Under PENROZ_SCHED_REPLICAS > 1 this is a serve/router.py
    # EngineRouter over N data-parallel replica engines — same submit()
    # surface, so everything below is placement-agnostic.
    engine = await decode_scheduler.acquire_engine(
        body.model_id, body.block_size, body.temperature, body.top_k)
    if engine is None:  # registry at capacity with nothing evictable
        return None
    rid = request.get("request_id") or tracing.new_request_id()
    # Per-request lifecycle trace (utils/tracing.py): the scheduler
    # records queue/prefill/decode/recovery spans against it and finishes
    # it at retirement; the shed paths below finish it here so no trace
    # leaks in the live table.
    trace = tracing.maybe_trace(rid, route="/generate/",
                                model_id=body.model_id,
                                stream=bool(body.stream))
    try:
        if not body.stream:
            tokens = await decode_scheduler.run_request(
                engine, prompt, body.max_new_tokens, body.stop_token,
                body.timeout_ms, adapter=adapter, request_id=rid,
                trace=trace, priority=body.priority, tenant=body.tenant,
                session_id=body.session_id)
            return _json({"tokens": tokens})
        log.info("Streaming token generation for model %s via the "
                 "continuous-batching scheduler", body.model_id)
        # submit BEFORE prepare: shed paths (429/503/504-queued) still get
        # their real status line instead of a broken 200 stream
        req, queue, stream = decode_scheduler.start_stream(
            engine, prompt, body.max_new_tokens, body.stop_token,
            body.timeout_ms, adapter=adapter, request_id=rid, trace=trace,
            priority=body.priority, tenant=body.tenant,
            session_id=body.session_id)
    except decode_scheduler.CircuitOpenError as exc:
        if trace is not None:
            trace.finish("breaker_open")
        if decode_scheduler.fallback_enabled():
            log.warning("Scheduler circuit open for model %s; falling back "
                        "to the single-sequence path", body.model_id)
            return None
        return _shed_response(exc)
    except decode_scheduler.QueueFullError as exc:
        if trace is not None:
            trace.finish("queue_full")
        return _shed_response(exc)
    except decode_scheduler.TenantQuotaExceeded as exc:
        if trace is not None:
            trace.finish("quota")
        return _shed_response(exc)
    except decode_scheduler.DeadlineExceeded as exc:
        if trace is not None:
            trace.finish("timeout")
        return _shed_response(exc)
    except Exception:
        # engine-owned traces are finished by the engine's crash-recovery
        # path (which still has recovery spans to record); only close
        # traces the scheduler never accepted
        if trace is not None and not trace.owned:
            trace.finish("error")
        raise
    response = web.StreamResponse(
        headers={"Content-Type": "text/plain; charset=utf-8",
                 "X-Request-Id": rid})
    await response.prepare(request)
    try:
        while True:
            seq, kind, value = await queue.get()
            if kind == "token":
                await response.write(f"{value}\n".encode())
            elif kind == "done":
                break
            elif kind == "timeout":
                # deadline hit mid-stream: tokens so far were delivered;
                # a final non-numeric event line ends the stream honestly
                await response.write(b"timeout\n")
                break
            else:
                raise value
    except asyncio.CancelledError:
        # aiohttp cancels the handler on client disconnect.  With a
        # detach grace configured (PENROZ_STREAM_DETACH_MS) the
        # generation keeps running and the replay ring keeps filling for
        # a GET /generate/{id}/stream reconnect; otherwise free the row
        # exactly as before.
        _stream_disconnect(stream, req)
        raise
    except ConnectionResetError:
        # A disconnect can also surface as a write-time reset ("Cannot
        # write to closing transport") instead of a cancellation — same
        # detach-or-cancel seam, but nothing more can be written.
        _stream_disconnect(stream, req)
        return response
    except Exception:  # noqa: BLE001 — headers already out; end + log
        req.cancelled = True
        log.exception("Scheduler streaming failed for model %s",
                      body.model_id)
    stream.release()
    await response.write_eof()
    return response


async def model_generate(request: web.Request):
    body = await _parse(request, schemas.GenerateRequest)
    log.info("Generating tokens using model %s%s", body.model_id,
             f" (adapter {body.adapter_id})" if body.adapter_id else "")
    entry = None
    if body.adapter_id:
        entry = await _resolve_adapter(body.adapter_id, body.model_id)
        if isinstance(entry, web.Response):
            return entry
    try:
        return await _model_generate_inner(request, body, entry)
    finally:
        if entry is not None:
            from penroz_tpu.serve import adapters
            adapters.REGISTRY.release(entry)


async def _model_generate_inner(request: web.Request, body, entry):
    response = await _try_scheduler_generate(request, body, adapter=entry)
    if response is not None:
        return response
    # Legacy single-sequence path: a one-span trace so /trace/ still
    # answers for requests the scheduler did not serve.
    rid = request.get("request_id") or tracing.new_request_id()
    trace = tracing.maybe_trace(rid, route="/generate/",
                                model_id=body.model_id, engine="legacy",
                                stream=bool(body.stream))
    sp = trace.span("legacy_generate") if trace is not None else None
    try:
        response = await _model_generate_legacy(request, body, entry, rid)
    except Exception:
        if trace is not None:
            trace.end(sp)
            trace.finish("error")
        raise
    if trace is not None:
        trace.end(sp)
        trace.finish("completed")
    return response


async def _model_generate_legacy(request: web.Request, body, entry, rid):
    model = await _run_blocking(NeuralNetworkModel.deserialize, body.model_id)
    if entry is not None:
        # Legacy single-sequence path: bind the adapter factors into the
        # flat param dict — every compiled program picks the delta up
        # through Ctx.params (models/lora.py bind_model).
        from penroz_tpu.models import lora
        model = lora.bind_model(model, entry.params, entry.config)
    if body.stream:
        log.info("Streaming token generation for model %s", body.model_id)
        response = web.StreamResponse(
            headers={"Content-Type": "text/plain; charset=utf-8",
                     "X-Request-Id": rid})
        await response.prepare(request)
        queue: asyncio.Queue = asyncio.Queue()
        loop = asyncio.get_running_loop()
        _DONE = object()

        def produce():
            try:
                # decode-priority marking lives inside the generate
                # methods themselves (models.model.decode_priority)
                for token in model.generate_tokens_stream(
                        body.input, body.block_size, body.max_new_tokens,
                        body.temperature, body.top_k, body.stop_token):
                    loop.call_soon_threadsafe(queue.put_nowait, token)
            finally:
                loop.call_soon_threadsafe(queue.put_nowait, _DONE)

        producer = loop.run_in_executor(_EXECUTOR, produce)
        while True:
            token = await queue.get()
            if token is _DONE:
                break
            await response.write(f"{token}\n".encode())
        try:
            await producer
        except Exception:  # noqa: BLE001
            # Headers already went out — we can only end the stream and log.
            log.exception("Streaming generation failed for model %s",
                          body.model_id)
        await response.write_eof()
        return response

    tokens = await _run_blocking(
        lambda: model.generate_tokens(body.input, body.block_size,
                                      body.max_new_tokens, body.temperature,
                                      body.top_k, body.stop_token))
    return _json({"tokens": tokens})


def _stream_disconnect(stream, req):
    """The streaming client vanished (handler cancelled or a write-time
    connection reset): detach when PENROZ_STREAM_DETACH_MS grants a
    grace, let a finished stream's ring linger for late reconnects, and
    otherwise fire the pre-existing cancellation path."""
    from penroz_tpu.serve import streams
    if stream.try_detach():
        return
    if stream.terminal:
        stream.release()
        return
    req.cancelled = True
    streams.STREAMS.discard(stream.request_id)


async def resume_stream(request: web.Request):
    """Reattach to a live token stream (GET
    /generate/{request_id}/stream?from_seq=N): replays the events the
    bounded per-request ring still holds from sequence number ``N`` on,
    then continues live — exactly-once across the seam
    (serve/streams.py).  Lines are ``seq:value`` (value = token int, or
    the terminal ``done`` / ``timeout`` / ``error``), so the client
    always knows the next ``from_seq`` to ask for.  404 for an unknown
    or already-purged request id; 410 when ``from_seq`` fell behind the
    ring (``PENROZ_STREAM_REPLAY``) or the detach grace already expired
    — resuming would skip tokens, so the client must restart."""
    from penroz_tpu.serve import streams
    rid = request.match_info["request_id"]
    try:
        from_seq = int(request.query.get("from_seq", "0"))
    except ValueError:
        raise web.HTTPUnprocessableEntity(
            text=json.dumps({"detail": "from_seq must be an integer"}),
            content_type="application/json")
    if from_seq < 0:
        raise web.HTTPUnprocessableEntity(
            text=json.dumps({"detail": "from_seq must be >= 0"}),
            content_type="application/json")
    sess = streams.STREAMS.get(rid)
    if sess is None:
        raise KeyError(
            f"no resumable stream for request id {rid!r} (terminal "
            f"streams linger briefly; expired/unknown ones do not)")
    loop = asyncio.get_running_loop()
    queue: asyncio.Queue = asyncio.Queue()
    try:
        backlog = sess.resume(loop, queue, from_seq)
    except streams.ReplayGapError as exc:
        return _json({"detail": f"Gone: {exc}"}, status=410)
    log.info("Stream %s resumed at seq %d (%d ring event(s) to replay)",
             rid, from_seq, len(backlog))

    def _line(seq: int, kind: str, value) -> bytes:
        return (f"{seq}:{value}\n" if kind == "token"
                else f"{seq}:{kind}\n").encode()

    response = web.StreamResponse(
        headers={"Content-Type": "text/plain; charset=utf-8",
                 "X-Request-Id": rid})
    await response.prepare(request)
    terminal = False
    try:
        for seq, kind, value in backlog:
            await response.write(_line(seq, kind, value))
            if kind in ("done", "timeout", "error"):
                terminal = True
                break
        while not terminal:
            seq, kind, value = await queue.get()
            await response.write(_line(seq, kind, value))
            if kind in ("done", "timeout", "error"):
                terminal = True
    except asyncio.CancelledError:
        # the resumed consumer vanished too: same detach-or-cancel seam
        # as the original stream handler
        _stream_disconnect(sess, sess.req)
        raise
    except ConnectionResetError:
        _stream_disconnect(sess, sess.req)
        return response
    except Exception:  # noqa: BLE001 — headers already out; end + log
        sess.req.cancelled = True
        log.exception("Resumed stream %s failed mid-write", rid)
    sess.release()
    await response.write_eof()
    return response


async def _resolve_batch_adapters(body):
    """Per-row adapter entries for /generate_batch/: ``adapter_ids`` (one
    per row, null = base) overrides the batch-wide ``adapter_id``.

    All-or-nothing like the PR-1 overflow 400: every bad row is named in
    ONE descriptive error — unknown/invalid adapters raise ValueError
    (400), still-loading adapters return a 409 Response — and on any
    failure every already-pinned entry is released.  Returns
    ``(row_entries, unique_entries)`` on success."""
    from penroz_tpu.serve import adapters
    n = len(body.inputs)
    if body.adapter_ids is not None:
        if len(body.adapter_ids) != n:
            raise ValueError(
                f"adapter_ids has {len(body.adapter_ids)} entries for "
                f"{n} input row(s); pass one per row (null = base model)")
        row_ids = list(body.adapter_ids)
    else:
        row_ids = [body.adapter_id] * n
    entries: Dict[str, object] = {}
    unknown: list = []
    loading: list = []
    for aid in row_ids:
        if aid is None or aid in entries:
            continue
        try:
            entries[aid] = await _run_blocking(
                adapters.REGISTRY.acquire, aid, body.model_id)
        except adapters.AdapterLoadingError:
            loading.append(aid)
        except ValueError as exc:
            unknown.append((aid, str(exc)))

    def _rows_for(aid):
        rows = [i for i, r in enumerate(row_ids) if r == aid]
        return ", ".join(f"row {i}" for i in rows[:8]) + (
            f" and {len(rows) - 8} more" if len(rows) > 8 else "")

    if unknown:
        for entry in entries.values():
            adapters.REGISTRY.release(entry)
        detail = "; ".join(f"adapter {aid!r} ({_rows_for(aid)}): {msg}"
                           for aid, msg in unknown)
        raise ValueError(f"batched generation rejected: {detail}")
    if loading:
        for entry in entries.values():
            adapters.REGISTRY.release(entry)
        detail = "; ".join(f"adapter {aid!r} ({_rows_for(aid)}) is still "
                           f"loading" for aid in loading)
        return _json({"detail": f"Conflict: {detail}; retry shortly"},
                     status=409)
    return [entries.get(aid) for aid in row_ids], entries


_SESSION_ID_RE = re.compile(r"^[A-Za-z0-9._-]{1,120}$")


def _batch_session_ids(body, n: int) -> list:
    """Per-row session ids for /generate_batch/ (``session_ids``, null =
    no session), validated with the same pattern as
    ``GenerateRequest.session_id`` — the id names a disk-tier blob file,
    so path-safe characters only.  ValueError → 400 (all-or-nothing,
    like adapter_ids)."""
    if body.session_ids is None:
        return [None] * n
    if len(body.session_ids) != n:
        raise ValueError(
            f"session_ids has {len(body.session_ids)} entries for "
            f"{n} input row(s); pass one per row (null = no session)")
    bad = [i for i, sid in enumerate(body.session_ids)
           if sid is not None and not _SESSION_ID_RE.match(sid)]
    if bad:
        raise ValueError(
            "batched generation rejected: invalid session_id at row(s) "
            + ", ".join(str(i) for i in bad[:8])
            + " (allowed: [A-Za-z0-9._-]{1,120})")
    return list(body.session_ids)


async def model_generate_batch(request: web.Request):
    """Ragged batched generation — N prompts share one forward per step
    (beyond the reference surface; its /generate/ is single-sequence).
    With PENROZ_CONTINUOUS_BATCHING=1 the rows join the shared in-flight
    batch instead, so they coalesce with concurrent /generate/ traffic
    and recycle KV slots as individual rows finish.  Rows may carry
    DIFFERENT LoRA adapters (``adapter_ids``) — the scheduler serves the
    mix in one shared step via the stacked adapter pack."""
    body = await _parse(request, schemas.GenerateBatchRequest)
    log.info("Batch-generating %d sequence(s) using model %s",
             len(body.inputs), body.model_id)
    resolved = await _resolve_batch_adapters(body)
    if isinstance(resolved, web.Response):
        return resolved
    row_entries, unique_entries = resolved
    try:
        return await _model_generate_batch_inner(request, body, row_entries)
    finally:
        from penroz_tpu.serve import adapters
        for entry in unique_entries.values():
            adapters.REGISTRY.release(entry)


async def _model_generate_batch_inner(request, body, row_entries):
    from penroz_tpu.serve import decode_scheduler
    if decode_scheduler.enabled() and body.max_new_tokens >= 1:
        prompts = [[int(t) for t in row] for row in body.inputs]
        engine = await decode_scheduler.acquire_engine(
            body.model_id, body.block_size, body.temperature, body.top_k)
        if engine is not None:
            # Same contract as the legacy path: reject (400) any row that
            # would silently truncate — raised BEFORE submitting so the
            # batch is all-or-nothing.
            from penroz_tpu.models.model import validate_batch_generation
            validate_batch_generation(prompts, body.block_size,
                                      body.max_new_tokens)
            # return_exceptions: a shed row (429/504/503) must not leave
            # its siblings decoding into a dropped response — every row
            # settles, then the batch answers as one.
            rid = request.get("request_id") or tracing.new_request_id()
            # Per-row traces under suffixed ids (rid-r0, rid-r1, ...): each
            # row has its own scheduler lifecycle, so each gets its own
            # span tree; shed rows are finished in the error sweep below.
            sids = _batch_session_ids(body, len(prompts))
            rows = [(f"{rid}-r{i}",
                     tracing.maybe_trace(f"{rid}-r{i}",
                                         route="/generate_batch/",
                                         model_id=body.model_id, row=i))
                    for i in range(len(prompts))]
            results = await asyncio.gather(*[
                decode_scheduler.run_request(
                    engine, p, body.max_new_tokens, body.stop_token,
                    body.timeout_ms, adapter=entry, request_id=row_rid,
                    trace=row_trace, priority=body.priority,
                    tenant=body.tenant, session_id=sid)
                for (p, entry, sid, (row_rid, row_trace))
                in zip(prompts, row_entries, sids, rows)],
                return_exceptions=True)
            reason_of = {
                decode_scheduler.QueueFullError: "queue_full",
                decode_scheduler.DeadlineExceeded: "timeout",
                decode_scheduler.CircuitOpenError: "breaker_open",
                decode_scheduler.TenantQuotaExceeded: "quota"}
            for (_, row_trace), res in zip(rows, results):
                if (row_trace is not None and not row_trace.finished
                        and not row_trace.owned):
                    row_trace.finish(
                        reason_of.get(type(res), "error")
                        if isinstance(res, BaseException) else "completed")
            errors = [r for r in results if isinstance(r, BaseException)]
            if not errors:
                return _json({"sequences": results})
            shed = next((e for e in errors if isinstance(
                e, (decode_scheduler.QueueFullError,
                    decode_scheduler.DeadlineExceeded,
                    decode_scheduler.CircuitOpenError,
                    decode_scheduler.TenantQuotaExceeded))), None)
            if shed is None:
                raise errors[0]
            if (isinstance(shed, decode_scheduler.CircuitOpenError)
                    and decode_scheduler.fallback_enabled()):
                log.warning("Scheduler circuit open for model %s; batch "
                            "falls back to the legacy path", body.model_id)
                # falls through to the legacy batched path below
            else:
                return _shed_response(shed)
    model = await _run_blocking(NeuralNetworkModel.deserialize, body.model_id)
    if not any(e is not None for e in row_entries):
        sequences = await _run_blocking(
            lambda: model.generate_tokens_batched(
                body.inputs, body.block_size, body.max_new_tokens,
                body.temperature, body.top_k, body.stop_token))
        return _json({"sequences": sequences})
    # Legacy path with adapters: group rows per adapter, run each group
    # through a bound model (one adapter per forward), reassemble in row
    # order.  The all-or-nothing 400 contract still holds — validate the
    # WHOLE batch before any group runs.
    from penroz_tpu.models import lora
    from penroz_tpu.models.model import validate_batch_generation
    prompts = [[int(t) for t in row] for row in body.inputs]
    validate_batch_generation(prompts, body.block_size, body.max_new_tokens)
    groups: Dict[object, list] = {}
    for i, entry in enumerate(row_entries):
        groups.setdefault(entry, []).append(i)
    sequences: list = [None] * len(prompts)

    def run_groups():
        for entry, rows in groups.items():
            bound = (model if entry is None
                     else lora.bind_model(model, entry.params, entry.config))
            outs = bound.generate_tokens_batched(
                [prompts[i] for i in rows], body.block_size,
                body.max_new_tokens, body.temperature, body.top_k,
                body.stop_token)
            for i, seq in zip(rows, outs):
                sequences[i] = seq

    await _run_blocking(run_groups)
    return _json({"sequences": sequences})


async def decode_tokens(request: web.Request):
    body = await _parse(request, schemas.DecodeTokensRequest)
    log.info("Requesting decoding of %d token(s)", len(body.tokens))
    text = Tokenizer(body.encoding).decode(body.tokens)
    return _json({"encoding": body.encoding, "text": text})


async def train_model(request: web.Request):
    body = await _parse(request, schemas.TrainingRequest)
    model_id = body.model_id
    log.info("Requesting training for model %s on device %s",
             model_id, body.device)
    # Validate early so a bad model id 404s, a bad device string 400s, and
    # a bad adapter config 400s instead of silently failing in the
    # fire-and-forget background task (the checkpoint read is cheap via
    # shm).
    from penroz_tpu.models.model import _resolve_device
    _resolve_device(body.device)
    await _run_blocking(NeuralNetworkModel.deserialize, model_id)
    adapter_cfg = None
    if body.adapter is not None:
        from penroz_tpu.models import lora
        adapter_cfg = lora.validate_config({
            "rank": body.adapter.rank, "alpha": body.adapter.alpha,
            "targets": body.adapter.targets})
        adapter_cfg["adapter_id"] = body.adapter.adapter_id

    # One lock per base model covers base AND adapter runs: an adapter
    # fine-tune reads the base weights, so it must never race a base
    # /train/ rewriting them mid-run.
    lock = model_locks.setdefault(model_id, asyncio.Lock())
    if lock.locked():
        return _json({"detail": f"Training already in progress for model {model_id}."},
                     status=409)

    # The job's timeline (utils/tracing.py): its id is this request's, in
    # the 202's X-Request-Id; GET /trace/{id} resolves it while the job
    # runs and after.  Sampled by PENROZ_TRACE_SAMPLE like any request.
    trace = tracing.maybe_trace(request["request_id"], job=True,
                                route="/train/", model_id=model_id)

    async def _launch():
        async with lock:
            log.info("Waiting for training of model %s to complete...", model_id)
            try:
                await _run_blocking(
                    NeuralNetworkModel.train_model_on_device, model_id,
                    body.device, body.dataset_id, body.shard, body.epochs,
                    body.batch_size, body.block_size, body.step_size,
                    adapter_cfg, trace)
            except Exception:  # noqa: BLE001
                log.exception("Training failed for model %s", model_id)
            else:
                log.info("Training completed for model %s", model_id)
            finally:
                if adapter_cfg is not None:
                    # Serving must pick up the fresh factors: the cached
                    # registry entry (if any) still holds the pre-train
                    # generation — drop it so the next request reloads
                    # under a new uid (which also retires its prefix-cache
                    # namespace).
                    from penroz_tpu.serve import adapters
                    adapters.REGISTRY.invalidate(adapter_cfg["adapter_id"])

    asyncio.get_running_loop().create_task(_launch())
    what = (f"adapter {adapter_cfg['adapter_id']} on model {model_id}"
            if adapter_cfg is not None else f"model {model_id}")
    return _json({"message": f"Training for {what} started asynchronously."},
                 status=202)


async def profile(request: web.Request):
    """Start/stop a jax.profiler trace capture (no reference equivalent —
    SURVEY.md §5 profiling upgrade)."""
    from penroz_tpu.utils import profiling
    body = await _parse(request, schemas.ProfileRequest)
    # start/stop serialize trace state (stop writes the whole capture to
    # disk) — keep them off the event loop like every other blocking op.
    if body.action == "start":
        if not await _run_blocking(profiling.start, body.log_dir):
            return _json({"detail": "A profile capture is already running."},
                         status=409)
        return _json({"message": f"Profiling started into {body.log_dir}"})
    if body.action == "stop":
        log_dir = await _run_blocking(profiling.stop)
        if log_dir is None:
            return _json({"detail": "No profile capture is running."},
                         status=409)
        return _json({"message": f"Profiling stopped; trace in {log_dir}"})
    raise ValueError(f"Unknown profile action {body.action!r}")


async def model_progress(request: web.Request):
    model_id = _query_param(request, "model_id")
    log.info("Requesting progress for model %s", model_id)
    model = await _run_blocking(NeuralNetworkModel.deserialize, model_id)
    return _json({
        "progress": model.progress,
        "average_cost": model.avg_cost,
        "average_cost_history": model.avg_cost_history,
        "status": model.status,
    })


async def model_stats(request: web.Request):
    model_id = _query_param(request, "model_id")
    log.info("Requesting stats for model %s", model_id)
    model = await _run_blocking(NeuralNetworkModel.deserialize, model_id)
    stats = model.stats
    # MoE observability (additive key — dashboard ignores unknowns): the
    # per-expert routing fractions updated each training step, so expert
    # collapse is visible without digging into checkpoints.  Only once
    # stats exist: an untrained model must keep returning null (dashboard
    # 'no stats yet' state), and its all-zero init fractions would
    # masquerade as observed routing.
    if stats is not None:
        routing = {name: [float(x) for x in np.asarray(buf)]
                   for name, buf in model.buffers.items()
                   if name.endswith("router_fraction")}
        if routing:
            stats = dict(stats)
            stats["moe_router_fractions"] = routing
    return _json(stats)


async def serving_stats(request: web.Request):
    """Continuous-batching scheduler observability: queue depth, batch
    occupancy, decode tokens/sec, admission latency, speculative-decoding
    accept rate / tokens per decode step, and the KV pool-capacity drop
    counter (serve/decode_scheduler.py)."""
    from penroz_tpu.serve import decode_scheduler
    stats = decode_scheduler.serving_stats()
    # Validate against the documented schema so /serving_stats/ and the
    # OpenAPI surface cannot drift apart silently.
    return _json(schemas.ServingStatsResponse.model_validate(
        stats).model_dump())


async def put_tenant_quota(request: web.Request):
    """Per-tenant token-rate override (PUT /tenants/{tenant_id}/quota):
    sets the tenant's sustained tokens/sec budget over emitted + prefilled
    tokens (serve/qos.py token bucket; env default
    PENROZ_QOS_TENANT_TOKENS_PER_S).  ``tokens_per_s: null`` clears the
    override; 0 blocks all new admissions for the tenant while in-flight
    rows finish."""
    from penroz_tpu.serve import qos
    tenant_id = request.match_info["tenant_id"]
    body = await _parse(request, schemas.TenantQuotaRequest)
    if body.tokens_per_s is not None and body.tokens_per_s < 0:
        raise ValueError("tokens_per_s must be >= 0 (or null to clear "
                         "the override)")
    qos.QUOTAS.set_rate(tenant_id, body.tokens_per_s)
    journal_fields = {"tenant": tenant_id, "rate": body.tokens_per_s}
    if "tier_mb" in body.model_fields_set:
        if body.tier_mb is not None and body.tier_mb < 0:
            raise ValueError("tier_mb must be >= 0 (or null to clear "
                             "the override)")
        qos.QUOTAS.set_tier_mb(tenant_id, body.tier_mb)
        journal_fields["tier_mb"] = body.tier_mb
    # Write-ahead: the override survives a process restart
    # (tierstore.recover() replays quota records last-write-wins).
    from penroz_tpu.serve import journal
    journal.JOURNAL.append("quota", **journal_fields)
    log.info("Tenant %s quota %s", tenant_id,
             "cleared (env default)" if body.tokens_per_s is None
             else f"set to {body.tokens_per_s} tokens/s")
    return _json({"tenant": tenant_id,
                  "tokens_per_s": qos.QUOTAS.rate_for(tenant_id),
                  "override": body.tokens_per_s is not None,
                  "tier_bytes": qos.QUOTAS.tier_bytes_for(tenant_id)})


async def list_sessions(request: web.Request):
    """Hibernated-session residency (GET /sessions/): every session
    parked in the KV tiers (serve/tierstore.py), across all engines and
    replicas — tier, size, and LRU age per session."""
    from penroz_tpu.serve import tierstore
    sessions = tierstore.TIERS.list_sessions()
    return _json({"sessions": sessions,
                  "sessions_resident": len(sessions),
                  "sessions_by_tier": tierstore.TIERS.sessions_by_tier(),
                  "tier_bytes": tierstore.TIERS.tier_bytes()})


async def delete_session(request: web.Request):
    """Evict one hibernated session from every tier (DELETE
    /sessions/{session_id}).  Idempotent: deleting a non-resident id is
    a 200 with deleted=false."""
    from penroz_tpu.serve import tierstore
    sid = request.match_info["session_id"]
    deleted = tierstore.TIERS.drop(sid, "api")
    log.info("Session %s %s", sid,
             "evicted from the KV tiers" if deleted else "not resident")
    return _json({"session_id": sid, "deleted": deleted})


async def list_tenants(request: web.Request):
    """Tenant quota state (GET /tenants/): configured overrides plus live
    bucket levels and rejection counts for every tenant the scheduler has
    seen — the admin view behind the dashboard per-tenant tile."""
    from penroz_tpu.serve import qos
    return _json({"tenants": qos.QUOTAS.stats(),
                  "default_tokens_per_s": qos.QUOTAS.rate_for(None)})


async def metrics_exposition(request: web.Request):
    """Prometheus text exposition (GET /metrics): process-wide counters +
    fixed-bucket latency histograms written by the scheduler at event
    time, gauges read from the live engine registry at scrape time
    (serve/metrics.py — dependency-free, format 0.0.4)."""
    from penroz_tpu.serve import metrics as serve_metrics
    body = await _run_blocking(serve_metrics.render)
    return web.Response(body=body.encode("utf-8"),
                        headers={"Content-Type": serve_metrics.CONTENT_TYPE})


async def trace_list(request: web.Request):
    """Recent request traces (GET /trace/): summaries of the completed
    ring (most recent first, PENROZ_TRACE_BUFFER entries) plus the
    currently in-flight traces — pick a request_id, then GET
    /trace/{request_id} for its span tree."""
    try:
        limit = max(1, min(1000, int(request.query.get("limit", "50"))))
    except ValueError:
        raise web.HTTPUnprocessableEntity(
            text=json.dumps({"detail": "limit must be an integer"}),
            content_type="application/json")
    return _json({
        "traces": [t.summary() for t in tracing.completed(limit)],
        "live": [t.summary() for t in tracing.live()],
    })


async def trace_detail(request: web.Request):
    """One request's lifecycle span tree (GET /trace/{request_id}):
    queue wait, prefix-cache match, prefill chunks, decode/verify steps,
    crash-recovery events, and the retirement reason — in-flight
    requests resolve too (their root span is still open).
    ``?format=chrome`` renders the same tree as Chrome trace-event JSON
    (save and load in Perfetto / chrome://tracing)."""
    rid = request.match_info["request_id"]
    trace = tracing.get(rid)
    if trace is None:
        raise KeyError(f"no trace for request id {rid!r} (ring holds "
                       f"PENROZ_TRACE_BUFFER most recent)")
    fmt = request.query.get("format", "json")
    if fmt == "chrome":
        return _json(trace.to_chrome())
    if fmt != "json":
        raise web.HTTPUnprocessableEntity(
            text=json.dumps({"detail": f"unknown format {fmt!r} "
                             "(expected 'json' or 'chrome')"}),
            content_type="application/json")
    return _json(trace.to_dict())


async def memory_stats(request: web.Request):
    """The HBM capacity ledger (GET /memory/): every paged-pool page
    attributed to its owner — free / live row (per tenant and adapter) /
    pinned or evictable prefix-cache node / preempted-session hold /
    reserved tail — plus byte accounting for contiguous and int8 KV,
    the LoRA pack, params, and the adapter host cache, with high-water
    marks and a token-burn-rate time-to-exhaustion estimate
    (serve/memledger.py)."""
    from penroz_tpu.serve import memledger
    stats = await _run_blocking(memledger.memory_stats)
    return _json(schemas.MemoryResponse.model_validate(
        stats).model_dump())


async def debug_dump(request: web.Request):
    """The engine flight recorder (GET /debug/dump): bounded ring of
    pre-crash snapshots — ledger, tick timeline, per-class/per-tenant
    queue depths, recent trace ids — captured at every engine crash,
    circuit-breaker open, and failed reset, before recovery wipes the
    state (serve/memledger.py FlightRecorder)."""
    from penroz_tpu.serve import memledger, tierstore
    dump = memledger.FLIGHT_RECORDER.dump()
    # Restart forensics ride along: what the last tierstore.recover()
    # replayed, dropped, and swept (empty dict before any recovery ran).
    dump["restart_recovery"] = dict(tierstore.TIERS.last_recovery)
    return _json(schemas.DebugDumpResponse.model_validate(
        dump).model_dump())


async def healthz(request: web.Request):
    """Liveness: the event loop is alive and answering.  Always 200 — an
    open circuit breaker is a readiness problem, not a liveness one
    (restarting the process would not fix a crashing model)."""
    return _json({"status": "ok"})


async def readyz(request: web.Request):
    """Readiness: 503 while the scheduler path cannot serve — an open
    standalone-engine breaker, or (PENROZ_SCHED_REPLICAS > 1) a replica
    group with EVERY breaker open, a worker stuck inside one tick
    dispatch past PENROZ_TICK_WATCHDOG_MS (same group-aware rule), or a
    drain in progress.  One healthy replica keeps its model ready: the
    router fails admissions over to it instead of 503ing, so load
    balancers keep routing here."""
    from penroz_tpu.serve import decode_scheduler
    breaker_open = decode_scheduler.breaker_open_engines()
    stuck = decode_scheduler.stuck_engines()
    draining = decode_scheduler.draining()
    ready = not breaker_open and not stuck and not draining
    return _json({"ready": ready, "draining": draining,
                  "breaker_open_engines": breaker_open,
                  "stuck_engines": stuck},
                 status=200 if ready else 503)


async def _startup_observability(app: web.Application):
    """App startup: bring up the live-profiling gRPC endpoint when
    PENROZ_PROFILER_PORT is set — embedded servers (tests, benches) get
    it too, not just the __main__ path."""
    from penroz_tpu.utils import profiling
    profiling.maybe_start_server()


async def _drain_on_shutdown(app: web.Application):
    """Graceful shutdown: stop admission, let in-flight decode rows finish
    within PENROZ_DRAIN_S, then join every engine worker thread (leaks are
    reported, not ignored — DecodeEngine.shutdown returns False)."""
    from penroz_tpu.serve import decode_scheduler
    await asyncio.get_running_loop().run_in_executor(
        None, decode_scheduler.drain_and_shutdown)


async def delete_model(request: web.Request):
    model_id = _query_param(request, "model_id")
    log.info("Requesting deletion of model %s", model_id)
    # Flush + delete the model's LoRA adapters first (registry cache AND
    # checkpoints): an adapter without its base can never serve again, and
    # a stale blob would resurrect under a recreated model id with
    # different weights (mirror of the PR-2 prefix-cache flush).
    from penroz_tpu.serve import adapters
    deleted = await _run_blocking(adapters.delete_model_adapters, model_id)
    if deleted:
        log.info("Deleted %d adapter(s) of model %s: %s", len(deleted),
                 model_id, ", ".join(deleted))
    NeuralNetworkModel.delete(model_id)
    return web.Response(status=204)


# ---------------------------------------------------------------------------
# LoRA adapter lifecycle (/adapters/ — serve/adapters.py, models/lora.py)
# ---------------------------------------------------------------------------

async def create_adapter(request: web.Request):
    body = await _parse(request, schemas.CreateAdapterRequest)
    log.info("Requesting creation of adapter %s for model %s",
             body.adapter_id, body.model_id)
    from penroz_tpu.models import lora
    from penroz_tpu.utils import checkpoint
    if body.init not in ("zeros", "random"):
        raise ValueError(f"init must be 'zeros' or 'random', "
                         f"got {body.init!r}")
    try:
        checkpoint.peek_adapter_tree(body.adapter_id)
        return _json({"detail": f"Adapter {body.adapter_id} already "
                                f"exists."}, status=409)
    except KeyError:
        pass
    model = await _run_blocking(NeuralNetworkModel.deserialize,
                                body.model_id)
    cfg = {"rank": body.rank, "alpha": body.alpha, "targets": body.targets}
    blob = await _run_blocking(
        lambda: lora.create_adapter(body.adapter_id, model, cfg,
                                    seed=body.seed, init=body.init))
    # Journal the registration (informational: the adapter's factors are
    # already durable as a checkpoint; the record makes the restart
    # recovery summary account for every registered adapter).
    from penroz_tpu.serve import journal
    journal.JOURNAL.append("adapter", adapter_id=body.adapter_id,
                           model_id=body.model_id)
    return _json({"adapter_id": body.adapter_id, "model_id": body.model_id,
                  "config": blob["config"],
                  "message": f"Adapter {body.adapter_id} created for model "
                             f"{body.model_id}"})


async def list_adapters(request: web.Request):
    from penroz_tpu.serve import adapters
    adapter_id = request.query.get("adapter_id")
    if adapter_id is not None:
        log.info("Requesting detail for adapter %s", adapter_id)
        return _json(await _run_blocking(adapters.adapter_detail,
                                         adapter_id))
    log.info("Requesting adapter listing")
    return _json({"adapters": await _run_blocking(adapters.list_adapters)})


async def delete_adapter(request: web.Request):
    adapter_id = _query_param(request, "adapter_id")
    log.info("Requesting deletion of adapter %s", adapter_id)
    from penroz_tpu.serve import adapters
    from penroz_tpu.utils import checkpoint
    checkpoint.peek_adapter_tree(adapter_id)  # KeyError → 404
    adapters.REGISTRY.invalidate(adapter_id)
    checkpoint.delete_adapter(adapter_id)
    return web.Response(status=204)


async def openapi_json(request: web.Request):
    """OpenAPI 3.1 spec (FastAPI gives the reference this for free;
    serve/openapi.py generates ours from the same pydantic schemas)."""
    from penroz_tpu.serve import openapi
    global _OPENAPI_CACHE
    if _OPENAPI_CACHE is None:
        _OPENAPI_CACHE = openapi.spec_json()
    return web.Response(text=_OPENAPI_CACHE, content_type="application/json")


async def docs(request: web.Request):
    from penroz_tpu.serve import openapi
    return web.Response(text=openapi.docs_html(), content_type="text/html")


_OPENAPI_CACHE = None


def _sweep_orphaned_training():
    """Mark stale 'Training' statuses as Error at server start.

    Training runs inside the server process (the TPU runtime is
    single-tenant per process), so at startup no training can possibly be
    running — a checkpoint still saying 'Training' was orphaned by a
    restart/crash mid-run.  The reference cannot make this inference (its
    training is a separate DDP process that may outlive the API,
    main.py:461-464) and leaves the status stuck forever; here the failure
    is detectable, so report it.  Header-only peeks keep the sweep cheap.
    """
    from penroz_tpu.utils import checkpoint
    for model_id in checkpoint.list_model_ids():
        try:
            if checkpoint.peek_tree(model_id).get(
                    "status", {}).get("code") != "Training":
                continue
            # header-only rewrite: the array payload streams through
            # untouched, so even multi-GB checkpoints patch in O(file copy)
            # with no decode and no RAM spike
            checkpoint.patch_meta(model_id, {"status": {
                "code": "Error",
                "message": "Training interrupted by server restart"}})
            log.warning("Marked orphaned training as Error: %s", model_id)
        except Exception:  # noqa: BLE001 — sweep must never block startup
            log.exception("Orphan sweep failed for model %s", model_id)


def create_app() -> web.Application:
    # Synchronous, BEFORE the socket binds: a client retrying /train/ right
    # after a restart must not race the sweep (a background sweep could mark
    # the new live run as Error and clobber its first checkpoint with the
    # stale pre-restart payload).  patch_meta keeps this cheap — O(file
    # copy) per orphan, no array decode.
    _sweep_orphaned_training()
    # Restart recovery (serve/tierstore.py): replay the write-ahead
    # journal and cross-check the disk tier BEFORE the socket binds, so
    # the first GET /sessions/ already lists every session that survived
    # a kill -9 — and a torn journal tail or orphaned atomic-write temp
    # is repaired before any request can race it.  A no-op (plus orphan
    # temp sweep) when PENROZ_JOURNAL_PATH is unset.
    from penroz_tpu.serve import tierstore
    tierstore.TIERS.recover()
    app = web.Application(middlewares=[request_id_middleware,
                                       error_middleware, gzip_middleware],
                          client_max_size=1024 ** 3)
    app.on_startup.append(_startup_observability)
    app.on_shutdown.append(_drain_on_shutdown)
    app.router.add_get("/", redirect_to_dashboard)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/readyz", readyz)
    app.router.add_get("/metrics", metrics_exposition)
    app.router.add_get("/trace/", trace_list)
    app.router.add_get("/trace/{request_id}", trace_detail)
    app.router.add_get("/dashboard", dashboard)
    app.router.add_get("/openapi.json", openapi_json)
    app.router.add_get("/docs", docs)
    app.router.add_post("/model/", create_model)
    app.router.add_post("/import/", import_from_huggingface)
    app.router.add_get("/dataset/", list_dataset)
    app.router.add_post("/dataset/", download_dataset)
    app.router.add_delete("/dataset/", delete_dataset)
    app.router.add_post("/tokenize/", tokenize_text)
    app.router.add_post("/output/", compute_model_output)
    app.router.add_post("/evaluate/", evaluate_model)
    app.router.add_post("/generate/", model_generate)
    app.router.add_get("/generate/{request_id}/stream", resume_stream)
    app.router.add_post("/generate_batch/", model_generate_batch)
    app.router.add_post("/decode/", decode_tokens)
    app.router.add_put("/train/", train_model)
    app.router.add_post("/profile/", profile)
    # Alias: profiler trace capture under the /profiler/ namespace (same
    # handler/semantics as /profile/ — start/stop a jax.profiler capture
    # whose timeline carries the penroz/sched_* span annotations).
    app.router.add_post("/profiler/trace/", profile)
    app.router.add_get("/progress/", model_progress)
    app.router.add_get("/stats/", model_stats)
    app.router.add_get("/serving_stats/", serving_stats)
    app.router.add_get("/memory/", memory_stats)
    app.router.add_get("/debug/dump", debug_dump)
    app.router.add_get("/tenants/", list_tenants)
    app.router.add_put("/tenants/{tenant_id}/quota", put_tenant_quota)
    app.router.add_get("/sessions/", list_sessions)
    app.router.add_delete("/sessions/{session_id}", delete_session)
    app.router.add_post("/adapters/", create_adapter)
    app.router.add_get("/adapters/", list_adapters)
    app.router.add_delete("/adapters/", delete_adapter)
    app.router.add_delete("/model/", delete_model)
    if os.path.isdir(STATIC_DIR):
        app.router.add_static("/static/", STATIC_DIR)
    return app


def _configure_logging():  # pragma: no cover
    """dictConfig from PENROZ_LOG_CONFIG (reference: main.py:503-506 loads
    log_config.json into uvicorn); fallback: basicConfig with the same
    processName-bearing format for DDP-style visibility."""
    import logging.config  # binds the submodule; `logging` itself is global
    config_path = os.environ.get("PENROZ_LOG_CONFIG")
    if config_path and os.path.exists(config_path):
        with open(config_path) as f:
            logging.config.dictConfig(json.load(f))
        return
    if config_path:
        import sys
        print(f"WARNING: PENROZ_LOG_CONFIG={config_path!r} does not exist; "
              "falling back to basicConfig", file=sys.stderr)
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s [%(processName)s] %(name)s: %(message)s")


def _configure_compile_cache():
    """Persistent XLA compile cache so server restarts skip the first
    compile of train/decode programs; placement rule in
    utils/compile_cache.py (``JAX_COMPILATION_CACHE_DIR`` wins)."""
    from penroz_tpu.utils import compile_cache
    log.info("Persistent compile cache: %s", compile_cache.configure())


def main(host: str = "127.0.0.1", port: int = 8000):  # pragma: no cover
    _configure_logging()
    _configure_compile_cache()
    from penroz_tpu.parallel import dist
    from penroz_tpu.utils import profiling
    dist.initialize()
    profiling.maybe_start_server()
    web.run_app(create_app(), host=host, port=port)


if __name__ == "__main__":  # pragma: no cover
    main(host=os.environ.get("HOST", "127.0.0.1"),
         port=int(os.environ.get("PORT", "8000")))
