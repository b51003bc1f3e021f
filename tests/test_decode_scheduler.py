"""Continuous-batching decode scheduler tests (serve/decode_scheduler.py).

Tier-1-safe: CPU, small shapes, no `slow` marker.  The parity contract is
the load-bearing one — every greedy sequence the scheduler returns must be
token-identical to the same request run alone through the legacy
single-sequence path, under concurrency, mid-flight admission, and slot
recycling.
"""

import asyncio
import queue
import threading
import time

import numpy as np
import pytest

from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import NeuralNetworkModel

# CI tier: heavier compiles (serving stack), same tier as test_app.
pytestmark = pytest.mark.runtime

BLOCK = 16
SGD = {"sgd": {"lr": 0.1}}


@pytest.fixture(autouse=True)
def _scheduler_registry(workdir):
    """Fresh engine registry + fault-injection counters + QoS quota state
    per test: engines cache model snapshots by id, and every test gets its
    own checkpoint dir (workdir)."""
    from penroz_tpu.ops import kv_cache as KV
    from penroz_tpu.serve import decode_scheduler, qos
    from penroz_tpu.utils import faults
    faults.reset()
    qos.reset()
    KV.reset_unpin_underflow_count()
    yield
    decode_scheduler.reset()
    faults.reset()
    qos.reset()
    KV.reset_unpin_underflow_count()


@pytest.fixture
def gpt_model(workdir, toy_gpt_layers):
    """A serialized toy GPT (attention + KV cache on the decode path)."""
    model = NeuralNetworkModel("schedgpt", Mapper(toy_gpt_layers, SGD))
    model.serialize(sync_flush=True)
    return model


@pytest.fixture
def make_engine():
    """Directly constructed engines (registry-bypassing tests) must not leak
    worker threads into later tests."""
    from penroz_tpu.serve import decode_scheduler
    engines = []

    def build(*args, **kwargs):
        engine = decode_scheduler.DecodeEngine(*args, **kwargs)
        engines.append(engine)
        return engine

    yield build
    for engine in engines:
        engine.shutdown()


class _Collector:
    """Thread-queue consumer for engine-level tests (the async layer is
    exercised separately through the HTTP routes)."""

    def __init__(self, prompt, hold_at=None):
        self.q = queue.Queue()
        self.tokens = list(prompt)
        self.received = 0
        # ``hold_at=n`` parks the engine's WORKER inside the delivery of
        # the n-th token (callbacks run on its thread, outside its lock)
        # until ``release`` is set: a test that needs "B arrives while A is
        # mid-decode" submits B in that window instead of racing A's
        # remaining tokens against the wall clock.
        self._hold_at = hold_at
        self._delivered = 0
        self.held = threading.Event()
        self.release = threading.Event()

    def on_event(self, kind, value):
        self.q.put((kind, value))
        if kind == "token":
            self._delivered += 1
            if self._delivered == self._hold_at:
                self.held.set()
                if not self.release.wait(timeout=120):
                    raise TimeoutError("test never released the worker")

    def result(self, timeout=180):
        deadline = time.monotonic() + timeout
        while True:
            kind, value = self.q.get(
                timeout=max(deadline - time.monotonic(), 0.1))
            if kind == "token":
                self.tokens.append(value)
                self.received += 1
            elif kind == "done":
                return self.tokens
            else:
                raise value


def _submit(engine, prompt, max_new, stop_token=None, timeout_ms=None,
            hold_at=None):
    from penroz_tpu.serve import decode_scheduler
    collector = _Collector(prompt, hold_at=hold_at)
    engine.submit(decode_scheduler.Request(prompt, max_new, stop_token,
                                           collector.on_event,
                                           timeout_ms=timeout_ms))
    return collector


def test_concurrent_parity_two_overlapping_requests(gpt_model, make_engine):
    """Two overlapping greedy requests through one shared batch return
    exactly the tokens each returns when run alone."""
    from penroz_tpu.serve import decode_scheduler
    p1, p2 = [1, 2, 3], [5]
    max_new = 6
    base1 = gpt_model.generate_tokens([p1], BLOCK, max_new, temperature=0.0)
    base2 = gpt_model.generate_tokens([p2], BLOCK, max_new, temperature=0.0)
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    c1 = _submit(engine, p1, max_new)
    c2 = _submit(engine, p2, max_new)
    assert c1.result() == base1
    assert c2.result() == base2


def test_mid_flight_admission(gpt_model, make_engine):
    """Request B admitted while A is mid-decode; both finish with their
    standalone token sequences (admission happens at a step boundary and
    prefills into a free row of the live batch)."""
    from penroz_tpu.serve import decode_scheduler
    pa, pb = [9, 10, 11], [4, 5]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 10, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 4, temperature=0.0)
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    ca = _submit(engine, pa, 10)
    deadline = time.monotonic() + 120
    while ca.received < 2:  # A provably mid-decode before B arrives
        assert time.monotonic() < deadline, "A never started decoding"
        try:
            kind, value = ca.q.get(timeout=1.0)
        except queue.Empty:
            continue
        assert kind == "token", kind
        ca.tokens.append(value)
        ca.received += 1
    cb = _submit(engine, pb, 4)
    assert cb.result() == base_b
    assert ca.result() == base_a
    assert engine.stats()["completed"] == 2


def test_slot_recycling_capacity_2_serves_4(gpt_model, make_engine):
    """A capacity-2 engine serves 4 requests: retired rows recycle their KV
    slot for the queued requests, all outputs match the standalone path."""
    from penroz_tpu.serve import decode_scheduler
    prompts = [[1, 2, 3], [5], [7, 8], [9, 10, 11, 12]]
    max_new = 5
    bases = [gpt_model.generate_tokens([p], BLOCK, max_new, temperature=0.0)
             for p in prompts]
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    collectors = [_submit(engine, p, max_new) for p in prompts]
    for base, collector in zip(bases, collectors):
        assert collector.result() == base
    stats = engine.stats()
    assert stats["capacity"] == 2
    assert stats["admissions"] == 4
    assert stats["completed"] == 4
    assert stats["decode_tokens"] > 0
    assert 0.0 < stats["occupancy_avg"] <= 1.0


def test_stop_token_retires_row_early(gpt_model, make_engine):
    from penroz_tpu.serve import decode_scheduler
    prompt, max_new = [1, 2, 3], 6
    base = gpt_model.generate_tokens([prompt], BLOCK, max_new,
                                     temperature=0.0)
    stop = base[len(prompt)]  # first generated token
    base_stop = gpt_model.generate_tokens([prompt], BLOCK, max_new,
                                          temperature=0.0, stop_token=stop)
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine, prompt, max_new, stop_token=stop).result() \
        == base_stop
    assert engine.stats()["completed"] == 1


def test_batch_overflow_rows_rejected_with_row_index(gpt_model):
    """Satellite: the batched path names the overflowing rows in its 400
    instead of silently truncating (no crop/re-prefill on that path)."""
    with pytest.raises(ValueError, match="row 1"):
        gpt_model.generate_tokens_batched([[1, 2], [1] * 14], BLOCK, 6,
                                          temperature=0.0)
    from penroz_tpu.models.model import validate_batch_generation
    with pytest.raises(ValueError, match="row 0"):
        validate_batch_generation([[1] * 15], BLOCK, 6)
    validate_batch_generation([[1] * 10], BLOCK, 6)  # exactly fits: ok


# -- HTTP surface ------------------------------------------------------------

@pytest.fixture
def client(workdir):
    from penroz_tpu.serve import app as app_mod
    app_mod.model_locks.clear()
    app_mod.dataset_locks.clear()
    from aiohttp.test_utils import TestClient, TestServer
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app_mod.create_app()), loop=loop)
    loop.run_until_complete(client.start_server())
    yield client, loop
    loop.run_until_complete(client.close())
    loop.close()


def _json(client_loop, method, path, **kw):
    client, loop = client_loop

    async def go():
        resp = await client.request(method, path, **kw)
        import json as _json_mod
        body = await resp.read()
        return resp.status, (_json_mod.loads(body) if body else None)

    return loop.run_until_complete(go())


def _wait_stats(client_loop, predicate, what, timeout=60):
    """Poll ``/serving_stats/`` until ``predicate(stats)`` holds: for what
    the worker does on its own thread AFTER it answered the request (the
    response alone does not order it)."""
    deadline = time.monotonic() + timeout
    while True:
        _, stats = _json(client_loop, "GET", "/serving_stats/")
        if predicate(stats):
            return stats
        assert time.monotonic() < deadline, f"{what}: {stats}"
        time.sleep(0.02)


def _gen_payload(**overrides):
    payload = {"model_id": "schedgpt", "input": [[1, 2, 3]],
               "block_size": BLOCK, "max_new_tokens": 4, "temperature": 0.0}
    payload.update(overrides)
    return payload


def test_generate_routes_through_scheduler(client, gpt_model, monkeypatch):
    """With PENROZ_CONTINUOUS_BATCHING=1 the /generate/ response is
    token-identical to the legacy path, /serving_stats/ reports the engine,
    and concurrent requests coalesce into the shared batch."""
    status, legacy = _json(client, "POST", "/generate/",
                           json=_gen_payload())
    assert status == 200
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    status, routed = _json(client, "POST", "/generate/",
                           json=_gen_payload())
    assert status == 200
    assert routed["tokens"] == legacy["tokens"]

    # concurrent requests, each equal to its solo baseline
    test_client, loop = client

    async def one(i):
        resp = await test_client.post(
            "/generate/", json=_gen_payload(input=[[1 + i, 2]]))
        body = await resp.json()
        assert resp.status == 200, body
        return body["tokens"]

    async def run_all():
        return await asyncio.gather(*[one(i) for i in range(3)])

    concurrent = loop.run_until_complete(run_all())
    monkeypatch.delenv("PENROZ_CONTINUOUS_BATCHING")
    for i, row in enumerate(concurrent):
        status, solo = _json(client, "POST", "/generate/",
                             json=_gen_payload(input=[[1 + i, 2]]))
        assert solo["tokens"] == row

    status, stats = _json(client, "GET", "/serving_stats/")
    assert status == 200
    assert stats["engines"], stats
    engine = stats["engines"][0]
    assert engine["model_id"] == "schedgpt"
    assert engine["completed"] >= 4
    assert stats["decode_tokens_per_sec"] >= 0
    assert "kv_pool_capacity_drops" in stats
    assert stats["admission_latency_ms_p50"] is not None


def test_generate_streaming_through_scheduler(client, gpt_model,
                                              monkeypatch):
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    test_client, loop = client

    async def go():
        resp = await test_client.post("/generate/",
                                      json=_gen_payload(stream=True))
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        return (await resp.read()).decode()

    body = loop.run_until_complete(go())
    streamed = [int(line) for line in body.strip().split("\n")]
    monkeypatch.delenv("PENROZ_CONTINUOUS_BATCHING")
    status, legacy = _json(client, "POST", "/generate/",
                           json=_gen_payload())
    assert streamed == legacy["tokens"][3:]  # generated tail only


def test_generate_batch_through_scheduler(client, gpt_model, monkeypatch):
    payload = {"model_id": "schedgpt", "inputs": [[1, 2, 3], [5]],
               "block_size": BLOCK, "max_new_tokens": 4, "temperature": 0.0}
    status, legacy = _json(client, "POST", "/generate_batch/", json=payload)
    assert status == 200
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    status, routed = _json(client, "POST", "/generate_batch/", json=payload)
    assert status == 200
    assert routed["sequences"] == legacy["sequences"]
    # per-row overflow → 400 naming the row, scheduler path included
    status, body = _json(client, "POST", "/generate_batch/", json=dict(
        payload, inputs=[[1, 2], [1] * 14]))
    assert status == 400
    assert "row 1" in body["detail"]


def test_serving_stats_disabled_and_openapi(client, workdir):
    """/serving_stats/ answers even with the scheduler off, and the OpenAPI
    spec documents the endpoint + response schema."""
    status, stats = _json(client, "GET", "/serving_stats/")
    assert status == 200
    assert stats["continuous_batching_enabled"] is False
    assert stats["engines"] == []
    assert stats["kv_pool_capacity_drops"] >= 0
    # fault-tolerance aggregates are present from day zero
    assert stats["queue_rejections"] == 0
    assert stats["deadline_timeouts"] == 0
    assert stats["breaker_open"] is False
    assert stats["crashes_total"] == 0
    assert stats["draining"] is False
    # speculative-decoding aggregates present from day zero
    assert stats["spec_decode_enabled"] is False
    assert stats["spec_accept_rate"] is None
    assert stats["tokens_per_decode_step"] == 0.0
    status, spec = _json(client, "GET", "/openapi.json")
    assert "/serving_stats/" in spec["paths"]
    assert "/healthz" in spec["paths"]
    assert "/readyz" in spec["paths"]
    assert "ServingStatsResponse" in spec["components"]["schemas"]
    gen = spec["paths"]["/generate/"]["post"]["responses"]
    assert {"429", "503", "504"} <= set(gen)


def test_oversized_request_falls_back_to_legacy_path(client, gpt_model,
                                                     monkeypatch):
    """A prompt+max_new that exceeds block_size is NOT scheduler-eligible
    (no crop/re-prefill in the shared batch) — it must still succeed via
    the legacy path's crop/re-prefill loop."""
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    status, body = _json(client, "POST", "/generate/", json=_gen_payload(
        input=[[1, 2, 3, 4, 5]], max_new_tokens=14))
    assert status == 200
    assert len(body["tokens"]) == 19
    status, stats = _json(client, "GET", "/serving_stats/")
    assert stats["engines"] == []  # never touched the scheduler


# -- chunked prefill + radix prefix-KV cache (PR 2) --------------------------

@pytest.fixture
def prefix_env(monkeypatch):
    """Paged pool + radix prefix cache + small chunks, sized to BLOCK=16
    toy prompts (page = 4 tokens, cache region = 8 pages)."""
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    monkeypatch.setenv("PENROZ_PREFIX_CACHE", "1")
    monkeypatch.setenv("PENROZ_PREFIX_CACHE_PAGES", "8")
    monkeypatch.setenv("PENROZ_PREFILL_CHUNK", "4")
    return monkeypatch


def test_chunked_prefill_parity_and_stall_bound(gpt_model, make_engine,
                                                monkeypatch):
    """A long prompt admitted mid-flight is prefilled in chunks interleaved
    with the shared decode steps: both requests keep their standalone
    greedy streams, and the decode batch is never stalled by more than ONE
    chunk between consecutive steps (the acceptance bound; the admission
    latency p50 reflects that interleaving instead of a full-prompt
    stall)."""
    monkeypatch.setenv("PENROZ_PREFILL_CHUNK", "2")
    pa, pb = [5], [9, 10, 11, 12, 13, 14, 15]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 8, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 6, temperature=0.0)
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    # A provably mid-decode when B arrives: the worker is parked inside the
    # delivery of A's second token until B is queued (waiting on the wall
    # clock instead let A finish first under a loaded machine, and then no
    # decode step ran between B's chunks)
    ca = _submit(engine, pa, 8, hold_at=2)
    assert ca.held.wait(timeout=120), "A never started decoding"
    cb = _submit(engine, pb, 6)
    ca.release.set()
    assert cb.result() == base_b
    assert ca.result() == base_a
    stats = engine.stats()
    # chunk plans: A = [1], B = [2, 2, 2, 1] (pow-2-bucketed tail)
    assert stats["prefill_chunks"] == 5
    # the acceptance bound: at most one chunk ever ran between two decode
    # steps (PENROZ_SCHED_MAX_STALL_MS defaults to 0)
    assert stats["prefill_max_chunks_between_steps"] == 1
    assert stats["prefill_chunk_stall_ms_p99"] is not None
    assert stats["admission_latency_ms_p50"] is not None
    assert stats["admission_latency_ms_p50"] > 0


def test_chunked_vs_oneshot_prefill_identical(gpt_model, make_engine,
                                              monkeypatch):
    """Greedy parity between one-dispatch prefill (chunk >= prompt, pow-2
    prompt length) and many-chunk prefill of the same prompt."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]  # 8 = one chunk at PENROZ_PREFILL_CHUNK=8
    base = gpt_model.generate_tokens([prompt], BLOCK, 6, temperature=0.0)
    monkeypatch.setenv("PENROZ_PREFILL_CHUNK", "8")
    one_shot = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(one_shot, prompt, 6).result() == base
    assert one_shot.stats()["prefill_chunks"] == 1
    monkeypatch.setenv("PENROZ_PREFILL_CHUNK", "2")
    chunked = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(chunked, prompt, 6).result() == base
    assert chunked.stats()["prefill_chunks"] == 4


def test_prefix_cache_hit_miss_parity(gpt_model, make_engine, prefix_env):
    """The greedy parity matrix over the radix cache: (miss), (hit on a
    different suffix), (repeat hit) — every stream token-identical to the
    standalone path, with the hits aliasing the shared prefix's pages
    (hit_tokens counts the skipped prefill)."""
    from penroz_tpu.serve import metrics as serve_metrics
    prefix = [1, 2, 3, 4, 5, 6, 7, 8]          # 2 full pages
    px, py = prefix + [9, 10], prefix + [11]
    base_x = gpt_model.generate_tokens([px], BLOCK, 4, temperature=0.0)
    base_y = gpt_model.generate_tokens([py], BLOCK, 4, temperature=0.0)
    hits0 = serve_metrics.PREFIX_HITS.value()
    misses0 = serve_metrics.PREFIX_MISSES.value()
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine, px, 4).result() == base_x   # miss
    assert _submit(engine, py, 4).result() == base_y   # hit (shared prefix)
    assert _submit(engine, px, 4).result() == base_x   # repeat hit
    pc = engine.stats()["prefix_cache"]
    assert pc["misses"] == 1 and pc["hits"] == 2, pc
    assert pc["hit_tokens"] == 16  # 2 pages x 4 tokens x 2 hits
    assert pc["hit_rate"] == pytest.approx(2 / 3)
    # /metrics counts the same admissions
    assert serve_metrics.PREFIX_HITS.value() - hits0 == 2
    assert serve_metrics.PREFIX_MISSES.value() - misses0 == 1


def test_prefix_cache_eviction_then_rematch_parity(gpt_model, make_engine,
                                                   prefix_env):
    """Eviction correctness: churn distinct prefixes through a 4-page cache
    region until the first prefix is LRU-evicted, then resubmit it — the
    re-prefilled (and re-registered) stream is token-identical."""
    prefix_env.setenv("PENROZ_PREFIX_CACHE_PAGES", "4")
    pa = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 4, temperature=0.0)
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine, pa, 4).result() == base_a
    for j in range(3):  # 3 distinct 2-page prefixes overflow 4 pages
        p = [20 + j] * 8 + [j]
        base = gpt_model.generate_tokens([p], BLOCK, 3, temperature=0.0)
        assert _submit(engine, p, 3).result() == base
    pc = engine.stats()["prefix_cache"]
    assert pc["evicted_pages"] > 0, pc
    assert _submit(engine, pa, 4).result() == base_a  # evicted → recompute
    pc = engine.stats()["prefix_cache"]
    assert pc["capacity_pages"] == 4


def test_serving_stats_reports_prefix_and_chunk_fields(client, gpt_model,
                                                       prefix_env):
    """/serving_stats/ carries the new observability: prefix-cache hit
    rate + evictions and the prefill chunk-stall p99, per engine and
    aggregated (dashboard tile inputs), validated against the schema."""
    prefix_env.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    payload = _gen_payload(input=[[1, 2, 3, 4, 5, 6, 7, 8, 9]])
    status, first = _json(client, "POST", "/generate/", json=payload)
    assert status == 200
    status, second = _json(client, "POST", "/generate/", json=payload)
    assert status == 200
    assert second["tokens"] == first["tokens"]
    status, stats = _json(client, "GET", "/serving_stats/")
    assert status == 200
    assert stats["prefix_cache_hit_rate"] == pytest.approx(0.5)
    assert stats["prefix_cache_evicted_pages"] == 0
    assert "prefill_chunk_stall_ms_p99" in stats
    engine = stats["engines"][0]
    assert engine["prefill_chunks"] >= 2
    assert engine["prefix_cache"]["hits"] == 1
    assert engine["prefix_cache"]["misses"] == 1
    assert engine["prefix_cache"]["hit_tokens"] == 8
    assert engine["prefill_max_chunks_between_steps"] <= 1


# -- fault tolerance: deadlines, backpressure, crash recovery (PR 3) --------

def _wait_tokens(collector, n, timeout=120):
    """Drain collector events until ``n`` tokens arrived (so the request is
    provably mid-decode)."""
    deadline = time.monotonic() + timeout
    while collector.received < n:
        assert time.monotonic() < deadline, "request never started decoding"
        try:
            kind, value = collector.q.get(timeout=1.0)
        except queue.Empty:
            continue
        assert kind == "token", kind
        collector.tokens.append(value)
        collector.received += 1


def test_step_crash_fails_all_cleanly_then_recovers_with_parity(
        gpt_model, make_engine, monkeypatch):
    """THE acceptance path: an injected decode.step crash fails every
    waiting request with a clean (typed) error, the engine fully resets
    its KV/prefix state, and the very next request completes with greedy
    output identical to the no-crash path."""
    from penroz_tpu.utils import faults
    pa, pb = [1, 2, 3], [5]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 6, temperature=0.0)
    monkeypatch.setenv(faults.ENV, "decode.step:raise@1")
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    c1 = _submit(engine, pa, 6)
    c2 = _submit(engine, pb, 6)
    with pytest.raises(faults.InjectedFault):
        c1.result()
    with pytest.raises(faults.InjectedFault):
        c2.result()
    monkeypatch.delenv(faults.ENV)
    faults.reset()
    # next request: same engine object, post-reset state, token-identical
    assert _submit(engine, pa, 6).result() == base_a
    stats = engine.stats()
    assert stats["crashes_total"] == 1
    assert stats["engine_resets"] == 1
    assert stats["consecutive_crashes"] == 0  # success zeroed it
    assert stats["breaker_open"] is False
    assert engine.active_rows == 0


def test_prefill_chunk_crash_recovers_with_parity(gpt_model, make_engine,
                                                  monkeypatch):
    """Same recovery contract for the second tick site: a crash inside an
    admission prefill chunk."""
    from penroz_tpu.utils import faults
    prompt = [9, 10, 11, 12]
    base = gpt_model.generate_tokens([prompt], BLOCK, 5, temperature=0.0)
    monkeypatch.setenv(faults.ENV, "decode.prefill_chunk:raise@1")
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    with pytest.raises(faults.InjectedFault):
        _submit(engine, prompt, 5).result()
    monkeypatch.delenv(faults.ENV)
    faults.reset()
    assert _submit(engine, prompt, 5).result() == base
    assert engine.stats()["engine_resets"] == 1


def test_queue_full_sheds_while_inflight_keeps_parity(gpt_model,
                                                      make_engine,
                                                      monkeypatch):
    """PENROZ_SCHED_MAX_QUEUE bounds admission: with the row busy and the
    queue full, submit raises QueueFullError immediately — and neither the
    in-flight nor the queued request's tokens change (no cross-request
    corruption under shedding)."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.serve import metrics as serve_metrics
    from penroz_tpu.utils import faults
    pa, pb, pc = [1, 2, 3], [5], [7, 8]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 6, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 4, temperature=0.0)
    monkeypatch.setenv(decode_scheduler.MAX_QUEUE_ENV, "1")
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@80")  # slow decode
    shed0 = serve_metrics.QUEUE_REJECTIONS.value()
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=1)
    ca = _submit(engine, pa, 6)
    _wait_tokens(ca, 1)          # A admitted: pending queue is empty
    cb = _submit(engine, pb, 4)  # queued (row busy) — fills the queue
    with pytest.raises(decode_scheduler.QueueFullError):
        _submit(engine, pc, 4)
    assert ca.result() == base_a
    assert cb.result() == base_b
    stats = engine.stats()
    assert stats["queue_rejections"] == 1
    assert serve_metrics.QUEUE_REJECTIONS.value() - shed0 == 1
    assert stats["queue_wait_ms_p99"] is not None


def test_deadline_expires_while_queued(gpt_model, make_engine, monkeypatch):
    """A queued request whose deadline passes before a row frees is shed
    with a 'queued'-phase DeadlineExceeded — before any prefill — while
    the in-flight request keeps its exact stream."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    pa, pb = [1, 2, 3], [5]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 8, temperature=0.0)
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@80")
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=1)
    ca = _submit(engine, pa, 8)
    _wait_tokens(ca, 1)
    cb = _submit(engine, pb, 4, timeout_ms=150)
    with pytest.raises(decode_scheduler.DeadlineExceeded) as exc:
        cb.result()
    assert exc.value.phase == "queued"
    assert cb.received == 0      # shed before prefill ever ran
    assert ca.result() == base_a
    assert engine.stats()["deadline_timeouts"] == 1


def test_deadline_expires_in_flight_retires_at_boundary(gpt_model,
                                                        make_engine,
                                                        monkeypatch):
    """An in-flight deadline retires the row at the next step boundary:
    the tokens produced so far were delivered, then the stream ends with a
    timeout event — and the engine immediately serves the next request."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    prompt = [1, 2, 3]
    base = gpt_model.generate_tokens([prompt], BLOCK, 4, temperature=0.0)
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@100")
    # Per-token deadline granularity is the n=1 contract: with supersteps
    # the sleep fires once per fused dispatch and the deadline is only
    # observed at block boundaries (covered by the dedicated superstep
    # deadline test below).
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "1")
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=1)
    c = _submit(engine, prompt, 50, timeout_ms=350)
    with pytest.raises(decode_scheduler.DeadlineExceeded) as exc:
        c.result()
    assert exc.value.phase == "inflight"
    assert 1 <= c.received < 50
    assert engine.active_rows == 0
    assert _submit(engine, prompt, 4).result() == base
    assert engine.stats()["deadline_timeouts"] == 1


def test_circuit_breaker_opens_after_consecutive_crashes_then_probe_closes(
        gpt_model, make_engine, monkeypatch):
    """PENROZ_ENGINE_MAX_CRASHES consecutive crashes open the breaker:
    submits are refused with CircuitOpenError during the cooldown, then
    ONE probe request is admitted and its success closes the breaker."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    prompt = [1, 2, 3]
    base = gpt_model.generate_tokens([prompt], BLOCK, 5, temperature=0.0)
    monkeypatch.setenv(decode_scheduler.MAX_CRASHES_ENV, "2")
    monkeypatch.setenv(decode_scheduler.BREAKER_COOLDOWN_ENV, "400")
    monkeypatch.setenv(faults.ENV,
                       "decode.step:raise@1,decode.step:raise@2")
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    with pytest.raises(faults.InjectedFault):
        _submit(engine, prompt, 5).result()          # crash 1
    assert engine.stats()["breaker_open"] is False
    assert engine.stats()["consecutive_crashes"] == 1
    with pytest.raises(faults.InjectedFault):
        _submit(engine, prompt, 5).result()          # crash 2 → breaker
    assert engine.stats()["breaker_open"] is True
    with pytest.raises(decode_scheduler.CircuitOpenError):
        _submit(engine, prompt, 5)                   # cooldown: refused
    time.sleep(0.5)                                  # cooldown elapses
    assert _submit(engine, prompt, 5).result() == base  # probe succeeds
    stats = engine.stats()
    assert stats["breaker_open"] is False            # probe closed it
    assert stats["consecutive_crashes"] == 0
    assert stats["crashes_total"] == 2
    assert stats["breaker_rejections"] == 1


def test_cancellation_frees_row_mid_flight(gpt_model, make_engine,
                                           monkeypatch):
    """req.cancelled (the client-disconnect signal) retires the row at the
    next boundary instead of decoding to max_new_tokens, and the slot
    serves the next request with exact parity."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    pa, pb = [1, 2, 3], [5]
    base_b = gpt_model.generate_tokens([pb], BLOCK, 5, temperature=0.0)
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@60")
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=1)
    collector = _Collector(pa)
    req = decode_scheduler.Request(pa, 50, None, collector.on_event)
    engine.submit(req)
    _wait_tokens(collector, 2)
    req.cancelled = True
    deadline = time.monotonic() + 30
    while engine.active_rows and time.monotonic() < deadline:
        time.sleep(0.02)
    assert engine.active_rows == 0
    assert collector.received < 50   # provably did not run to completion
    assert _submit(engine, pb, 5).result() == base_b


def test_graceful_shutdown_drains_inflight_rows(gpt_model, make_engine,
                                                monkeypatch):
    """shutdown(drain_s=...) lets the in-flight request finish (every
    token delivered, done event sent) before the worker joins, and
    reports the successful join (returns True) — the satellite contract."""
    from penroz_tpu.utils import faults
    prompt = [1, 2, 3]
    base = gpt_model.generate_tokens([prompt], BLOCK, 6, temperature=0.0)
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@40")
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    c = _submit(engine, prompt, 6)
    _wait_tokens(c, 1)
    assert engine.shutdown(timeout=30.0, drain_s=30.0) is True
    assert c.result(timeout=5) == base   # drained, not killed


def test_shutdown_reports_failed_join(gpt_model, make_engine, monkeypatch):
    """A worker thread that cannot join within the timeout is REPORTED
    (False + log) instead of silently leaked — satellite fix for the old
    fire-and-forget join."""
    from penroz_tpu.utils import faults
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@1500")
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=1)
    c = _submit(engine, [1, 2], 2)
    _wait_tokens(c, 1)               # worker is now inside the slow step
    assert engine.shutdown(timeout=0.2) is False
    # the fixture's teardown shutdown() joins for real once the step ends


def test_max_stall_budget_runs_multiple_chunks(gpt_model, make_engine,
                                               monkeypatch):
    """PENROZ_SCHED_MAX_STALL_MS > 0 trades inter-token latency for
    admission speed: with a generous budget, several chunks run between
    decode steps (the default budget of 0 pins that at one)."""
    monkeypatch.setenv("PENROZ_PREFILL_CHUNK", "1")
    monkeypatch.setenv("PENROZ_SCHED_MAX_STALL_MS", "60000")
    pa, pb = [5], [9, 10, 11, 12, 13, 14]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 8, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 4, temperature=0.0)
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    # B is queued while the worker sits in the delivery of A's first token,
    # so it is admitted at the next boundary with 6 of A's 8 tokens still
    # to come: its chunks provably run against a decoding row.
    ca = _submit(engine, pa, 8, hold_at=1)
    assert ca.held.wait(timeout=120), "A never started decoding"
    cb = _submit(engine, pb, 4)
    ca.release.set()
    assert cb.result() == base_b
    assert ca.result() == base_a
    # all 6 of B's 1-token chunks fit one boundary under the huge budget
    assert engine.stats()["prefill_max_chunks_between_steps"] == 6


# -- fault tolerance over HTTP (429/504/503, lifecycle endpoints) ------------

def test_http_queue_full_429_with_retry_after(client, gpt_model,
                                              monkeypatch):
    """Queue-full sheds 429 + Retry-After while the in-flight and queued
    requests keep token-identical greedy outputs (the acceptance's
    no-corruption-under-shedding clause, end to end)."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    pa, pb = [1, 2, 3], [5]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 8, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 4, temperature=0.0)
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    monkeypatch.setenv(decode_scheduler.MAX_ROWS_ENV, "1")
    monkeypatch.setenv(decode_scheduler.MAX_QUEUE_ENV, "1")
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@80")
    test_client, loop = client

    async def go():
        task_a = asyncio.ensure_future(test_client.post(
            "/generate/", json=_gen_payload(input=[pa], max_new_tokens=8)))
        # wait until A occupies the row (pending queue empty again)
        for _ in range(200):
            stats = await (await test_client.get("/serving_stats/")).json()
            if stats["active_rows"] >= 1 and stats["queue_depth"] == 0:
                break
            await asyncio.sleep(0.02)
        else:
            raise AssertionError("A never admitted")
        task_b = asyncio.ensure_future(test_client.post(
            "/generate/", json=_gen_payload(input=[pb], max_new_tokens=4)))
        for _ in range(200):
            stats = await (await test_client.get("/serving_stats/")).json()
            if stats["queue_depth"] >= 1:
                break
            await asyncio.sleep(0.02)
        else:
            raise AssertionError("B never queued")
        resp_c = await test_client.post(
            "/generate/", json=_gen_payload(input=[[7, 8]],
                                            max_new_tokens=4))
        resp_a, resp_b = await task_a, await task_b
        return (resp_a.status, await resp_a.json(),
                resp_b.status, await resp_b.json(),
                resp_c.status, await resp_c.json(),
                resp_c.headers.get("Retry-After"))

    a_status, a_body, b_status, b_body, c_status, c_body, retry = \
        loop.run_until_complete(go())
    assert a_status == 200 and a_body["tokens"] == base_a
    assert b_status == 200 and b_body["tokens"] == base_b
    assert c_status == 429, c_body
    assert "overloaded" in c_body["detail"]
    assert retry is not None
    _, stats = _json(client, "GET", "/serving_stats/")
    assert stats["queue_rejections"] == 1


def test_http_deadline_504_queued_and_inflight(client, gpt_model,
                                               monkeypatch):
    """timeout_ms maps to 504 in both phases: shed from the queue while a
    slow request holds the row, and expired mid-flight afterwards — the
    concurrent in-flight request's tokens stay exact."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    pa = [1, 2, 3]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 8, temperature=0.0)
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    monkeypatch.setenv(decode_scheduler.MAX_ROWS_ENV, "1")
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@80")
    # Per-token deadline granularity is the n=1 contract (see the
    # superstep deadline test for the boundary-granularity behavior).
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "1")
    test_client, loop = client

    async def go():
        task_a = asyncio.ensure_future(test_client.post(
            "/generate/", json=_gen_payload(input=[pa], max_new_tokens=8)))
        for _ in range(200):
            stats = await (await test_client.get("/serving_stats/")).json()
            if stats["active_rows"] >= 1:
                break
            await asyncio.sleep(0.02)
        # queued-phase 504: B can't get the row within its 100ms budget
        resp_q = await test_client.post(
            "/generate/", json=_gen_payload(input=[[5]], max_new_tokens=4,
                                            timeout_ms=100))
        resp_a = await task_a
        # inflight-phase 504: row is free now; the deadline expires
        # mid-generation (slow steps, many tokens)
        resp_i = await test_client.post(
            "/generate/", json=_gen_payload(input=[[7]], max_new_tokens=14,
                                            timeout_ms=300))
        return (resp_q.status, await resp_q.json(), resp_a.status,
                await resp_a.json(), resp_i.status, await resp_i.json())

    q_status, q_body, a_status, a_body, i_status, i_body = \
        loop.run_until_complete(go())
    assert q_status == 504 and "queued" in q_body["detail"]
    assert a_status == 200 and a_body["tokens"] == base_a
    assert i_status == 504, i_body
    _, stats = _json(client, "GET", "/serving_stats/")
    assert stats["deadline_timeouts"] == 2


def test_http_stream_deadline_emits_timeout_line(client, gpt_model,
                                                 monkeypatch):
    """A streaming request whose deadline expires mid-flight delivers the
    tokens produced so far, then a literal 'timeout' line, then ends."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@100")
    # Per-token deadline granularity is the n=1 contract (see the
    # superstep deadline test for the boundary-granularity behavior).
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "1")
    test_client, loop = client

    async def go():
        resp = await test_client.post("/generate/", json=_gen_payload(
            input=[[1, 2]], max_new_tokens=13, stream=True, timeout_ms=350))
        assert resp.status == 200
        return (await resp.read()).decode()

    lines = loop.run_until_complete(go()).strip().split("\n")
    assert lines[-1] == "timeout"
    assert 1 <= len(lines) - 1 < 13
    assert all(line.isdigit() for line in lines[:-1])


def test_http_breaker_503_readyz_and_probe_recovery(client, gpt_model,
                                                    monkeypatch):
    """The breaker acceptance, end to end: N injected crashes → 503 from
    the scheduler path + /readyz not ready; after the cooldown one probe
    request succeeds with exact greedy parity and /readyz recovers."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    prompt = [1, 2, 3]
    base = gpt_model.generate_tokens([prompt], BLOCK, 4, temperature=0.0)
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    monkeypatch.setenv(decode_scheduler.MAX_CRASHES_ENV, "1")
    monkeypatch.setenv(decode_scheduler.BREAKER_COOLDOWN_ENV, "100000")
    monkeypatch.setenv(faults.ENV, "decode.step:raise@1")

    status, body = _json(client, "POST", "/generate/", json=_gen_payload())
    assert status == 500                     # the injected crash itself

    status, body = _json(client, "GET", "/readyz")
    assert status == 503
    assert body["ready"] is False
    assert body["breaker_open_engines"] == ["schedgpt"]
    status, _ = _json(client, "GET", "/healthz")
    assert status == 200                     # liveness unaffected

    status, body = _json(client, "POST", "/generate/", json=_gen_payload())
    assert status == 503                     # breaker sheds during cooldown
    assert "circuit breaker" in body["detail"]

    # the breaker opens before the crashed request is failed; the reset
    # runs after it, on the worker's thread
    stats = _wait_stats(client, lambda s: s["engine_resets"] == 1,
                        "engine never reset after the crash")
    assert stats["breaker_open"] is True
    assert stats["crashes_total"] == 1

    # cooldown over (0ms), fault disarmed: the next request is the probe
    monkeypatch.setenv(decode_scheduler.BREAKER_COOLDOWN_ENV, "0")
    monkeypatch.delenv(faults.ENV)
    from penroz_tpu.utils import faults as _f
    _f.reset()
    status, body = _json(client, "POST", "/generate/", json=_gen_payload())
    assert status == 200
    assert body["tokens"] == base            # post-reset greedy parity
    status, body = _json(client, "GET", "/readyz")
    assert status == 200 and body["ready"] is True
    _, stats = _json(client, "GET", "/serving_stats/")
    assert stats["breaker_open"] is False


def test_http_breaker_fallback_to_legacy_path(client, gpt_model,
                                              monkeypatch):
    """PENROZ_SCHED_FALLBACK=1 degrades an open-breaker request to the
    pre-PR-1 single-sequence path (200 + exact tokens) instead of 503."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    base = gpt_model.generate_tokens([[1, 2, 3]], BLOCK, 4, temperature=0.0)
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    monkeypatch.setenv(decode_scheduler.MAX_CRASHES_ENV, "1")
    monkeypatch.setenv(decode_scheduler.BREAKER_COOLDOWN_ENV, "100000")
    monkeypatch.setenv(faults.ENV, "decode.step:raise@1")
    status, _ = _json(client, "POST", "/generate/", json=_gen_payload())
    assert status == 500                     # crash opens the breaker
    monkeypatch.setenv(decode_scheduler.FALLBACK_ENV, "1")
    status, body = _json(client, "POST", "/generate/", json=_gen_payload())
    assert status == 200                     # degraded, not refused
    assert body["tokens"] == base
    _, stats = _json(client, "GET", "/serving_stats/")
    assert stats["breaker_open"] is True     # breaker itself stays open


def test_healthz_readyz_and_draining(client, workdir, monkeypatch):
    """Lifecycle endpoints: /healthz always 200; /readyz 200 when clean,
    503 while the scheduler registry is draining for shutdown."""
    from penroz_tpu.serve import decode_scheduler
    status, body = _json(client, "GET", "/healthz")
    assert status == 200 and body["status"] == "ok"
    status, body = _json(client, "GET", "/readyz")
    assert status == 200 and body["ready"] is True
    monkeypatch.setattr(decode_scheduler, "_DRAINING", True)
    status, body = _json(client, "GET", "/readyz")
    assert status == 503 and body["draining"] is True
    _, stats = _json(client, "GET", "/serving_stats/")
    assert stats["draining"] is True


# -- speculative decoding: prompt-lookup drafts + verify steps (PR 4) --------

REP_PROMPT = [1, 2, 3, 1, 2, 3, 1, 2]   # repetitive text: 2 pages of 4


@pytest.fixture
def spec_env(monkeypatch):
    """Spec decode on, with the aggressive 1-gram matcher so toy streams
    (which lock into short cycles) draft early."""
    monkeypatch.setenv("PENROZ_SPEC_DECODE", "1")
    monkeypatch.setenv("PENROZ_SPEC_NGRAM", "1")
    return monkeypatch


def _oracle_drafter(bases):
    """Draft the exact greedy continuation (from the precomputed standalone
    sequences) — deterministic full acceptance, so the verify/rollback
    path provably runs and multi-token emission is exercised."""
    def propose(history, k, n):
        for base in bases:
            if len(history) < len(base) and history == base[:len(history)]:
                return [int(t) for t in base[len(history):len(history) + k]]
        return []
    return propose


@pytest.mark.parametrize("paged_prefix,int8,chunk", [
    (paged, int8, chunk)
    for paged in (0, 1) for int8 in (0, 1) for chunk in ("16", "2")])
def test_spec_parity_matrix(gpt_model, make_engine, monkeypatch,
                            paged_prefix, int8, chunk):
    """THE acceptance matrix: greedy outputs with PENROZ_SPEC_DECODE=1 are
    token-identical to spec-off across prefix cache on/off, int8 KV
    on/off (all four cache variants) and chunked/one-shot prefill — with
    the verify path provably engaged (oracle drafts, full acceptance)."""
    from penroz_tpu.serve import metrics as serve_metrics
    from penroz_tpu.serve import spec_decode
    if paged_prefix:
        monkeypatch.setenv("PAGED_KV_CACHE", "1")
        monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
        monkeypatch.setenv("PENROZ_PREFIX_CACHE", "1")
        monkeypatch.setenv("PENROZ_PREFIX_CACHE_PAGES", "8")
    if int8:
        monkeypatch.setenv("TURBO_QUANT_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_PREFILL_CHUNK", chunk)
    monkeypatch.setenv("PENROZ_SPEC_DECODE", "1")
    # spec-off baseline: the legacy path under the same KV env flags
    base = gpt_model.generate_tokens([REP_PROMPT], BLOCK, 6,
                                     temperature=0.0)
    monkeypatch.setattr(spec_decode, "propose", _oracle_drafter([base]))
    drafted0 = serve_metrics.SPEC_DRAFTED.value()
    accepted0 = serve_metrics.SPEC_ACCEPTED.value()
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine, REP_PROMPT, 6).result() == base
    # second request: a prefix-cache HIT when the cache is on
    assert _submit(engine, REP_PROMPT, 6).result() == base
    stats = engine.stats()
    assert stats["spec_decode"] is True
    assert stats["spec_verify_steps"] > 0
    assert stats["spec_drafted_tokens"] > 0
    assert stats["spec_accept_rate"] == 1.0          # oracle drafts
    assert stats["tokens_per_decode_step"] > 1.0
    # /metrics counts the same drafts
    assert serve_metrics.SPEC_DRAFTED.value() - drafted0 \
        == stats["spec_drafted_tokens"]
    assert serve_metrics.SPEC_ACCEPTED.value() - accepted0 \
        == stats["spec_accepted_tokens"]
    if paged_prefix:
        assert stats["prefix_cache"]["hits"] >= 1


def test_spec_real_drafter_parity(gpt_model, make_engine, spec_env):
    """The real prompt-lookup drafter (no oracle): repetitive prompt +
    1-gram matching — parity is exact whatever the accept rate lands at,
    and drafting provably engaged on the toy stream's cycles."""
    prompt = [1, 2, 3, 1, 2]
    base = gpt_model.generate_tokens([prompt], BLOCK, 11, temperature=0.0)
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine, prompt, 11).result() == base
    stats = engine.stats()
    assert stats["spec_drafted_tokens"] > 0
    assert 0.0 <= stats["spec_accept_rate"] <= 1.0


def test_spec_adversarial_drafter_zero_accept_keeps_parity(
        gpt_model, make_engine, spec_env):
    """An always-wrong drafter costs accept rate, never correctness: every
    draft token is rejected (accept_rate == 0), each verify step's bonus
    token still advances the row, and the stream is token-identical."""
    from penroz_tpu.serve import spec_decode
    base = gpt_model.generate_tokens([REP_PROMPT], BLOCK, 6,
                                     temperature=0.0)

    def wrong(history, k, n):
        nxt = base[len(history)] if len(history) < len(base) else 0
        return [(int(nxt) + 1) % 64] * min(k, 2)   # first token always wrong

    spec_env.setattr(spec_decode, "propose", wrong)
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine, REP_PROMPT, 6).result() == base
    stats = engine.stats()
    assert stats["spec_drafted_tokens"] > 0
    assert stats["spec_accepted_tokens"] == 0
    assert stats["spec_accept_rate"] == 0.0
    assert stats["tokens_per_decode_step"] == pytest.approx(1.0)


def test_spec_stop_token_inside_accepted_draft(gpt_model, make_engine,
                                               spec_env):
    """A stop token accepted mid-draft retires the row exactly where the
    plain path would: the tokens after the stop are discarded even though
    the verify step accepted them."""
    from penroz_tpu.serve import spec_decode
    # REP_PROMPT's greedy continuation is one token repeated, so its
    # "third" token is also the one prefill emits and no draft is ever
    # built; this prompt's third generated token differs from the first two.
    prompt = [5]
    base = gpt_model.generate_tokens([prompt], BLOCK, 8, temperature=0.0)
    stop = base[len(prompt) + 2]                   # third generated token
    assert stop not in base[len(prompt):len(prompt) + 2], base
    base_stop = gpt_model.generate_tokens([prompt], BLOCK, 8,
                                          temperature=0.0, stop_token=stop)
    assert base_stop == base[:len(prompt) + 3]
    spec_env.setattr(spec_decode, "propose", _oracle_drafter([base]))
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine, prompt, 8, stop_token=stop).result() \
        == base_stop
    stats = engine.stats()
    assert stats["spec_verify_steps"] > 0
    # the oracle drafted past the stop and the verify step accepted it
    assert stats["spec_accepted_tokens"] > 2
    assert engine.active_rows == 0


def test_spec_mid_flight_admission_during_verify(gpt_model, make_engine,
                                                 spec_env):
    """A new row admitted while another row advances through verify steps:
    both keep their standalone streams (the newcomer prefills between
    ticks; the verifying row's rollbacks never touch other rows)."""
    from penroz_tpu.serve import spec_decode
    pa, pb = REP_PROMPT, [5, 6, 5, 6]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 7, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 5, temperature=0.0)
    spec_env.setattr(spec_decode, "propose",
                     _oracle_drafter([base_a, base_b]))
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    ca = _submit(engine, pa, 7)
    _wait_tokens(ca, 2)            # A provably mid-generation
    cb = _submit(engine, pb, 5)
    assert cb.result() == base_b
    assert ca.result() == base_a
    stats = engine.stats()
    assert stats["spec_verify_steps"] > 0
    assert stats["completed"] == 2


def test_spec_non_greedy_engine_bypasses_drafting(gpt_model, make_engine,
                                                  spec_env):
    """Non-greedy engines on the LEGACY (contiguous-cache phased) path
    still bypass drafting — its dispatch-order sampling keys would be
    perturbed by verify dispatches.  The unified ragged engine lifts the
    gate via positional-key rejection sampling
    (tests/test_pipeline_serving.py pins that parity); no PAGED_KV_CACHE
    here, so this engine is the phased one."""
    engine = make_engine("schedgpt", BLOCK, 0.8, 4, capacity=2)
    result = _submit(engine, [1, 2, 3], 4).result()
    assert len(result) == 7
    stats = engine.stats()
    assert stats["spec_decode"] is False
    assert stats["spec_drafted_tokens"] == 0
    assert stats["spec_verify_steps"] == 0


def _radix_nodes(cache):
    # walk every namespace root (adapter namespaces included)
    nodes, stack = [], [nd for root in cache._roots.values()
                        for nd in root.children.values()]
    while stack:
        nd = stack.pop()
        nodes.append(nd)
        stack.extend(nd.children.values())
    return nodes


def test_spec_verify_crash_recovers_with_parity(gpt_model, make_engine,
                                                spec_env, prefix_env):
    """Fault site decode.verify: a crash during a verify step fails the
    request cleanly, the engine reallocates its KV + prefix state
    (_alloc_state), and the next identical request is greedy-identical
    with no leaked paged blocks or pinned prefix pages."""
    from penroz_tpu.serve import spec_decode
    from penroz_tpu.utils import faults
    base = gpt_model.generate_tokens([REP_PROMPT], BLOCK, 6,
                                     temperature=0.0)
    spec_env.setattr(spec_decode, "propose", _oracle_drafter([base]))
    spec_env.setenv(faults.ENV, "decode.verify:raise@1")
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    with pytest.raises(faults.InjectedFault):
        _submit(engine, REP_PROMPT, 6).result()
    spec_env.delenv(faults.ENV)
    faults.reset()
    assert _submit(engine, REP_PROMPT, 6).result() == base
    stats = engine.stats()
    assert stats["crashes_total"] == 1
    assert stats["engine_resets"] == 1
    assert stats["breaker_open"] is False
    assert engine.active_rows == 0
    # no leaked pool state: every radix page accounted for, nothing pinned
    cache = engine._prefix_cache
    assert cache.free_pages + cache.cached_pages == cache.capacity_pages
    assert all(nd.refs == 0 for nd in _radix_nodes(cache))


def test_spec_http_serving_stats_and_streaming(client, gpt_model,
                                               monkeypatch):
    """End to end over HTTP: spec decode on the scheduler path keeps
    /generate/ token-identical (buffered + streaming), and
    /serving_stats/ carries the new spec fields through the schema."""
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    payload = _gen_payload(input=[[1, 2, 3, 1, 2]], max_new_tokens=9)
    status, legacy = _json(client, "POST", "/generate/", json=payload)
    assert status == 200
    monkeypatch.setenv("PENROZ_SPEC_DECODE", "1")
    monkeypatch.setenv("PENROZ_SPEC_NGRAM", "1")
    status, routed = _json(client, "POST", "/generate/", json=payload)
    assert status == 200
    assert routed["tokens"] == legacy["tokens"]

    test_client, loop = client

    async def go():
        resp = await test_client.post("/generate/",
                                      json=dict(payload, stream=True))
        assert resp.status == 200
        return (await resp.read()).decode()

    body = loop.run_until_complete(go())
    streamed = [int(line) for line in body.strip().split("\n")]
    assert streamed == legacy["tokens"][5:]

    status, stats = _json(client, "GET", "/serving_stats/")
    assert status == 200
    assert stats["spec_decode_enabled"] is True
    assert stats["spec_drafted_tokens"] >= 0
    assert stats["tokens_per_decode_step"] >= 1.0
    engine = stats["engines"][0]
    assert engine["spec_decode"] is True
    assert "spec_accept_rate" in engine


# -- compiled multi-step decode: fused supersteps (PENROZ_SCHED_SUPERSTEP) ---


def _settled_stats(engine, timeout=30):
    """Engine stats once the worker loop has finished the tick that
    retired the last request: the 'done' event is delivered from inside
    the emit loop, BEFORE the tick's counter/timeline updates, so a
    reader racing the worker can see the pre-tick totals."""
    deadline = time.monotonic() + timeout
    stats = engine.stats()
    while time.monotonic() < deadline:
        time.sleep(0.05)
        nxt = engine.stats()
        if (engine.idle()
                and nxt["decode_tokens"] == stats["decode_tokens"]
                and nxt["dispatches_total"] == stats["dispatches_total"]
                and len(nxt["tick_timeline"]) == len(stats["tick_timeline"])):
            return nxt
        stats = nxt
    return stats


@pytest.mark.parametrize("superstep", [1, 4, 8])
@pytest.mark.parametrize("paged_prefix,int8,chunk", [
    (0, 0, "16"), (1, 0, "2"), (0, 1, "16"), (1, 1, "2")],
    ids=["fp-contig", "paged-prefix-chunked", "int8-contig",
         "int8-paged-prefix-chunked"])
def test_superstep_parity_matrix(gpt_model, make_engine, monkeypatch,
                                 superstep, paged_prefix, int8, chunk):
    """THE multi-step acceptance matrix: greedy outputs are
    token-identical across superstep ∈ {1, 4, 8} × prefix-cache on/off ×
    int8 KV on/off (all four cache variants) × chunked/one-shot prefill
    — two overlapping rows with different budgets, so rows provably
    finish (and keep compute-but-discarding) mid-block, plus a second
    wave for real prefix-cache hits in the 'on' combos."""
    from penroz_tpu.serve import decode_scheduler
    if paged_prefix:
        monkeypatch.setenv("PAGED_KV_CACHE", "1")
        monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
        monkeypatch.setenv("PENROZ_PREFIX_CACHE", "1")
        monkeypatch.setenv("PENROZ_PREFIX_CACHE_PAGES", "8")
    if int8:
        monkeypatch.setenv("TURBO_QUANT_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_PREFILL_CHUNK", chunk)
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, str(superstep))
    pa, pb = [1, 2, 3, 4, 5, 6, 7, 8], [5]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 6, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 9, temperature=0.0)
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    ca, cb = _submit(engine, pa, 6), _submit(engine, pb, 9)
    assert ca.result() == base_a
    assert cb.result() == base_b
    # second wave: prefix-cache hit (when on) feeding straight into a
    # fused block
    assert _submit(engine, pa, 6).result() == base_a
    stats = _settled_stats(engine)
    assert stats["superstep"] == superstep
    assert stats["dispatches_total"] > 0
    if superstep > 1:
        # at least one dispatch actually fused >1 steps
        assert any(e["superstep"] > 1 for e in stats["tick_timeline"])
        assert stats["tokens_per_dispatch_avg"] > 1.0
    # fusing must not inflate the SPECULATION metric: a superstep counts
    # as N decode steps, so tokens/step stays bounded by the row count
    assert 1.0 <= stats["tokens_per_decode_step"] <= 2.0


def test_superstep_stop_token_detected_on_device(gpt_model, make_engine,
                                                 monkeypatch):
    """A stop token sampled mid-block deactivates the row ON DEVICE: the
    stream truncates exactly where the legacy per-token path stops
    (stop token delivered, nothing after it), and the row's slot
    recycles for the next request."""
    from penroz_tpu.serve import decode_scheduler
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "8")
    prompt = [1, 2, 3]
    base = gpt_model.generate_tokens([prompt], BLOCK, 12, temperature=0.0)
    stop = base[len(prompt) + 4]          # sampled mid-superstep
    base_stop = gpt_model.generate_tokens([prompt], BLOCK, 12,
                                          temperature=0.0, stop_token=stop)
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=1)
    assert _submit(engine, prompt, 12, stop_token=stop).result() \
        == base_stop
    # slot recycles cleanly after the on-device early stop
    assert _submit(engine, prompt, 12).result() == base
    stats = _settled_stats(engine)
    assert stats["completed"] == 2
    assert any(e["superstep"] > 1 for e in stats["tick_timeline"])


def test_superstep_crash_mid_generation_recovers_with_parity(
        gpt_model, make_engine, monkeypatch):
    """decode.step:raise@2 with superstep 4 crashes the SECOND fused
    dispatch — the request is several supersteps deep when the scan's
    tick dies.  The waiting request fails cleanly, _alloc_state rebuilds
    the engine, and the resubmitted request is greedy-identical."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "4")
    prompt = [1, 2, 3]
    base = gpt_model.generate_tokens([prompt], BLOCK, 12, temperature=0.0)
    monkeypatch.setenv(faults.ENV, "decode.step:raise@2")
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    c = _submit(engine, prompt, 12)
    with pytest.raises(faults.InjectedFault):
        c.result()
    # the crash landed mid-request: the first fused block (4 tokens) plus
    # the prefill token were already delivered, the rest never arrived
    assert 1 <= c.received < 12, c.received
    monkeypatch.delenv(faults.ENV)
    faults.reset()
    assert _submit(engine, prompt, 12).result() == base
    stats = engine.stats()
    assert stats["crashes_total"] == 1
    assert stats["engine_resets"] == 1
    assert engine.active_rows == 0


def test_superstep_deadline_retires_at_boundary(gpt_model, make_engine,
                                                monkeypatch):
    """A deadline expiring MID-superstep is only observed at the block
    boundary (the documented ≤N-token granularity trade): the row retires
    there with a timeout event and a 'timeout' trace retirement reason,
    and the engine serves the next request cleanly."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults, tracing
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "8")
    prompt = [1, 2, 3]
    base = gpt_model.generate_tokens([prompt], BLOCK, 4, temperature=0.0)
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=1)
    # warm: compiles the prefill + superstep programs so the deadline below
    # measures the slow dispatch, not XLA
    _submit(engine, prompt, 12).result()
    # each fused dispatch now sleeps well past the deadline: the expiry
    # lands mid-block and must surface at the boundary
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@400")
    monkeypatch.setenv("PENROZ_TRACE_SAMPLE", "1")
    trace = tracing.maybe_trace("req-superstep-deadline")
    collector = _Collector(prompt)
    req = decode_scheduler.Request(prompt, 12, None, collector.on_event,
                                   timeout_ms=150,
                                   request_id="req-superstep-deadline",
                                   trace=trace)
    engine.submit(req)
    with pytest.raises(decode_scheduler.DeadlineExceeded) as exc:
        collector.result()
    assert exc.value.phase == "inflight"
    # tokens delivered before the boundary noticed the expiry — the
    # overshoot is bounded by one block, never the full budget
    assert 1 <= collector.received < 12
    assert trace.finished
    assert trace.meta.get("retire_reason") == "timeout"
    assert engine.stats()["deadline_timeouts"] == 1
    monkeypatch.delenv(faults.ENV)
    faults.reset()
    assert _submit(engine, prompt, 4).result() == base


def test_superstep_cancellation_observed_at_boundary(gpt_model,
                                                     make_engine,
                                                     monkeypatch):
    """req.cancelled flipped mid-superstep frees the row at the block
    boundary; the slot then serves the next request with exact parity."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "4")
    pa, pb = [1, 2, 3], [5]
    base_b = gpt_model.generate_tokens([pb], BLOCK, 5, temperature=0.0)
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@60")
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=1)
    collector = _Collector(pa)
    req = decode_scheduler.Request(pa, 12, None, collector.on_event)
    engine.submit(req)
    _wait_tokens(collector, 1)
    req.cancelled = True
    deadline = time.monotonic() + 30
    while engine.active_rows and time.monotonic() < deadline:
        time.sleep(0.02)
    assert engine.active_rows == 0
    assert collector.received < 12
    assert _submit(engine, pb, 5).result() == base_b


def test_superstep_falls_back_while_admissions_pending(gpt_model,
                                                       make_engine,
                                                       monkeypatch):
    """A queued request must not wait N tokens for its slot: with the
    queue non-empty the planner falls back to n=1 ticks, so admission
    happens at the very next boundary (and the fused path resumes once
    the queue drains — both visible in the tick timeline)."""
    from penroz_tpu.serve import decode_scheduler
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "8")
    pa, pb = [1, 2, 3], [5]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 12, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 8, temperature=0.0)
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=1)
    ca = _submit(engine, pa, 12)
    cb = _submit(engine, pb, 8)   # queued behind A (capacity 1)
    assert ca.result() == base_a
    assert cb.result() == base_b
    timeline = _settled_stats(engine)["tick_timeline"]
    assert any(e["superstep"] == 1 for e in timeline)   # fallback ticks
    assert any(e["superstep"] > 1 for e in timeline)    # fused ticks


def test_superstep_dispatch_accounting(gpt_model, make_engine,
                                       monkeypatch):
    """The new dispatch metrics, exactly: prompt [1] + 12 tokens at
    superstep 8 is one prefill token + supersteps of 8, 2 and a single
    step (pow-2-bucketed tail) — 3 decode dispatches for 11 decode
    tokens, with the histogram-backed tokens_per_dispatch reflecting the
    fused blocks and tokens_per_decode_step pinned at 1.0 (fusing is not
    speculation)."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.serve import metrics as serve_metrics
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "8")
    dispatches0 = serve_metrics.DISPATCHES.value()
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=1)
    _submit(engine, [1], 12).result()
    stats = _settled_stats(engine)
    assert stats["dispatches_total"] == 3
    assert serve_metrics.DISPATCHES.value() - dispatches0 == 3
    assert stats["decode_tokens"] == 11     # 12 minus the prefill token
    assert stats["decode_steps"] == 11
    assert stats["tokens_per_decode_step"] == pytest.approx(1.0)
    assert stats["tokens_per_dispatch_avg"] == pytest.approx(11 / 3, abs=1e-3)
    assert stats["tokens_per_dispatch_p50"] == pytest.approx(2.0)
    supersteps = [e["superstep"] for e in stats["tick_timeline"]
                  if e["superstep"] > 0]
    assert sorted(supersteps) == [1, 2, 8]


def test_idle_engine_parks_on_condvar_no_spin(gpt_model, make_engine):
    """An idle engine burns no CPU: the worker loop parks on the
    condition variable (untimed wait) after its last request, so neither
    the loop counter nor the tick telemetry advances while idle — the
    old 1s-timeout poll would have woken it repeatedly."""
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=1)
    _submit(engine, [1, 2], 3).result()
    time.sleep(0.1)                      # let the loop finish its pass
    loops0 = engine._loops
    ticks0 = len(engine._tick_timeline)
    steps0 = engine.stats()["decode_steps"]
    time.sleep(1.5)                      # > the old poll interval
    assert engine._loops == loops0       # zero wakeups while idle
    assert len(engine._tick_timeline) == ticks0
    assert engine.stats()["decode_steps"] == steps0
    # and the parked engine still wakes instantly for new work
    assert engine.idle()
    _submit(engine, [1, 2], 3).result(timeout=30)


def test_step_rng_fold_in_jit_matches_host_fold(gpt_model):
    """The hoisted sampler-key advance is bit-identical: folding the
    dispatch ordinal into the base key INSIDE the jitted step (the new
    path) samples exactly the tokens the old host-side fold produced —
    seeded non-greedy output is unchanged by the hoist."""
    import jax
    from penroz_tpu.ops import kv_cache as KV
    model = gpt_model

    def fresh_kv():
        return (KV.create_kv_state(model.arch.kv_specs, 2, BLOCK,
                                   model._kv_dtype())
                .with_static_table()
                .with_lengths(np.zeros(2, np.int32)))

    toks = np.array([[3], [5]], np.int32)
    lengths = np.array([1, 1], np.int32)
    rng = jax.random.key(7)
    old, _ = model.decode_step_batched(fresh_kv(), toks, lengths,
                                       jax.random.fold_in(rng, 5),
                                       temperature=1.0)
    new, _ = model.decode_step_batched(fresh_kv(), toks, lengths, rng,
                                       temperature=1.0, dispatch=5)
    assert np.array_equal(np.asarray(old), np.asarray(new))


def test_non_greedy_seeded_output_invariant_under_superstep(
        gpt_model, make_engine, monkeypatch):
    """Sequential single-row NON-greedy traffic samples the identical
    token sequence at superstep 1 and 8: each fused step consumes the
    same dispatch ordinal (hence the same folded key) the single-step
    loop would have, so fusing never perturbs seeded sampling."""
    from penroz_tpu.serve import decode_scheduler
    prompt = [1, 2, 3]
    outs = {}
    for superstep in (1, 8):
        monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, str(superstep))
        engine = make_engine("schedgpt", BLOCK, 1.0, None, capacity=2)
        outs[superstep] = [
            _submit(engine, prompt, 10).result(),
            _submit(engine, [5], 6).result(),
        ]
        engine.shutdown()
    assert outs[1] == outs[8]


# -- ragged unified prefill+decode ticks (one mixed dispatch per tick) -------


UNIFIED_MATRIX = [
    # (prefix, int8, superstep, spec, chunk) — an L8-style cover: every
    # axis hits both values and the heavy pairings (int8×fused,
    # prefix×spec, spec×chunked) all appear at least once.
    (0, 0, "1", 0, "16"),
    (1, 0, "8", 1, "2"),
    (0, 1, "8", 0, "2"),
    (1, 1, "1", 1, "16"),
    (1, 1, "8", 0, "16"),
    (0, 0, "8", 1, "16"),
    (1, 0, "1", 0, "2"),
    (0, 1, "1", 1, "2"),
]


@pytest.mark.parametrize("prefix,int8,superstep,spec,chunk", UNIFIED_MATRIX)
def test_unified_parity_matrix(gpt_model, make_engine, monkeypatch,
                               prefix, int8, superstep, spec, chunk):
    """THE unified-tick acceptance matrix: with the paged cache on, the
    ragged one-dispatch scheduler returns greedy tokens identical to the
    legacy phased scheduler AND to the standalone legacy path — across
    prefix cache, int8 KV, superstep {1,8}, spec decode (oracle drafts)
    and chunked/one-shot prefill, with two overlapping rows per run so
    the dispatch is genuinely mixed."""
    from penroz_tpu.serve import decode_scheduler, spec_decode
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    if prefix:
        monkeypatch.setenv("PENROZ_PREFIX_CACHE", "1")
        monkeypatch.setenv("PENROZ_PREFIX_CACHE_PAGES", "16")
    if int8:
        monkeypatch.setenv("TURBO_QUANT_KV_CACHE", "1")
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, superstep)
    monkeypatch.setenv(decode_scheduler.PREFILL_CHUNK_ENV, chunk)
    pa, pb = REP_PROMPT, [5, 6, 5, 6]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 6, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 6, temperature=0.0)
    if spec:
        monkeypatch.setenv("PENROZ_SPEC_DECODE", "1")
        monkeypatch.setattr(spec_decode, "propose",
                            _oracle_drafter([base_a, base_b]))
    for ragged in ("1", "0"):
        monkeypatch.setenv(decode_scheduler.RAGGED_ENV, ragged)
        engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
        ca = _submit(engine, pa, 6)
        cb = _submit(engine, pb, 6)
        assert ca.result() == base_a, f"row A diverged (ragged={ragged})"
        assert cb.result() == base_b, f"row B diverged (ragged={ragged})"
        stats = engine.stats()
        unified_ticks = [e for e in stats["tick_timeline"]
                         if e.get("unified")]
        if ragged == "1":
            assert unified_ticks, "paged engine must take the unified path"
        else:
            assert not unified_ticks, \
                "PENROZ_RAGGED_ATTENTION=0 must restore phased ticks"
        if spec:
            assert stats["spec_verify_steps"] > 0
            assert stats["spec_accept_rate"] == 1.0
        engine.shutdown()


def test_unified_tick_fuses_chunks_and_drafts(gpt_model, make_engine,
                                              monkeypatch):
    """Superstep-fallback removal, asserted from the tick timeline: a
    unified tick holding BOTH pending prefill chunks and a spec-verify
    span still dispatches a fused block (superstep > 1).  The legacy
    scheduler dropped to single-step whenever either was present; the
    ragged dispatch has no such fallback."""
    from penroz_tpu.serve import decode_scheduler, spec_decode
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "8")
    monkeypatch.setenv(decode_scheduler.PREFILL_CHUNK_ENV, "2")
    monkeypatch.setenv("PENROZ_SPEC_DECODE", "1")
    monkeypatch.setenv("PENROZ_SPEC_K", "2")
    pa, pb = [1, 2], [1, 2, 3, 1, 2, 3, 1, 2, 3, 1, 2, 3]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 8, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 4, temperature=0.0)
    monkeypatch.setattr(spec_decode, "propose",
                        _oracle_drafter([base_a, base_b]))
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=2)
    # Queue the long chunked prompt while the worker sits in the delivery
    # of A's first token: B is admitted at the next boundary with A still
    # decoding, so a later tick plans A's verify span alongside B's chunks.
    ca = _submit(engine, pa, 8, hold_at=1)
    assert ca.held.wait(timeout=120), "row A produced no token"
    cb = _submit(engine, pb, 4)
    ca.release.set()
    assert ca.result() == base_a
    assert cb.result() == base_b
    fused_mixed = [e for e in engine.stats()["tick_timeline"]
                   if e.get("unified") and e["prefill_chunks"] > 0
                   and e["verify_rows"] > 0 and e["superstep"] > 1]
    assert fused_mixed, \
        "no tick fused prefill chunks with a verify span at superstep > 1"


def test_unified_compile_budget(gpt_model, make_engine, monkeypatch):
    """Compile-churn guard end to end: 50 requests with varied prompt and
    output lengths through the unified path compile a bounded mixed-step
    program set — descriptor-count buckets (pow-2, utils/bucketing.py)
    times step-count buckets {1,2,4,8}, never a program per shape."""
    from penroz_tpu.serve import decode_scheduler
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "8")
    monkeypatch.setenv(decode_scheduler.PREFILL_CHUNK_ENV, "4")
    engine = make_engine("schedgpt", BLOCK, 0.0, None, capacity=4)
    rng = np.random.default_rng(42)
    pending = []
    for i in range(50):
        plen = int(rng.integers(2, 11))
        max_new = int(rng.integers(1, min(6, BLOCK - plen)))
        prompt = [int(t) for t in rng.integers(1, 9, size=plen)]
        pending.append(_submit(engine, prompt, max_new))
        if len(pending) >= 8:
            pending.pop(0).result()
    for collector in pending:
        collector.result()
    counts = engine.jit_program_counts()
    assert counts.get("mixed_step", 0) >= 1, \
        "the unified path never dispatched"
    # n ∈ {1,2,4,8} step buckets × NB ∈ {1,2,4,8} descriptor buckets
    # = 16 is the pow-2 ceiling for this workload (an unbucketed planner
    # would compile a program per distinct (plen, max_new, rows) shape —
    # dozens); the exact subset reached depends on admission timing
    assert counts["mixed_step"] <= 16, \
        f"mixed-step program churn: {counts['mixed_step']} programs"
