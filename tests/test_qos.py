"""Multi-tenant QoS tests (serve/qos.py + the scheduler's WFQ admission,
per-tenant token quotas, and preempt-to-prefix-cache resume).

Tier-1-safe: CPU, small shapes, no `slow` marker.  The load-bearing
contracts:

- WFQ: an interactive backlog drains ahead of a batch flood in weight
  proportion; default traffic (no priority, no tenant) stays exact FIFO.
- Quotas: an exhausted tenant's NEW admissions 429 with a refill-derived
  Retry-After while a victim tenant on the same engine is untouched.
- Preemption: a preempted-then-resumed request is greedy token-identical
  to an unpreempted run (across int8 × superstep × LoRA), the resume
  recomputes zero cached prompt tokens (``preempted_resume_cached_tokens``),
  and a crash injected at ``qos.preempt`` recovers with no leaked radix
  pins.
"""

import asyncio
import json
import math
import queue
import threading
import time

import pytest

from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import NeuralNetworkModel

# CI tier: heavier compiles (serving stack), same tier as test_app.
pytestmark = pytest.mark.runtime

BLOCK = 16
SGD = {"sgd": {"lr": 0.1}}


@pytest.fixture(autouse=True)
def _qos_state(workdir):
    """Fresh engine registry, fault counters, quota buckets, and underflow
    counters per test — all of them are process-wide by design."""
    from penroz_tpu.ops import kv_cache as KV
    from penroz_tpu.serve import decode_scheduler, qos
    from penroz_tpu.serve import metrics as serve_metrics
    from penroz_tpu.utils import faults, tracing
    faults.reset()
    tracing.reset()
    serve_metrics.reset()
    qos.reset()
    KV.reset_unpin_underflow_count()
    yield
    decode_scheduler.reset()
    faults.reset()
    tracing.reset()
    serve_metrics.reset()
    qos.reset()
    KV.reset_unpin_underflow_count()


@pytest.fixture
def gpt_model(workdir, toy_gpt_layers):
    model = NeuralNetworkModel("qosgpt", Mapper(toy_gpt_layers, SGD))
    model.serialize(sync_flush=True)
    return model


@pytest.fixture
def make_engine():
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
    def __init__(self, prompt, label=None, order=None):
        self.q = queue.Queue()
        self.tokens = list(prompt)
        self.received = 0
        self.label = label
        self.order = order

    def on_event(self, kind, value):
        if kind == "done" and self.order is not None:
            self.order.append(self.label)
        self.q.put((kind, value))

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


def _submit(engine, prompt, max_new, priority=None, tenant=None,
            adapter=None, label=None, order=None):
    from penroz_tpu.serve import decode_scheduler
    collector = _Collector(prompt, label=label, order=order)
    engine.submit(decode_scheduler.Request(prompt, max_new, None,
                                           collector.on_event,
                                           adapter=adapter,
                                           priority=priority, tenant=tenant))
    return collector


def _wait_tokens(collector, n, timeout=120):
    deadline = time.monotonic() + timeout
    while collector.received < n:
        assert time.monotonic() < deadline, \
            f"only {collector.received}/{n} tokens arrived"
        try:
            kind, value = collector.q.get(timeout=1.0)
        except queue.Empty:
            continue
        assert kind == "token", (kind, value)
        collector.tokens.append(value)
        collector.received += 1


def _all_pins(cache) -> int:
    """Total live refcounts across every namespace of a radix cache."""
    total = 0
    stack = [nd for root in cache._roots.values()
             for nd in root.children.values()]
    while stack:
        nd = stack.pop()
        total += nd.refs
        stack.extend(nd.children.values())
    return total


# ---------------------------------------------------------------------------
# qos.py unit layer: priorities, tenants, WFQ drain order, quota buckets
# ---------------------------------------------------------------------------

def test_validate_priority_and_tenant_of():
    from penroz_tpu.serve import qos
    assert qos.validate_priority(None) == "standard"
    assert qos.validate_priority("interactive") == "interactive"
    with pytest.raises(ValueError, match="priority"):
        qos.validate_priority("urgent")
    # explicit tenant > adapter id > shared default
    assert qos.tenant_of("acme", "adapterX") == "acme"
    assert qos.tenant_of(None, "adapterX") == "adapterX"
    assert qos.tenant_of(None, None) == qos.DEFAULT_TENANT


def _mk_req(priority=None, tenant=None):
    from penroz_tpu.serve import decode_scheduler
    return decode_scheduler.Request([1], 1, None, lambda *a: None,
                                    priority=priority, tenant=tenant)


def test_wfq_weighted_drain_prefers_interactive(monkeypatch):
    """With the default 8/4/1 weights, a queued interactive burst drains
    ahead of a batch flood: after at most one batch pop (DRR cursor), every
    interactive request pops before the flood continues."""
    from penroz_tpu.serve import qos
    q = qos.WFQueue()
    for i in range(4):
        q.push(_mk_req(priority="batch", tenant="flood"))
    for i in range(3):
        q.push(_mk_req(priority="interactive", tenant="ui"))
    drained = [q.pop().priority for _ in range(7)]
    first_interactive = drained.index("interactive")
    assert first_interactive <= 1, drained
    # all interactive out before the flood's SECOND pop completes
    assert drained[first_interactive:first_interactive + 3] == \
        ["interactive"] * 3, drained
    assert len(q) == 0 and q.pop() is None


def test_wfq_default_traffic_is_exact_fifo():
    """No priority, no tenant → one sub-queue → byte-for-byte the old FIFO
    (the backward-compat clause)."""
    from penroz_tpu.serve import qos
    q = qos.WFQueue()
    reqs = [_mk_req() for _ in range(6)]
    for r in reqs:
        q.push(r)
    assert [q.pop() for _ in range(6)] == reqs
    # push_front requeues at the head of the sub-queue (preempt resume)
    a, b = _mk_req(), _mk_req()
    q.push(a)
    q.push_front(b)
    assert q.pop() is b and q.pop() is a


def test_wfq_weights_env_parsing(monkeypatch):
    from penroz_tpu.serve import qos
    monkeypatch.setenv("PENROZ_QOS_WEIGHTS", "interactive:12,batch:junk")
    w = qos.weights()
    assert w["interactive"] == 12
    assert w["batch"] >= 1          # junk falls back, never zero/negative
    monkeypatch.setenv("PENROZ_QOS_MAX_QUEUE_BATCH", "3")
    assert qos.class_queue_bound("batch") == 3
    assert qos.class_queue_bound("interactive") is None  # unset → aggregate


def test_wfq_class_tokens_tracks_queued_prompt_tokens():
    """class_tokens(cls) is the sum of queued prompt lengths per class —
    the router's least-loaded scoring reads it so a queue of three 8k
    prompts outweighs a queue of five 3-token prompts.  Every mutation
    path (push, push_front, pop, purge via _take, drain) keeps it exact."""
    from penroz_tpu.serve import decode_scheduler, qos

    def mk(n_tokens, priority=None):
        return decode_scheduler.Request(list(range(1, n_tokens + 1)), 1,
                                        None, lambda *a: None,
                                        priority=priority)

    q = qos.WFQueue()
    assert q.class_tokens("standard") == 0
    q.push(mk(5))
    q.push(mk(7))
    q.push(mk(100, priority="batch"))
    assert q.class_tokens("standard") == 12
    assert q.class_tokens("batch") == 100
    q.push_front(mk(3))
    assert q.class_tokens("standard") == 15
    popped = q.pop()                      # head of standard: the 3-token
    assert len(popped.prompt) == 3
    assert q.class_tokens("standard") == 12
    # purge (deadline/cancel sweep) decrements exactly the dropped prompts
    stale = mk(9)
    stale.cancelled = True
    q.push(stale)
    assert q.class_tokens("standard") == 21
    dropped = q.purge(lambda r: r.cancelled)
    assert dropped == [stale]
    assert q.class_tokens("standard") == 12
    q.drain()
    assert all(q.class_tokens(c) == 0 for c in qos.PRIORITIES)


def test_quota_bucket_retry_after_tracks_refill(monkeypatch):
    """Satellite: the quota 429's Retry-After is the bucket's refill time
    (deficit / rate, ceil, clamped) — a deeper deficit means a longer
    hint, and a request after the hinted wait is admitted again."""
    from penroz_tpu.serve import qos
    quotas = qos.QuotaManager()
    quotas.set_rate("t", 2.0)
    quotas.admit("t")                       # burst available
    quotas.charge("t", 8)                   # tokens ≈ 2 - 8 = -6
    with pytest.raises(qos.TenantQuotaExceeded) as exc:
        quotas.admit("t")
    assert exc.value.tenant == "t"
    # deficit 6 + the 1-token headroom, rate 2/s → ceil(7/2) = 4s
    assert exc.value.retry_after == 4
    quotas.charge("t", 20)                  # deepen the deficit
    with pytest.raises(qos.TenantQuotaExceeded) as deeper:
        quotas.admit("t")
    assert deeper.value.retry_after > exc.value.retry_after
    assert deeper.value.retry_after <= 60   # clamp
    # refill: simulate the wait by back-dating the bucket's clock
    bucket = quotas._buckets["t"]
    bucket.last -= 20.0                     # 20s ago → +40 tokens
    quotas.admit("t")                       # admitted again
    assert quotas.stats()["rejections"]["t"] == 2


def test_unpin_underflow_warns_once_and_counts():
    """Satellite: an unpaired unpin clamps to zero AND surfaces — one
    warning per distinct node key, every occurrence counted."""
    # capture on the module logger directly: an earlier suite test may
    # have applied dictConfig and cut propagation to caplog's root handler
    import logging
    from penroz_tpu.ops import kv_cache as KV
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    handler = _Capture(level=logging.WARNING)
    logger = logging.getLogger("penroz_tpu.ops.kv_cache")
    old_level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.WARNING)
    try:
        cache = KV.RadixPrefixCache(pages=[0, 1, 2, 3], page_size=2)
        cache.insert([1, 2, 3, 4])
        nodes = cache.match([1, 2, 3, 4])
        assert len(nodes) == 2
        cache.pin(nodes)
        cache.unpin(nodes)
        assert KV.unpin_underflow_count() == 0   # paired: no underflow
        cache.unpin(nodes)                   # unpaired: both nodes clamp
        cache.unpin(nodes[:1])               # same key again: no new warn
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)
    assert KV.unpin_underflow_count() == 3
    assert all(nd.refs == 0 for nd in nodes)
    warnings = [r for r in records
                if "unpin underflow" in r.getMessage()]
    assert len(warnings) == 2                # once per distinct key
    assert repr(nodes[0].key) in warnings[0].getMessage()


# ---------------------------------------------------------------------------
# engine layer: WFQ drain, per-class bounds, quotas, load-aware Retry-After
# ---------------------------------------------------------------------------

def test_queue_retry_after_scales_with_depth(gpt_model, make_engine):
    """Satellite: the queue-full Retry-After is depth × recent tick p50
    (clamped to [1, 30]) — not a static hint."""
    engine = make_engine("qosgpt", BLOCK, 0.0, None, capacity=1)
    with engine._cond:                     # worker provably parked out
        for _ in range(40):
            engine._h_tick.observe(2000.0)
        tick_p50 = engine._h_tick.quantile(0.5)
        assert tick_p50 >= 1000.0
        for n in (1, 5):
            while len(engine._pending) < n:
                engine._pending.push(_mk_req())
            expect = int(min(30, max(1, math.ceil(n * tick_p50 / 1000.0))))
            assert engine._queue_retry_after() == expect
        assert engine._queue_retry_after() > 1      # provably load-derived
        while len(engine._pending) < 100:
            engine._pending.push(_mk_req())
        assert engine._queue_retry_after() == 30    # clamp
        engine._pending.drain()


def test_interactive_backlog_outdrains_batch_flood(gpt_model, make_engine,
                                                   monkeypatch):
    """WFQ through the real engine: with one row and a queued batch flood
    + interactive pair, both interactive requests complete before the
    flood's second request — and every stream is greedy-exact."""
    from penroz_tpu.utils import faults
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@50")
    prompts = {"A": [1, 2, 3], "B1": [5], "B2": [6], "B3": [7],
               "I1": [9, 10], "I2": [11]}
    bases = {k: gpt_model.generate_tokens([p], BLOCK, 4, temperature=0.0)
             for k, p in prompts.items()}
    engine = make_engine("qosgpt", BLOCK, 0.0, None, capacity=1)
    order: list = []
    ca = _submit(engine, prompts["A"], 4, label="A", order=order)
    _wait_tokens(ca, 1)                       # A holds the row
    cs = {k: _submit(engine, prompts[k], 4, priority=pri, tenant=ten,
                     label=k, order=order)
          for k, pri, ten in (("B1", "batch", "flood"),
                              ("B2", "batch", "flood"),
                              ("B3", "batch", "flood"),
                              ("I1", "interactive", "ui"),
                              ("I2", "interactive", "ui"))}
    assert ca.result() == bases["A"]
    for k, c in cs.items():
        assert c.result() == bases[k], k
    assert order[0] == "A"
    # both interactives beat the flood's 2nd and 3rd requests
    assert order.index("I1") < order.index("B2")
    assert order.index("I2") < order.index("B2")
    stats = engine.stats()
    assert stats["admissions_by_class"] == {"interactive": 2, "standard": 1,
                                            "batch": 3}
    assert stats["queue_depth_by_class"] == {"interactive": 0, "standard": 0,
                                             "batch": 0}
    assert stats["ttft_ms_p99_by_class"]["interactive"] is not None


def test_per_class_bound_sheds_only_that_class(gpt_model, make_engine,
                                               monkeypatch):
    """PENROZ_QOS_MAX_QUEUE_BATCH bounds ONLY the batch sub-queues: a
    batch flood 429s at its bound while an interactive request still
    queues (and the error names the class)."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    monkeypatch.setenv("PENROZ_QOS_MAX_QUEUE_BATCH", "1")
    monkeypatch.setenv(decode_scheduler.MAX_QUEUE_ENV, "8")  # roomy aggregate
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@60")
    base = {p: gpt_model.generate_tokens([list(p)], BLOCK, 3,
                                         temperature=0.0)
            for p in ((1, 2, 3), (5,), (9, 10))}
    engine = make_engine("qosgpt", BLOCK, 0.0, None, capacity=1)
    ca = _submit(engine, [1, 2, 3], 3)
    _wait_tokens(ca, 1)
    cb = _submit(engine, [5], 3, priority="batch")       # fills batch bound
    with pytest.raises(decode_scheduler.QueueFullError) as exc:
        _submit(engine, [6], 3, priority="batch")
    assert "batch" in str(exc.value)
    assert exc.value.retry_after >= 1
    # a DIFFERENT class still queues: the bound is per-class, not global
    ci = _submit(engine, [9, 10], 3, priority="interactive")
    assert ca.result() == base[(1, 2, 3)]
    assert cb.result() == base[(5,)]
    assert ci.result() == base[(9, 10)]
    assert engine.stats()["queue_rejections"] == 1


def test_quota_sheds_offender_only(gpt_model, make_engine, monkeypatch):
    """An exhausted tenant's NEXT admission 429s with a refill Retry-After
    while a victim tenant on the same engine admits and keeps greedy
    parity — and the offender's in-flight request was never touched."""
    from penroz_tpu.serve import decode_scheduler
    # near-zero refill: deterministic under CPU compile stalls (rate 4
    # would quietly refill the deficit away during a slow first request)
    monkeypatch.setenv("PENROZ_QOS_TENANT_TOKENS_PER_S", "0.05")
    prompt = [1, 2, 3]
    base = gpt_model.generate_tokens([prompt], BLOCK, 6, temperature=0.0)
    engine = make_engine("qosgpt", BLOCK, 0.0, None, capacity=2)
    # burst (min 1 token) admits the first request; prefill + emits then
    # charge 3 + 6 = 9 tokens, driving the bucket deep negative
    assert _submit(engine, prompt, 6, tenant="noisy").result() == base
    with pytest.raises(decode_scheduler.TenantQuotaExceeded) as exc:
        _submit(engine, prompt, 6, tenant="noisy")
    assert exc.value.tenant == "noisy"
    assert exc.value.retry_after >= 1
    # victim: same engine, own bucket — full parity, zero rejections
    assert _submit(engine, prompt, 6, tenant="victim").result() == base
    stats = engine.stats()
    assert stats["quota_rejections"] == 1
    # the stats view counts EMITTED tokens; the quota bucket additionally
    # billed each tenant's 3 prefilled prompt tokens
    assert stats["tenant_tokens"]["noisy"] == 6
    assert stats["tenant_tokens"]["victim"] == 6
    from penroz_tpu.serve import qos
    assert qos.QUOTAS.stats()["charged"] == {"noisy": 9, "victim": 9}


# ---------------------------------------------------------------------------
# preemption: evict-to-prefix-cache, zero-recompute resume, crash recovery
# ---------------------------------------------------------------------------

def _preempt_env(monkeypatch, superstep, int8):
    from penroz_tpu.serve import decode_scheduler
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    monkeypatch.setenv("PENROZ_PREFIX_CACHE", "1")
    monkeypatch.setenv("PENROZ_PREFIX_CACHE_PAGES", "16")
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, str(superstep))
    if int8:
        monkeypatch.setenv("TURBO_QUANT_KV_CACHE", "1")


@pytest.mark.parametrize("int8,superstep", [
    pytest.param(0, 1, id="fp-1",
                 marks=pytest.mark.slow),  # fp step-1 covered by int8-1 arm
    pytest.param(0, 8, id="fp-8",
                 marks=pytest.mark.slow),  # fp step-8 covered by int8-8 arm
    pytest.param(1, 1, id="int8-1",
                 marks=pytest.mark.slow),  # step-1 seam covered elsewhere
    pytest.param(1, 8, id="int8-8")])
def test_preempt_resume_parity_matrix(gpt_model, make_engine, monkeypatch,
                                      superstep, int8):
    """THE acceptance matrix: a batch row evicted mid-generation for a
    queued interactive request resumes greedy token-identical to an
    unpreempted run (ONE uninterrupted stream), across int8 × superstep —
    with the cached prefix provably restored without recompute
    (``preempted_resume_cached_tokens``) and zero pins leaked."""
    from penroz_tpu.utils import faults
    _preempt_env(monkeypatch, superstep, int8)
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@150")
    pa, pb = [1, 2, 3, 4, 5, 6], [9, 10]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 10, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 4, temperature=0.0)
    engine = make_engine("qosgpt", BLOCK, 0.0, None, capacity=1)
    ca = _submit(engine, pa, 10, priority="batch", tenant="flood")
    _wait_tokens(ca, 1)          # the victim provably holds the only row
    cb = _submit(engine, pb, 4, priority="interactive", tenant="ui")
    assert cb.result() == base_b
    assert ca.result() == base_a  # stream continuity across preempt+resume
    stats = engine.stats()
    assert stats["preemptions"] == 1
    # zero-recompute clause: the resume aliased ≥ 1 cached page back
    assert stats["preempted_resume_cached_tokens"] >= 4
    assert stats["preempted_resume_cached_tokens"] % 4 == 0  # whole pages
    assert stats["completed"] == 2
    assert engine.active_rows == 0
    assert _all_pins(engine._prefix_cache) == 0   # every pin released


# slow lane (tier1_budget): the preempt matrix [int8-8] and the LoRA
# crash-recovery tests keep both halves of this composition fast
@pytest.mark.slow
def test_preempt_resume_parity_with_lora_adapter(gpt_model, make_engine,
                                                 monkeypatch):
    """The mixed-LoRA clause: the victim decodes through a LoRA adapter —
    its eviction lands in the adapter-namespaced radix root, the base
    interactive request cannot alias it, and the resumed adapter stream
    stays token-identical to the unpreempted adapter run."""
    from penroz_tpu.models import lora
    from penroz_tpu.serve import adapters
    from penroz_tpu.utils import faults
    _preempt_env(monkeypatch, 1, 0)
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@150")
    cfg = lora.validate_config({"rank": 4})
    params = lora.init_params(gpt_model.arch, cfg, seed=7, init="random")
    lora.save_adapter("qten", "qosgpt", cfg, params, {"code": "Created"},
                      sync_flush=True)
    adapters.REGISTRY.reset()
    entry = adapters.REGISTRY.acquire("qten", "qosgpt")
    try:
        pa, pb = [1, 2, 3, 4, 5, 6], [9, 10]
        base_b = gpt_model.generate_tokens([pb], BLOCK, 4, temperature=0.0)
        # unpreempted adapter oracle from an isolated engine
        iso = make_engine("qosgpt", BLOCK, 0.0, None, capacity=1)
        faults.reset()
        oracle = _submit(iso, pa, 8, adapter=entry).result()
        iso.shutdown()
        faults.reset()
        engine = make_engine("qosgpt", BLOCK, 0.0, None, capacity=1)
        ca = _submit(engine, pa, 8, priority="batch", adapter=entry)
        _wait_tokens(ca, 1)
        cb = _submit(engine, pb, 4, priority="interactive")
        assert cb.result() == base_b
        assert ca.result() == oracle
        stats = engine.stats()
        assert stats["preemptions"] == 1
        assert stats["preempted_resume_cached_tokens"] >= 4
        # the quota/tenant identity defaulted to the adapter id
        assert "qten" in stats["tenant_tokens"]
        assert _all_pins(engine._prefix_cache) == 0
    finally:
        adapters.REGISTRY.reset()


@pytest.mark.parametrize("superstep", [1, 8])
def test_preempt_crash_recovers_with_no_leaked_pins(gpt_model, make_engine,
                                                    monkeypatch, superstep):
    """Acceptance: a crash injected at ``qos.preempt`` fails the tick,
    ``_alloc_state`` rebuilds KV + a fresh radix cache (no pin can outlive
    the state it guards), and both replays are greedy-identical — on the
    unified tick at one step and at a fused superstep of 8."""
    from penroz_tpu.utils import faults
    _preempt_env(monkeypatch, superstep, 0)
    monkeypatch.setenv(faults.ENV,
                       "qos.preempt:raise@1,decode.step:sleep@120")
    pa, pb = [1, 2, 3, 4, 5, 6], [9, 10]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 8, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 4, temperature=0.0)
    engine = make_engine("qosgpt", BLOCK, 0.0, None, capacity=1)
    ca = _submit(engine, pa, 8, priority="batch")
    _wait_tokens(ca, 1)
    cb = _submit(engine, pb, 4, priority="interactive")  # triggers preempt
    with pytest.raises(faults.InjectedFault):
        ca.result()
    with pytest.raises(faults.InjectedFault):
        cb.result()
    monkeypatch.setenv(faults.ENV, "")
    faults.reset()
    stats = engine.stats()
    assert stats["crashes_total"] == 1 and stats["engine_resets"] == 1
    assert stats["preemptions"] == 0        # the fault fired before any
    assert _all_pins(engine._prefix_cache) == 0
    # greedy-identical replays through the rebuilt engine
    assert _submit(engine, pa, 8, priority="batch").result() == base_a
    assert _submit(engine, pb, 4, priority="interactive").result() == base_b
    assert _all_pins(engine._prefix_cache) == 0


def test_preempt_disabled_env_queues_instead(gpt_model, make_engine,
                                             monkeypatch):
    """PENROZ_QOS_PREEMPT=0: the interactive request waits its WFQ turn —
    no eviction, victim runs to completion uninterrupted."""
    from penroz_tpu.utils import faults
    _preempt_env(monkeypatch, 1, 0)
    monkeypatch.setenv("PENROZ_QOS_PREEMPT", "0")
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@40")
    pa, pb = [1, 2, 3, 4, 5, 6], [9, 10]
    base_a = gpt_model.generate_tokens([pa], BLOCK, 8, temperature=0.0)
    base_b = gpt_model.generate_tokens([pb], BLOCK, 4, temperature=0.0)
    engine = make_engine("qosgpt", BLOCK, 0.0, None, capacity=1)
    ca = _submit(engine, pa, 8, priority="batch")
    _wait_tokens(ca, 1)
    cb = _submit(engine, pb, 4, priority="interactive")
    assert ca.result() == base_a
    assert cb.result() == base_b
    assert engine.stats()["preemptions"] == 0


# ---------------------------------------------------------------------------
# breaker half-open race (satellite)
# ---------------------------------------------------------------------------

def test_breaker_half_open_admits_exactly_one_probe(gpt_model, make_engine,
                                                    monkeypatch):
    """Satellite: N concurrent submits racing the cooldown expiry admit
    exactly ONE probe (the _cond-serialized _probe_inflight flag) — the
    rest 503 — and the probe's success closes the breaker."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    prompt = [1, 2, 3]
    base = gpt_model.generate_tokens([prompt], BLOCK, 4, temperature=0.0)
    monkeypatch.setenv(decode_scheduler.MAX_CRASHES_ENV, "1")
    monkeypatch.setenv(decode_scheduler.BREAKER_COOLDOWN_ENV, "300")
    monkeypatch.setenv(faults.ENV, "decode.step:raise@1")
    engine = make_engine("qosgpt", BLOCK, 0.0, None, capacity=2)
    with pytest.raises(faults.InjectedFault):
        _submit(engine, prompt, 4).result()
    assert engine.stats()["breaker_open"] is True
    monkeypatch.setenv(faults.ENV, "")
    faults.reset()
    time.sleep(0.4)                          # cooldown provably expired

    n = 8
    barrier = threading.Barrier(n)
    outcomes: list = [None] * n

    def racer(i):
        barrier.wait()
        try:
            outcomes[i] = _submit(engine, prompt, 4)
        except decode_scheduler.CircuitOpenError:
            outcomes[i] = "open"

    threads = [threading.Thread(target=racer, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    admitted = [o for o in outcomes if o != "open"]
    assert len(admitted) == 1, outcomes      # exactly one probe
    assert admitted[0].result() == base      # and it closes the breaker
    stats = engine.stats()
    assert stats["breaker_open"] is False
    assert stats["breaker_rejections"] == n - 1
    # breaker closed: everyone is admitted again
    assert _submit(engine, prompt, 4).result() == base


# ---------------------------------------------------------------------------
# HTTP layer: /tenants endpoints, shed-reason trace spans, underflow gauge
# ---------------------------------------------------------------------------

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


def _request(client_loop, method, path, **kw):
    client, loop = client_loop

    async def go():
        resp = await client.request(method, path, **kw)
        body = await resp.read()
        return resp, body

    return loop.run_until_complete(go())


def _json(client_loop, method, path, **kw):
    resp, body = _request(client_loop, method, path, **kw)
    return resp.status, (json.loads(body) if body else None)


def _gen_payload(**overrides):
    payload = {"model_id": "qosgpt", "input": [[1, 2, 3]],
               "block_size": BLOCK, "max_new_tokens": 4, "temperature": 0.0}
    payload.update(overrides)
    return payload


def _trace_for(client, rid, timeout=10.0):
    deadline = time.monotonic() + timeout
    while True:
        status, tree = _json(client, "GET", f"/trace/{rid}")
        if status == 200 and tree["finished"]:
            return tree
        assert time.monotonic() < deadline, (status, tree)
        time.sleep(0.05)


def _span_names(span):
    return [c["name"] for c in span.get("children", [])]


def test_tenant_quota_endpoints_roundtrip(client):
    status, body = _json(client, "PUT", "/tenants/acme/quota",
                         json={"tokens_per_s": 5})
    assert status == 200
    assert body == {"tenant": "acme", "tokens_per_s": 5.0, "override": True,
                    "tier_bytes": 0.0}
    status, body = _json(client, "GET", "/tenants/")
    assert status == 200
    assert body["tenants"]["overrides"] == {"acme": 5.0}
    assert body["default_tokens_per_s"] == 0.0   # env default: disabled
    # null clears back to the env default
    status, body = _json(client, "PUT", "/tenants/acme/quota",
                         json={"tokens_per_s": None})
    assert status == 200
    assert body["override"] is False and body["tokens_per_s"] == 0.0
    # negative rate is a client error, not a silent clamp
    status, body = _json(client, "PUT", "/tenants/acme/quota",
                         json={"tokens_per_s": -1})
    assert status == 400
    status, body = _json(client, "GET", "/tenants/")
    assert body["tenants"]["overrides"] == {}


def test_trace_queue_shed_429(client, gpt_model, monkeypatch):
    """Satellite: a queue-full 429's trace ends 'queue_full' and still
    carries the queue-wait span + typed shed event."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    monkeypatch.setenv(decode_scheduler.MAX_ROWS_ENV, "1")
    monkeypatch.setenv(decode_scheduler.MAX_QUEUE_ENV, "1")
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@80")
    test_client, loop = client

    async def go():
        task_a = asyncio.ensure_future(test_client.post(
            "/generate/", json=_gen_payload(max_new_tokens=8)))
        for _ in range(200):
            stats = await (await test_client.get("/serving_stats/")).json()
            if stats["active_rows"] >= 1 and stats["queue_depth"] == 0:
                break
            await asyncio.sleep(0.02)
        task_b = asyncio.ensure_future(test_client.post(
            "/generate/", json=_gen_payload(input=[[5]])))
        for _ in range(200):
            stats = await (await test_client.get("/serving_stats/")).json()
            if stats["queue_depth"] >= 1:
                break
            await asyncio.sleep(0.02)
        resp_c = await test_client.post(
            "/generate/", json=_gen_payload(input=[[7, 8]]))
        body_c = await resp_c.json()
        resp_a, resp_b = await task_a, await task_b
        return (resp_a.status, resp_b.status, resp_c.status, body_c,
                resp_c.headers.get("Retry-After"),
                resp_c.headers["X-Request-Id"])

    a_status, b_status, c_status, c_body, retry, rid = \
        loop.run_until_complete(go())
    assert (a_status, b_status, c_status) == (200, 200, 429), c_body
    assert retry is not None and int(retry) >= 1
    tree = _trace_for(client, rid)
    assert tree["meta"]["retire_reason"] == "queue_full"
    names = _span_names(tree["root"])
    assert "queue" in names and "shed" in names


def test_trace_quota_shed_429(client, gpt_model, monkeypatch):
    """Satellite: an exhausted tenant bucket 429s with a refill-derived
    Retry-After and a 'quota' retirement in the trace — while the same
    prompt under a different tenant still serves 200."""
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    # near-zero refill keeps the deficit deterministic under compile stalls
    status, _ = _json(client, "PUT", "/tenants/noisy/quota",
                      json={"tokens_per_s": 0.05})
    assert status == 200
    resp, body = _request(client, "POST", "/generate/",
                          json=_gen_payload(tenant="noisy"))
    assert resp.status == 200   # burst admits; charges 3 + 4 = 7 tokens
    resp, body = _request(client, "POST", "/generate/",
                          json=_gen_payload(tenant="noisy"))
    assert resp.status == 429
    detail = json.loads(body)["detail"]
    assert "noisy" in detail and "quota" in detail
    assert int(resp.headers["Retry-After"]) >= 1
    tree = _trace_for(client, resp.headers["X-Request-Id"])
    assert tree["meta"]["retire_reason"] == "quota"
    names = _span_names(tree["root"])
    assert "queue" in names and "shed" in names
    # the victim tenant is untouched
    resp, _ = _request(client, "POST", "/generate/",
                       json=_gen_payload(tenant="victim"))
    assert resp.status == 200
    _, stats = _json(client, "GET", "/serving_stats/")
    assert stats["quota_rejections"] == 1
    # emitted tokens per tenant (the quota bucket billed prompts on top)
    assert stats["tenant_tokens"] == {"noisy": 4, "victim": 4}


def test_trace_queued_deadline_504(client, gpt_model, monkeypatch):
    """Satellite: a request whose deadline expires while still QUEUED
    504s with a 'timeout' retirement and a queue span (it never reached
    prefill)."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    monkeypatch.setenv(decode_scheduler.MAX_ROWS_ENV, "1")
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "1")
    monkeypatch.setenv(faults.ENV, "decode.step:sleep@120")
    test_client, loop = client

    async def go():
        task_a = asyncio.ensure_future(test_client.post(
            "/generate/", json=_gen_payload(max_new_tokens=8)))
        for _ in range(200):
            stats = await (await test_client.get("/serving_stats/")).json()
            if stats["active_rows"] >= 1 and stats["queue_depth"] == 0:
                break
            await asyncio.sleep(0.02)
        resp_b = await test_client.post(
            "/generate/", json=_gen_payload(input=[[5]], timeout_ms=150))
        body_b = await resp_b.json()
        resp_a = await task_a
        return (resp_a.status, resp_b.status, body_b,
                resp_b.headers["X-Request-Id"])

    a_status, b_status, b_body, rid = loop.run_until_complete(go())
    monkeypatch.delenv(faults.ENV)
    faults.reset()
    assert a_status == 200
    assert b_status == 504, b_body
    assert "queued" in b_body["detail"]
    tree = _trace_for(client, rid)
    assert tree["meta"]["retire_reason"] == "timeout"
    names = _span_names(tree["root"])
    assert "queue" in names and "prefill" not in names


def test_metrics_exposes_unpin_underflow_gauge(client):
    from penroz_tpu.ops import kv_cache as KV
    resp, body = _request(client, "GET", "/metrics")
    assert b"penroz_prefix_cache_unpin_underflow 0" in body
    KV.record_unpin_underflow(("k", 1))
    resp, body = _request(client, "GET", "/metrics")
    assert b"penroz_prefix_cache_unpin_underflow 1" in body
