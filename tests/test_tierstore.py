"""Hierarchical KV tiering + session hibernation tests (serve/tierstore.py).

Two layers:

* TierStore unit tests — registration/match/placement semantics, tenant
  quotas, host→disk spill and disk-cap LRU drops, corrupt-blob policy —
  driven with synthetic numpy blobs, no engine.
* Engine/API tests — the load-bearing parity contract: a session
  hibernated at retirement and resumed from each tier (HBM radix alias,
  host blob import on a different engine, disk blob import after a
  ``decode_scheduler.reset()``) streams exactly the tokens the same
  history produces cold, across int8 × superstep; corrupt blobs recompute
  instead of crashing or mis-serving; the memledger ``hibernating`` state
  balances under strict audits; both fault sites crash-recover.
"""

import queue
import time

import numpy as np
import pytest

from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import NeuralNetworkModel

pytestmark = pytest.mark.runtime

BLOCK = 16
SGD = {"sgd": {"lr": 0.1}}


@pytest.fixture(autouse=True)
def _tier_registry(workdir, tmp_path, monkeypatch):
    """Fresh engine registry + tier store + fault/quota state per test;
    the disk tier writes under this test's tmp dir, never shared shm."""
    from penroz_tpu.ops import kv_cache as KV
    from penroz_tpu.serve import decode_scheduler, qos, tierstore
    from penroz_tpu.utils import faults
    monkeypatch.setenv("PENROZ_TIER_DISK_PATH", str(tmp_path / "tier"))
    faults.reset()
    qos.reset()
    tierstore.reset()
    KV.reset_unpin_underflow_count()
    yield
    decode_scheduler.reset()
    tierstore.reset()
    faults.reset()
    qos.reset()
    KV.reset_unpin_underflow_count()


# -- TierStore unit layer ----------------------------------------------------

def _register(store, sid, tokens, *, tenant="default", model_id="m",
              stamp=7, page_size=4, nbytes=1024, owner=1, replica="r0",
              quantized=False):
    return store.register(
        sid, tenant=tenant, model_id=model_id, model_stamp=stamp,
        tokens=tuple(tokens), kv_len=(len(tokens) // page_size) * page_size,
        page_size=page_size, quantized=quantized, nbytes=nbytes,
        owner=owner, replica=replica)


def _blob(pages=2, page_size=4, quantized=False):
    """A synthetic export_pages-shaped blob (one layer, tiny planes)."""
    plane = np.zeros((1, pages * page_size, 2), dtype=np.float32)
    return {"page_size": page_size, "pages": pages,
            "length": pages * page_size, "quantized": quantized,
            "k": [plane], "v": [plane.copy()]}


def test_register_match_depth_and_token_verification():
    """match() returns the DEEPEST whole-page-verified session, caps the
    usable span at len(tokens)-1, and never aliases on a token mismatch
    even when fingerprints would collide on a prefix."""
    from penroz_tpu.serve.tierstore import TierStore
    store = TierStore()
    assert _register(store, "s1", range(8))          # 2 pages: [0..7]
    assert _register(store, "s2", range(12))         # 3 pages: [0..11]
    # 13 tokens agree with s2 for all 3 pages (12 < 13 usable)
    rec, depth = store.match(list(range(13)), model_id="m", model_stamp=7,
                             page_size=4, quantized=False)
    assert rec.session_id == "s2" and depth == 3
    # exactly 12 tokens: one must remain to sample, so only 2 pages usable
    rec, depth = store.match(list(range(12)), model_id="m", model_stamp=7,
                             page_size=4, quantized=False)
    assert depth == 2
    # diverges inside page 2 -> only the first page may alias
    rec, depth = store.match([0, 1, 2, 3, 99, 98, 97, 96, 8], model_id="m",
                             model_stamp=7, page_size=4, quantized=False)
    assert rec is not None and depth == 1
    # wrong pool layout or model: no match
    assert store.match(list(range(13)), model_id="m", model_stamp=7,
                       page_size=4, quantized=True) == (None, 0)
    assert store.match(list(range(13)), model_id="other", model_stamp=7,
                       page_size=4, quantized=False) == (None, 0)


def test_match_stale_model_stamp_drops_session():
    """A session hibernated under superseded weights is dropped at match
    time (stale KV is never served) and counted as a stale promotion."""
    from penroz_tpu.serve.tierstore import TierStore
    store = TierStore()
    assert _register(store, "s1", range(8), stamp=7)
    rec, depth = store.match(list(range(9)), model_id="m", model_stamp=8,
                             page_size=4, quantized=False)
    assert (rec, depth) == (None, 0)
    assert store.resident_sessions() == 0
    assert store.promotions[("hbm", "stale")] == 1
    assert store.drops["stale_model"] == 1


def test_reregister_replaces_and_drop_owner_spares_lower_tiers():
    """Re-registering a session id supersedes the old record; drop_owner
    only reaps tier-"hbm" records (host/disk blobs left HBM already)."""
    from penroz_tpu.serve.tierstore import TierStore
    store = TierStore()
    assert _register(store, "s1", range(8), owner=1)
    assert _register(store, "s1", range(12), owner=1)   # multi-turn update
    assert store.resident_sessions() == 1
    assert store.drops["replaced"] == 1
    assert store.get("s1").kv_len == 12
    assert _register(store, "s2", range(4), owner=1)
    assert store.demote_to_host("s2", _blob(1))
    assert store.get("s2").tier == "host"
    assert store.drop_owner(1, "engine_reset") == 1     # only s1 (hbm)
    assert store.get("s1") is None
    assert store.get("s2").tier == "host"


def test_tenant_tier_quota_evicts_lru_then_refuses(monkeypatch):
    """PENROZ_QOS_TENANT_TIER_MB: a hibernation over cap evicts that
    tenant's LRU sessions first; one that can never fit is refused; other
    tenants' residency is untouched."""
    from penroz_tpu.serve.tierstore import TierStore
    monkeypatch.setenv("PENROZ_QOS_TENANT_TIER_MB", "0.002")  # 2000 bytes
    store = TierStore()
    assert _register(store, "a1", range(8), tenant="acme", nbytes=900)
    assert _register(store, "a2", range(4), tenant="acme", nbytes=900)
    assert _register(store, "b1", range(4), tenant="beta", nbytes=900)
    # 900 more puts acme at 2700 > 2000: a1 (LRU) is evicted
    assert _register(store, "a3", [50, 51, 52, 53], tenant="acme",
                     nbytes=900)
    assert store.get("a1") is None
    assert store.drops["quota"] == 1
    assert {r["session_id"] for r in store.list_sessions()} \
        == {"a2", "b1", "a3"}
    # a session larger than the whole cap is refused outright
    assert not _register(store, "a4", range(4), tenant="acme", nbytes=3000)
    assert store.drops["quota_refused"] == 1
    assert store.get("a2") is not None   # refusal evicted nothing


def test_host_cap_spills_lru_to_disk_and_disk_cap_drops(monkeypatch,
                                                        tmp_path):
    """Host-cap overflow spills LRU host blobs into the CRC disk store
    (files appear under PENROZ_TIER_DISK_PATH); disk-cap overflow drops
    LRU disk sessions, blob files included."""
    from penroz_tpu.serve.tierstore import TierStore
    from penroz_tpu.utils import checkpoint
    store = TierStore()
    blob_bytes = checkpoint.page_blob_nbytes(_blob(2))
    assert blob_bytes > 0
    # host cap fits exactly one blob
    monkeypatch.setenv("PENROZ_TIER_HOST_MB", str(blob_bytes / 1e6))
    for i, sid in enumerate(("s1", "s2", "s3")):
        assert _register(store, sid, range(i * 8, i * 8 + 8))
        assert store.demote_to_host(sid, _blob(2))
    # s3 is the only host resident; s1, s2 spilled LRU-first to disk
    tiers = {r["session_id"]: r["tier"] for r in store.list_sessions()}
    assert tiers == {"s1": "disk", "s2": "disk", "s3": "host"}
    assert store.demotions["host"] == 3 and store.demotions["disk"] == 2
    assert checkpoint.tier_blob_nbytes("s1") > 0
    stats = store.stats()
    assert stats["tier_bytes"]["host_tier"] == blob_bytes
    assert stats["tier_bytes"]["disk_tier"] \
        == checkpoint.tier_blob_nbytes("s1") * 2
    # shrink the disk cap to one stored blob: s1 (LRU) is dropped fully
    monkeypatch.setenv("PENROZ_TIER_DISK_MB",
                       str(checkpoint.tier_blob_nbytes("s1") / 1e6))
    assert _register(store, "s4", range(40, 48))
    assert store.demote_to_host("s4", _blob(2))
    assert store.get("s1") is None
    assert store.drops["disk_cap"] >= 1
    assert checkpoint.tier_blob_nbytes("s1") == 0   # file reclaimed


def test_corrupt_and_missing_disk_blobs_are_misses(monkeypatch):
    """A disk blob that fails CRC is a miss + corrupt counter (record
    dropped, file reclaimed); a vanished file is a plain miss. fetch()
    never raises — the admission recomputes."""
    import os
    from penroz_tpu.serve.tierstore import TierStore
    from penroz_tpu.utils import checkpoint
    monkeypatch.setenv("PENROZ_TIER_HOST_MB", "0")  # straight to disk
    store = TierStore()
    for sid in ("sc", "sm"):
        assert _register(store, sid, range(8) if sid == "sc"
                         else range(8, 16))
        assert store.demote_to_host(sid, _blob(2))
        assert store.get(sid).tier == "disk"
    path = checkpoint.tier_blob_path("sc")
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF                       # bit-flip the payload
    with open(path, "wb") as f:
        f.write(raw)
    assert store.fetch("sc") is None
    assert store.corrupt_blobs == 1
    assert store.promotions[("disk", "corrupt")] == 1
    assert store.get("sc") is None and not os.path.exists(path)
    os.remove(checkpoint.tier_blob_path("sm"))       # blob vanished
    assert store.fetch("sm") is None
    assert store.promotions[("disk", "miss")] == 1
    assert store.corrupt_blobs == 1                  # not corrupt, missing
    # truncation corrupts too (container header/CRC can't validate)
    assert _register(store, "st", range(16, 24))
    assert store.demote_to_host("st", _blob(2))
    path = checkpoint.tier_blob_path("st")
    raw = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(raw[:len(raw) // 2])
    assert store.fetch("st") is None
    assert store.corrupt_blobs == 2


def test_placement_is_side_effect_free_and_quant_agnostic():
    """placement() (the router's steering probe) finds a session without
    touching LRU order or any counter, and matches across the quantized
    pool-layout variants the router cannot see."""
    from penroz_tpu.serve.tierstore import TierStore
    store = TierStore()
    assert _register(store, "s1", range(8), quantized=True)
    assert _register(store, "s2", range(20, 28))
    before_order = list(store._sessions)
    before_promos = dict(store.promotions)
    rec = store.placement(list(range(9)), model_id="m", page_size=4)
    assert rec is not None and rec.session_id == "s1"
    assert list(store._sessions) == before_order     # no LRU touch
    assert dict(store.promotions) == before_promos   # no counters
    assert store.placement([7, 7, 7, 7, 7], model_id="m",
                           page_size=4) is None
    # match() (the engine-side path) DOES touch LRU
    store.match(list(range(9)), model_id="m", model_stamp=7, page_size=4,
                quantized=True)
    assert list(store._sessions)[-1] == "s1"


# -- engine / API layer ------------------------------------------------------

@pytest.fixture
def tier_env(monkeypatch):
    """Paged pool + radix cache sized for BLOCK=16 toy prompts, strict
    memledger audits on every transition."""
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    monkeypatch.setenv("PENROZ_PREFIX_CACHE", "1")
    monkeypatch.setenv("PENROZ_PREFIX_CACHE_PAGES", "8")
    monkeypatch.setenv("PENROZ_MEMLEDGER_STRICT", "1")
    return monkeypatch


@pytest.fixture
def gpt_model(workdir, toy_gpt_layers):
    model = NeuralNetworkModel("tiergpt", Mapper(toy_gpt_layers, SGD))
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
    def __init__(self, prompt):
        self.q = queue.Queue()
        self.tokens = list(prompt)

    def on_event(self, kind, value):
        self.q.put((kind, value))

    def result(self, timeout=180):
        deadline = time.monotonic() + timeout
        while True:
            kind, value = self.q.get(
                timeout=max(deadline - time.monotonic(), 0.1))
            if kind == "token":
                self.tokens.append(value)
            elif kind == "done":
                return self.tokens
            else:
                raise value


def _submit(engine, prompt, max_new, session_id=None):
    from penroz_tpu.serve import decode_scheduler
    collector = _Collector(prompt)
    engine.submit(decode_scheduler.Request(prompt, max_new, None,
                                           collector.on_event,
                                           session_id=session_id))
    return collector


def _wait_tier(sid, tier, timeout=60):
    """Demotion is async (worker-loop tail) — poll the store."""
    from penroz_tpu.serve import tierstore
    deadline = time.monotonic() + timeout
    while True:
        rec = tierstore.TIERS.get(sid)
        if rec is not None and rec.tier == tier:
            return rec
        assert time.monotonic() < deadline, \
            f"session {sid} never reached tier {tier!r}: {rec}"
        time.sleep(0.02)


def _wait_pins_released(engine, timeout=60):
    """The worker pops a hibernation hold, exports (or, for a dropped
    session, skips) and only then unpins: between the two the books are
    mid-transition by design, and every in-program audit runs on the
    worker's own thread after the unpin.  A test auditing from its own
    thread waits for the engine to get there: no hold queued and no radix
    node pinned (no row is live when this is called)."""
    deadline = time.monotonic() + timeout
    while True:
        with engine._cond:
            pinned = [nd.page for nd in engine._prefix_cache.iter_nodes()
                      if nd.refs]
            settled = not (pinned or engine._hib_holds
                           or engine._hib_pending)
        if settled:
            return
        assert time.monotonic() < deadline, \
            f"hibernation pins never released: pages {pinned}"
        time.sleep(0.02)


@pytest.mark.parametrize("int8,superstep", [
    # fp step-1 rides the slow lane too (tier1_budget): the int8-step8
    # diagonal keeps hibernate/resume parity fast
    pytest.param(0, 1, marks=pytest.mark.slow),
    pytest.param(0, 8, marks=pytest.mark.slow),  # step8 covered by int8-step8
    pytest.param(1, 1, marks=pytest.mark.slow),  # int8 covered at step8
    (1, 8)],
    ids=["fp-step1", "fp-step8", "int8-step1", "int8-step8"])
def test_hibernate_resume_parity_matrix(gpt_model, make_engine, tier_env,
                                        int8, superstep):
    """THE tiering acceptance matrix: a session hibernated at retirement
    resumes token-identically from (a) the still-resident radix copy and
    (b) the host blob on a FRESH engine after ``decode_scheduler.reset()``
    dropped the radix pages — across int8 KV and superstep sizes."""
    from penroz_tpu.serve import decode_scheduler, tierstore
    from penroz_tpu.serve import metrics as serve_metrics
    if int8:
        tier_env.setenv("TURBO_QUANT_KV_CACHE", "1")
    tier_env.setenv("PENROZ_SCHED_SUPERSTEP", str(superstep))
    hibernated0 = serve_metrics.SESSIONS_HIBERNATED.value()
    promoted0 = serve_metrics.TIER_PROMOTIONS.value(tier="host",
                                                    outcome="ok")
    prompt = [1, 2, 3, 4, 5, 6, 7]
    out = gpt_model.generate_tokens([prompt], BLOCK, 4, temperature=0.0)
    cont = out + [9]                       # next turn extends the history
    base = gpt_model.generate_tokens([cont], BLOCK, 3, temperature=0.0)

    engine = make_engine("tiergpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine, prompt, 4, session_id="conv").result() == out
    _wait_tier("conv", "host")
    # (a) HBM-fast wake: radix copy still resident on the live engine
    assert _submit(engine, cont, 3).result() == base
    stats = engine.stats()
    assert stats["sessions_hibernated"] >= 1
    # no blob import — the radix copy served the wake
    assert stats["session_promotions"] == 0
    assert tierstore.TIERS.promotions[("hbm", "ok")] == 1

    # (b) host-blob wake on a brand-new engine (old pool is gone)
    decode_scheduler.reset()
    engine2 = make_engine("tiergpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine2, cont, 3).result() == base
    assert engine2.stats()["session_promotions"] == 1
    assert tierstore.TIERS.promotions[("host", "ok")] == 1
    # /metrics counts the same hibernations and the one blob import
    assert serve_metrics.SESSIONS_HIBERNATED.value() - hibernated0 \
        == stats["sessions_hibernated"]
    assert serve_metrics.TIER_PROMOTIONS.value(
        tier="host", outcome="ok") - promoted0 == 1


def test_cross_replica_wake_without_session_id(gpt_model, make_engine,
                                               tier_env):
    """Promotion is content-addressed: a session hibernated on replica A
    wakes on replica B from the shared host tier — no session_id on the
    resume request, radix caches not shared."""
    from penroz_tpu.serve import tierstore
    prompt = [3, 1, 4, 1, 5]
    out = gpt_model.generate_tokens([prompt], BLOCK, 4, temperature=0.0)
    cont = out + [2]
    base = gpt_model.generate_tokens([cont], BLOCK, 3, temperature=0.0)
    a = make_engine("tiergpt", BLOCK, 0.0, None, capacity=2, replica=0)
    b = make_engine("tiergpt", BLOCK, 0.0, None, capacity=2, replica=1)
    assert _submit(a, prompt, 4, session_id="nomad").result() == out
    rec = _wait_tier("nomad", "host")
    assert rec.replica == 0
    assert _submit(b, cont, 3).result() == base
    assert b.stats()["session_promotions"] == 1
    assert tierstore.TIERS.promotions[("host", "ok")] == 1
    assert a.stats()["session_promotions"] == 0


def test_disk_wake_survives_engine_reset(gpt_model, make_engine, tier_env):
    """With a zero host cap the demotion spills straight to disk; the blob
    outlives ``decode_scheduler.reset()`` and resumes with parity."""
    from penroz_tpu.serve import decode_scheduler, tierstore
    tier_env.setenv("PENROZ_TIER_HOST_MB", "0")
    prompt = [9, 10, 11, 12, 13]
    out = gpt_model.generate_tokens([prompt], BLOCK, 4, temperature=0.0)
    cont = out + [7]
    base = gpt_model.generate_tokens([cont], BLOCK, 3, temperature=0.0)
    engine = make_engine("tiergpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine, prompt, 4, session_id="frozen").result() == out
    _wait_tier("frozen", "disk")
    decode_scheduler.reset()
    engine2 = make_engine("tiergpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine2, cont, 3).result() == base
    assert tierstore.TIERS.promotions[("disk", "ok")] == 1
    assert tierstore.TIERS.stats()["tier_demotions"]["disk"] == 1


def test_corrupt_disk_blob_recomputes_never_missserves(gpt_model,
                                                       make_engine,
                                                       tier_env):
    """Satellite: a bit-flipped disk blob yields the SAME tokens via
    recompute — a miss plus ``penroz_tier_corrupt_blobs_total``, never a
    crash or a wrong stream."""
    from penroz_tpu.serve import decode_scheduler, tierstore
    from penroz_tpu.utils import checkpoint
    tier_env.setenv("PENROZ_TIER_HOST_MB", "0")
    prompt = [5, 4, 3, 2, 1]
    out = gpt_model.generate_tokens([prompt], BLOCK, 4, temperature=0.0)
    cont = out + [6]
    base = gpt_model.generate_tokens([cont], BLOCK, 3, temperature=0.0)
    engine = make_engine("tiergpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine, prompt, 4, session_id="bitrot").result() == out
    _wait_tier("bitrot", "disk")
    path = checkpoint.tier_blob_path("bitrot")
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(raw)
    decode_scheduler.reset()
    engine2 = make_engine("tiergpt", BLOCK, 0.0, None, capacity=2)
    assert _submit(engine2, cont, 3).result() == base   # recomputed
    assert tierstore.TIERS.corrupt_blobs == 1
    assert tierstore.TIERS.promotions[("disk", "corrupt")] == 1
    assert tierstore.TIERS.get("bitrot") is None
    assert engine2.stats()["crashes_total"] == 0


def test_memledger_hibernating_state_balances(gpt_model, make_engine,
                                              tier_env):
    """The partition invariant with the new state: pages pinned under a
    hibernation hold count ``hibernating`` (strict audit at every
    transition), return to plain cache residency after demotion, and the
    aggregate hbm_bytes gains host_tier/disk_tier entries."""
    from penroz_tpu.serve import memledger, tierstore
    engine = make_engine("tiergpt", BLOCK, 0.0, None, capacity=2)
    prompt = [1, 2, 3, 4, 5, 6, 7]
    _submit(engine, prompt, 4, session_id="ledger").result()
    _wait_tier("ledger", "host")
    _wait_pins_released(engine)
    snap = engine.memory_snapshot()
    pool = snap["pool_pages"]
    # demoted: the hold is released, pages are evictable cache residents
    assert pool["hibernating"] == 0
    assert pool["prefix_evictable"] > 0
    engine._ledger.audit("test.after_demote")
    agg = memledger.memory_stats()
    assert agg["hbm_bytes"]["host_tier"] \
        == tierstore.TIERS.tier_bytes()["host_tier"] > 0
    assert agg["pool_pages"]["hibernating"] == 0
    # DELETE while a later hold is pending: hibernate again, then drop
    # before demotion — the worker releases the pin, books still balance
    cont = _submit(engine, prompt + [8], 3, session_id="ledger2")
    cont.result()
    assert tierstore.TIERS.drop("ledger2", "api")
    _wait_tier("ledger", "host")     # original still resident
    _wait_pins_released(engine)
    engine._ledger.audit("test.after_drop")
    assert engine.memory_snapshot()["pool_pages"]["hibernating"] == 0
    assert tierstore.TIERS.get("ledger2") is None


@pytest.mark.parametrize("site", ["tier.demote", "tier.promote"])
def test_tier_fault_sites_crash_recover_with_parity(gpt_model, make_engine,
                                                    tier_env, monkeypatch,
                                                    site):
    """Both injection sites fail the tick into standard crash recovery:
    the engine resets, strict audits stay green, and the SAME histories
    then hibernate/resume with parity."""
    from penroz_tpu.utils import faults
    prompt = [1, 2, 3, 4, 5, 6, 7]
    out = gpt_model.generate_tokens([prompt], BLOCK, 4, temperature=0.0)
    cont = out + [9]
    base = gpt_model.generate_tokens([cont], BLOCK, 3, temperature=0.0)
    monkeypatch.setenv("PENROZ_FAULT_INJECT", f"{site}:raise@1")
    faults.reset()
    engine = make_engine("tiergpt", BLOCK, 0.0, None, capacity=2)
    if site == "tier.demote":
        # the generation succeeds; the async demotion tick crashes
        assert _submit(engine, prompt, 4, session_id="chaos").result() == out
        # ... and has failed what was queued: the crash is counted before
        # that, the reset after it, so a request submitted on the count
        # alone can still be failed with the crash's error
        deadline = time.monotonic() + 60
        while engine.stats()["engine_resets"] < 1:
            assert time.monotonic() < deadline, "demote fault never fired"
            time.sleep(0.02)
        assert engine.stats()["crashes_total"] == 1
    else:
        # hibernate cleanly first, then the WAKE admission crashes: the
        # client gets the injected error, not a hang
        assert _submit(engine, prompt, 4, session_id="chaos").result() == out
        _wait_tier("chaos", "host")
        # churn enough distinct prefixes through the 8-page radix region
        # to LRU-evict the session's copy, so the wake must import
        for j in range(5):
            filler = [30 + j] * 8
            _submit(engine, filler, 2).result()
        with pytest.raises(Exception, match="injected fault"):
            _submit(engine, cont, 3).result()
        assert engine.stats()["crashes_total"] == 1
    # disarmed now (raise@1): the full flow works on the recovered engine
    assert _submit(engine, prompt, 4, session_id="after").result() == out
    _wait_tier("after", "host")
    assert _submit(engine, cont, 3).result() == base
    assert engine.stats()["breaker_open"] is False


# -- HTTP surface ------------------------------------------------------------

@pytest.fixture
def client(workdir):
    import asyncio
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


def test_sessions_api_surface(client, gpt_model, tier_env):
    """session_id on /generate/ hibernates; GET /sessions/ shows the
    residency across tiers; DELETE /sessions/{id} is an idempotent evict;
    session_ids on /generate_batch/ validates per row."""
    tier_env.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    payload = {"model_id": "tiergpt", "input": [[1, 2, 3, 4, 5]],
               "block_size": BLOCK, "max_new_tokens": 4,
               "temperature": 0.0, "session_id": "api-conv"}
    status, body = _json(client, "POST", "/generate/", json=payload)
    assert status == 200 and len(body["tokens"]) == 9
    deadline = time.monotonic() + 60
    while True:
        status, listing = _json(client, "GET", "/sessions/")
        assert status == 200
        if listing["sessions_by_tier"]["host"] == 1:
            break
        assert time.monotonic() < deadline, listing
        time.sleep(0.02)
    (sess,) = listing["sessions"]
    assert sess["session_id"] == "api-conv" and sess["tier"] == "host"
    assert sess["pages"] * 4 == sess["tokens"]
    assert listing["tier_bytes"]["host_tier"] > 0
    # malformed id: schema-rejected before any engine work (422)
    status, _ = _json(client, "POST", "/generate/",
                      json=dict(payload, session_id="bad id!"))
    assert status == 422
    # batched path: one id per row, null = no session
    status, body = _json(client, "POST", "/generate_batch/", json={
        "model_id": "tiergpt", "inputs": [[1, 2, 3], [4, 5]],
        "block_size": BLOCK, "max_new_tokens": 3, "temperature": 0.0,
        "session_ids": ["api-b0", None]})
    assert status == 200 and len(body["sequences"]) == 2
    # wrong arity is a 400 naming the mismatch
    status, err = _json(client, "POST", "/generate_batch/", json={
        "model_id": "tiergpt", "inputs": [[1, 2, 3], [4, 5]],
        "block_size": BLOCK, "max_new_tokens": 3, "temperature": 0.0,
        "session_ids": ["only-one"]})
    assert status == 400
    # delete: evicts everywhere, idempotent on re-delete
    status, body = _json(client, "DELETE", "/sessions/api-conv")
    assert status == 200 and body["deleted"] is True
    status, body = _json(client, "DELETE", "/sessions/api-conv")
    assert status == 200 and body["deleted"] is False
    status, listing = _json(client, "GET", "/sessions/")
    assert "api-conv" not in {s["session_id"]
                              for s in listing["sessions"]}
