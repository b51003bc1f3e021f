"""Multi-tenant LoRA serving tests: mixed-adapter rows in the shared
decode batch (serve/decode_scheduler.py + serve/adapters.py), the
/adapters/ HTTP surface, and the training-worker exit contract.

THE acceptance bar: a mixed-adapter shared batch (adapters A, B, and base
interleaved) is token-identical to running each adapter in its own
isolated engine — across prefix-cache on/off × spec-decode on/off ×
chunked/one-shot prefill — and the prefix cache never serves pages across
different adapter ids.
"""

import asyncio
import json
import queue
import time

import numpy as np
import pytest

from penroz_tpu.models import lora
from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import NeuralNetworkModel
from penroz_tpu.utils import checkpoint, faults

pytestmark = pytest.mark.runtime

BLOCK = 16
SGD = {"sgd": {"lr": 0.1}}


@pytest.fixture(autouse=True)
def _serving_state(workdir):
    from penroz_tpu.serve import adapters, decode_scheduler
    faults.reset()
    adapters.REGISTRY.reset()
    yield
    decode_scheduler.reset()
    adapters.REGISTRY.reset()
    faults.reset()


@pytest.fixture
def gpt_model(workdir, toy_gpt_layers):
    model = NeuralNetworkModel("mtgpt", Mapper(toy_gpt_layers, SGD))
    model.serialize(sync_flush=True)
    return model


@pytest.fixture
def tenants(gpt_model):
    """Two random (non-identity) adapters registered + registry entries."""
    from penroz_tpu.serve import adapters
    entries = {}
    for aid, (rank, seed) in (("tenA", (4, 11)), ("tenB", (2, 22))):
        cfg = lora.validate_config({"rank": rank})
        params = lora.init_params(gpt_model.arch, cfg, seed=seed,
                                  init="random")
        lora.save_adapter(aid, "mtgpt", cfg, params, {"code": "Created"},
                          sync_flush=True)
        entries[aid] = adapters.REGISTRY.acquire(aid, "mtgpt")
    return entries


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


def _submit(engine, prompt, max_new, adapter=None):
    from penroz_tpu.serve import decode_scheduler
    collector = _Collector(prompt)
    engine.submit(decode_scheduler.Request(prompt, max_new, None,
                                           collector.on_event,
                                           adapter=adapter))
    return collector


# ---------------------------------------------------------------------------
# THE parity matrix: mixed batch == isolated per-adapter engines
# ---------------------------------------------------------------------------

# the whole matrix rides the slow lane (tier1_budget): mixed-adapter
# parity stays fast via test_mixed_adapter_superstep_parity[8] below
@pytest.mark.slow
@pytest.mark.parametrize("prefix_cache", [pytest.param(False,
                                                       marks=pytest.mark.slow),
                                          True],
                         ids=["nocache", "prefix"])
# spec-off mixing covered by the superstep parity test below
@pytest.mark.parametrize("spec", [pytest.param(False,
                                               marks=pytest.mark.slow),
                                  True],
                         ids=["nospec", "spec"])
@pytest.mark.parametrize("chunked", [pytest.param(False, marks=pytest.mark.slow),
                                     True],
                         ids=["oneshot", "chunked"])
def test_mixed_adapter_parity_matrix(gpt_model, tenants, make_engine,
                                     monkeypatch, prefix_cache, spec,
                                     chunked):
    """Adapters A, B, and base interleaved in ONE shared batch return
    exactly the tokens each tenant gets from an engine serving only that
    tenant — with the prefix cache on/off, speculative decoding on/off,
    and chunked/one-shot prefill.  Two waves per engine so the 'on'
    prefix-cache combos exercise real hits on the second wave."""
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", "1")
    if prefix_cache:
        monkeypatch.setenv("PAGED_KV_CACHE", "1")
        monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
        monkeypatch.setenv("PENROZ_PREFIX_CACHE", "1")
        monkeypatch.setenv("PENROZ_PREFIX_CACHE_PAGES", "16")
    if spec:
        monkeypatch.setenv("PENROZ_SPEC_DECODE", "1")
    if chunked:
        monkeypatch.setenv("PENROZ_PREFILL_CHUNK", "4")
    # distinct leading tokens keep the oracle-drafter corpus unambiguous
    jobs = [("tenA", [1, 2, 1, 2, 1, 2]),
            (None, [5, 6, 5, 6]),
            ("tenB", [7, 8, 7, 8, 7])]
    max_new = 5

    # Ground truth per tenant: the spec-free LEGACY path through a bound
    # model (same KV env flags).  The baselines double as the oracle
    # drafter's corpus in the spec combos, so the verify/rollback path
    # provably engages (full acceptance) instead of depending on the toy
    # stream happening to cycle.
    oracles = {}
    for aid, prompt in jobs:
        model = gpt_model
        if aid is not None:
            entry = tenants[aid]
            model = lora.bind_model(gpt_model, entry.params, entry.config)
        oracles[aid] = model.generate_tokens([prompt], BLOCK, max_new,
                                             temperature=0.0)
    if spec:
        from penroz_tpu.serve import spec_decode

        def oracle_drafter(history, k, n):
            for base in oracles.values():
                if (len(history) < len(base)
                        and history == base[:len(history)]):
                    return [int(t)
                            for t in base[len(history):len(history) + k]]
            return []

        monkeypatch.setattr(spec_decode, "propose", oracle_drafter)

    for aid, prompt in jobs:
        iso = make_engine("mtgpt", BLOCK, 0.0, None, capacity=2)
        for _ in range(2):  # wave 2 = prefix-cache hit in the 'on' combos
            assert _submit(iso, prompt, max_new,
                           adapter=tenants.get(aid)).result() \
                == oracles[aid], f"isolated engine diverged for {aid}"
        iso.shutdown()

    mixed = make_engine("mtgpt", BLOCK, 0.0, None, capacity=3)
    for wave in range(2):
        collectors = [(aid, _submit(mixed, prompt, max_new,
                                    adapter=tenants.get(aid)))
                      for aid, prompt in jobs]
        for aid, collector in collectors:
            assert collector.result() == oracles[aid], \
                f"wave {wave}: adapter {aid} diverged in the mixed batch"
    stats = mixed.stats()
    assert stats["lora_active_adapters"] == 2
    assert stats["lora_adapter_tokens"]["tenA"] == 2 * max_new
    assert stats["lora_adapter_tokens"]["tenB"] == 2 * max_new
    if spec:
        assert stats["spec_drafted_tokens"] > 0  # the combo really drafted
    if prefix_cache:
        pc = stats["prefix_cache"]
        assert pc is not None and pc["hits"] > 0  # wave 2 really hit


def test_prefix_cache_never_crosses_adapter_ids(gpt_model, tenants,
                                                make_engine, monkeypatch):
    """Same prompt through base, then adapter A, then base again: the
    adapter request must MISS (pages were inserted under the base
    namespace) and only the second base request may hit."""
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    monkeypatch.setenv("PENROZ_PREFIX_CACHE", "1")
    monkeypatch.setenv("PENROZ_PREFIX_CACHE_PAGES", "16")
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]  # two full 4-token pages
    engine = make_engine("mtgpt", BLOCK, 0.0, None, capacity=2)
    _submit(engine, prompt, 3).result()
    assert engine._prefix_cache.hits == 0
    _submit(engine, prompt, 3, adapter=tenants["tenA"]).result()
    assert engine._prefix_cache.hits == 0, \
        "adapter row must not hit base-namespace pages"
    _submit(engine, prompt, 3).result()
    assert engine._prefix_cache.hits == 1
    _submit(engine, prompt, 3, adapter=tenants["tenA"]).result()
    assert engine._prefix_cache.hits == 2  # its OWN namespace now hits


def test_crash_recovery_rebuilds_adapter_row_tables(gpt_model, tenants,
                                                    make_engine,
                                                    monkeypatch):
    """An injected decode.step crash mid-mixed-batch fails the in-flight
    requests, _alloc_state rebuilds the adapter row tables (all rows
    re-park on the base slot, the stacked pack drops), and the next
    adapter request is greedy-identical to the no-crash path."""
    pa = [1, 2, 3]
    iso = make_engine("mtgpt", BLOCK, 0.0, None, capacity=2)
    oracle = _submit(iso, pa, 6, adapter=tenants["tenA"]).result()
    iso.shutdown()

    monkeypatch.setenv(faults.ENV, "decode.step:raise@1")
    engine = make_engine("mtgpt", BLOCK, 0.0, None, capacity=2)
    c1 = _submit(engine, pa, 6, adapter=tenants["tenA"])
    c2 = _submit(engine, [5], 6)
    with pytest.raises(faults.InjectedFault):
        c1.result()
    with pytest.raises(faults.InjectedFault):
        c2.result()
    monkeypatch.delenv(faults.ENV)
    faults.reset()
    # _fail_all delivers the errors BEFORE _alloc_state rebuilds the
    # engine — wait for the reset to land before poking at internals
    deadline = time.monotonic() + 30
    while engine._lora_pack is not None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert engine._lora_pack is None
    assert all(int(s) == engine._max_live for s in engine._row_adapter)
    assert all(e is None for e in engine._slot_entries)
    assert _submit(engine, pa, 6,
                   adapter=tenants["tenA"]).result() == oracle
    assert engine.stats()["engine_resets"] == 1


def test_more_adapters_than_live_slots_all_complete(gpt_model, make_engine,
                                                    monkeypatch):
    """With PENROZ_LORA_MAX_LIVE=1 and two tenants in flight, the second
    tenant waits for a slot (requeued at the head, FIFO) and still
    completes with its isolated-engine tokens — never a wrong-adapter
    forward."""
    from penroz_tpu.serve import adapters
    monkeypatch.setenv(lora.MAX_LIVE_ENV, "1")
    entries = {}
    for aid, seed in (("slotA", 31), ("slotB", 32)):
        cfg = lora.validate_config({"rank": 2})
        lora.save_adapter(aid, "mtgpt", cfg,
                          lora.init_params(gpt_model.arch, cfg, seed=seed,
                                           init="random"),
                          {"code": "Created"}, sync_flush=True)
        entries[aid] = adapters.REGISTRY.acquire(aid, "mtgpt")
    oracles = {}
    for aid in entries:
        iso = make_engine("mtgpt", BLOCK, 0.0, None, capacity=2)
        oracles[aid] = _submit(iso, [1, 2, 3], 5,
                               adapter=entries[aid]).result()
        iso.shutdown()
    engine = make_engine("mtgpt", BLOCK, 0.0, None, capacity=4)
    ca = _submit(engine, [1, 2, 3], 5, adapter=entries["slotA"])
    cb = _submit(engine, [1, 2, 3], 5, adapter=entries["slotB"])
    assert ca.result() == oracles["slotA"]
    assert cb.result() == oracles["slotB"]


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------

@pytest.fixture
def client(workdir):
    from aiohttp.test_utils import TestClient, TestServer
    from penroz_tpu.serve import app as app_mod
    app_mod.model_locks.clear()
    app_mod.dataset_locks.clear()
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app_mod.create_app()), loop=loop)
    loop.run_until_complete(client.start_server())

    class Sync:
        def request(self, method, path, **kw):
            async def go():
                resp = await client.request(method, path, **kw)
                body = await resp.read()
                return resp, body
            return loop.run_until_complete(go())

        def json(self, method, path, **kw):
            resp, body = self.request(method, path, **kw)
            return resp.status, (json.loads(body) if body else None)

    yield Sync()
    loop.run_until_complete(client.close())
    loop.close()


def _create_gpt(client, toy_gpt_layers, model_id="mtgpt"):
    status, _ = client.json("POST", "/model/", json={
        "model_id": model_id, "layers": toy_gpt_layers,
        "optimizer": SGD})
    assert status == 200


def test_adapters_http_lifecycle(client, toy_gpt_layers):
    _create_gpt(client, toy_gpt_layers)
    status, body = client.json("POST", "/adapters/", json={
        "model_id": "mtgpt", "adapter_id": "t1", "rank": 4,
        "init": "random", "seed": 3})
    assert status == 200, body
    assert body["config"]["rank"] == 4
    # duplicate → 409
    status, _ = client.json("POST", "/adapters/", json={
        "model_id": "mtgpt", "adapter_id": "t1"})
    assert status == 409
    # unknown model → 404
    status, _ = client.json("POST", "/adapters/", json={
        "model_id": "ghost", "adapter_id": "t2"})
    assert status == 404
    # rank over PENROZ_LORA_MAX_RANK → 400
    status, body = client.json("POST", "/adapters/", json={
        "model_id": "mtgpt", "adapter_id": "t3", "rank": 4096})
    assert status == 400 and "rank" in body["detail"]
    # listing + detail
    status, body = client.json("GET", "/adapters/")
    assert status == 200
    assert [a["adapter_id"] for a in body["adapters"]] == ["t1"]
    status, body = client.json("GET", "/adapters/",
                               params={"adapter_id": "t1"})
    assert status == 200 and body["model_id"] == "mtgpt"
    status, _ = client.json("GET", "/adapters/",
                            params={"adapter_id": "nope"})
    assert status == 404
    # delete
    status, _ = client.json("DELETE", "/adapters/",
                            params={"adapter_id": "t1"})
    assert status == 204
    status, _ = client.json("DELETE", "/adapters/",
                            params={"adapter_id": "t1"})
    assert status == 404


@pytest.mark.parametrize("batching", ["0", "1"], ids=["legacy", "sched"])
def test_generate_unknown_adapter_400_names_it(client, toy_gpt_layers,
                                               monkeypatch, batching):
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", batching)
    _create_gpt(client, toy_gpt_layers)
    status, body = client.json("POST", "/generate/", json={
        "model_id": "mtgpt", "input": [[1, 2, 3]], "block_size": BLOCK,
        "max_new_tokens": 4, "temperature": 0.0, "adapter_id": "ghost"})
    assert status == 400, body
    assert "ghost" in body["detail"]
    assert "500" not in str(status)


def test_generate_batch_per_row_unknown_adapter_400(client, toy_gpt_layers):
    _create_gpt(client, toy_gpt_layers)
    status, _ = client.json("POST", "/adapters/", json={
        "model_id": "mtgpt", "adapter_id": "ok", "rank": 2})
    assert status == 200
    status, body = client.json("POST", "/generate_batch/", json={
        "model_id": "mtgpt", "inputs": [[1, 2], [3, 4], [5, 6]],
        "block_size": BLOCK, "max_new_tokens": 3, "temperature": 0.0,
        "adapter_ids": ["bad1", "ok", "bad1"]})
    assert status == 400, body
    assert "bad1" in body["detail"]
    assert "row 0" in body["detail"] and "row 2" in body["detail"]
    # mismatched adapter_ids length is a 400 too
    status, body = client.json("POST", "/generate_batch/", json={
        "model_id": "mtgpt", "inputs": [[1, 2], [3, 4]],
        "block_size": BLOCK, "max_new_tokens": 3, "temperature": 0.0,
        "adapter_ids": ["ok"]})
    assert status == 400 and "one per row" in body["detail"]


def test_generate_still_loading_adapter_409(client, toy_gpt_layers,
                                            monkeypatch):
    """A request arriving while another request's adapter load is in
    flight gets a 409 naming the adapter, not a stall or a 500."""
    import threading
    from penroz_tpu.serve import adapters
    _create_gpt(client, toy_gpt_layers)
    status, _ = client.json("POST", "/adapters/", json={
        "model_id": "mtgpt", "adapter_id": "slowy", "rank": 2})
    assert status == 200
    monkeypatch.setenv(faults.ENV, "lora.load:sleep@500")
    holder = threading.Thread(
        target=lambda: adapters.REGISTRY.acquire("slowy", "mtgpt"))
    holder.start()
    time.sleep(0.1)  # holder is inside the injected load sleep
    status, body = client.json("POST", "/generate/", json={
        "model_id": "mtgpt", "input": [[1, 2, 3]], "block_size": BLOCK,
        "max_new_tokens": 3, "temperature": 0.0, "adapter_id": "slowy"})
    holder.join(timeout=10)
    assert status == 409, body
    assert "slowy" in body["detail"]


def test_delete_model_flushes_its_adapters(client, toy_gpt_layers):
    """DELETE /model/ drops the model's adapters — registry cache AND
    checkpoints — while another model's adapters survive (the PR-2
    prefix-cache-flush contract extended to adapters)."""
    from penroz_tpu.serve import adapters
    _create_gpt(client, toy_gpt_layers, "mtgpt")
    _create_gpt(client, toy_gpt_layers, "other")
    for model_id, aid in (("mtgpt", "mine"), ("other", "theirs")):
        status, _ = client.json("POST", "/adapters/", json={
            "model_id": model_id, "adapter_id": aid, "rank": 2})
        assert status == 200
    adapters.REGISTRY.acquire("mine", "mtgpt")
    status, _ = client.json("DELETE", "/model/",
                            params={"model_id": "mtgpt"})
    assert status == 204
    assert checkpoint.list_adapter_ids() == ["theirs"]
    assert adapters.REGISTRY.cached_ids() == []
    status, body = client.json("GET", "/adapters/")
    assert [a["adapter_id"] for a in body["adapters"]] == ["theirs"]


# the legacy (non-scheduler) serve path is covered by the nocache arms
@pytest.mark.parametrize("batching", [pytest.param("0",
                                                   marks=pytest.mark.slow),
                                      "1"],
                         ids=["legacy", "sched"])
def test_api_trained_adapter_roundtrips_and_serves(client, toy_gpt_layers,
                                                   toy_shards, monkeypatch,
                                                   batching):
    """PUT /train/ with an adapter config fine-tunes against the frozen
    base, GET /adapters/ reports Trained + progress, and /generate/ with
    the adapter_id serves the trained factors — through the scheduler and
    the legacy path alike."""
    monkeypatch.setenv("PENROZ_CONTINUOUS_BATCHING", batching)
    _create_gpt(client, toy_gpt_layers)
    status, body = client.json("PUT", "/train/", json={
        "model_id": "mtgpt", "device": "cpu", "dataset_id": toy_shards,
        "shard": 0, "epochs": 2, "batch_size": 2, "block_size": 8,
        "step_size": 1,
        "adapter": {"adapter_id": "ft", "rank": 2}})
    assert status == 202, body
    assert "adapter ft" in body["message"]
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        status, body = client.json("GET", "/adapters/",
                                   params={"adapter_id": "ft"})
        if status == 200 and body["status"]["code"] in ("Trained", "Error"):
            break
        time.sleep(0.3)
    assert body["status"]["code"] == "Trained", body
    assert len(body["progress"]) == 2
    # base model status untouched by the adapter run
    status, prog = client.json("GET", "/progress/",
                               params={"model_id": "mtgpt"})
    assert prog["status"]["code"] == "Created"
    # the trained adapter serves
    payload = {"model_id": "mtgpt", "input": [[1, 2, 3]],
               "block_size": BLOCK, "max_new_tokens": 4,
               "temperature": 0.0, "adapter_id": "ft"}
    status, body = client.json("POST", "/generate/", json=payload)
    assert status == 200, body
    assert len(body["tokens"]) == 7
    # invalid adapter config 400s BEFORE the 202
    status, body = client.json("PUT", "/train/", json={
        "model_id": "mtgpt", "device": "cpu", "dataset_id": toy_shards,
        "shard": 0, "epochs": 1, "batch_size": 2, "block_size": 8,
        "step_size": 1,
        "adapter": {"adapter_id": "bad", "rank": 4096}})
    assert status == 400 and "rank" in body["detail"]


# ---------------------------------------------------------------------------
# Training-worker exit propagation (PENROZ_TRAIN_WORKER=1)
# ---------------------------------------------------------------------------

def test_train_worker_clean_failure_exits_nonzero_and_parent_logs(
        gpt_model, monkeypatch):
    """A clean Python-level training failure in the worker subprocess
    (missing dataset → status Error, not a native crash) must exit
    nonzero, and the parent must log the death — not swallow it because
    the status was already Error.

    Asserted via a logger-method spy, not caplog — other suite tests
    reconfigure logging handlers, which silently empties caplog (same
    workaround as test_attention's softcap-warning test)."""
    from penroz_tpu.models import model as model_mod
    monkeypatch.setenv("PENROZ_TRAIN_WORKER", "1")
    errors = []
    monkeypatch.setattr(
        model_mod.log, "error",
        lambda msg, *args, **kw: errors.append(msg % tuple(args)
                                               if args else msg))
    model = NeuralNetworkModel.train_model_on_device(
        "mtgpt", "cpu", "no-such-dataset", 0, 1, 1, 8, 1)
    assert model.status["code"] == "Error"
    assert any("Training worker for model mtgpt" in m and "rc=" in m
               for m in errors), errors


@pytest.mark.parametrize("superstep", [
    # step-1 mixing is covered by the parity matrix above; 4 adds no
    # seam beyond 8
    pytest.param(1, marks=pytest.mark.slow),
    pytest.param(4, marks=pytest.mark.slow),
    8])
def test_mixed_adapter_superstep_parity(gpt_model, tenants, make_engine,
                                        monkeypatch, superstep):
    """Compiled multi-step decode over a MIXED-adapter batch: rows bound
    to adapter A, adapter B and the base model share one fused
    PENROZ_SCHED_SUPERSTEP-step dispatch (the stacked pack and per-row
    slot gather ride the scan carry unchanged), and every tenant's
    stream is token-identical to its bound-model standalone run at every
    superstep size."""
    from penroz_tpu.serve import decode_scheduler
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, str(superstep))
    jobs = [("tenA", [1, 2, 1, 2, 1, 2]),
            (None, [5, 6, 5, 6]),
            ("tenB", [7, 8, 7, 8, 7])]
    max_new = 6
    oracles = {}
    for aid, prompt in jobs:
        model = gpt_model
        if aid is not None:
            entry = tenants[aid]
            model = lora.bind_model(gpt_model, entry.params, entry.config)
        oracles[aid] = model.generate_tokens([prompt], BLOCK, max_new,
                                             temperature=0.0)
    engine = make_engine("mtgpt", BLOCK, 0.0, None, capacity=3)
    for wave in range(2):
        collectors = [(aid, _submit(engine, prompt, max_new,
                                    adapter=tenants.get(aid)))
                      for aid, prompt in jobs]
        for aid, collector in collectors:
            assert collector.result() == oracles[aid], \
                f"wave {wave}: adapter {aid} diverged at superstep " \
                f"{superstep}"
    stats = engine.stats()
    assert stats["lora_active_adapters"] == 2
    assert stats["lora_adapter_tokens"] == {"tenA": 2 * max_new,
                                            "tenB": 2 * max_new}
    if superstep > 1:
        assert any(e["superstep"] > 1 for e in stats["tick_timeline"])


# adapter mixing under the unified tick is also pinned by the
# chunked-spec-prefix arm of the parity matrix above
@pytest.mark.slow
def test_unified_mixed_adapter_parity(gpt_model, tenants, make_engine,
                                      monkeypatch):
    """The ragged unified tick serves a mixed-adapter batch (A, B, base
    interleaved, paged KV, chunked prefill) token-identically to the
    legacy phased scheduler AND to each tenant's bound-model standalone
    run — the per-row LoRA slot gather rides the one mixed dispatch."""
    from penroz_tpu.serve import decode_scheduler
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    monkeypatch.setenv("PENROZ_PREFILL_CHUNK", "4")
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, "8")
    jobs = [("tenA", [1, 2, 1, 2, 1, 2]),
            (None, [5, 6, 5, 6]),
            ("tenB", [7, 8, 7, 8, 7])]
    max_new = 6
    oracles = {}
    for aid, prompt in jobs:
        model = gpt_model
        if aid is not None:
            entry = tenants[aid]
            model = lora.bind_model(gpt_model, entry.params, entry.config)
        oracles[aid] = model.generate_tokens([prompt], BLOCK, max_new,
                                             temperature=0.0)
    for ragged in ("1", "0"):
        monkeypatch.setenv(decode_scheduler.RAGGED_ENV, ragged)
        engine = make_engine("mtgpt", BLOCK, 0.0, None, capacity=3)
        collectors = [(aid, _submit(engine, prompt, max_new,
                                    adapter=tenants.get(aid)))
                      for aid, prompt in jobs]
        for aid, collector in collectors:
            assert collector.result() == oracles[aid], \
                f"adapter {aid} diverged (ragged={ragged})"
        stats = engine.stats()
        assert stats["lora_active_adapters"] == 2
        unified_ticks = [e for e in stats["tick_timeline"]
                         if e.get("unified")]
        if ragged == "1":
            assert unified_ticks, "paged engine must take the unified path"
        else:
            assert not unified_ticks, "escape hatch must restore phased"
        engine.shutdown()
