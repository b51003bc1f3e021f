"""Replica-group router tests (serve/router.py).

Tier-1-safe: CPU, small shapes, no `slow` marker.  Three contracts carry
the weight here: (1) greedy parity — routing a request through any number
of replicas returns exactly the tokens the legacy single-engine path
returns; (2) failover — one breaker-tripped replica never surfaces a
client-visible 503 while a healthy sibling exists, and the half-open
probe re-admits it afterwards; (3) affinity — a repeated page-aligned
prefix family is steered to the replica whose prefix cache holds the
pages.
"""

import queue
import time

import pytest

from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import NeuralNetworkModel

# CI tier: heavier compiles (serving stack), same tier as test_app.
pytestmark = pytest.mark.runtime

BLOCK = 16
SGD = {"sgd": {"lr": 0.1}}


@pytest.fixture(autouse=True)
def _router_registry(workdir):
    """Fresh engine+router registries and fault/QoS counters per test."""
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


def _submit(router, prompt, max_new):
    from penroz_tpu.serve import decode_scheduler
    collector = _Collector(prompt)
    router.submit(decode_scheduler.Request(prompt, max_new, None,
                                           collector.on_event))
    return collector


def _get_router(monkeypatch, n=2):
    """The production seam: get_engine hands back a router when
    PENROZ_SCHED_REPLICAS > 1."""
    from penroz_tpu.serve import decode_scheduler, router
    monkeypatch.setenv(decode_scheduler.REPLICAS_ENV, str(n))
    engine = decode_scheduler.get_engine("schedgpt", BLOCK, 0.0, None)
    assert isinstance(engine, router.EngineRouter)
    assert len(engine.replicas) == n
    return engine


def test_router_failover_then_probe_readmission(gpt_model, monkeypatch):
    """Breaker trips on replica 0 → requests reroute to replica 1 with no
    client-visible refusal; after the cooldown the half-open probe goes to
    replica 0 first and its success re-admits it."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    prompt = [1, 2, 3]
    base = gpt_model.generate_tokens([prompt], BLOCK, 5, temperature=0.0)
    monkeypatch.setenv(decode_scheduler.MAX_CRASHES_ENV, "2")
    monkeypatch.setenv(decode_scheduler.BREAKER_COOLDOWN_ENV, "100000")
    monkeypatch.setenv(faults.ENV,
                       "decode.step:raise@1,decode.step:raise@2")
    router = _get_router(monkeypatch, n=2)
    # Idle group → deterministic tie-break: both crashes land on replica 0.
    with pytest.raises(faults.InjectedFault):
        _submit(router, prompt, 5).result()
    with pytest.raises(faults.InjectedFault):
        _submit(router, prompt, 5).result()
    r0, r1 = router.replicas
    assert r0.stats()["breaker_open"] is True
    # One open replica must NOT mark the model not-ready: a healthy
    # sibling still serves.
    assert "schedgpt" not in decode_scheduler.breaker_open_engines()
    # Reroute: submissions succeed on replica 1, no CircuitOpenError.
    for _ in range(2):
        assert _submit(router, prompt, 5).result() == base
    assert r1.stats()["completed"] == 2
    assert r0.stats()["completed"] == 0
    # Cooldown over (0ms): probes outrank healthy replicas, so the next
    # admission IS the probe.
    monkeypatch.setenv(decode_scheduler.BREAKER_COOLDOWN_ENV, "0")
    assert _submit(router, prompt, 5).result() == base
    s0 = r0.stats()
    assert s0["completed"] == 1          # the probe ran on replica 0
    assert s0["breaker_open"] is False   # and closed the breaker
    assert s0["consecutive_crashes"] == 0


def test_router_all_replicas_open_surfaces_circuit_error(gpt_model,
                                                         monkeypatch):
    """Only when EVERY replica's breaker is open does the client see
    CircuitOpenError — and only then is the model listed not-ready."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    prompt = [1, 2, 3]
    monkeypatch.setenv(decode_scheduler.MAX_CRASHES_ENV, "1")
    monkeypatch.setenv(decode_scheduler.BREAKER_COOLDOWN_ENV, "100000")
    monkeypatch.setenv(faults.ENV,
                       "decode.step:raise@1,decode.step:raise@2")
    router = _get_router(monkeypatch, n=2)
    with pytest.raises(faults.InjectedFault):
        _submit(router, prompt, 5).result()      # replica 0 opens
    assert decode_scheduler.breaker_open_engines() == []
    with pytest.raises(faults.InjectedFault):
        _submit(router, prompt, 5).result()      # replica 1 opens
    assert decode_scheduler.breaker_open_engines() == ["schedgpt"]
    with pytest.raises(decode_scheduler.CircuitOpenError):
        _submit(router, prompt, 5)


# single-replica arms ride the slow lane (tier1_budget): a 1-replica
# router is engine passthrough (the scheduler parity matrix pins it);
# both 2-replica arms keep every real routing seam fast
@pytest.mark.parametrize("replicas,affinity", [
    pytest.param(1, "1", marks=pytest.mark.slow),
    (2, "1"), (2, "0")])
@pytest.mark.parametrize("prefix", [False, True])
@pytest.mark.parametrize("superstep", ["1", "8"])
def test_router_greedy_parity_matrix(gpt_model, monkeypatch, replicas,
                                     affinity, prefix, superstep):
    """Token parity through the router under {1 replica, 2 affinity-on,
    2 affinity-off} × prefix-cache × superstep, with the 1-device serving
    mesh active throughout."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.serve import router as router_mod
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, superstep)
    monkeypatch.setenv(router_mod.AFFINITY_ENV, affinity)
    monkeypatch.setenv("PENROZ_SERVE_MESH", "1")
    if prefix:
        monkeypatch.setenv("PAGED_KV_CACHE", "1")
        monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
        monkeypatch.setenv("PENROZ_PREFIX_CACHE", "1")
        monkeypatch.setenv("PENROZ_PREFIX_CACHE_PAGES", "8")
    # A page-aligned shared-prefix pair plus a disjoint prompt: exercises
    # steering (when on) and cold placement in the same run.
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8],
               [1, 2, 3, 4, 5, 6, 7, 8, 9],
               [11, 12]]
    bases = [gpt_model.generate_tokens([p], BLOCK, 5, temperature=0.0)
             for p in prompts]
    monkeypatch.setenv(decode_scheduler.REPLICAS_ENV, str(replicas))
    engine = decode_scheduler.get_engine("schedgpt", BLOCK, 0.0, None)
    if replicas > 1:
        assert isinstance(engine, router_mod.EngineRouter)
    collectors = [_submit(engine, p, 5) for p in prompts]
    for collector, base in zip(collectors, bases):
        assert collector.result() == base
    stats = decode_scheduler.serving_stats()
    assert stats["router_replicas"] == (replicas if replicas > 1 else 0)


def test_router_prefix_affinity_steers_family_to_one_replica(gpt_model,
                                                             monkeypatch):
    """A repeated-prefix family (same two leading pages, different tails)
    lands on the replica that cached those pages: first request is the
    cold miss, every later one an affinity hit on the same replica."""
    from penroz_tpu.serve import decode_scheduler
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    monkeypatch.setenv("PENROZ_PREFIX_CACHE", "1")
    monkeypatch.setenv("PENROZ_PREFIX_CACHE_PAGES", "8")
    router = _get_router(monkeypatch, n=2)
    shared = [1, 2, 3, 4, 5, 6, 7, 8]          # two full pages
    family = [shared + tail for tail in ([9], [10, 11], [12], [13])]
    bases = [gpt_model.generate_tokens([p], BLOCK, 5, temperature=0.0)
             for p in family]
    for prompt, base in zip(family, bases):
        assert _submit(router, prompt, 5).result() == base
    assert router.affinity_misses == 1          # the cold first request
    assert router.affinity_hits == len(family) - 1
    done = [e.stats()["completed"] for e in router.replicas]
    assert sorted(done) == [0, len(family)]     # whole family, one replica
    stats = decode_scheduler.serving_stats()
    assert stats["router_affinity_hits"] == len(family) - 1
    assert stats["router_affinity_misses"] == 1
    assert stats["router_affinity_hit_rate"] == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# Disaggregated prefill (PENROZ_DISAGG_PREFILL=1)
# ---------------------------------------------------------------------------

def _disagg_env(monkeypatch, prefill_replicas="1", prefix=True):
    from penroz_tpu.serve import router as router_mod
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    if prefix:
        monkeypatch.setenv("PENROZ_PREFIX_CACHE", "1")
        monkeypatch.setenv("PENROZ_PREFIX_CACHE_PAGES", "8")
    monkeypatch.setenv("PENROZ_MEMLEDGER_STRICT", "1")
    monkeypatch.setenv(router_mod.DISAGG_ENV, "1")
    monkeypatch.setenv(router_mod.DISAGG_REPLICAS_ENV, prefill_replicas)


def _assert_no_transit_or_blob_leaks():
    """Strict partition check after a disagg run: every page owned, no
    lingering transit attribution, no staged blob left on shm."""
    import glob
    import os
    from penroz_tpu.serve import memledger
    from penroz_tpu.utils import checkpoint
    mem = memledger.memory_stats()
    for entry in mem["engines"]:
        pools = entry["pool_pages"]
        assert pools.get("transit", 0) == 0, pools
        assert sum(pools.values()) == entry["pool_pages_total"]
    blobs = glob.glob(os.path.join(checkpoint.SHM_PATH, "**", "pageblob_*"),
                      recursive=True)
    assert blobs == [], blobs


@pytest.mark.parametrize("transport", ["d2d", "host"])
# int8 KV parity through the hand-off is pinned by the single-engine
# matrices and the int8 codec property tests
@pytest.mark.parametrize("int8", [False,
                                  pytest.param(True, marks=pytest.mark.slow)])
@pytest.mark.parametrize("prefix", [False, True])
@pytest.mark.parametrize("superstep", ["1", "8"])
def test_router_disagg_greedy_parity_matrix(gpt_model, monkeypatch, int8,
                                            prefix, superstep, transport):
    """Tentpole acceptance: disaggregated prefill is token-identical to the
    legacy single-engine path across int8 KV × prefix-cache × superstep ×
    hand-off transport (d2d device arrays / host-staged blob) — and every
    request provably travelled the export → import seam (no silent
    monolithic fallback)."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.serve import metrics as serve_metrics
    from penroz_tpu.serve import router as router_mod
    _disagg_env(monkeypatch, prefix=prefix)
    monkeypatch.setenv(decode_scheduler.DISAGG_TRANSPORT_ENV, transport)
    if int8:
        monkeypatch.setenv("TURBO_QUANT_KV_CACHE", "1")
    monkeypatch.setenv(decode_scheduler.SUPERSTEP_ENV, superstep)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8],
               [1, 2, 3, 4, 5, 6, 7, 8, 9],
               [11, 12]]
    # legacy baseline under the same KV env flags
    bases = [gpt_model.generate_tokens([p], BLOCK, 5, temperature=0.0)
             for p in prompts]
    handoffs0 = serve_metrics.DISAGG_HANDOFFS.value(outcome="ok",
                                                    transport=transport)
    router = _get_router(monkeypatch, n=2)
    assert [e.role for e in router.replicas] == ["prefill", "decode"]
    collectors = [_submit(router, p, 5) for p in prompts]
    for collector, base in zip(collectors, bases):
        assert collector.result() == base
    per = [e.stats() for e in router.replicas]
    assert serve_metrics.DISAGG_HANDOFFS.value(
        outcome="ok", transport=transport) - handoffs0 == len(prompts)
    assert sum(p["disagg_exports"] for p in per) == len(prompts)
    assert sum(p["disagg_imports"] for p in per) == len(prompts)
    assert sum(p["disagg_handoff_failures"] for p in per) == 0
    # prefill replicas never decode: every emitted token is the decode
    # replica's (the first token ships inside the hand-off)
    assert per[0]["completed"] == 0
    assert per[1]["completed"] == len(prompts)
    stats = decode_scheduler.serving_stats()
    assert stats["disagg_prefill_replicas"] == 1
    assert stats["disagg_exports"] == len(prompts)
    assert stats["disagg_imports"] == len(prompts)
    assert stats["disagg_handoff_ms_p99"] is not None
    assert stats["disagg_transport"] == transport
    assert [e["role"] for e in stats["engines"]] == ["prefill", "decode"]
    assert all(e["disagg_transport"] == transport
               for e in stats["engines"])
    _assert_no_transit_or_blob_leaks()


def test_router_disagg_off_keeps_flat_routing(gpt_model, monkeypatch):
    """PENROZ_DISAGG_PREFILL=0 (or unset) leaves the PR 14 flat group:
    every replica role 'decode', no sinks installed, zero disagg counters
    in /serving_stats/."""
    from penroz_tpu.serve import decode_scheduler
    router = _get_router(monkeypatch, n=2)
    assert [e.role for e in router.replicas] == ["decode", "decode"]
    assert all(e._handoff_sink is None for e in router.replicas)
    assert router.disagg is False
    base = gpt_model.generate_tokens([[1, 2, 3]], BLOCK, 4, temperature=0.0)
    assert _submit(router, [1, 2, 3], 4).result() == base
    stats = decode_scheduler.serving_stats()
    assert stats["disagg_prefill_replicas"] == 0
    assert stats["disagg_exports"] == 0
    assert stats["disagg_imports"] == 0


@pytest.mark.parametrize("ordinal,phase", [(1, "export"), (2, "import")])
def test_router_disagg_handoff_failure_falls_back_with_parity(
        gpt_model, monkeypatch, ordinal, phase):
    """disagg.handoff crash mid-export (@1) or mid-import (@2): the request
    falls back to monolithic prefill on a decode replica, output is
    greedy-identical, the failure is counted, and neither a transit page
    nor a staged blob outlives the hand-off."""
    from penroz_tpu.utils import faults
    _disagg_env(monkeypatch)
    monkeypatch.setenv(faults.ENV, f"disagg.handoff:raise@{ordinal}")
    prompt = [1, 2, 3, 4, 5, 6, 7]
    base = gpt_model.generate_tokens([prompt], BLOCK, 5, temperature=0.0)
    router = _get_router(monkeypatch, n=2)
    assert _submit(router, prompt, 5).result() == base
    per = [e.stats() for e in router.replicas]
    assert sum(p["disagg_handoff_failures"] for p in per) == 1, phase
    assert sum(p["disagg_imports"] for p in per) == 0
    # the decode replica ran the request whole either way
    assert per[1]["completed"] == 1
    _assert_no_transit_or_blob_leaks()


def test_router_disagg_drain_finishes_inflight_export(gpt_model,
                                                      monkeypatch):
    """Draining a prefill replica lets its in-flight export complete
    before the worker stops: the hand-off lands on the decode replica and
    the client sees the full greedy output, not an error."""
    import time as time_mod
    from penroz_tpu.utils import faults
    _disagg_env(monkeypatch)
    # widen the export window so the drain provably overlaps it
    monkeypatch.setenv(faults.ENV, "disagg.handoff:sleep@300")
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]
    base = gpt_model.generate_tokens([prompt], BLOCK, 5, temperature=0.0)
    router = _get_router(monkeypatch, n=2)
    r0, r1 = router.replicas
    collector = _submit(router, prompt, 5)
    deadline = time_mod.monotonic() + 120
    while r0.active_rows == 0 and time_mod.monotonic() < deadline:
        time_mod.sleep(0.002)
    assert r0.active_rows == 1          # prefill (or export) in flight
    assert r0.shutdown(timeout=60, drain_s=60) is True
    assert r0.stats()["disagg_exports"] == 1
    assert collector.result() == base
    assert r1.stats()["disagg_imports"] == 1


@pytest.mark.parametrize("ordinal,phase", [(1, "export"), (2, "import")])
def test_router_disagg_d2d_fault_falls_back_to_host_transport(
        gpt_model, monkeypatch, ordinal, phase):
    """disagg.d2d transport failure at either end — the exporter's device
    gather (@1) or the importer's re-shard+scatter (@2, which refuses the
    hand-off back so the exporter re-sends from its parked source pages) —
    falls back to the host-staged blob codec FOR THAT HAND-OFF: greedy
    parity, the import still lands, and neither a transit page nor a
    staged blob outlives the request."""
    from penroz_tpu.utils import faults
    _disagg_env(monkeypatch)
    monkeypatch.setenv(faults.ENV, f"disagg.d2d:raise@{ordinal}")
    prompt = [1, 2, 3, 4, 5, 6, 7]
    base = gpt_model.generate_tokens([prompt], BLOCK, 5, temperature=0.0)
    router = _get_router(monkeypatch, n=2)
    assert _submit(router, prompt, 5).result() == base
    per = [e.stats() for e in router.replicas]
    assert sum(p["disagg_imports"] for p in per) == 1, phase
    assert sum(p["disagg_handoff_failures"] for p in per) == 1, phase
    # the hand-off ultimately shipped host-side and decoded remotely
    assert per[0]["completed"] == 0 and per[1]["completed"] == 1
    _assert_no_transit_or_blob_leaks()


def test_router_disagg_d2d_midstream_fallback_parity(gpt_model,
                                                     monkeypatch):
    """Acceptance: a d2d failure in the MIDDLE of a hand-off stream
    downgrades only THAT hand-off to the host codec — its neighbours stay
    d2d, every output is greedy-identical, and nothing leaks."""
    from penroz_tpu.utils import faults
    _disagg_env(monkeypatch)
    # Sequential submits make the site ordinals deterministic: calls 1+2
    # are hand-off A's export+import, call 3 is hand-off B's exporter-side
    # device gather (fails -> host re-stage, no importer d2d call), calls
    # 4+5 are hand-off C back on the fast path.
    monkeypatch.setenv(faults.ENV, "disagg.d2d:raise@3")
    prompts = [[1, 2, 3, 4, 5], [6, 7, 8], [9, 10, 11, 12]]
    bases = [gpt_model.generate_tokens([p], BLOCK, 5, temperature=0.0)
             for p in prompts]
    router = _get_router(monkeypatch, n=2)
    for prompt, base in zip(prompts, bases):
        assert _submit(router, prompt, 5).result() == base
    per = [e.stats() for e in router.replicas]
    assert sum(p["disagg_exports"] for p in per) == len(prompts)
    assert sum(p["disagg_imports"] for p in per) == len(prompts)
    assert sum(p["disagg_handoff_failures"] for p in per) == 1
    _assert_no_transit_or_blob_leaks()


# ---------------------------------------------------------------------------
# Elastic roles (PENROZ_DISAGG_ELASTIC=1)
# ---------------------------------------------------------------------------

def _wait_for_roles(router, want, timeout=60):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if sorted(e.role for e in router.replicas) == sorted(want):
            return
        time.sleep(0.005)
    raise AssertionError([e.role for e in router.replicas])


def test_router_affinity_stale_role_entry_ages_out(gpt_model, monkeypatch):
    """Affinity-index hygiene satellite: a fingerprint entry pointing at a
    replica that has since flipped to prefill-role is deleted on lookup
    (outcome="stale_role") instead of steering decode traffic at it — the
    repeat prompt still completes, on a replica that actually decodes."""
    from penroz_tpu.serve import metrics as serve_metrics
    _disagg_env(monkeypatch)
    router = _get_router(monkeypatch, n=3)
    assert [e.role for e in router.replicas] == \
        ["prefill", "decode", "decode"]
    shared = [1, 2, 3, 4, 5, 6, 7, 8]        # two full pages
    base = gpt_model.generate_tokens([shared + [9]], BLOCK, 5,
                                     temperature=0.0)
    assert _submit(router, shared + [9], 5).result() == base
    with router._lock:
        warm_idx = set(router._affinity.values())
    assert warm_idx and all(i in (1, 2) for i in warm_idx)
    victim = router.replicas[min(warm_idx)]
    victim_done = victim.stats()["completed"]
    victim.request_role("prefill")           # the elastic flip, applied by
    _wait_for_roles(router, ["prefill", "prefill", "decode"])  # the worker
    before = serve_metrics.ROUTER_AFFINITY.value(outcome="stale_role")
    assert _submit(router, shared + [10], 5).result() == \
        gpt_model.generate_tokens([shared + [10]], BLOCK, 5, temperature=0.0)
    assert router.affinity_stale_roles >= 1
    assert serve_metrics.ROUTER_AFFINITY.value(outcome="stale_role") > before
    with router._lock:                        # the index self-cleaned
        assert victim.replica not in set(router._affinity.values())
    # the repeat prompt decoded elsewhere — the stale target got nothing
    assert victim.stats()["completed"] == victim_done
    _assert_no_transit_or_blob_leaks()


def test_router_elastic_shrink_flips_idle_prefill_to_decode(gpt_model,
                                                            monkeypatch):
    """Elastic rebalance, shrink direction: with the backlog/occupancy
    ratio parked below PENROZ_DISAGG_REBALANCE_DOWN, the submit-path
    rebalancer asks the emptiest prefill replica to flip to decode; the
    engine applies it at a drain boundary, the counters record it, and the
    cached router survives the drifted role vector (no rebuild)."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.serve import metrics as serve_metrics
    from penroz_tpu.serve import router as router_mod
    _disagg_env(monkeypatch, prefill_replicas="2")
    monkeypatch.setenv(router_mod.DISAGG_ELASTIC_ENV, "1")
    monkeypatch.setenv(router_mod.REBALANCE_COOLDOWN_ENV, "0")
    monkeypatch.setenv(router_mod.REBALANCE_DOWN_ENV, "1000000000")
    router = _get_router(monkeypatch, n=3)
    assert [e.role for e in router.replicas] == \
        ["prefill", "prefill", "decode"]
    before = serve_metrics.DISAGG_ROLE_CHANGES.value()
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]
    base = gpt_model.generate_tokens([prompt], BLOCK, 5, temperature=0.0)
    assert _submit(router, prompt, 5).result() == base
    assert router.role_changes_requested >= 1
    _wait_for_roles(router, ["prefill", "decode", "decode"])
    stats = decode_scheduler.serving_stats()
    assert stats["disagg_role_changes"] >= 1
    assert serve_metrics.DISAGG_ROLE_CHANGES.value() > before
    # PENROZ_DISAGG_PREFILL_MIN floor: never flips the last prefill away
    assert "prefill" in [e.role for e in router.replicas]
    assert decode_scheduler.get_engine("schedgpt", BLOCK, 0.0, None) \
        is router
    _assert_no_transit_or_blob_leaks()


def test_engine_role_flip_chaos_retries_and_audits_clean(gpt_model,
                                                         monkeypatch):
    """disagg.rebalance crash mid-flip: the fault fires BEFORE the
    mutation, so the role registry stays consistent through crash
    recovery, the strict ledger audit is green, and the flip retries at
    the next drain boundary (grow direction, at the engine seam)."""
    from penroz_tpu.serve import metrics as serve_metrics
    from penroz_tpu.utils import faults
    _disagg_env(monkeypatch)
    monkeypatch.setenv(faults.ENV, "disagg.rebalance:raise@1")
    router = _get_router(monkeypatch, n=2)
    r0, r1 = router.replicas
    assert [r0.role, r1.role] == ["prefill", "decode"]
    before = serve_metrics.DISAGG_ROLE_CHANGES.value()
    r1.request_role("prefill")
    _wait_for_roles(router, ["prefill", "prefill"])
    assert r1.stats()["disagg_role_changes"] == 1
    assert serve_metrics.DISAGG_ROLE_CHANGES.value() == before + 1
    assert r1._requested_role is None
    _assert_no_transit_or_blob_leaks()
    r1.request_role("decode")                # restore the startup split
    _wait_for_roles(router, ["prefill", "decode"])


def test_router_disagg_prefill_breakers_open_decode_serves_monolithic(
        gpt_model, monkeypatch):
    """All prefill replicas breaker-open: /readyz stays ready (a healthy
    decode replica can serve the request whole) and submissions complete
    monolithically on the decode replica with greedy parity."""
    from penroz_tpu.serve import decode_scheduler
    from penroz_tpu.utils import faults
    _disagg_env(monkeypatch)
    monkeypatch.setenv(decode_scheduler.MAX_CRASHES_ENV, "2")
    monkeypatch.setenv(decode_scheduler.BREAKER_COOLDOWN_ENV, "100000")
    monkeypatch.setenv(faults.ENV,
                       "decode.prefill_chunk:raise@1,"
                       "decode.prefill_chunk:raise@2")
    prompt = [1, 2, 3, 4, 5]
    base = gpt_model.generate_tokens([prompt], BLOCK, 5, temperature=0.0)
    router = _get_router(monkeypatch, n=2)
    r0, r1 = router.replicas
    assert r0.role == "prefill"
    # phase steering sends both doomed prefills to the prefill replica
    for _ in range(2):
        with pytest.raises(faults.InjectedFault):
            _submit(router, prompt, 5).result()
    assert r0.stats()["breaker_open"] is True
    # every prefill replica open but decode healthy → still ready
    assert "schedgpt" not in decode_scheduler.breaker_open_engines()
    assert _submit(router, prompt, 5).result() == base
    s1 = r1.stats()
    assert s1["completed"] == 1
    assert s1["disagg_imports"] == 0    # monolithic, not an import
    assert r0.stats()["completed"] == 0


def test_router_disagg_scoring_counts_queued_prefill_tokens():
    """Satellite: least-loaded placement ranks by queued prompt TOKENS of
    the request's class before queue depth — a replica holding two
    100-token prompts is more loaded than one holding five 3-token
    prompts, which depth-based scoring would get backwards."""
    import threading
    from penroz_tpu.serve import decode_scheduler, qos
    from penroz_tpu.serve import router as router_mod

    class _FakeEngine:
        def __init__(self, replica):
            self.replica = replica
            self.role = "decode"
            self._shutdown = False
            self._draining = False
            self._breaker_open = False
            self._probe_inflight = False
            self._breaker_open_t = 0.0
            self._cond = threading.Condition()
            self._pending = qos.WFQueue()
            self.active_rows = 0

    def _req(n_tokens):
        return decode_scheduler.Request(list(range(1, n_tokens + 1)), 1,
                                        None, lambda *a: None)

    router = object.__new__(router_mod.EngineRouter)
    router.replicas = [_FakeEngine(0), _FakeEngine(1)]
    router.disagg = False
    few_huge, many_tiny = router.replicas
    for _ in range(2):
        few_huge._pending.push(_req(100))     # depth 2, 200 tokens
    for _ in range(5):
        many_tiny._pending.push(_req(3))      # depth 5, 15 tokens
    order = router._candidates(_req(4), target=None)
    assert [e.replica for e in order] == [1, 0]


def test_router_replicas_visible_in_stats_and_memory(gpt_model,
                                                     monkeypatch):
    """Replica engines surface individually in /serving_stats/ and the
    memledger /memory/ view, tagged with their replica index, and each
    reports its own partition-invariant pool."""
    from penroz_tpu.serve import decode_scheduler, memledger
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    router = _get_router(monkeypatch, n=2)
    base = gpt_model.generate_tokens([[1, 2, 3]], BLOCK, 4, temperature=0.0)
    assert _submit(router, [1, 2, 3], 4).result() == base
    engines = decode_scheduler.serving_stats()["engines"]
    assert [(e["replica"], e["mesh_devices"]) for e in engines] == \
        [(0, 1), (1, 1)]
    mem = memledger.memory_stats()
    assert [e["replica"] for e in mem["engines"]] == [0, 1]
    for entry in mem["engines"]:
        pools = entry["pool_pages"]
        assert sum(pools.values()) == entry["pool_pages_total"]


# ---------------------------------------------------------------------------
# Hibernated-session placement (serve/tierstore.py, PR 17)
# ---------------------------------------------------------------------------

def _session_env(monkeypatch, tmp_path):
    from penroz_tpu.serve import tierstore
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    monkeypatch.setenv("PENROZ_PREFIX_CACHE", "1")
    monkeypatch.setenv("PENROZ_PREFIX_CACHE_PAGES", "8")
    monkeypatch.setenv("PENROZ_TIER_DISK_PATH", str(tmp_path / "tier"))
    tierstore.reset()


def _submit_session(router, prompt, max_new, session_id):
    from penroz_tpu.serve import decode_scheduler
    collector = _Collector(prompt)
    router.submit(decode_scheduler.Request(prompt, max_new, None,
                                           collector.on_event,
                                           session_id=session_id))
    return collector


def _wait_tier(sid, tier, timeout=60):
    from penroz_tpu.serve import tierstore
    deadline = time.monotonic() + timeout
    while True:
        rec = tierstore.TIERS.get(sid)
        if rec is not None and rec.tier == tier:
            return rec
        assert time.monotonic() < deadline, \
            f"session {sid} never reached tier {tier!r}: {rec}"
        time.sleep(0.02)


def test_router_session_steer_to_home_replica(gpt_model, monkeypatch,
                                              tmp_path):
    """A wake prompt whose affinity entries are gone (LRU churn) still
    lands on the replica that hibernated the session: the tier store's
    placement record steers it home (outcome="session_steer"), where the
    radix copy makes the wake HBM-fast."""
    from penroz_tpu.serve import metrics as serve_metrics
    from penroz_tpu.serve import tierstore
    _session_env(monkeypatch, tmp_path)
    router = _get_router(monkeypatch, n=2)
    prompt = [1, 2, 3, 4, 5, 6, 7]
    out = gpt_model.generate_tokens([prompt], BLOCK, 4, temperature=0.0)
    cont = out + [9]
    base = gpt_model.generate_tokens([cont], BLOCK, 3, temperature=0.0)
    assert _submit_session(router, prompt, 4, "conv").result() == out
    rec = _wait_tier("conv", "host")
    home = int(rec.replica)
    done_before = router.replicas[home].stats()["completed"]
    with router._lock:          # simulate affinity-index LRU churn
        router._affinity.clear()
    before = serve_metrics.ROUTER_AFFINITY.value(outcome="session_steer")
    assert _submit(router, cont, 3).result() == base
    assert router.session_steers == 1
    assert router.session_redirects == 0
    assert serve_metrics.ROUTER_AFFINITY.value(outcome="session_steer") \
        == before + 1
    assert router.replicas[home].stats()["completed"] == done_before + 1
    assert tierstore.TIERS.promotions[("hbm", "ok")] == 1  # radix-fast wake


def test_router_session_redirect_when_home_breaker_open(gpt_model,
                                                        monkeypatch,
                                                        tmp_path):
    """A hibernated session whose home replica is breaker-open wakes on a
    healthy sibling (outcome="session_redirect") via the process-wide
    host tier — and the record survives to steer home again after the
    breaker closes."""
    from penroz_tpu.serve import metrics as serve_metrics
    from penroz_tpu.serve import tierstore
    _session_env(monkeypatch, tmp_path)
    router = _get_router(monkeypatch, n=2)
    prompt = [1, 2, 3, 4, 5, 6, 7]
    out = gpt_model.generate_tokens([prompt], BLOCK, 4, temperature=0.0)
    cont = out + [9]
    base = gpt_model.generate_tokens([cont], BLOCK, 3, temperature=0.0)
    assert _submit_session(router, prompt, 4, "conv").result() == out
    rec = _wait_tier("conv", "host")
    home = int(rec.replica)
    other = 1 - home
    router.replicas[home]._breaker_open = True
    router.replicas[home]._breaker_open_t = time.monotonic()
    with router._lock:
        router._affinity.clear()
    assert _submit(router, cont, 3).result() == base
    assert router.session_redirects == 1
    assert serve_metrics.ROUTER_AFFINITY.value(outcome="session_redirect") \
        >= 1
    assert router.replicas[other].stats()["completed"] == 1
    # blob import on the sibling, not an HBM alias on the dead home
    assert tierstore.TIERS.promotions[("host", "ok")] == 1
    # the record was NOT dropped: once the home recovers, steering resumes
    router.replicas[home]._breaker_open = False
    assert tierstore.TIERS.get("conv") is not None
    with router._lock:
        router._affinity.clear()
    assert _submit(router, cont, 3).result() == base
    assert router.session_steers == 1


def test_router_session_placement_survives_role_flip(gpt_model,
                                                     monkeypatch,
                                                     tmp_path):
    """Affinity-hygiene satellite: unlike prefix-affinity entries (which
    age out on a stale role), a hibernated session's placement record
    survives its home replica flipping to prefill-role — wakes redirect
    to a decode sibling while flipped, then steer home again after the
    replica flips back."""
    from penroz_tpu.serve import tierstore
    _disagg_env(monkeypatch)
    monkeypatch.setenv("PENROZ_TIER_DISK_PATH", str(tmp_path / "tier"))
    tierstore.reset()
    router = _get_router(monkeypatch, n=3)
    assert [e.role for e in router.replicas] == \
        ["prefill", "decode", "decode"]
    prompt = [1, 2, 3, 4, 5, 6, 7]
    out = gpt_model.generate_tokens([prompt], BLOCK, 4, temperature=0.0)
    cont = out + [9]
    base = gpt_model.generate_tokens([cont], BLOCK, 3, temperature=0.0)
    assert _submit_session(router, prompt, 4, "conv").result() == out
    rec = _wait_tier("conv", "host")
    home = int(rec.replica)
    assert router.replicas[home].role == "decode"   # retired on decode
    router.replicas[home].request_role("prefill")   # elastic flip
    deadline = time.monotonic() + 60
    while router.replicas[home].role != "prefill":
        assert time.monotonic() < deadline
        time.sleep(0.005)
    with router._lock:
        router._affinity.clear()
    assert _submit(router, cont, 3).result() == base
    assert router.session_redirects == 1
    assert tierstore.TIERS.get("conv") is not None  # record survived
    router.replicas[home].request_role("decode")    # flip back
    while router.replicas[home].role != "decode":
        assert time.monotonic() < deadline
        time.sleep(0.005)
    with router._lock:
        router._affinity.clear()
    assert _submit(router, cont, 3).result() == base
    assert router.session_steers == 1               # home again
    _assert_no_transit_or_blob_leaks()
