"""Serving metrics: the ``GET /metrics`` Prometheus exposition.

Process-wide, monotonic counters + histograms that the decode scheduler
writes at event time (engines come and go with the registry — reset an
engine and its lifetime counters would march backwards, so cumulative
totals live HERE, not on the engine), and scrape-time gauges that read
the live engine registry.  ``/serving_stats/`` keeps its JSON shape for
humans and the dashboard; ``/metrics`` is the machine-scrape surface
over the same events.

Everything renders through utils/metrics.py — no prometheus_client
dependency.  Series (all prefixed ``penroz_``):

counters   requests_total{outcome}, decode_tokens_total,
           prefill_chunks_total, queue_rejections_total,
           deadline_timeouts_total, breaker_rejections_total,
           engine_crashes_total, engine_resets_total,
           spec_drafted_tokens_total, spec_accepted_tokens_total,
           prefix_cache_hits_total, prefix_cache_misses_total,
           lora_adapter_tokens_total{adapter_id}, traces_completed_total,
           dispatches_total, quota_rejections_total{tenant},
           class_admissions_total{priority}, tenant_tokens_total{tenant},
           preemptions_total, preempted_resume_cached_tokens_total,
           router_affinity_total{outcome},
           disagg_handoffs_total{outcome,transport},
           disagg_role_changes_total,
           tier_promotions_total{tier,outcome}, tier_demotions_total{tier},
           tier_corrupt_blobs_total, sessions_hibernated_total,
           journal_appends_total, journal_errors_total,
           journal_bad_records_total, journal_compactions_total,
           sessions_recovered_total, stream_detaches_total,
           stream_resumes_total, stream_detach_expired_total
gauges     engines, active_rows, queue_depth, batch_occupancy,
           breaker_open, draining, lora_live_adapters,
           pipe_stages, pipe_ticks, pipe_bubble_ticks,
           pipe_handoffs{path},
           kv_pool_capacity_drops, prefix_cache_unpin_underflow
           (both monotonic in practice, exposed as gauges because the
           source counters live in ops/kv_cache.py),
           jit_programs{function} (live compiled-program count per jit
           family — the ragged descriptor compile-churn guard),
           train_pass_loss{pass}, train_exit_mass{pass},
           train_moe{counter}, train_hc{counter}, train_ssd{counter}
           (what a model's modules
           declare to report of a training epoch, newest /train/ epoch:
           utils/tracing.py::TRAIN_FAMILIES)
histograms ttft_ms, itl_ms, queue_wait_ms, chunk_stall_ms, tick_ms
           (fixed LATENCY_BUCKETS_MS buckets; cumulative ``_bucket``
           series sum to ``_count`` — asserted by the strict-format
           parser test), tokens_per_dispatch (token-count buckets —
           the compiled multi-step decode headline), and the labeled
           QoS pair ttft_ms_by_class{priority} /
           queue_wait_ms_by_class{priority} (one series family per
           SLO class), plus the disagg pair disagg_handoff_ms /
           disagg_handoff_bytes (hand-off latency and payload size),
           and session_resume_ttft_ms (hibernated-session wake latency),
           and train_span_ms{span} (every span of /train/ jobs by name,
           observed by utils/tracing.py)

The tier/session series (tier_pages{tier}, sessions_resident, and the
tier_* counters) describe the hierarchical session store
(serve/tierstore.py): HBM radix cache → host-RAM blob cache → disk.
"""

from __future__ import annotations

from penroz_tpu.utils import metrics as m
from penroz_tpu.utils import tracing

REGISTRY = m.Registry()

# -- counters (event-time writes from the scheduler) ------------------------

REQUESTS = REGISTRY.register(m.Counter(
    "penroz_requests_total",
    "Scheduler requests by terminal outcome (completed|error|timeout|"
    "cancelled|queue_full|breaker_open|pool_capacity)", ("outcome",)))
DECODE_TOKENS = REGISTRY.register(m.Counter(
    "penroz_decode_tokens_total",
    "Tokens emitted by the shared decode batch"))
PREFILL_CHUNKS = REGISTRY.register(m.Counter(
    "penroz_prefill_chunks_total", "Chunked-prefill dispatches"))
QUEUE_REJECTIONS = REGISTRY.register(m.Counter(
    "penroz_queue_rejections_total",
    "Requests shed 429 at a full admission queue"))
DEADLINE_TIMEOUTS = REGISTRY.register(m.Counter(
    "penroz_deadline_timeouts_total",
    "Requests expired on their deadline (queued or in flight)"))
BREAKER_REJECTIONS = REGISTRY.register(m.Counter(
    "penroz_breaker_rejections_total",
    "Submits refused while an engine circuit breaker was open"))
ENGINE_CRASHES = REGISTRY.register(m.Counter(
    "penroz_engine_crashes_total", "Scheduler tick crashes"))
ENGINE_RESETS = REGISTRY.register(m.Counter(
    "penroz_engine_resets_total",
    "Full engine state reallocations after crashes"))
SPEC_DRAFTED = REGISTRY.register(m.Counter(
    "penroz_spec_drafted_tokens_total",
    "Speculative-decoding draft tokens proposed"))
SPEC_ACCEPTED = REGISTRY.register(m.Counter(
    "penroz_spec_accepted_tokens_total",
    "Speculative-decoding draft tokens accepted"))
PREFIX_HITS = REGISTRY.register(m.Counter(
    "penroz_prefix_cache_hits_total",
    "Admissions matching at least one cached prefix page"))
PREFIX_MISSES = REGISTRY.register(m.Counter(
    "penroz_prefix_cache_misses_total",
    "Admissions matching no cached prefix page"))
LORA_TOKENS = REGISTRY.register(m.Counter(
    "penroz_lora_adapter_tokens_total",
    "Tokens emitted per LoRA adapter", ("adapter_id",)))
TRACES_COMPLETED = REGISTRY.register(m.Counter(
    "penroz_traces_completed_total",
    "Request traces finished into the /trace/ ring"))
DISPATCHES = REGISTRY.register(m.Counter(
    "penroz_dispatches_total",
    "Decode dispatches (shared steps, spec-decode verify steps, fused "
    "supersteps) — the host round-trip count the multi-step decode path "
    "exists to shrink"))
QUOTA_REJECTIONS = REGISTRY.register(m.Counter(
    "penroz_quota_rejections_total",
    "Admissions shed 429 by a tenant's exhausted token bucket", ("tenant",)))
CLASS_ADMISSIONS = REGISTRY.register(m.Counter(
    "penroz_class_admissions_total",
    "Requests admitted to a decode row per SLO class", ("priority",)))
TENANT_TOKENS = REGISTRY.register(m.Counter(
    "penroz_tenant_tokens_total",
    "Tokens emitted per tenant (quota accounting view)", ("tenant",)))
PREEMPTIONS = REGISTRY.register(m.Counter(
    "penroz_preemptions_total",
    "Decode rows evicted mid-generation for a higher-priority admission"))
RESUME_CACHED_TOKENS = REGISTRY.register(m.Counter(
    "penroz_preempted_resume_cached_tokens_total",
    "Prompt+generated tokens restored from the prefix cache (zero "
    "recompute) when preempted requests resumed"))
ROUTER_AFFINITY = REGISTRY.register(m.Counter(
    "penroz_router_affinity_total",
    "Replica-router placements of fingerprinted prompts: 'hit' landed on "
    "the replica whose prefix cache holds the prompt's pages, 'miss' "
    "anywhere else, 'stale_role' an index entry aged out because its "
    "replica became prefill-role (elastic rebalance), 'session_steer' a "
    "hibernated-session wake steered at its home replica, "
    "'session_redirect' a wake whose home replica was unhealthy or "
    "role-flipped so placement chose a healthy sibling", ("outcome",)))
ROUTER_FAILOVERS = REGISTRY.register(m.Counter(
    "penroz_router_failovers_total",
    "Admissions rerouted past a refusing replica (breaker open, queue "
    "full, draining) to a live sibling"))
DISAGG_HANDOFFS = REGISTRY.register(m.Counter(
    "penroz_disagg_handoffs_total",
    "Disaggregated-prefill page hand-offs by outcome and transport "
    "('d2d' device-array hand-over, 'host' staged shm blob): 'ok' "
    "(exported, imported, decoding), 'export_failed' / 'import_failed' "
    "(fell back — d2d re-stages host-side, host falls back to "
    "monolithic prefill), 'ack_timeout' (d2d importer never acked; "
    "parked source pages reaped)", ("outcome", "transport")))
DISAGG_ROLE_CHANGES = REGISTRY.register(m.Counter(
    "penroz_disagg_role_changes_total",
    "Elastic prefill/decode role flips applied by engines at drain "
    "boundaries (PENROZ_DISAGG_ELASTIC=1)"))
TIER_PROMOTIONS = REGISTRY.register(m.Counter(
    "penroz_tier_promotions_total",
    "Hibernated-session KV promotions by source tier and outcome: 'ok' "
    "(blob scattered into the radix cache and aliased), 'partial' "
    "(radix allocation ran out of unpinned pages mid-import — the "
    "promoted prefix is shorter but still valid), 'corrupt' (CRC/"
    "container failure, treated as a miss), 'stale' (model reloaded "
    "since hibernation; session dropped), 'miss' (blob vanished "
    "under the record)", ("tier", "outcome")))
TIER_DEMOTIONS = REGISTRY.register(m.Counter(
    "penroz_tier_demotions_total",
    "Hibernated-session KV spills into a tier: 'host' = HBM radix "
    "pages exported to the pinned host-RAM blob cache (background "
    "demotion), 'disk' = host blob written to the disk/shm tier under "
    "host-cap pressure", ("tier",)))
TIER_CORRUPT = REGISTRY.register(m.Counter(
    "penroz_tier_corrupt_blobs_total",
    "Disk-tier blobs that failed CRC/container validation at promotion "
    "— each is treated as a cache miss (recompute), never an error"))
SESSIONS_HIBERNATED = REGISTRY.register(m.Counter(
    "penroz_sessions_hibernated_total",
    "Session retirements that hibernated the row's full prompt+"
    "generated KV into the tier store"))
JOURNAL_APPENDS = REGISTRY.register(m.Counter(
    "penroz_journal_appends_total",
    "Records durably framed into the write-ahead session journal "
    "(serve/journal.py, PENROZ_JOURNAL_PATH)"))
JOURNAL_ERRORS = REGISTRY.register(m.Counter(
    "penroz_journal_errors_total",
    "Journal appends dropped by a write failure (injected or real) — "
    "contained: serving continues, restart recovery degrades"))
JOURNAL_BAD = REGISTRY.register(m.Counter(
    "penroz_journal_bad_records_total",
    "Frames dropped by replay truncation (torn tail / CRC mismatch) — "
    "bounded loss of the newest record(s), never a crash"))
JOURNAL_COMPACTIONS = REGISTRY.register(m.Counter(
    "penroz_journal_compactions_total",
    "Journal rewrites triggered by the dead-record ratio "
    "(PENROZ_JOURNAL_COMPACT_RATIO)"))
SESSIONS_RECOVERED = REGISTRY.register(m.Counter(
    "penroz_sessions_recovered_total",
    "Hibernated sessions restored into the tier store by startup journal "
    "replay + disk-scan cross-check (they resume from the disk tier "
    "instead of cold after a process restart)"))
STREAM_DETACHES = REGISTRY.register(m.Counter(
    "penroz_stream_detaches_total",
    "Client disconnects that detached a /generate/ stream instead of "
    "cancelling it (PENROZ_STREAM_DETACH_MS grace; decode keeps running)"))
STREAM_RESUMES = REGISTRY.register(m.Counter(
    "penroz_stream_resumes_total",
    "Reconnects via GET /generate/{request_id}/stream?from_seq=N that "
    "replayed the missed events exactly once from the replay ring"))
STREAM_EXPIRED = REGISTRY.register(m.Counter(
    "penroz_stream_detach_expired_total",
    "Detached streams whose grace window expired with no reconnect — "
    "the normal cancellation path then fired"))

# -- histograms (engine observes the global mirror alongside its own) -------

TTFT_MS = REGISTRY.register(m.Histogram(
    "penroz_ttft_ms", "Enqueue to first token (admission latency), ms"))
ITL_MS = REGISTRY.register(m.Histogram(
    "penroz_itl_ms", "Inter-token latency per decoding row, ms"))
QUEUE_WAIT_MS = REGISTRY.register(m.Histogram(
    "penroz_queue_wait_ms", "Enqueue to admission (prefill start), ms"))
CHUNK_STALL_MS = REGISTRY.register(m.Histogram(
    "penroz_chunk_stall_ms",
    "Decode-batch stall injected per step boundary by prefill chunks, ms"))
TICK_MS = REGISTRY.register(m.Histogram(
    "penroz_tick_ms", "Scheduler tick dispatch wall time, ms"))
TOKENS_PER_DISPATCH = REGISTRY.register(m.Histogram(
    "penroz_tokens_per_dispatch",
    "Tokens emitted per decode dispatch (≈ PENROZ_SCHED_SUPERSTEP for "
    "unconstrained fused decode, 1 on the per-token path; distinct from "
    "tokens_per_decode_step, which measures speculation not fusing)",
    buckets=m.TOKENS_PER_DISPATCH_BUCKETS))
TTFT_BY_CLASS = REGISTRY.register(m.Histogram(
    "penroz_ttft_ms_by_class",
    "Enqueue to first token per SLO class, ms", labelnames=("priority",)))
QUEUE_WAIT_BY_CLASS = REGISTRY.register(m.Histogram(
    "penroz_queue_wait_ms_by_class",
    "Enqueue to admission per SLO class, ms", labelnames=("priority",)))
DISAGG_HANDOFF_MS = REGISTRY.register(m.Histogram(
    "penroz_disagg_handoff_ms",
    "Prefill-complete to decode-replica first token per hand-off, ms "
    "(export + transport — d2d device hand-over or host blob staging — "
    "+ router placement + import)"))
DISAGG_HANDOFF_BYTES = REGISTRY.register(m.Histogram(
    "penroz_disagg_handoff_bytes",
    "KV payload per hand-off (page planes + int8 scale planes), bytes — "
    "observed at export for both transports, so d2d and host-staged "
    "size distributions compare directly",
    buckets=(4096, 16384, 65536, 262144, 1048576, 4194304, 16777216,
             67108864)))
SESSION_RESUME_TTFT_MS = REGISTRY.register(m.Histogram(
    "penroz_session_resume_ttft_ms",
    "Enqueue to first token for admissions that resumed a hibernated "
    "session (radix hit on still-resident pages, or a host/disk-tier "
    "promotion) — compare against penroz_ttft_ms for the cold-"
    "re-prefill baseline"))

# Every span of every /train/ job by name (train_epoch, ckpt_save and its
# children, ...): the object lives with the code that observes it.
TRAIN_SPAN_MS = REGISTRY.register(tracing.TRAIN_SPAN_MS)
TRAIN_STATS = [REGISTRY.register(gauge)
               for gauge in tracing.TRAIN_STAT_GAUGES]

# -- gauges (scrape-time reads of live state) -------------------------------

ENGINES_GAUGE = REGISTRY.register(m.Gauge(
    "penroz_engines", "Live decode engines in the registry"))
ACTIVE_ROWS = REGISTRY.register(m.Gauge(
    "penroz_active_rows", "In-flight decode rows across engines"))
QUEUE_DEPTH = REGISTRY.register(m.Gauge(
    "penroz_queue_depth", "Requests waiting for admission"))
OCCUPANCY = REGISTRY.register(m.Gauge(
    "penroz_batch_occupancy", "active_rows / capacity across engines"))
BREAKER_OPEN = REGISTRY.register(m.Gauge(
    "penroz_breaker_open", "1 if any engine circuit breaker is open"))
DRAINING = REGISTRY.register(m.Gauge(
    "penroz_draining", "1 while graceful shutdown drains admission"))
LORA_LIVE = REGISTRY.register(m.Gauge(
    "penroz_lora_live_adapters", "Adapters occupying live engine slots"))
POOL_DROPS = REGISTRY.register(m.Gauge(
    "penroz_kv_pool_capacity_drops",
    "KV writes dropped at pool capacity (process-wide counter in "
    "ops/kv_cache.py, exposed at scrape)"))
UNPIN_UNDERFLOW = REGISTRY.register(m.Gauge(
    "penroz_prefix_cache_unpin_underflow",
    "RadixPrefixCache unpins that drove a refcount negative — any "
    "nonzero value is a pin/unpin pairing bug (process-wide counter in "
    "ops/kv_cache.py, exposed at scrape)"))
JIT_PROGRAMS = REGISTRY.register(m.Gauge(
    "penroz_jit_programs",
    "Live compiled XLA programs per model jit family summed across "
    "engines — flat between scrapes means descriptor shape bucketing "
    "is holding; unbounded growth under steady traffic is compile churn",
    labelnames=("function",)))
POOL_PAGES = REGISTRY.register(m.Gauge(
    "penroz_pool_pages",
    "Paged KV pool pages by owner state across engines (capacity ledger, "
    "serve/memledger.py) — the states partition the pool, so the series "
    "sum to total pool capacity", labelnames=("state",)))
POOL_PAGES_HWM = REGISTRY.register(m.Gauge(
    "penroz_pool_pages_hwm",
    "High-water mark of pool pages per ledger state since engine start "
    "('used' = total minus free — the capacity-planning peak)",
    labelnames=("state",)))
TENANT_KV_PAGES = REGISTRY.register(m.Gauge(
    "penroz_tenant_kv_pages",
    "Pool pages owned by live rows per tenant (page-granular HBM "
    "attribution; prefix/preempted pages are shared, not tenant-owned)",
    labelnames=("tenant",)))
HBM_BYTES = REGISTRY.register(m.Gauge(
    "penroz_hbm_bytes",
    "Serving memory bytes by component: kv_values/kv_scales/"
    "kv_block_table (device), lora_pack (device), params (device), "
    "ssm_state (device, constant per row), adapter_host_cache (host RAM)",
    labelnames=("component",)))
KV_TTE = REGISTRY.register(m.Gauge(
    "penroz_kv_time_to_exhaustion_s",
    "Most-pressed engine's free-pool runway at the current token burn "
    "rate, seconds — series ABSENT (not 0) when no engine has a recent "
    "burn rate"))
TIER_PAGES = REGISTRY.register(m.Gauge(
    "penroz_tier_pages",
    "KV pages held per storage tier of the hierarchical session store: "
    "'hbm' = radix pages pinned awaiting background demotion "
    "(hibernating ledger state), 'host' = pages in the pinned host-RAM "
    "blob cache, 'disk' = pages in the disk/shm blob store",
    labelnames=("tier",)))
SESSIONS_RESIDENT = REGISTRY.register(m.Gauge(
    "penroz_sessions_resident",
    "Hibernated sessions currently resident across all tiers (process-"
    "wide tier store)"))
ENGINE_STUCK = REGISTRY.register(m.Gauge(
    "penroz_engine_stuck",
    "Engines whose in-flight tick dispatch has exceeded "
    "PENROZ_TICK_WATCHDOG_MS (watchdog; 0 when the knob is off)"))
STREAMS_DETACHED = REGISTRY.register(m.Gauge(
    "penroz_streams_detached",
    "Resumable /generate/ streams currently inside their disconnect "
    "grace window, decode still running"))
PIPE_STAGES_GAUGE = REGISTRY.register(m.Gauge(
    "penroz_pipe_stages",
    "Widest pipeline-parallel serving group across engines "
    "(PENROZ_SERVE_PIPE_STAGES; 1 = no piped engine)"))
PIPE_TICKS = REGISTRY.register(m.Gauge(
    "penroz_pipe_ticks",
    "Pipeline schedule ticks across piped engines (lifetime counter "
    "read at scrape) — with penroz_pipe_bubble_ticks this derives the "
    "bubble fraction: bubble_ticks / (ticks × stages)"))
PIPE_BUBBLE_TICKS = REGISTRY.register(m.Gauge(
    "penroz_pipe_bubble_ticks",
    "Idle stage-ticks across piped engines (a stage with no live "
    "micro-block to advance that tick)"))
PIPE_HANDOFFS = REGISTRY.register(m.Gauge(
    "penroz_pipe_handoffs",
    "Stage-to-stage activation hand-offs by path: 'device' direct "
    "array hand-over, 'host' re-staged through the host after a "
    "pipe.handoff fault (contained; numerics identical)",
    labelnames=("path",)))


def _wire_gauges():
    from penroz_tpu.ops import kv_cache as KV
    from penroz_tpu.serve import decode_scheduler as ds

    def engines():
        with ds._REG_LOCK:
            return [e for e in ds._ENGINES.values() if not e._shutdown]

    ENGINES_GAUGE.set_function(lambda: len(engines()))
    ACTIVE_ROWS.set_function(
        lambda: sum(e.active_rows for e in engines()))
    QUEUE_DEPTH.set_function(
        lambda: sum(e.queue_depth for e in engines()))

    def occupancy():
        es = engines()
        cap = sum(e.capacity for e in es)
        return (sum(e.active_rows for e in es) / cap) if cap else 0.0

    OCCUPANCY.set_function(occupancy)
    BREAKER_OPEN.set_function(
        lambda: 1 if ds.breaker_open_engines() else 0)
    DRAINING.set_function(lambda: 1 if ds.draining() else 0)
    LORA_LIVE.set_function(lambda: sum(
        e.live_adapters for e in engines()))
    POOL_DROPS.set_function(KV.pool_drop_count)
    UNPIN_UNDERFLOW.set_function(KV.unpin_underflow_count)

    def jit_programs():
        out: dict = {}
        for e in engines():
            for fam, n in e.jit_program_counts().items():
                out[fam] = out.get(fam, 0) + n
        return out

    JIT_PROGRAMS.set_function(jit_programs)

    # Capacity-ledger gauges (lazy import: memledger lazy-imports the
    # scheduler registry back, and neither may cycle at module load).
    from penroz_tpu.serve import memledger
    POOL_PAGES.set_function(memledger.pool_page_totals)
    POOL_PAGES_HWM.set_function(memledger.pool_page_hwm_totals)
    TENANT_KV_PAGES.set_function(memledger.tenant_page_totals)
    HBM_BYTES.set_function(memledger.hbm_byte_totals)
    KV_TTE.set_function(memledger.min_time_to_exhaustion)

    from penroz_tpu.serve import tierstore
    TIER_PAGES.set_function(lambda: tierstore.TIERS.pages_by_tier())
    SESSIONS_RESIDENT.set_function(
        lambda: tierstore.TIERS.resident_sessions())

    ENGINE_STUCK.set_function(lambda: len(ds.stuck_engines()))

    from penroz_tpu.serve import streams
    STREAMS_DETACHED.set_function(streams.STREAMS.detached_count)

    # Pipeline-parallel serving (PENROZ_SERVE_PIPE_STAGES >= 2): scrape-
    # time reads of the engines' lifetime schedule counters, like the
    # other gauge families above.
    PIPE_STAGES_GAUGE.set_function(lambda: max(
        (e._pipe.stages for e in engines() if e._pipe is not None),
        default=1))
    PIPE_TICKS.set_function(
        lambda: sum(e._pipe_ticks for e in engines()))
    PIPE_BUBBLE_TICKS.set_function(
        lambda: sum(e._pipe_bubble_ticks for e in engines()))

    def pipe_handoffs():
        host = sum(e._pipe_handoff_host_fallbacks for e in engines())
        total = sum(e._pipe_handoffs for e in engines())
        return {"device": total - host, "host": host}

    PIPE_HANDOFFS.set_function(pipe_handoffs)


_WIRED = False


def render() -> str:
    """The /metrics response body (text exposition format 0.0.4)."""
    global _WIRED
    if not _WIRED:
        _wire_gauges()
        _WIRED = True
    return REGISTRY.render()


CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


def reset() -> None:
    """Zero counters/histograms (tests and bench phase isolation)."""
    REGISTRY.reset()
