"""Continuous-batching decode scheduler: coalesce concurrent /generate/
requests into one shared in-flight batch.

Without it, K concurrent clients cost K independent batch-1 decode programs
per token; the TPU runs the same weights K times.  This module owns, per
(model, block_size, sampling config), a fixed-capacity decode batch whose
rows are KV-cache slots (paged pool pages when ``PAGED_KV_CACHE=1``):

- a dedicated worker thread runs ONE shared jitted decode step per tick
  across all active rows (``NeuralNetworkModel.decode_step_batched``);
- newcomers are admitted at step boundaries into a PREFILLING row: the
  prompt is fed in fixed-size, power-of-two-bucketed CHUNKS
  (``PENROZ_PREFILL_CHUNK``, default 256) straight into the row's slice of
  the shared KV state (``decode_prefill_chunk`` → ``KVState.row_view`` /
  ``merge_row``), at most one chunk between decode steps — a long prompt
  can never stall the in-flight batch for more than one chunk's latency
  (``PENROZ_SCHED_MAX_STALL_MS`` budgets >1 chunk per boundary; with no
  decode rows in flight, chunks run back-to-back);
- with ``PENROZ_PREFIX_CACHE=1`` (+ ``PAGED_KV_CACHE=1``) admission first
  matches the prompt against a radix tree of page-granularity blocks over
  a reserved region of the paged pool (``PENROZ_PREFIX_CACHE_PAGES``),
  aliases the matched pages into the row's block table (ref-count pinned,
  LRU-evicted — ops/kv_cache.py ``RadixPrefixCache``) and chunk-prefills
  only the suffix: repeated system prompts pay prefill once;
- rows retire on stop-token / max_new_tokens and their slot is recycled
  immediately for the next queued request (``KVState.reset_row``);
- with ``PENROZ_SPEC_DECODE=1`` (greedy engines only), each tick first
  runs a multi-token **verify step** for every row whose prompt-lookup
  drafter proposed candidates (``serve/spec_decode.py`` — the row's own
  history is the draft model), accepting the longest greedy-matching
  prefix + bonus token and rolling the row's KV back past rejections
  (``KVState.rollback_row``); rows with no draft share one plain batched
  step as before, so acceptance is ragged per row and a predictable row
  can emit up to ``PENROZ_SPEC_K + 1`` tokens per decode step;
- with ``PENROZ_SCHED_SUPERSTEP`` > 1 (default 8, **compiled multi-step
  decode**), a tick with no pending prefill chunks, no queued admissions
  and no spec-decode drafts fuses up to that many decode steps into ONE
  jitted ``lax.scan`` dispatch (``NeuralNetworkModel.decode_superstep``):
  sampling, RNG-key folding, length advance and stop-token/budget
  detection all run on device behind a per-row active mask (finished
  rows compute-but-discard, like padded rows), and the host surfaces
  once per block to stream the emitted tokens, admit newcomers, and
  check deadlines/cancellation — which are therefore observed up to N
  tokens late (the documented granularity trade);
- greedy outputs are token-identical to the single-sequence path with the
  prefix cache hitting, missing, or off, with chunked or one-shot
  prefill, and under any superstep size (tested — the chunked program
  family is the same cached-attention path, reading the same absolute
  positions, and each fused step is the identical per-step program);
- with LoRA adapters registered (``serve/adapters.py``), requests carrying
  an ``adapter_id`` bind to one of ``PENROZ_LORA_MAX_LIVE`` live slots per
  engine: the slots' low-rank factors stack into static ``[L+1, R, ·]``
  tensors and a per-row slot-index vector gathers each row's adapter
  inside the SAME shared step (models/lora.py ``build_pack`` — rows with
  different adapters, or none, decode together); chunked prefill and
  spec-decode verify apply the row's adapter through the same pack, the
  radix prefix cache namespaces pages per adapter generation (a base
  prefix never aliases an adapter's KV), and crash recovery rebuilds the
  adapter row tables with the rest of the engine state.

Fault tolerance (PR 3) — overload and failure are scheduler features, not
error-handler afterthoughts:

- **Deadlines**: per-request ``timeout_ms`` (server-capped by
  ``PENROZ_REQ_TIMEOUT_MS``; 0/unset = off) is enforced while queued (the
  request is shed with a ``timeout`` event before prefill starts → HTTP
  504) and in flight (the row retires at the next step boundary and the
  stream ends with a ``timeout`` event).
- **Backpressure**: ``PENROZ_SCHED_MAX_QUEUE`` bounds the admission queue
  (aggregate; per-class ``PENROZ_QOS_MAX_QUEUE_<CLASS>`` overrides it per
  SLO class); a full queue rejects ``submit`` with :class:`QueueFullError`
  (→ HTTP 429 + a load-aware ``Retry-After``: queue depth × recent tick
  p50, clamped) instead of queueing forever.
- **Crash recovery**: a failed tick fails every waiting request with a
  clean error AND fully resets the engine — fresh KV allocation, fresh
  prefix cache, clean block tables — so the next request decodes from
  provably uncorrupted state (greedy-identical to the no-crash path,
  tested under injected ``decode.step`` / ``decode.prefill_chunk``
  faults).
- **Circuit breaker**: ``PENROZ_ENGINE_MAX_CRASHES`` consecutive crashes
  (no successfully completed request in between) open a per-engine
  breaker: ``submit`` raises :class:`CircuitOpenError` (→ HTTP 503, or the
  legacy single-sequence path when ``PENROZ_SCHED_FALLBACK=1``) until
  ``PENROZ_BREAKER_COOLDOWN_MS`` elapses, then ONE probe request is
  admitted; its success closes the breaker, its failure re-arms the
  cooldown.  ``/readyz`` reports not-ready while any breaker is open.
- **Cancellation**: ``req.cancelled`` (client disconnect) frees the row
  and its prefix pins at the next boundary; queued cancelled requests are
  purged without ever prefilling.
- **Graceful shutdown**: ``drain_and_shutdown`` stops admission, lets
  in-flight rows finish within ``PENROZ_DRAIN_S``, then joins the worker
  thread — ``shutdown`` returns False (and logs) if the thread leaks.

Multi-tenant QoS (serve/qos.py) — SLO isolation on top of the overload
machinery:

- **Priority classes + WFQ**: requests carry ``priority`` (``interactive``
  | ``standard`` | ``batch``, default ``standard``); the admission queue is
  per-(tenant, class) sub-queues drained by deficit-weighted round robin
  (``PENROZ_QOS_WEIGHTS``, default ``interactive:8,standard:4,batch:1``) —
  one tenant's burst can no longer starve another tenant's queue wait.
- **Per-tenant token quotas**: a token bucket per tenant id (explicit
  ``tenant`` field > adapter id > ``"default"``) over emitted + prefilled
  tokens (``PENROZ_QOS_TENANT_TOKENS_PER_S``; per-tenant overrides via
  ``PUT /tenants/{id}/quota``).  An exhausted bucket 429s that tenant's
  NEW admissions with a refill-derived ``Retry-After`` while its in-flight
  rows finish; other tenants are untouched.
- **Preemption with zero-recompute resume**: an ``interactive`` arrival
  facing a full batch evicts the lowest-priority longest-running decode
  row — its history's KV pages are already pool-resident, so eviction is
  "insert into the radix tree + copy the uncached pages + free the row"
  (``PENROZ_QOS_PREEMPT=0`` disables).  The victim requeues at the head of
  its sub-queue and resumes through the normal prefix-match path with zero
  recompute of the cached prefix; greedy output is token-identical to the
  unpreempted run (tested across prefix restore × int8 × superstep ×
  LoRA).  Preemption is observed at step boundaries, so it can lag the
  interactive arrival by up to one superstep (the same
  ``PENROZ_SCHED_SUPERSTEP`` granularity trade as deadlines — and a
  non-empty queue already collapses the superstep to 1).

All of the above is deterministically testable through
``penroz_tpu/utils/faults.py`` (``PENROZ_FAULT_INJECT`` —
``decode.step:raise@N`` / ``decode.step:sleep@MS`` sites inside the tick,
plus ``qos.preempt`` at the top of the eviction path).

Enabled by routing: serve/app.py sends eligible ``/generate/`` and
``/generate_batch/`` traffic here when ``PENROZ_CONTINUOUS_BATCHING=1``.
Knobs: ``PENROZ_SCHED_MAX_ROWS`` (decode batch capacity, default 8),
``PENROZ_SCHED_ADMIT_MS`` (idle-burst coalescing window, default 0),
``PENROZ_SCHED_MAX_ENGINES`` (engine registry cap, default 4),
``PENROZ_PREFILL_CHUNK`` / ``PENROZ_SCHED_MAX_STALL_MS`` /
``PENROZ_PREFIX_CACHE`` / ``PENROZ_PREFIX_CACHE_PAGES`` (above),
``PENROZ_SPEC_DECODE`` / ``PENROZ_SPEC_K`` / ``PENROZ_SPEC_NGRAM``
(serve/spec_decode.py), ``PENROZ_SCHED_SUPERSTEP`` (fused decode steps
per dispatch, above).
Observability: ``serving_stats()`` backs ``GET /serving_stats/`` — queue
depth, batch occupancy, decode tokens/sec, admission latency, prefill
chunk-stall p99, prefix-cache hit rate/evictions, speculative-decoding
accept rate + tokens per decode step, and the KV pool-capacity drop
counter (ops/kv_cache.py).

This is the serving shape the ragged paged-attention kernel line of work
exists for (PAPERS.md "Ragged Paged Attention"): per-row ragged KV lengths
+ right-padded ragged prefill were the prerequisites, both already in tree.
"""

from __future__ import annotations

import asyncio
import collections
import logging
import math
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from penroz_tpu.models import lora as lora_mod
from penroz_tpu.models import model as model_mod
from penroz_tpu.models.model import NeuralNetworkModel
from penroz_tpu.ops import kv_cache as KV
from penroz_tpu.serve import adapters as adapters_mod
from penroz_tpu.serve import journal
from penroz_tpu.serve import memledger
from penroz_tpu.serve import metrics as serve_metrics
from penroz_tpu.serve import qos
from penroz_tpu.serve import spec_decode
from penroz_tpu.serve import streams
from penroz_tpu.serve import tierstore
from penroz_tpu.serve.qos import TenantQuotaExceeded  # noqa: F401 — re-export
from penroz_tpu.utils import bucketing, checkpoint, faults, tracing
from penroz_tpu.utils import metrics as metrics_util
from penroz_tpu.utils import stats as stats_util

log = logging.getLogger(__name__)

ENABLE_ENV = "PENROZ_CONTINUOUS_BATCHING"
MAX_ROWS_ENV = "PENROZ_SCHED_MAX_ROWS"
ADMIT_MS_ENV = "PENROZ_SCHED_ADMIT_MS"
MAX_ENGINES_ENV = "PENROZ_SCHED_MAX_ENGINES"
PREFILL_CHUNK_ENV = "PENROZ_PREFILL_CHUNK"
MAX_STALL_MS_ENV = "PENROZ_SCHED_MAX_STALL_MS"
REQ_TIMEOUT_ENV = "PENROZ_REQ_TIMEOUT_MS"
MAX_QUEUE_ENV = "PENROZ_SCHED_MAX_QUEUE"
MAX_CRASHES_ENV = "PENROZ_ENGINE_MAX_CRASHES"
FALLBACK_ENV = "PENROZ_SCHED_FALLBACK"
BREAKER_COOLDOWN_ENV = "PENROZ_BREAKER_COOLDOWN_MS"
DRAIN_S_ENV = "PENROZ_DRAIN_S"
TICK_TIMELINE_ENV = "PENROZ_TICK_TIMELINE"
SUPERSTEP_ENV = "PENROZ_SCHED_SUPERSTEP"
RAGGED_ENV = "PENROZ_RAGGED_ATTENTION"
REPLICAS_ENV = "PENROZ_SCHED_REPLICAS"
# Disaggregated-prefill hand-off transport: "d2d" (device arrays handed
# over in-process, re-sharded onto the importer's pools — the default
# when source and destination replicas live in the same process) or
# "host" (the CRC-checked shm page-blob codec, which also remains the
# crash-safe fallback whenever the d2d path fails mid-hand-off).
DISAGG_TRANSPORT_ENV = "PENROZ_DISAGG_TRANSPORT"
DISAGG_ACK_TIMEOUT_ENV = "PENROZ_DISAGG_ACK_TIMEOUT_MS"
# Worker-tick watchdog: an engine is "stuck" when its worker has been
# inside ONE tick dispatch longer than this many ms (0/unset = off).
TICK_WATCHDOG_ENV = "PENROZ_TICK_WATCHDOG_MS"
# Pipeline-parallel serving (MPMD stage partition of the unified ragged
# path): PENROZ_SERVE_PIPE_STAGES=S splits the layer stack over S
# stage-engines (composing with PENROZ_SERVE_MESH_MODEL TP width per
# stage); the scheduler keeps stages busy by splitting each tick's mixed
# batch into PENROZ_SERVE_PIPE_BLOCKS micro-blocks (default = S) that
# flow between stages.  Unset or S<=1 leaves the fused single-dispatch
# path untouched (byte-identical — the whole pipeline branch is dead).
PIPE_STAGES_ENV = "PENROZ_SERVE_PIPE_STAGES"
PIPE_BLOCKS_ENV = "PENROZ_SERVE_PIPE_BLOCKS"

# Max tick-timeline entries served per /serving_stats/ payload (the ring
# itself holds PENROZ_TICK_TIMELINE entries).
_TIMELINE_SERVE = 120

# Sliding window for the tokens/sec stat (seconds).
_TPS_WINDOW_S = 30.0


class QueueFullError(RuntimeError):
    """Admission queue at its bound (per-class PENROZ_QOS_MAX_QUEUE_* or
    the aggregate PENROZ_SCHED_MAX_QUEUE) — shed the request (429).

    ``retry_after`` is the load-aware hint (seconds): queue depth × recent
    tick p50, clamped — a deep queue behind a slow model tells the client
    to back off longer than a shallow one behind a fast model."""

    def __init__(self, msg: str, retry_after: int = 1):
        super().__init__(msg)
        self.retry_after = int(retry_after)


class CircuitOpenError(RuntimeError):
    """Engine circuit breaker open after repeated crashes (503, or the
    legacy path with PENROZ_SCHED_FALLBACK=1).  ``retry_after`` is the
    remaining cooldown, rounded up (seconds)."""

    def __init__(self, msg: str, retry_after: int = 1):
        super().__init__(msg)
        self.retry_after = int(retry_after)


class DeadlineExceeded(RuntimeError):
    """Request deadline (timeout_ms / PENROZ_REQ_TIMEOUT_MS) expired (504).

    ``phase`` is ``"queued"`` (shed before prefill started) or
    ``"inflight"`` (row retired at a step boundary mid-generation)."""

    def __init__(self, phase: str, detail: str):
        super().__init__(detail)
        self.phase = phase


def enabled() -> bool:
    return os.environ.get(ENABLE_ENV, "0") == "1"


def fallback_enabled() -> bool:
    return os.environ.get(FALLBACK_ENV, "0") == "1"


def _env_int(name: str, default: int, lo: int = 1) -> int:
    try:
        return max(lo, int(os.environ.get(name, str(default))))
    except ValueError:
        log.warning("Unparseable %s=%r; using default %d", name,
                    os.environ.get(name), default)
        return default


def _watchdog_ms() -> float:
    try:
        return max(0.0, float(os.environ.get(TICK_WATCHDOG_ENV, "0")))
    except ValueError:
        return 0.0


def _env_float(name: str, default: float) -> float:
    try:
        return max(0.0, float(os.environ.get(name, str(default))))
    except ValueError:
        log.warning("Unparseable %s=%r; using %s", name,
                    os.environ.get(name), default)
        return default


def _max_rows() -> int:
    return _env_int(MAX_ROWS_ENV, 8)


def _max_engines() -> int:
    return _env_int(MAX_ENGINES_ENV, 4)


def _replicas() -> int:
    """Data-parallel engine replicas per (model, config) key.  > 1 routes
    acquisition through serve/router.py; 1 (the default) is byte-for-byte
    today's single-engine registry."""
    return _env_int(REPLICAS_ENV, 1)


def _admit_ms() -> float:
    return _env_float(ADMIT_MS_ENV, 0.0)


def _disagg_transport() -> str:
    """Hand-off transport for disaggregated prefill: ``d2d`` by default
    (all replicas of a router group share this process, so device arrays
    hand over without host staging); ``host`` forces the blob codec."""
    v = os.environ.get(DISAGG_TRANSPORT_ENV, "d2d").strip().lower()
    if v not in ("d2d", "host"):
        log.warning("Unknown %s=%r; using d2d", DISAGG_TRANSPORT_ENV, v)
        return "d2d"
    return v


def _ack_timeout_s() -> float:
    """How long an exporter row parks awaiting the importer's d2d ack
    before its pages are reaped (the importer owns the request's stream by
    then, so a lost ack must not leak transit pages forever)."""
    return _env_float(DISAGG_ACK_TIMEOUT_ENV, 10000.0) / 1000.0


def _prefill_chunk() -> int:
    return _env_int(PREFILL_CHUNK_ENV, 256)


_STALL_DEPRECATION_WARNED = False


def _max_stall_ms() -> float:
    return _env_float(MAX_STALL_MS_ENV, 0.0)


def ragged_enabled() -> bool:
    """Unified ragged dispatch (paged caches): prefill chunks, decode
    steps and spec-verify spans share ONE kernel dispatch per tick.
    On by default wherever the cache is paged; ``PENROZ_RAGGED_ATTENTION=0``
    is the one-release escape hatch back to phased scheduling."""
    return os.environ.get(RAGGED_ENV, "1") != "0"


def _warn_stall_deprecated():
    """PENROZ_SCHED_MAX_STALL_MS is meaningless on the unified path (there
    is no prefill/decode phase boundary left to budget) — warn once when a
    deployment still sets it so the knob can be dropped next release."""
    global _STALL_DEPRECATION_WARNED
    if _STALL_DEPRECATION_WARNED or MAX_STALL_MS_ENV not in os.environ:
        return
    _STALL_DEPRECATION_WARNED = True
    log.warning(
        "%s is deprecated and ignored on the unified ragged path: prefill "
        "chunks ride the same dispatch as decode steps, so there is no "
        "inter-phase stall to budget.  It still applies to the legacy "
        "phased path (%s=0 or contiguous KV) and will be removed next "
        "release.", MAX_STALL_MS_ENV, RAGGED_ENV)


def _max_queue() -> int:
    """Admission queue bound (0 = unbounded, the pre-PR-3 behavior)."""
    return _env_int(MAX_QUEUE_ENV, 0, lo=0)


def _max_crashes() -> int:
    return _env_int(MAX_CRASHES_ENV, 3)


def _breaker_cooldown_ms() -> float:
    return _env_float(BREAKER_COOLDOWN_ENV, 1000.0)


def _drain_s() -> float:
    return _env_float(DRAIN_S_ENV, 5.0)


def _tick_timeline_len() -> int:
    return _env_int(TICK_TIMELINE_ENV, 256)


def _superstep_max() -> int:
    """Decode steps fused per dispatch (compiled multi-step decode).
    1 restores the legacy one-dispatch-per-token tick loop."""
    return _env_int(SUPERSTEP_ENV, 8)


def _pipe_stages() -> int:
    """Pipeline stage count for one serving group (1 = off)."""
    return _env_int(PIPE_STAGES_ENV, 1)


def _pipe_blocks(stages: int) -> int:
    """Micro-blocks the mixed batch splits into per pipeline tick — at
    least ``stages`` so every stage can be busy once the fill drains."""
    return max(int(stages), _env_int(PIPE_BLOCKS_ENV, stages))


def _effective_timeout_ms(timeout_ms) -> float | None:
    """Deadline budget for one request: the client's ``timeout_ms`` capped
    by the server-wide ``PENROZ_REQ_TIMEOUT_MS`` (which also applies to
    requests that asked for no deadline).  None = no deadline (both off,
    the default)."""
    cap = _env_float(REQ_TIMEOUT_ENV, 0.0)
    t = float(timeout_ms) if timeout_ms else 0.0
    if cap > 0:
        t = min(t, cap) if t > 0 else cap
    return t if t > 0 else None


def _chunk_plan(n: int, chunk: int) -> list[int]:
    """Chunk sizes covering ``n`` prefill tokens: fixed ``chunk``-size
    pieces, then a descending power-of-two decomposition of the remainder —
    the compiled chunk-program set stays bounded by {chunk} ∪ {2^k < chunk}
    instead of retracing per prompt length (utils/bucketing.py, shared
    with the superstep planner and the ragged descriptor bucketing)."""
    return bucketing.chunk_plan(n, chunk)


class Request:
    """One generation request in flight through an engine.

    ``on_event(kind, value)`` is invoked FROM THE SCHEDULER THREAD with
    ``("token", int)`` per generated token (stop token included, matching
    ``generate_tokens``), then ``("done", None)`` — or ``("error", exc)``,
    or ``("timeout", DeadlineExceeded)`` when the request's deadline
    expires (queued or in flight).  Consumers bridge to their own
    concurrency world (asyncio queue, thread queue); setting ``cancelled``
    retires the row at the next boundary.
    """

    __slots__ = ("prompt", "max_new_tokens", "stop_token", "on_event",
                 "enqueue_t", "cancelled", "deadline", "adapter",
                 "request_id", "trace", "priority", "tenant",
                 "resume_history", "resume_produced", "resume_nodes",
                 "preempted", "handoff", "session_id")

    def __init__(self, prompt, max_new_tokens, stop_token, on_event,
                 timeout_ms=None, adapter=None, request_id=None,
                 trace=None, priority=None, tenant=None, session_id=None):
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.stop_token = stop_token
        self.on_event = on_event
        self.enqueue_t = time.monotonic()
        self.cancelled = False
        # serve.adapters.AdapterEntry (refcount-pinned by the HTTP layer
        # for the request's lifetime) or None for base-model rows.
        self.adapter = adapter
        # QoS identity: SLO class (WFQ sub-queue + preemption rank) and
        # tenant id (quota bucket + per-tenant accounting) — explicit
        # field > adapter id > shared "default".
        self.priority = qos.validate_priority(priority)
        self.tenant = qos.tenant_of(
            tenant, adapter.adapter_id if adapter is not None else None)
        # Preempt-to-prefix-cache resume state: the full history (prompt +
        # emitted tokens) becomes the effective prompt of the resume
        # admission; ``resume_nodes`` hold the radix pins that guarantee
        # the cached pages survive until the resume prefix-match re-pins
        # them (zero recompute).
        self.resume_history = None
        self.resume_produced = 0
        self.resume_nodes: list = []
        self.preempted = 0
        # Disaggregated-prefill hand-off: set by the prefill replica after a
        # successful export ({"blob_id", "kv_len", "first_token", "t0"});
        # the decode replica consumes it at admission (import path) and the
        # request was already quota-admitted on the prefill side.
        self.handoff = None
        # Session hibernation (serve/tierstore.py): a retirement carrying a
        # session id parks the row's full prompt+generated KV in the tier
        # store instead of letting it die with the row.
        self.session_id = session_id
        # utils/tracing.py: request_id is the X-Request-Id correlation
        # key; trace (None when sampled out / tracing off) records the
        # lifecycle span tree — every recording site below is None-guarded
        # so the disabled path costs one comparison.
        self.request_id = request_id
        self.trace = trace
        budget = _effective_timeout_ms(timeout_ms)
        self.deadline = (self.enqueue_t + budget / 1000.0
                         if budget is not None else None)

    def expired(self, now: float | None = None) -> bool:
        return (self.deadline is not None
                and (now or time.monotonic()) >= self.deadline)


class _Row:
    __slots__ = ("req", "produced", "finished", "prefilling", "prefilled",
                 "chunks", "chunk_idx", "prefix_nodes", "history",
                 "last_emit_t", "sp_prefill", "sp_decode", "admit_t",
                 "resumed", "transit", "session_wake")

    def __init__(self, req):
        self.req = req
        self.produced = 0
        self.finished = False
        # preemption bookkeeping: admission time ranks "longest-running"
        # victims; a resumed row skips TTFT (its first token already
        # shipped before the preempt).
        self.admit_t = time.monotonic()
        self.resumed = False
        # inter-token-latency anchor (monotonic s of the last emitted
        # token) + the row's open trace spans (utils/tracing.py)
        self.last_emit_t = None
        self.sp_prefill = None
        self.sp_decode = None
        # prompt + every emitted token, in order — the prompt-lookup
        # drafter's corpus (spec decode); bounded by block_size.
        self.history = list(req.prompt)
        # PREFILLING phase state: ``prefilled`` is the row's KV valid length
        # so far (starts at the radix-matched prefix length); ``chunks`` is
        # the pow-2-bucketed plan covering the remaining suffix;
        # ``prefix_nodes`` are the pinned radix nodes whose pages the row's
        # block table aliases (unpinned at retirement).
        self.prefilling = True
        self.prefilled = 0
        self.chunks: list = []
        self.chunk_idx = 0
        self.prefix_nodes: list = []
        # Hand-off import in flight: the row's pages are owned but not yet
        # decode-visible — the memledger attributes them to ``transit``.
        self.transit = False
        # Admission matched a hibernated session (radix-resident pages or
        # a host/disk-tier promotion): first token observes the
        # session-resume TTFT histogram alongside the plain one.
        self.session_wake = False


class DecodeEngine:
    """Per-(model, block_size, sampling) continuous-batching decode engine.

    The worker thread owns the persistent multi-row KV state, the host-side
    per-row lengths (authoritative — free slots are parked at length 0 so
    the shared step's writes for them land in their own row and are never
    attended), and the admission queue.  All device work runs under
    ``decode_priority`` so a co-resident trainer yields between epochs.
    """

    def __init__(self, model_id: str, block_size: int, temperature,
                 top_k, capacity: int | None = None, replica: int = 0,
                 role: str = "decode"):
        self.model_id = model_id
        self.block_size = int(block_size)
        self.temperature = temperature
        self.top_k = top_k
        self.capacity = capacity or _max_rows()
        self.greedy = temperature is None or float(temperature) == 0.0
        # Data-parallel replica index within a serve/router.py group (0 for
        # standalone engines); router-owned engines are exempt from the
        # registry's idle eviction — the router owns their lifecycle.
        self.replica = int(replica)
        self._router_owned = False
        self._mesh_devices = 1  # set by _alloc_state under PENROZ_SERVE_MESH
        # Disaggregated prefill (serve/router.py): "prefill" replicas run
        # chunked prefill to completion, export the row's KV pages as a
        # checkpoint page blob, and hand the request to a decode replica
        # through ``_handoff_sink`` (router._place_handoff); "decode"
        # replicas import the blob at admission and skip prefill entirely.
        self.role = role
        self._handoff_sink = None
        # d2d free-after-ack protocol: rows whose device planes shipped but
        # whose import is unacknowledged park in _transit_rows (pages stay
        # owned, attributed to ``transit`` by the ledger); importer acks
        # land in _acks from the importing thread and drain at worker-loop
        # boundaries.  _requested_role is the elastic rebalancer's pending
        # flip, applied by the worker at a drain boundary.
        self._transit_rows: dict = {}
        self._acks: list = []
        self._requested_role = None
        self._disagg_role_changes = 0

        self._model = NeuralNetworkModel.deserialize(model_id)
        self._ckpt_stamp_v = self._ckpt_stamp()
        # Pipeline-parallel serving (PENROZ_SERVE_PIPE_STAGES >= 2): the
        # MPMD stage partition of the unified ragged path.  Built before
        # _alloc_state so the fresh KV pools land stage-by-stage
        # (enter_serve_mesh).  Requires the paged+ragged unified dispatch
        # — micro-blocks are slices of the mixed plan — and is mutually
        # exclusive with mixed-adapter serving (stage re-keying does not
        # thread the LoRA pack; gate loudly rather than corrupt).
        self._pipe = None
        self._pipe_ticks = 0
        self._pipe_bubble_ticks = 0
        self._pipe_stage_busy: collections.Counter = collections.Counter()
        self._pipe_handoffs = 0
        self._pipe_handoff_host_fallbacks = 0
        self._pipe_lora_warned = False
        stages = _pipe_stages()
        if stages > 1:
            if not (KV.paged_enabled() and ragged_enabled()):
                log.warning(
                    "%s=%d ignored: pipeline serving rides the unified "
                    "ragged dispatch (PAGED_KV_CACHE=1 + %s=1)",
                    PIPE_STAGES_ENV, stages, RAGGED_ENV)
            else:
                try:
                    self._pipe = self._model.serve_pipeline(stages)
                except ValueError as e:
                    log.warning("%s=%d ignored: %s", PIPE_STAGES_ENV,
                                stages, e)
        # Constant-memory sequence rows (ops/ssm.py): archs with recurrent
        # blocks carry a per-row SSMState alongside (or instead of) the KV
        # pools.  Prefix-KV sharing is fundamentally incompatible — a radix
        # match aliases token-extent pages, but the matching row's recurrent
        # state cannot be reconstructed from them — so the cache (and with
        # it preempt/hibernate/promote, which all ride it) gates off.
        self._has_ssm = bool(self._model.arch.ssm_specs)
        self._extra_pages = 0
        if KV.prefix_cache_enabled():
            if self._has_ssm:
                log.warning(
                    "%s=1 ignored: arch has %d SSM layer(s); recurrent row "
                    "state cannot be rebuilt from shared prefix pages",
                    KV.PREFIX_CACHE_ENV, len(self._model.arch.ssm_specs))
            elif KV.paged_enabled():
                self._extra_pages = KV.prefix_cache_pages()
            else:
                log.warning(
                    "%s=1 ignored: prefix-KV sharing is page-granular and "
                    "needs PAGED_KV_CACHE=1", KV.PREFIX_CACHE_ENV)
        self._lengths = np.zeros(self.capacity, np.int32)
        self._last_tok = np.zeros(self.capacity, np.int32)
        self._rows: list = [None] * self.capacity
        # Mixed-adapter serving (models/lora.py): up to PENROZ_LORA_MAX_LIVE
        # adapters occupy live slots whose factors stack into one static
        # [L+1, R, ·] pack; _row_adapter maps each batch row to its slot
        # (slot _max_live = the always-zero base slot).
        self._max_live = lora_mod.max_live()
        self._adapter_tokens: dict = {}
        # Capacity ledger (serve/memledger.py): derives per-page ownership
        # from the structures below; must exist before the first
        # _alloc_state so crash recovery can carry counters across
        # prefix-cache instances.
        self._ledger = memledger.MemoryLedger(self)
        self._alloc_state()

        # Admission queue: per-(tenant, class) sub-queues drained by
        # deficit-weighted round robin (serve/qos.py).  All mutations
        # happen under _cond, exactly like the deque it replaced; with
        # only default traffic it degrades to the same FIFO.
        self._pending: qos.WFQueue = qos.WFQueue()
        self._cond = threading.Condition()
        self._shutdown = False
        self._draining = False

        # circuit breaker (written under _cond by submit / the worker)
        self._breaker_open = False
        self._breaker_open_t = 0.0
        self._probe_inflight = False
        self._crashes = 0          # consecutive, since last completed req
        self._crashes_total = 0
        self._engine_resets = 0

        self._rng = jax.random.key(0)
        self._dispatch = 0
        # Worker-loop iteration count: an idle engine's loop is parked on
        # the condition variable, so this must not advance while idle
        # (the idle-spin regression test reads it).
        self._loops = 0

        # metrics (ints/floats written only by the worker thread; readers
        # tolerate torn-but-valid snapshots)
        self._admissions = 0
        self._completed = 0
        self._decode_steps = 0
        self._decode_tokens = 0
        self._decode_time_s = 0.0
        self._occupancy_sum = 0.0
        self._token_window: collections.deque = collections.deque()
        self._queue_rejections = 0
        self._breaker_rejections = 0
        self._deadline_timeouts = 0
        self._prefill_chunks = 0
        # QoS accounting: preemptions, resume cached-token credit (the
        # zero-recompute proof), quota sheds, per-class admissions, and
        # per-tenant emitted+prefilled tokens.
        self._preemptions = 0
        self._resume_cached_tokens = 0
        self._quota_rejections = 0
        self._class_admissions = collections.Counter()
        self._tenant_tokens: dict = {}
        # Latency distributions: true fixed-bucket histograms
        # (utils/metrics.py Hist), not truncated sample deques — the p99s
        # /serving_stats/ reports derive from these, and /metrics exposes
        # the process-wide mirrors the engine observes alongside.
        # _h_ttft: enqueue → first token (admission latency);
        # _h_queue_wait: enqueue → admission (prefill start);
        # _h_chunk_stall: decode-batch stall per step boundary from
        # interleaved prefill chunks (only sampled while decode rows are
        # in flight — idle-engine prefill stalls nobody);
        # _h_itl: per-row inter-token gap; _h_tick: tick dispatch wall.
        self._h_ttft = metrics_util.Hist()
        self._h_queue_wait = metrics_util.Hist()
        self._h_chunk_stall = metrics_util.Hist()
        self._h_itl = metrics_util.Hist()
        self._h_tick = metrics_util.Hist()
        # Per-class latency breakdown (SLO isolation is only verifiable if
        # the interactive distribution is separable from the flood's).
        self._h_ttft_cls = {c: metrics_util.Hist() for c in qos.PRIORITIES}
        self._h_queue_wait_cls = {c: metrics_util.Hist()
                                  for c in qos.PRIORITIES}
        # Compiled multi-step decode accounting: one "dispatch" is one
        # device round trip of the decode path (shared step, verify step,
        # or fused superstep) — tokens_per_dispatch ≈ PENROZ_SCHED_SUPERSTEP
        # for unconstrained fused decode is the feature's acceptance shape
        # (distinct from tokens_per_decode_step, which measures what
        # SPECULATION buys per logical step).
        self._dispatches = 0
        self._h_tokens_per_dispatch = metrics_util.Hist(
            metrics_util.TOKENS_PER_DISPATCH_BUCKETS)
        # Tick-level telemetry ring: per-tick phase composition (prefill
        # chunks / verify rows / shared-step rows), batch occupancy, and
        # dispatch wall time — the dashboard occupancy/latency strip.
        self._tick_timeline: collections.deque = collections.deque(
            maxlen=_tick_timeline_len())
        self._chunks_between_steps = 0
        self._max_chunks_between_steps = 0
        # speculative decoding (PENROZ_SPEC_DECODE=1, greedy engines)
        self._spec_verify_steps = 0
        self._spec_drafted_tokens = 0
        self._spec_accepted_tokens = 0

        # Disaggregated-prefill hand-off accounting (both roles: exports on
        # prefill replicas, imports on decode replicas; failures on either
        # side of the seam).
        self._disagg_exports = 0
        self._disagg_imports = 0
        self._disagg_handoff_failures = 0
        self._h_handoff = metrics_util.Hist()

        # Session hibernation accounting (serve/tierstore.py): lifetime
        # hibernations and tier promotions this engine performed, plus the
        # enqueue→first-token distribution of session-resume admissions.
        self._sessions_hibernated = 0
        self._session_promotions = 0
        self._h_resume_ttft = metrics_util.Hist()

        # Worker-tick watchdog (PENROZ_TICK_WATCHDOG_MS): _dispatch_t0 is
        # set for exactly the duration of one tick's device dispatch and
        # cleared in a finally, so "stuck" is computable lazily at scrape
        # //readyz time with no extra thread — a wedged dispatch (device
        # hang, pathological compile) becomes visible while it is still
        # wedged.  _watchdog_fired makes the flight-recorder postmortem
        # one-shot per episode.
        self._dispatch_t0 = None
        self._watchdog_fired = False

        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"penroz-sched-{model_id}-{self.block_size}")
        self._thread.start()

    def _alloc_state(self):
        """(Re)allocate the engine's device-facing state from scratch:
        the multi-row KV buffers, the static block-table partition, and
        the radix prefix cache over the reserved pool tail (pages
        [capacity * pages_per_seq, num_pool_pages) are never touched by
        the static per-row partition, so they are exclusively the radix
        tree's to hand out).  Used at construction AND by crash recovery —
        after a failed tick the old KV/prefix state is presumed corrupt
        and nothing from it survives."""
        old_cache = getattr(self, "_prefix_cache", None)
        self._kv = (KV.create_kv_state(self._model.arch.kv_specs,
                                       self.capacity, self.block_size,
                                       self._model._kv_dtype(),
                                       extra_pool_pages=self._extra_pages,
                                       ssm_specs=self._model.arch.ssm_specs)
                    .with_static_table()
                    .with_lengths(np.zeros(self.capacity, np.int32)))
        # Serving mesh (PENROZ_SERVE_MESH=1): params/buffers shard over the
        # model axis once, the fresh KV pools follow; a 1-device mesh is a
        # GSPMD no-op so the CPU parity suite covers this path.  Block
        # table and lengths stay host-authored either way.  With a
        # pipeline group, placement is stage-partitioned instead: stage
        # params and KV-pool slices land on per-stage meshes.
        self._kv, self._mesh_devices = self._model.enter_serve_mesh(
            self._kv, pipe=self._pipe, replica=self.replica)
        self._prefix_cache = None
        if self._extra_pages > 0 and isinstance(self._kv, KV.PagedKVState):
            base = self.capacity * self._kv.pages_per_seq
            self._prefix_cache = KV.RadixPrefixCache(
                list(range(base, self._kv.num_pool_pages)),
                self._kv.page_size)
        self._lengths[:] = 0
        self._last_tok[:] = 0
        self._rows = [None] * self.capacity
        # Hibernation holds: session_id -> pinned radix node chain whose
        # pages the ledger counts ``hibernating`` until the background
        # demotion exports them.  A reallocation killed the pool those
        # pages lived in, so the holds die here and the tier store drops
        # the matching tier-"hbm" records (host/disk copies survive).
        self._hib_holds: dict = {}
        self._hib_pending: collections.deque = collections.deque()
        tierstore.TIERS.drop_owner(id(self), "engine_reset")
        # Adapter row tables rebuild with the rest of the engine state:
        # after a crash nothing about the old slot assignment is trusted —
        # every row re-parks on the base slot and the stacked pack drops
        # (admission re-binds live adapters from their pinned entries).
        self._slot_entries: list = [None] * self._max_live
        self._row_adapter = np.full(self.capacity, self._max_live, np.int32)
        self._lora_pack = None
        # Fold the dying prefix cache's instance counters into the
        # ledger's lifetime carry (engine-scoped underflow attribution
        # must survive the recovery that replaces the cache).
        self._ledger.on_realloc(old_cache)

    # -- public surface -----------------------------------------------------

    def _queue_retry_after(self) -> int:
        """Load-aware backoff hint for a queue shed: the queued work's
        rough drain time (depth × recent tick p50), clamped to [1, 30]s —
        callers hold _cond."""
        tick_ms = self._h_tick.quantile(0.5) or 50.0
        depth = len(self._pending)
        return int(min(30, max(1, math.ceil(depth * tick_ms / 1000.0))))

    def _shed_span(self, req: Request, reason: str):
        """A shed request never reaches an engine row, but its trace must
        still carry the queue wait (enqueue → shed) and the typed reason —
        'why did my 429/504 take this long' reads off the one tree."""
        if req.trace is not None:
            sp = req.trace.span("queue", t0=req.enqueue_t)
            req.trace.end(sp)
            req.trace.event("shed", reason=reason)

    def submit(self, req: Request):
        """Enqueue ``req`` or refuse it NOW: shedding happens at the door
        (bounded queue, exhausted tenant quota, open breaker, draining
        engine) so clients get an immediate, typed answer instead of a
        stalled connection."""
        with self._cond:
            if self._shutdown or self._draining:
                raise RuntimeError("decode engine is shut down")
            if self._breaker_open:
                cooldown_ms = _breaker_cooldown_ms()
                now = time.monotonic()
                cooldown_done = (now >= self._breaker_open_t
                                 + cooldown_ms / 1000.0)
                if self._probe_inflight or not cooldown_done:
                    self._breaker_rejections += 1
                    serve_metrics.BREAKER_REJECTIONS.inc()
                    serve_metrics.REQUESTS.inc(outcome="breaker_open")
                    if req.trace is not None:
                        req.trace.event("shed", reason="breaker_open")
                    remaining_s = max(
                        0.0, self._breaker_open_t + cooldown_ms / 1000.0
                        - now)
                    raise CircuitOpenError(
                        f"engine {self.model_id}: circuit breaker open "
                        f"after {self._crashes} consecutive crashes",
                        retry_after=min(30, max(1,
                                                math.ceil(remaining_s))))
                # Half-open: exactly one probe request goes through; its
                # completion closes the breaker (_retire), its failure
                # re-arms the cooldown (_fail_all).
                self._probe_inflight = True
            # Tenant token quota: an exhausted bucket sheds THIS tenant's
            # new admissions (429 + refill-derived Retry-After); in-flight
            # rows — anyone's — are never touched.  Hand-off arrivals were
            # already admitted (and prompt-charged) on the prefill replica.
            if req.handoff is None:
                try:
                    qos.QUOTAS.admit(req.tenant)
                except TenantQuotaExceeded:
                    self._quota_rejections += 1
                    serve_metrics.QUOTA_REJECTIONS.inc(tenant=req.tenant)
                    serve_metrics.REQUESTS.inc(outcome="quota")
                    self._shed_span(req, "quota")
                    raise
            # Per-class bound when PENROZ_QOS_MAX_QUEUE_<CLASS> is set
            # (0 = explicitly unbounded); otherwise the pre-QoS aggregate
            # PENROZ_SCHED_MAX_QUEUE applies unchanged.
            cls_bound = qos.class_queue_bound(req.priority)
            if cls_bound is not None:
                full = (cls_bound
                        and self._pending.class_depth(req.priority)
                        >= cls_bound)
                bound_desc = (f"{cls_bound} {req.priority} waiting"
                              if cls_bound else "")
            else:
                max_queue = _max_queue()
                full = max_queue and len(self._pending) >= max_queue
                bound_desc = f"{max_queue} waiting"
            if full:
                self._queue_rejections += 1
                serve_metrics.QUEUE_REJECTIONS.inc()
                serve_metrics.REQUESTS.inc(outcome="queue_full")
                self._shed_span(req, "queue_full")
                raise QueueFullError(
                    f"engine {self.model_id}: admission queue full "
                    f"({bound_desc})",
                    retry_after=self._queue_retry_after())
            self._pending.push(req)
            if req.trace is not None:
                # From here on every terminal path (retire, purge, crash
                # recovery, shutdown) runs through this engine — it owns
                # the trace's finish so the recovery span can be recorded
                # after the error event already reached the client.
                req.trace.owned = True
            self._cond.notify_all()

    def shutdown(self, timeout: float = 10.0, drain_s: float = 0.0) -> bool:
        """Stop the engine; returns True iff the worker thread joined.

        ``drain_s > 0`` first stops admission (``_draining``) and gives
        in-flight rows that long to finish before the hard stop — the
        graceful path ``drain_and_shutdown`` uses at server shutdown.
        A thread that fails to join within ``timeout`` is reported
        (False + log) instead of silently leaked."""
        if drain_s > 0:
            with self._cond:
                self._draining = True
                self._cond.notify_all()
            deadline = time.monotonic() + drain_s
            while self.active_rows and time.monotonic() < deadline:
                time.sleep(0.01)
            if self.active_rows:
                log.warning(
                    "Decode engine %s: %d row(s) still in flight after "
                    "%.1fs drain; failing them", self.model_id,
                    self.active_rows, drain_s)
        with self._cond:
            self._shutdown = True
            self._cond.notify_all()
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            log.error("Decode engine %s: worker thread failed to join "
                      "within %.1fs (leaked)", self.model_id, timeout)
            return False
        # HBM-tier session records die with the engine's pool; demoted
        # host/disk copies survive and wake on the next engine (restart or
        # another replica) via the content-addressed match.
        self._drop_hib_holds()
        tierstore.TIERS.drop_owner(id(self), "engine_shutdown")
        return True

    @property
    def active_rows(self) -> int:
        return sum(1 for r in self._rows if r is not None)

    @property
    def queue_depth(self) -> int:
        return len(self._pending)

    def idle(self) -> bool:
        return self.active_rows == 0 and not self._pending

    def stuck(self) -> bool:
        """Watchdog verdict, computed lazily at read time (scrape /
        /readyz / serving_stats — no watchdog thread exists): True while
        the worker has been inside ONE tick dispatch longer than
        ``PENROZ_TICK_WATCHDOG_MS`` (0/unset = watchdog off).  The first
        read of a stuck episode records a ``watchdog`` flight-recorder
        entry so the pre-hang tick timeline survives for the postmortem
        even if the process is later killed."""
        limit = _watchdog_ms()
        t0 = self._dispatch_t0
        if limit <= 0 or t0 is None:
            return False
        if (time.monotonic() - t0) * 1000.0 < limit:
            return False
        if not self._watchdog_fired:
            self._watchdog_fired = True
            memledger.FLIGHT_RECORDER.record(
                self, "watchdog",
                error=f"tick dispatch exceeded {limit:.0f} ms")
            log.warning("Decode engine %s watchdog: tick dispatch running "
                        "for %.0f ms (limit %.0f ms)", self.model_id,
                        (time.monotonic() - t0) * 1000.0, limit)
        return True

    @property
    def disagg_transport(self) -> str:
        """Live hand-off transport this engine exports with."""
        return _disagg_transport()

    @property
    def live_adapters(self) -> int:
        return sum(1 for e in self._slot_entries if e is not None)

    def jit_program_counts(self) -> dict[str, int]:
        return self._model.arch.jit_program_counts()

    def _round_q(self, hist: metrics_util.Hist, q: float):
        v = hist.quantile(q)
        return round(v, 3) if v is not None else None

    def stats(self) -> dict:
        """THE engine observability accessor: every cross-engine aggregate
        (``serving_stats()``) and every scrape reads through here — no
        caller reaches into private engine state, so the worker thread's
        writes race only with the lock-guarded histogram snapshots below
        (the scalar counters are single-writer ints; readers tolerate
        torn-but-valid snapshots).  The ``histograms`` key carries the raw
        bucket snapshots the aggregation layer merges; pydantic drops it
        from the HTTP payload (not a declared schema field)."""
        now = time.monotonic()
        window = [(t, n) for t, n in self._token_window
                  if now - t <= _TPS_WINDOW_S]
        span = (now - window[0][0]) if window else 0.0
        recent = sum(n for _, n in window)
        tps = recent / span if span > 0.2 else (
            self._decode_tokens / self._decode_time_s
            if self._decode_time_s > 0 else 0.0)
        active = self.active_rows
        stall_p99 = self._h_chunk_stall.quantile(0.99)
        queue_wait_p99 = self._h_queue_wait.quantile(0.99)
        tpd = self._h_tokens_per_dispatch.snapshot()
        # newest-first tail of the ring (age_s ≈ 0 leads)
        timeline = list(self._tick_timeline)[-_TIMELINE_SERVE:][::-1]
        return {
            "histograms": {
                "ttft_ms": self._h_ttft.snapshot(),
                "itl_ms": self._h_itl.snapshot(),
                "queue_wait_ms": self._h_queue_wait.snapshot(),
                "chunk_stall_ms": self._h_chunk_stall.snapshot(),
                "tick_ms": self._h_tick.snapshot(),
                "tokens_per_dispatch": tpd,
                "ttft_ms_by_class": {
                    c: h.snapshot() for c, h in self._h_ttft_cls.items()},
                "queue_wait_ms_by_class": {
                    c: h.snapshot()
                    for c, h in self._h_queue_wait_cls.items()},
                "handoff_ms": self._h_handoff.snapshot(),
                "session_resume_ttft_ms": self._h_resume_ttft.snapshot(),
            },
            "superstep": _superstep_max(),
            "dispatches_total": self._dispatches,
            "tokens_per_dispatch_avg": (round(tpd["sum"] / tpd["count"], 3)
                                        if tpd["count"] else None),
            "tokens_per_dispatch_p50": self._round_q(
                self._h_tokens_per_dispatch, 0.5),
            "ttft_ms_p99": self._round_q(self._h_ttft, 0.99),
            "itl_ms_p50": self._round_q(self._h_itl, 0.5),
            "itl_ms_p99": self._round_q(self._h_itl, 0.99),
            "tick_ms_p50": self._round_q(self._h_tick, 0.5),
            "tick_ms_p99": self._round_q(self._h_tick, 0.99),
            "tick_timeline": [
                {"age_s": round(now - e["t"], 3),
                 **{k: v for k, v in e.items() if k != "t"}}
                for e in timeline],
            "kv_pool_capacity_drops": self._ledger.pool_capacity_drops,
            "unpin_underflows": self._ledger.unpin_underflows,
            "memory": self._ledger.snapshot(),
            "queue_rejections": self._queue_rejections,
            "deadline_timeouts": self._deadline_timeouts,
            "breaker_rejections": self._breaker_rejections,
            "quota_rejections": self._quota_rejections,
            "preemptions": self._preemptions,
            "preempted_resume_cached_tokens": self._resume_cached_tokens,
            "queue_depth_by_class": self._pending.class_depths(),
            "admissions_by_class": {
                c: self._class_admissions[c] for c in qos.PRIORITIES},
            "tenant_tokens": dict(self._tenant_tokens),
            "ttft_ms_p99_by_class": {
                c: self._round_q(h, 0.99)
                for c, h in self._h_ttft_cls.items()},
            "queue_wait_ms_p99_by_class": {
                c: self._round_q(h, 0.99)
                for c, h in self._h_queue_wait_cls.items()},
            "queue_wait_ms_p99": (round(queue_wait_p99, 3)
                                  if queue_wait_p99 is not None else None),
            "breaker_open": self._breaker_open,
            "stuck": self.stuck(),
            "consecutive_crashes": self._crashes,
            "crashes_total": self._crashes_total,
            "engine_resets": self._engine_resets,
            "model_id": self.model_id,
            "block_size": self.block_size,
            "temperature": 0.0 if self.greedy else float(self.temperature),
            "top_k": self.top_k,
            "capacity": self.capacity,
            "replica": self.replica,
            "mesh_devices": self._mesh_devices,
            "role": self.role,
            "disagg_exports": self._disagg_exports,
            "disagg_imports": self._disagg_imports,
            "disagg_handoff_failures": self._disagg_handoff_failures,
            "disagg_handoff_ms_p50": self._round_q(self._h_handoff, 0.5),
            "disagg_handoff_ms_p99": self._round_q(self._h_handoff, 0.99),
            "disagg_transport": _disagg_transport(),
            "disagg_role_changes": self._disagg_role_changes,
            "pipe_stages": (self._pipe.stages if self._pipe is not None
                            else 1),
            "pipe_microblocks": (_pipe_blocks(self._pipe.stages)
                                 if self._pipe is not None else 0),
            "pipe_ticks": self._pipe_ticks,
            "pipe_bubble_fraction": (
                round(self._pipe_bubble_ticks
                      / (self._pipe_ticks * self._pipe.stages), 4)
                if self._pipe is not None and self._pipe_ticks else None),
            "pipe_stage_busy": {str(s): int(c) for s, c
                                in sorted(self._pipe_stage_busy.items())},
            "pipe_handoffs": self._pipe_handoffs,
            "pipe_handoff_host_fallbacks":
                self._pipe_handoff_host_fallbacks,
            "sessions_hibernated": self._sessions_hibernated,
            "session_promotions": self._session_promotions,
            "session_resume_ttft_ms_p50": self._round_q(
                self._h_resume_ttft, 0.5),
            "session_resume_ttft_ms_p99": self._round_q(
                self._h_resume_ttft, 0.99),
            "active_rows": active,
            "queue_depth": self.queue_depth,
            "occupancy": active / self.capacity,
            "occupancy_avg": (self._occupancy_sum / self._decode_steps
                              if self._decode_steps else 0.0),
            "decode_steps": self._decode_steps,
            "decode_tokens": self._decode_tokens,
            "decode_tokens_per_sec": round(tps, 2),
            "admissions": self._admissions,
            "completed": self._completed,
            "admission_latency_ms_p50": self._round_q(self._h_ttft, 0.5),
            "prefill_chunks": self._prefill_chunks,
            "prefill_chunk_stall_ms_p99": (round(stall_p99, 3)
                                           if stall_p99 is not None
                                           else None),
            "prefill_max_chunks_between_steps":
                self._max_chunks_between_steps,
            "prefix_cache": (self._prefix_cache.stats()
                             if self._prefix_cache is not None else None),
            "lora_active_adapters": self.live_adapters,
            "lora_rows": sum(
                1 for i, r in enumerate(self._rows)
                if r is not None
                and int(self._row_adapter[i]) != self._max_live),
            "lora_adapter_tokens": dict(self._adapter_tokens),
            "ssm_rows": active if self._has_ssm else 0,
            "ssm_state_bytes": (int(self._kv.ssm.nbytes())
                                if getattr(self._kv, "ssm", None) is not None
                                else 0),
            "spec_decode": self._spec_on(),
            "spec_verify_steps": self._spec_verify_steps,
            "spec_drafted_tokens": self._spec_drafted_tokens,
            "spec_accepted_tokens": self._spec_accepted_tokens,
            "spec_accept_rate": stats_util.rate(self._spec_accepted_tokens,
                                                self._spec_drafted_tokens),
            "tokens_per_decode_step": round(
                stats_util.rate(self._decode_tokens, self._decode_steps)
                or 0.0, 3),
        }

    def memory_snapshot(self) -> dict:
        """The engine's capacity-ledger view (GET /memory/ reads through
        here — same no-private-state contract as ``stats()``)."""
        return self._ledger.snapshot()

    # -- worker loop --------------------------------------------------------

    def _run(self):
        while True:
            with self._cond:
                while (not self._shutdown and not self._pending
                       and not self._acks and self._requested_role is None
                       and not self._hib_pending
                       and self.active_rows == len(self._transit_rows)):
                    # Untimed wait: every state change the predicate reads
                    # notifies (submit, shutdown, drain, hand-off ack, role
                    # request), so an idle engine parks on the condition
                    # variable and burns zero CPU — no periodic wake, no
                    # empty ticks (tested).  With rows parked awaiting d2d
                    # importer acks the wait turns timed, so a lost ack is
                    # reaped at its deadline instead of never.
                    if self._transit_rows:
                        self._cond.wait(timeout=0.05)
                        if self._ack_overdue():
                            break
                    else:
                        self._cond.wait()
                if self._shutdown:
                    break
            self._loops += 1
            try:
                self._drain_acks()
                self._maybe_flip_role()
                self._purge_expired()
                self._coalesce_burst()
                self._admit()
                self._tick()
                # Background demotion AFTER the tick: hibernated pages
                # spill down a tier only once live traffic has been
                # served this iteration (the hot path never exports).
                self._process_demotions()
            except Exception as exc:  # noqa: BLE001 — fail requests, not thread
                log.exception("Decode engine %s failed a tick", self.model_id)
                # Count the crash, then postmortem BEFORE _fail_all /
                # _alloc_state destroy the pre-crash ledger/timeline
                # state the dump exists for — the recorded crashes_total
                # names which crash the entry belongs to.
                self._record_crash()
                memledger.FLIGHT_RECORDER.record(
                    self, "engine_crash", error=repr(exc))
                crashed_traces = self._fail_all(exc, crashed=True)
                try:
                    # Full reset: the exception left KV/prefix state in an
                    # unknown shape — reallocate so the NEXT request runs
                    # against provably clean buffers and block tables.
                    self._engine_resets += 1
                    serve_metrics.ENGINE_RESETS.inc()
                    t_crash = time.monotonic()
                    self._alloc_state()
                    # Recovery must hand back a provably clean pool: a
                    # strict audit failure here means _alloc_state itself
                    # leaked, and the breaker (outer except) is the only
                    # honest response.
                    if memledger.strict():
                        self._ledger.audit("crash_recovery")
                    for tr in crashed_traces:
                        # The failed request's trace carries the recovery it
                        # triggered: crash site → clean engine, so "where
                        # did this 504/500 come from" reads off one tree.
                        sp = tr.span("recovery", t0=t_crash,
                                     resets=self._engine_resets)
                        tr.end(sp)
                        tr.finish("error")
                    log.warning("Decode engine %s reset after crash %d "
                                "(consecutive %d)", self.model_id,
                                self._crashes_total, self._crashes)
                except Exception:  # noqa: BLE001 — can't trust the engine
                    log.exception("Decode engine %s reset FAILED; opening "
                                  "circuit breaker", self.model_id)
                    memledger.FLIGHT_RECORDER.record(self, "reset_failed")
                    for tr in crashed_traces:
                        tr.finish("error")
                    with self._cond:
                        self._breaker_open = True
                        self._breaker_open_t = time.monotonic()
        self._fail_all(RuntimeError("decode engine shut down"))

    def _tick(self):
        """One scheduler tick: interleaved prefill chunks, then the decode
        dispatch — either the legacy verify+shared single step or ONE fused
        ``PENROZ_SCHED_SUPERSTEP``-step program (``_plan_superstep``
        decides) — instrumented as a unit (dispatch wall time, phase
        composition, occupancy, fused step count) into the tick timeline,
        the tick-duration histogram, and a profiler span, so both a
        Perfetto capture and the dashboard strip show what the loop
        actually did between dispatches.
        """
        prefilling = self._next_prefill_row() is not None
        decoding = bool(self._decoding_rows())
        if not prefilling and not decoding:
            return
        if self._unified():
            self._tick_unified()
            return
        prefill_rows = sum(1 for r in self._rows
                           if r is not None and r.prefilling)
        chunks0 = self._prefill_chunks
        verify_rows = shared_rows = emitted = steps = 0
        t0 = time.monotonic()
        self._dispatch_t0 = t0
        try:
            with tracing.span("penroz/sched_tick"):
                self._prefill_tick()
                if self._decoding_rows():
                    n = self._plan_superstep()
                    if n > 1:
                        shared_rows, emitted = self._superstep(n)
                        steps = n
                    else:
                        verify_rows, shared_rows, emitted = self._step()
                        steps = 1
        finally:
            self._dispatch_t0 = None
            self._watchdog_fired = False
        dur_ms = (time.monotonic() - t0) * 1000.0
        self._h_tick.observe(dur_ms)
        serve_metrics.TICK_MS.observe(dur_ms)
        self._tick_timeline.append({
            "t": t0,
            "dispatch_ms": round(dur_ms, 3),
            "occupancy": round(self.active_rows / self.capacity, 4),
            "prefill_chunks": self._prefill_chunks - chunks0,
            "verify_rows": verify_rows,
            "shared_rows": shared_rows,
            "emitted": emitted,
            "superstep": steps,
            "unified": False,
            "prefill_rows": prefill_rows,
            "decode_rows": shared_rows,
            "pipe_ticks": 0,
            "pipe_bubbles": 0,
        })

    def _unified(self) -> bool:
        """Unified ragged dispatch is THE paged fast path: every tick is
        one ``decode_mixed_step`` block in which prefill chunks, decode
        steps and spec-verify spans share a single kernel dispatch — no
        prefill/decode phase boundary, no stall budget, none of the PR 7
        superstep fallbacks.  ``PENROZ_RAGGED_ATTENTION=0`` (one-release
        escape hatch) or a contiguous cache keeps the legacy phased tick."""
        return isinstance(self._kv, KV.PagedKVState) and ragged_enabled()

    def _tick_unified(self):
        """One unified tick: host-plan an n-step mixed block (prefill
        chunks, decode steps and verify spans all in the SAME dispatches),
        run it as ONE ``decode_mixed_step`` device round trip, replay the
        sampled block through the normal per-token retirement path.

        There is no phase distinction left: a prefill chunk does not stall
        the decode batch (they share the dispatch), so the stall budget is
        gone, and none of the phased superstep fallbacks apply — pending
        prefill chunks and spec drafts fuse INTO the block instead of
        collapsing it to n=1.  Host-only terminal conditions (deadline,
        cancel) are observed at the block boundary, the same documented
        ``PENROZ_SCHED_SUPERSTEP`` granularity trade as the phased path."""
        _warn_stall_deprecated()
        t0 = time.monotonic()
        self._dispatch_t0 = t0
        superstep = 0
        try:
            with tracing.span("penroz/sched_tick"):
                if self._pipe is not None and self._lora_pack is None:
                    plans = self._plan_mixed_blocks()
                    if not plans:
                        return
                    comp = self._pipeline_dispatch(plans)
                    superstep = max(p["n"] for p in plans)
                else:
                    if (self._pipe is not None
                            and not self._pipe_lora_warned):
                        self._pipe_lora_warned = True
                        log.warning(
                            "pipeline serving suspended while LoRA "
                            "adapters are live: stage re-keying does not "
                            "thread the adapter pack")
                    plan = self._plan_mixed()
                    if plan is None:
                        return
                    comp = self._mixed_dispatch(plan)
                    superstep = plan["n"]
        finally:
            self._dispatch_t0 = None
            self._watchdog_fired = False
        dur_ms = (time.monotonic() - t0) * 1000.0
        self._h_tick.observe(dur_ms)
        serve_metrics.TICK_MS.observe(dur_ms)
        self._tick_timeline.append({
            "t": t0,
            "dispatch_ms": round(dur_ms, 3),
            "occupancy": round(self.active_rows / self.capacity, 4),
            "prefill_chunks": comp["prefill_chunks"],
            "verify_rows": comp["verify_rows"],
            "shared_rows": comp["decode_rows"],
            "emitted": comp["emitted"],
            "superstep": superstep,
            "unified": True,
            "prefill_rows": comp["prefill_rows"],
            "decode_rows": comp["decode_rows"],
            "pipe_ticks": comp.get("pipe_ticks", 0),
            "pipe_bubbles": comp.get("pipe_bubbles", 0),
        })

    def _plan_mixed(self, rows=None):
        """Host-side plan for one unified block: simulate every row's next
        ``PENROZ_SCHED_SUPERSTEP`` steps of work — a prefilling row runs
        one pow-2-bucketed chunk per step and flows STRAIGHT into decode
        mid-block (its final chunk's sample feeds the next step through
        the device carry), a drafted row runs its K+1 verify span at step
        0 then parks (acceptance is a host decision), a decode row runs a
        1-token span per step until its budget or the row capacity is
        spent — and pack each step's spans into shape-bucketed descriptor
        arrays (utils/bucketing.py: the step count takes the pow-2 floor,
        the block count the pow-2 ceiling, so the compiled mixed-program
        set stays O(log²) for any workload).  ``rows`` restricts the plan
        to a subset of ``(index, state)`` pairs — pipeline micro-blocks
        plan disjoint row partitions through this."""
        from penroz_tpu.ops.pallas.ragged_paged_attention import (
            default_block_q)
        if rows is None:
            rows = [(i, r) for i, r in enumerate(self._rows)
                    if r is not None and not r.transit]
        if not rows:
            return None
        subset = {i for i, _ in rows}
        block_q = default_block_q()
        n_max = max(1, _superstep_max())
        spec = self._spec_on()
        drafts = dict(self._plan_drafts(
            [i for i in self._decoding_rows() if i in subset]))
        sim = {}
        for i, state in rows:
            sim[i] = {
                "mode": ("prefill" if state.prefilling
                         else "verify" if i in drafts else "decode"),
                "len": int(self._lengths[i]),
                "chunk": state.chunk_idx,
                "produced": state.produced,
            }
        steps = []          # per step: list of replay ops
        blocks_per_step = []
        for s in range(n_max):
            spans = []      # (row, q_start, q_len)
            ops = []
            for i, state in rows:
                st = sim[i]
                req = state.req
                if st["mode"] == "prefill":
                    size = state.chunks[st["chunk"]]
                    final = st["chunk"] + 1 >= len(state.chunks)
                    spans.append((i, st["len"], size))
                    ops.append(("chunk", i, state, st["len"], size, final,
                                len(spans) - 1))
                    st["len"] += size
                    st["chunk"] += 1
                    if final:
                        # Park at the final chunk: its sample is the
                        # request's FIRST token and must ship at this
                        # block's boundary, not after n-1 more in-block
                        # decode steps (TTFT) — and with spec decode on,
                        # the row's next step should be a drafted verify
                        # span, which only the host can plan.
                        st["mode"] = "parked"
                        st["produced"] += 1     # the chunk's own sample
                elif st["mode"] == "verify":
                    if s == 0:
                        draft = drafts[i]
                        spans.append((i, st["len"], len(draft) + 1))
                        ops.append(("verify", i, state, draft,
                                    len(spans) - 1))
                        st["mode"] = "parked"
                elif st["mode"] == "decode":
                    if (st["produced"] < req.max_new_tokens
                            and st["len"] < self.block_size):
                        spans.append((i, st["len"], 1))
                        ops.append(("decode", i, state, len(spans) - 1))
                        st["len"] += 1
                        st["produced"] += 1
            if not ops:
                break
            steps.append((spans, ops))
            blocks_per_step.append(
                sum(-(-q_len // block_q) for _, _, q_len in spans))
        if not steps:
            return None
        n = bucketing.clamp_pow2_floor(len(steps), hi=n_max)
        steps = steps[:n]
        NB = bucketing.bucket_count(max(blocks_per_step[:n]))
        Tp = NB * block_q
        descs = np.zeros((n, NB, 4), np.int32)
        tok_lit = np.zeros((n, Tp), np.int32)
        tok_src = np.full((n, Tp), -1, np.int32)
        positions = np.zeros((n, Tp), np.int32)
        sample_slot = np.full((n, self.capacity), -1, np.int32)
        lora_slots = np.full((n, Tp), self._max_live, np.int32)
        row_ids = np.full((n, Tp), -1, np.int32)
        replay = []
        for s, (spans, ops) in enumerate(steps):
            d, offsets = KV.build_descriptors(spans, block_q, NB)
            descs[s] = d
            step_ops = []
            for op in ops:
                kind, i, state = op[0], op[1], op[2]
                span_idx = op[-1]
                q_start, q_len = spans[span_idx][1], spans[span_idx][2]
                slots = KV.packed_slots(offsets[span_idx], q_len, block_q)
                positions[s, slots] = q_start + np.arange(q_len)
                lora_slots[s, slots] = int(self._row_adapter[i])
                row_ids[s, slots] = i
                if kind == "chunk":
                    _, _, _, start, size, final, _ = op
                    tok_lit[s, slots] = state.history[start:start + size]
                    if final:
                        sample_slot[s, i] = slots[-1]
                        step_ops.append(("chunk", i, state, size,
                                         int(slots[-1])))
                    else:
                        step_ops.append(("chunk", i, state, size, None))
                elif kind == "verify":
                    draft = op[3]
                    tok_lit[s, slots] = ([int(self._last_tok[i])]
                                         + [int(t) for t in draft])
                    step_ops.append(("verify", i, state, draft,
                                     [int(sl) for sl in slots]))
                else:
                    tok_src[s, slots[0]] = i
                    sample_slot[s, i] = slots[0]
                    step_ops.append(("decode", i, state, int(slots[0])))
            replay.append(step_ops)
        return {"n": n, "descs": descs, "tok_lit": tok_lit,
                "tok_src": tok_src, "positions": positions,
                "sample_slot": sample_slot, "lora_slots": lora_slots,
                "row_ids": row_ids, "replay": replay}

    def _mixed_dispatch(self, plan) -> dict:
        """Run the planned block as ONE ``decode_mixed_step`` dispatch and
        replay its ``(n, Tp)`` sample array step-major through the normal
        retirement path — the same replay contract as ``_superstep``
        (``is not states[i]`` skips rows the host retired mid-block), plus
        chunk bookkeeping (``_finish_prefill`` on a final chunk emits the
        first token with its TTFT) and verify acceptance + KV rollback.
        Host lengths stay authoritative throughout."""
        faults.check("decode.step")
        n, replay = plan["n"], plan["replay"]
        has_chunks = any(op[0] == "chunk" for ops in replay for op in ops)
        has_verify = any(op[0] == "verify" for ops in replay for op in ops)
        if has_chunks:
            faults.check("decode.prefill_chunk")
        if has_verify:
            faults.check("decode.verify")
        if self._has_ssm:
            faults.check("ssm.scan")
        dispatch = self._dispatch
        self._dispatch += n
        t0 = time.monotonic()
        with model_mod.decode_priority(), \
                tracing.span("penroz/sched_mixed"):
            sampled, self._kv = self._model.decode_mixed_step(
                self._kv, plan["descs"], plan["tok_lit"], plan["tok_src"],
                plan["positions"], plan["sample_slot"], self._last_tok,
                self._rng, dispatch, self.temperature, self.top_k,
                lora=self._lora_pack, lora_slots=plan["lora_slots"],
                row_ids=plan["row_ids"])
            arr = np.asarray(sampled)
        t1 = time.monotonic()
        return self._replay_block(plan, arr, t0, t1)

    def _replay_block(self, plan, arr, t0, t1) -> dict:
        """Replay one planned block's ``(n, Tp)`` sample array through the
        per-token retirement path (shared by the fused single-dispatch
        path and each pipeline micro-block) and account its metrics.
        Host lengths stay authoritative throughout."""
        n, replay = plan["n"], plan["replay"]
        prefill_rows = {op[1] for ops in replay for op in ops
                        if op[0] == "chunk"}
        decode_rows = {op[1] for ops in replay for op in ops
                       if op[0] == "decode"}
        verify_rows = {op[1] for ops in replay for op in ops
                       if op[0] == "verify"}
        for i in decode_rows | verify_rows:
            state = self._rows[i]
            if state is not None and state.req.trace is not None:
                sp = state.req.trace.span("decode_step", t0=t0,
                                          parent=state.sp_decode,
                                          superstep=n)
                state.req.trace.end(sp, t1=t1)
        emitted = 0         # decode-path tokens (decode_tokens parity)
        emitted_total = 0   # every token out of this dispatch
        chunks_run = 0
        steps_decode = 0
        for s, ops in enumerate(replay):
            if any(op[0] in ("decode", "verify") for op in ops):
                steps_decode += 1
            for op in ops:
                kind, i, state = op[0], op[1], op[2]
                if self._rows[i] is not state:
                    continue    # retired mid-block (stop/budget/deadline)
                if kind == "chunk":
                    size, final_slot = op[3], op[4]
                    req = state.req
                    if req.cancelled:
                        self._retire(i, notify=False, reason="cancelled")
                        continue
                    if req.expired():
                        self._deadline_timeouts += 1
                        serve_metrics.DEADLINE_TIMEOUTS.inc()
                        self._retire(i, notify=False, reason="timeout")
                        self._deliver(req, "timeout", DeadlineExceeded(
                            "inflight",
                            "request deadline expired during prefill"))
                        continue
                    if req.trace is not None:
                        sp = req.trace.span(
                            "prefill_chunk", t0=t0,
                            parent=state.sp_prefill, size=size,
                            start=state.prefilled)
                        req.trace.end(sp, t1=t1)
                    state.prefilled += size
                    state.chunk_idx += 1
                    self._prefill_chunks += 1
                    serve_metrics.PREFILL_CHUNKS.inc()
                    self._lengths[i] = state.prefilled
                    chunks_run += 1
                    if final_slot is not None:
                        emitted_total += 1
                        self._finish_prefill(i, state, int(arr[s, final_slot]))
                elif kind == "decode":
                    slot = op[3]
                    self._lengths[i] += 1
                    tok = int(arr[s, slot])
                    self._last_tok[i] = tok
                    emitted += 1
                    emitted_total += 1
                    self._emit_token(i, state, tok)
                else:   # verify
                    draft, slots = op[3], op[4]
                    out = [int(arr[s, sl]) for sl in slots]
                    accepted = spec_decode.accept_length(draft, out)
                    self._spec_verify_steps += 1
                    self._spec_drafted_tokens += len(draft)
                    self._spec_accepted_tokens += accepted
                    serve_metrics.SPEC_DRAFTED.inc(len(draft))
                    serve_metrics.SPEC_ACCEPTED.inc(accepted)
                    # The span wrote K+1 fresh positions; only accepted+1
                    # were fed greedy-consistent tokens — rewind the rest.
                    new_len = int(self._lengths[i]) + accepted + 1
                    self._kv = self._kv.rollback_row(i, new_len)
                    self._lengths[i] = new_len
                    for tok in out[:accepted + 1]:
                        self._last_tok[i] = tok
                        emitted += 1
                        emitted_total += 1
                        self._emit_token(i, state, tok)
                        if self._rows[i] is not state:
                            break
        now = time.monotonic()
        self._decode_steps += steps_decode
        self._decode_tokens += emitted
        serve_metrics.DECODE_TOKENS.inc(emitted)
        self._decode_time_s += now - t0
        self._occupancy_sum += (steps_decode
                                * len(decode_rows | verify_rows)
                                / self.capacity)
        self._token_window.append((now, emitted))
        while (self._token_window
               and now - self._token_window[0][0] > _TPS_WINDOW_S):
            self._token_window.popleft()
        if chunks_run and steps_decode:
            # Chunks rode the decode dispatch: the decode batch stalled
            # ZERO ms for prefill — record the win where the phased path
            # recorded its stall.
            self._h_chunk_stall.observe(0.0)
            serve_metrics.CHUNK_STALL_MS.observe(0.0)
        self._record_dispatch(emitted_total)
        return {"prefill_chunks": chunks_run,
                "prefill_rows": len(prefill_rows),
                "decode_rows": len(decode_rows),
                "verify_rows": len(verify_rows),
                "emitted": emitted_total}

    def _plan_mixed_blocks(self) -> list:
        """Partition the active rows round-robin into pipeline
        micro-blocks and plan each as its own mixed block.  ≥ S blocks
        (``PENROZ_SERVE_PIPE_BLOCKS``, capped by the live row count) keep
        every stage busy once the pipeline fills; fewer live rows than
        stages degenerates gracefully — the schedule still completes,
        just with fill/drain bubbles the telemetry reports."""
        rows = [(i, r) for i, r in enumerate(self._rows)
                if r is not None and not r.transit]
        if not rows:
            return []
        m = min(_pipe_blocks(self._pipe.stages), len(rows))
        plans = []
        for b in range(m):
            plan = self._plan_mixed(rows[b::m])
            if plan is not None:
                plans.append(plan)
        return plans

    def _pipeline_dispatch(self, plans: list) -> dict:
        """Run the planned micro-blocks through the MPMD stage pipeline
        and replay each block through the shared retirement path.

        Software-pipeline schedule, host-orchestrated: the unit of work
        is (block b, step i, stage s) — one ``decode_pipe_stage`` dispatch
        over block b's step-i packed batch against stage s's KV slice.
        Within a block, step i's stage 0 needs step i-1's sampled tokens
        (the ``tok_src`` carry the fused scan threads on-device), so ONE
        block occupies exactly one stage at a time; overlap comes from
        multiple blocks — each pipeline tick walks stages LAST→FIRST and
        advances at most one block per stage, so a block moves one stage
        per tick and S blocks keep S stages busy (PAPERS.md #3's
        micro-batching, applied to decode).  ``bubbles`` counts
        stage-ticks spent idle (fill, drain, or too few live blocks):
        bubble fraction = bubbles / (ticks × S).

        Activations hand off stage-to-stage as device arrays (the PR 16
        d2d style); an injected ``pipe.handoff`` fault is CONTAINED — the
        transfer re-stages through the host (bounce via numpy, numerics
        identical) and counts in ``pipe_handoff_host_fallbacks``.
        ``pipe.stage_crash`` propagates like any tick crash: the worker's
        crash handler recovers the WHOLE group via ``_alloc_state``.

        KV safety: every stage dispatch reads the current full state's
        stage view and merges back pools + counters/lengths.  Blocks own
        disjoint rows, so interleaved merges touch disjoint ragged-length
        entries; within a block, stages share one step's descriptors and
        recompute identical lengths — merge order cannot change any
        value the attention kernel reads (descriptors and the static
        block table, both host-authored)."""
        faults.check("decode.step")
        if any(op[0] == "chunk" for p in plans
               for ops in p["replay"] for op in ops):
            faults.check("decode.prefill_chunk")
        if any(op[0] == "verify" for p in plans
               for ops in p["replay"] for op in ops):
            faults.check("decode.verify")
        pipe = self._pipe
        S = pipe.stages
        self._dispatch += sum(p["n"] for p in plans)
        t0 = time.monotonic()
        last_local = self._last_tok.copy()
        blocks = [{"plan": p, "step": 0, "stage": 0, "h": None,
                   "arr": np.zeros(p["tok_lit"].shape, np.int32)}
                  for p in plans]
        live = set(range(len(blocks)))
        ticks = bubbles = 0
        with model_mod.decode_priority(), \
                tracing.span("penroz/sched_pipeline"):
            while live:
                ran_stage = 0
                for s in reversed(range(S)):
                    b = next((b for b in sorted(live)
                              if blocks[b]["stage"] == s), None)
                    if b is None:
                        continue
                    st = blocks[b]
                    plan = st["plan"]
                    i = st["step"]
                    faults.check("pipe.stage_crash")
                    if s == 0:
                        tsrc = plan["tok_src"][i]
                        x = np.where(tsrc >= 0,
                                     last_local[np.clip(tsrc, 0, None)],
                                     plan["tok_lit"][i])
                    else:
                        x = st["h"]
                    lo, hi = pipe.kv_bounds[s]
                    view = KV.stage_kv_view(self._kv, lo, hi)
                    out, view2 = self._model.decode_pipe_stage(
                        pipe, s, view, x, plan["descs"][i],
                        plan["positions"][i], plan["row_ids"][i],
                        self._rng, self.temperature, self.top_k)
                    self._kv = KV.merge_stage_kv(self._kv, lo, hi, view2)
                    ran_stage += 1
                    self._pipe_stage_busy[s] += 1
                    if s < S - 1:
                        self._pipe_handoffs += 1
                        try:
                            faults.check("pipe.handoff")
                        except faults.InjectedFault:
                            # Mid-transfer fault: bounce the activations
                            # through the host and carry on — numerics
                            # identical, parity preserved.
                            out = jnp.asarray(np.asarray(out))
                            self._pipe_handoff_host_fallbacks += 1
                        st["h"] = out
                        st["stage"] = s + 1
                        continue
                    sampled = np.asarray(out)
                    st["arr"][i] = sampled
                    sslot = plan["sample_slot"][i]
                    upd = np.where(sslot >= 0)[0]
                    last_local[upd] = sampled[sslot[upd]]
                    st["h"] = None
                    st["step"] += 1
                    st["stage"] = 0
                    if st["step"] >= plan["n"]:
                        live.discard(b)
                ticks += 1
                bubbles += S - ran_stage
        t1 = time.monotonic()
        self._pipe_ticks += ticks
        self._pipe_bubble_ticks += bubbles
        comp = {"prefill_chunks": 0, "prefill_rows": 0, "decode_rows": 0,
                "verify_rows": 0, "emitted": 0}
        for st in blocks:
            part = self._replay_block(st["plan"], st["arr"], t0, t1)
            for k in comp:
                comp[k] += part[k]
        comp["pipe_ticks"] = ticks
        comp["pipe_bubbles"] = bubbles
        return comp

    def _record_crash(self):
        serve_metrics.ENGINE_CRASHES.inc()
        with self._cond:
            self._crashes += 1
            self._crashes_total += 1
            if self._crashes >= _max_crashes() and not self._breaker_open:
                self._breaker_open = True
                self._breaker_open_t = time.monotonic()
                log.error(
                    "Decode engine %s: circuit breaker OPEN after %d "
                    "consecutive crashes (next probe in %.0fms)",
                    self.model_id, self._crashes, _breaker_cooldown_ms())
                # _cond is an RLock via Condition: the recorder's locked
                # snapshot nests safely under this breaker-open hold.
                memledger.FLIGHT_RECORDER.record(self, "circuit_open")

    def _purge_expired(self):
        """Shed queued requests whose deadline passed (504 before prefill
        ever starts) and silently drop cancelled ones (disconnected
        clients must not spend a prefill)."""
        now = time.monotonic()
        with self._cond:
            if not self._pending:
                return
            removed = self._pending.purge(
                lambda r: r.cancelled or r.expired(now))
        for req in removed:
            if req.cancelled:
                self._release_resume(req)
                self._finish_trace(req, "cancelled")
                serve_metrics.REQUESTS.inc(outcome="cancelled")
            else:
                self._timeout_queued(req)

    def _timeout_queued(self, req: Request):
        """Shed one queued request on an expired deadline (504 before
        prefill ever starts) — counter, metrics, trace, event delivery."""
        self._release_resume(req)
        self._deadline_timeouts += 1
        serve_metrics.DEADLINE_TIMEOUTS.inc()
        serve_metrics.REQUESTS.inc(outcome="timeout")
        if req.trace is not None:
            sp = req.trace.span("queue", t0=req.enqueue_t)
            req.trace.end(sp)
        self._finish_trace(req, "timeout")
        self._deliver(req, "timeout", DeadlineExceeded(
            "queued", "request deadline expired while queued "
            "(before prefill started)"))

    def _finish_trace(self, req: Request, reason: str):
        if req.trace is not None:
            req.trace.finish(reason)

    def _coalesce_burst(self):
        """Optional idle-burst coalescing: when the batch is empty, wait up
        to PENROZ_SCHED_ADMIT_MS after the first arrival so a concurrent
        burst shares its very first decode step instead of trickling in."""
        admit_ms = _admit_ms()
        if admit_ms <= 0 or self.active_rows:
            return
        with self._cond:
            first_t = self._pending.oldest_enqueue_t()
            if first_t is None:
                return
            deadline = first_t + admit_ms / 1000.0
            while (len(self._pending) < self.capacity
                   and not self._shutdown
                   and time.monotonic() < deadline):
                self._cond.wait(timeout=max(deadline - time.monotonic(),
                                            0.001))

    def _free_row(self):
        for i, r in enumerate(self._rows):
            if r is None:
                return i
        return None

    def _decoding_rows(self) -> list[int]:
        """Rows with prefill complete — the shared decode step's real
        participants (prefilling/free/transit rows ride along parked; a
        transit row's pages belong to an in-flight hand-off, not a decode
        participant)."""
        return [i for i, r in enumerate(self._rows)
                if r is not None and not r.prefilling and not r.transit]

    def _admit(self):
        while True:
            row = self._free_row()
            req = None
            if row is None:
                row, req = self._try_preempt()
                if row is None:
                    return
            if req is None:
                with self._cond:
                    if self._draining or not self._pending:
                        return
                    req = self._pending.pop()
                if req is None:
                    return
            if req.cancelled:
                self._release_resume(req)
                self._finish_trace(req, "cancelled")
                serve_metrics.REQUESTS.inc(outcome="cancelled")
                continue
            if req.expired():
                self._timeout_queued(req)
                continue
            if self.active_rows == 0:
                self._maybe_reload()
            slot = self._adapter_slot(req)
            if slot is None:
                # Every live slot belongs to a DIFFERENT in-flight adapter
                # (PENROZ_LORA_MAX_LIVE of them) — requeue at the head
                # (FIFO order preserved) and stop admitting this tick;
                # a slot frees as soon as its last row retires.  This can
                # only happen with rows in flight, so the worker loop
                # keeps stepping and re-tries every boundary.
                with self._cond:
                    self._pending.push_front(req)
                return
            if req.handoff is not None:
                self._admit_handoff(row, req, slot)
                continue
            self._begin_prefill(row, req, slot)

    # -- preemption (preempt-to-prefix-cache, resume with zero recompute) ----

    def _try_preempt(self):
        """With the batch full and an ``interactive`` request queued, evict
        the lowest-priority longest-running decode row into the radix
        prefix cache and hand its slot to the interactive request
        specifically (DRR order would happily give the freed row back to
        the flood).  Returns ``(row, request)`` or ``(None, None)``."""
        if not qos.preempt_enabled() or self._prefix_cache is None:
            return None, None
        with self._cond:
            if (self._draining
                    or self._pending.class_depth("interactive") == 0):
                return None, None
        victim = self._preempt_victim()
        if victim is None:
            return None, None
        self._preempt_row(victim)
        with self._cond:
            req = self._pending.pop_class("interactive")
        return victim, req

    def _preempt_victim(self):
        """Victim row: strictly lower class than ``interactive`` (an
        interactive row is never preempted for another), decode phase only
        (a prefilling row has produced nothing a client is waiting on —
        and its partial KV is not yet a cacheable history), lowest class
        first, then longest-running (earliest admission)."""
        best = None
        best_rank = None
        for i, state in enumerate(self._rows):
            if state is None or state.prefilling:
                continue
            pri = state.req.priority
            if pri == "interactive":
                continue
            # batch outranks standard as a victim; earlier admit_t wins
            # within a class.
            rank = (0 if pri == "batch" else 1, state.admit_t)
            if best_rank is None or rank < best_rank:
                best, best_rank = i, rank
        return best

    def _preempt_row(self, row: int):
        """Evict one decode row into the radix prefix cache: its pages are
        already pool-resident, so eviction is "insert history into the
        radix tree + copy the uncached pages + free the row".  The request
        requeues at the head of its sub-queue carrying pinned resume nodes;
        the resume admission's normal prefix-match path aliases them back
        with zero recompute of the cached prefix.  Crash-safe: the
        ``qos.preempt`` fault site fires before any mutation, and a crash
        anywhere in here fails the tick → ``_alloc_state`` rebuilds KV and
        a fresh prefix cache, so no pin can outlive the state it guards."""
        faults.check("qos.preempt")
        state = self._rows[row]
        req = state.req
        t0 = time.monotonic()
        # KV valid length: a decode row has KV for len(history) - 1 tokens
        # (the newest sampled token's KV is written by the step that feeds
        # it) — insert exactly the full pages below it.
        kv_len = int(self._lengths[row])
        ns = self._prefix_ns(req)
        created = self._prefix_cache.insert(state.history, limit=kv_len,
                                            namespace=ns)
        if created:
            S = self._kv.pages_per_seq
            self._kv = self._kv.copy_pages(
                [row * S + b for b, _ in created],
                [page for _, page in created])
        # Pin the whole cached chain until the resume re-pins it — LRU
        # eviction must not recycle these pages while the request waits.
        nodes = self._prefix_cache.chain(state.history, limit=kv_len,
                                         namespace=ns)
        self._prefix_cache.pin(nodes)
        cached = len(nodes) * self._prefix_cache.page_size
        # Free the row (retire mechanics WITHOUT a terminal event — the
        # stream stays open across the preemption).
        self._rows[row] = None
        self._lengths[row] = 0
        self._last_tok[row] = 0
        self._row_adapter[row] = self._max_live
        self._release_prefix(row, state)
        self._kv = self._kv.reset_row(row)
        req.resume_history = list(state.history)
        req.resume_produced = state.produced
        req.resume_nodes = nodes
        req.preempted += 1
        # Queue wait restarts at the preempt: the resume admission's queue
        # span/histogram measure the requeue wait, not the original one
        # (the deadline stays anchored at the ORIGINAL enqueue).
        req.enqueue_t = t0
        self._preemptions += 1
        serve_metrics.PREEMPTIONS.inc()
        # A preemption IS a capacity-pressure event: the pool was too
        # small for the admitted load and someone's pages were taken.
        self._ledger.note_pressure()
        if req.trace is not None:
            req.trace.end(state.sp_prefill)
            req.trace.end(state.sp_decode, produced=state.produced)
            sp = req.trace.span("preempt", t0=t0, cached_tokens=cached,
                                produced=state.produced)
            req.trace.end(sp)
            req.trace.event("capacity_pressure", reason="preempted",
                            cached_tokens=cached)
        with self._cond:
            self._pending.push_front(req)
        log.info("Decode engine %s: preempted row %d (%s/%s, %d produced, "
                 "%d tokens cached) for a queued interactive request",
                 self.model_id, row, req.tenant, req.priority,
                 state.produced, cached)
        # The preempt path hands pages across three owners (row →
        # preempted-hold → cache); prove the handoff balanced.
        if memledger.strict():
            self._ledger.audit("preempt")

    def _release_resume(self, req: Request):
        """Drop a preempted request's resume pins (resume admission,
        deadline purge, cancellation, engine failure) — without this, a
        preempted request that never comes back would pin its pages
        forever."""
        if req.resume_nodes:
            if self._prefix_cache is not None:
                self._prefix_cache.unpin(req.resume_nodes)
            req.resume_nodes = []

    # -- adapter slots (mixed-adapter batches, models/lora.py) ---------------

    def _adapter_slot(self, req: Request):
        """Slot index for ``req``'s adapter: the base slot for plain rows,
        a live slot holding the SAME adapter generation (uid) when one
        exists, else a free/reclaimable slot (stacked pack rebuilt).
        None when all slots hold other adapters with rows in flight."""
        if req.adapter is None:
            return self._max_live
        for s, e in enumerate(self._slot_entries):
            if e is not None and e.uid == req.adapter.uid:
                return s
        in_flight = {int(self._row_adapter[i])
                     for i, r in enumerate(self._rows) if r is not None}
        for s in range(self._max_live):
            if self._slot_entries[s] is None or s not in in_flight:
                self._slot_entries[s] = req.adapter
                self._rebuild_pack()
                return s
        return None

    def _rebuild_pack(self):
        self._lora_pack = lora_mod.build_pack(
            [e.params if e is not None else None
             for e in self._slot_entries],
            [e.config if e is not None else None
             for e in self._slot_entries],
            self._max_live)

    def _prefix_ns(self, req: Request):
        """Radix prefix-cache namespace for the row: adapter rows key on
        the adapter LOAD GENERATION (entry.uid), so a retrained or
        recreated adapter can never alias KV its previous weights wrote;
        base rows share the None namespace."""
        return req.adapter.uid if req.adapter is not None else None

    # -- chunked prefill (admission state machine) ---------------------------

    def _begin_prefill(self, row: int, req: Request, slot: int | None = None):
        """Claim ``row`` for ``req`` in the PREFILLING phase: match the
        radix prefix cache (paged + ``PENROZ_PREFIX_CACHE=1``), alias the
        matched pages into the row's block table, and plan pow-2-bucketed
        chunks over the remaining suffix.  No device prefill work happens
        here — ``_prefill_tick`` interleaves it with decode steps.

        A PREEMPTED request resumes through this very path: its effective
        prompt is the full history (prompt + tokens already emitted), whose
        KV the preempt pinned into the radix tree — the prefix match below
        aliases those pages back, the final chunk reproduces the exact
        sampling position of the unpreempted step, and greedy output is
        token-identical with zero recompute of the cached prefix."""
        state = _Row(req)
        resumed = req.resume_history is not None
        if resumed:
            state.resumed = True
            state.history = list(req.resume_history)
            state.produced = req.resume_produced
        eff_prompt = state.history  # == req.prompt for fresh admissions
        self._row_adapter[row] = (slot if slot is not None
                                  else self._max_live)
        trace = req.trace
        if trace is not None:
            # Retroactive queue span (enqueue → now): its duration IS the
            # queue wait the histogram records below.
            sp = trace.span("queue", t0=req.enqueue_t)
            trace.end(sp)
            if req.adapter is not None:
                trace.event("adapter_slot", adapter_id=req.adapter.adapter_id,
                            slot=int(self._row_adapter[row]))
        if self._prefix_cache is not None:
            # Cap the usable match at len(prompt) - 1: the final chunk must
            # feed at least one real token to produce the first-sample
            # logits (a full-prompt hit would leave nothing to run).
            # Namespaced per adapter generation: a base prefix must never
            # alias an adapter's KV (or vice versa) — the pages hold
            # weight-dependent K/V.
            nodes = self._prefix_cache.match(eff_prompt,
                                             limit=len(eff_prompt) - 1,
                                             namespace=self._prefix_ns(req))
            # Promote-on-match: a hibernated session whose KV covers MORE
            # of this prompt than the radix cache does imports its blob
            # pages into fresh radix slots, then aliases like a normal hit.
            try:
                nodes = self._promote_session(state, req, eff_prompt, nodes)
            except BaseException:
                # Mid-admission failure (tier.promote fault, import error):
                # the request is already off the queue but not yet in
                # _rows — park the partly-built row so crash recovery's
                # _fail_all fails ITS waiter too instead of orphaning the
                # client on a request that no longer exists anywhere.
                self._rows[row] = state
                raise
            if nodes:
                self._prefix_cache.pin(nodes)
                state.prefix_nodes = nodes
                state.prefilled = len(nodes) * self._prefix_cache.page_size
                serve_metrics.PREFIX_HITS.inc()
            else:
                serve_metrics.PREFIX_MISSES.inc()
            if trace is not None:
                trace.event("prefix_match", matched_tokens=state.prefilled,
                            pages=len(nodes))
            # Rebuild the row's table on miss too: re-basing to the static
            # partition is one tiny host write, and it guarantees no stale
            # alias survives an abnormal retirement path.
            self._kv = self._kv.with_row_prefix(
                row, [n.page for n in nodes])
        if resumed:
            # The row's own pins now hold the pages — drop the preempt-time
            # hold and record the zero-recompute credit.
            self._resume_cached_tokens += state.prefilled
            serve_metrics.RESUME_CACHED_TOKENS.inc(state.prefilled)
            self._release_resume(req)
            req.resume_history = None
            req.resume_produced = 0
            if trace is not None:
                sp = trace.span("resume", cached_tokens=state.prefilled,
                                produced=state.produced)
                trace.end(sp)
        if getattr(self._kv, "ssm", None) is not None:
            # A recycled row's recurrent state is stale garbage — the shared
            # decode step advances every batch row, parked or not, so unlike
            # KV rows (whose stale tail the masks never attend) SSM rows
            # must be explicitly re-zeroed before the first prefill chunk.
            self._kv.ssm = self._kv.ssm.reset_row(row)
        state.chunks = _chunk_plan(len(eff_prompt) - state.prefilled,
                                   _prefill_chunk())
        self._rows[row] = state
        # Quota charges cover prefilled + emitted tokens: bill the compute
        # this admission will actually run (the radix-matched prefix costs
        # nothing, so a resume re-charges only its final chunk).
        qos.QUOTAS.charge(req.tenant,
                          len(eff_prompt) - state.prefilled)
        self._class_admissions[req.priority] += 1
        serve_metrics.CLASS_ADMISSIONS.inc(priority=req.priority)
        # Park the row's decode-step write position at the next prefill
        # position: the interleaved shared step's (discarded) K/V write for
        # this row lands exactly where the next chunk writes real data, so
        # it can never clobber prefilled content — nor an aliased shared
        # page, which only covers positions below ``prefilled``.
        self._lengths[row] = state.prefilled
        self._last_tok[row] = 0
        self._admissions += 1
        wait_ms = (time.monotonic() - req.enqueue_t) * 1000.0
        self._h_queue_wait.observe(wait_ms)
        self._h_queue_wait_cls[req.priority].observe(wait_ms)
        serve_metrics.QUEUE_WAIT_MS.observe(wait_ms)
        serve_metrics.QUEUE_WAIT_BY_CLASS.observe(wait_ms,
                                                  priority=req.priority)
        if trace is not None:
            state.sp_prefill = trace.span(
                "prefill", prompt_tokens=len(eff_prompt),
                cached_tokens=state.prefilled, chunks=len(state.chunks))

    def _promote_session(self, state: _Row, req: Request, eff_prompt,
                         nodes: list) -> list:
        """Wake a hibernated session for this admission (serve/tierstore.py).

        Content-addressed: the prompt's page fingerprints are matched
        against the tier store regardless of whether the request carries a
        ``session_id``, so a session hibernated on ANOTHER replica — or
        before an engine restart — wakes here too.  Outcomes:

        - radix already covers the session's depth → HBM-fast wake, no
          import (``penroz_tier_promotions_total{tier="hbm"}``);
        - host/disk blob → ``insert()`` fresh radix slots for the blocks
          the cache lacks and scatter the blob's pages into them
          (``import_pages``), then re-walk the chain — the caller pins
          and aliases it exactly like a plain radix hit;
        - corrupt/vanished blob → counted + dropped by the store's
          ``fetch``; the admission recomputes (never wrong tokens).

        The ``tier.promote`` fault site fires before any mutation: a
        crash mid-wake fails the tick, ``_alloc_state`` rebuilds, and the
        retried admission recomputes from scratch at greedy parity."""
        if (req.adapter is not None
                or self._prefix_cache is None
                or not isinstance(self._kv, KV.PagedKVState)
                or not tierstore.TIERS.resident_sessions()):
            return nodes
        P = self._prefix_cache.page_size
        rec, depth = tierstore.TIERS.match(
            eff_prompt, model_id=self.model_id,
            model_stamp=self._ckpt_stamp_v, page_size=P,
            quantized=bool(getattr(self._kv, "quantized", False)))
        if rec is None:
            return nodes
        if depth <= len(nodes):
            # The session's pages are still radix-resident (demoted but
            # not yet LRU-evicted, or hibernating on this very engine).
            state.session_wake = True
            tierstore.TIERS.note_promotion("hbm", "ok")
            return nodes
        if rec.tier == "hbm":
            # Hibernated on another replica whose background demotion has
            # not run yet — the pages exist only in that engine's pool.
            return nodes
        sid, tier = rec.session_id, rec.tier
        faults.check("tier.promote")
        blob = tierstore.TIERS.fetch(sid)
        if blob is None:
            return nodes
        created = self._prefix_cache.insert(eff_prompt, limit=depth * P,
                                            namespace=None)
        if created:
            self._kv = self._kv.import_pages(
                [page for _, page in created], blob,
                blob_offset=created[0][0])
        out = self._prefix_cache.chain(eff_prompt,
                                       limit=len(eff_prompt) - 1,
                                       namespace=None)
        state.session_wake = True
        self._session_promotions += 1
        tierstore.TIERS.note_promotion(
            tier, "ok" if len(out) >= depth else "partial")
        if req.trace is not None:
            req.trace.event("session_promote", session_id=sid, tier=tier,
                            imported_pages=len(created), depth_pages=depth)
        return out

    def _next_prefill_row(self):
        """FIFO over prefilling rows (earliest enqueue first) so chunk
        interleaving cannot starve an early long prompt behind later
        arrivals."""
        best = None
        for i, r in enumerate(self._rows):
            if r is None or not r.prefilling or r.transit:
                continue
            if best is None or r.req.enqueue_t \
                    < self._rows[best].req.enqueue_t:
                best = i
        return best

    def _prefill_tick(self):
        """Run prefill chunks for this step boundary: exactly one when
        decode rows are in flight (the stall bound), more while under the
        ``PENROZ_SCHED_MAX_STALL_MS`` budget; with an idle decode batch one
        chunk per loop iteration keeps admission responsive while chunks
        effectively run back-to-back."""
        if self._next_prefill_row() is None:
            return
        budget_ms = _max_stall_ms()
        stalling = bool(self._decoding_rows())
        t0 = time.monotonic()
        while True:
            row = self._next_prefill_row()
            if row is None:
                break
            self._run_prefill_chunk(row)
            if not stalling:
                break
            self._chunks_between_steps += 1
            if (time.monotonic() - t0) * 1000.0 >= budget_ms:
                break
        if stalling:
            stall_ms = (time.monotonic() - t0) * 1000.0
            self._h_chunk_stall.observe(stall_ms)
            serve_metrics.CHUNK_STALL_MS.observe(stall_ms)

    def _run_prefill_chunk(self, row: int):
        state = self._rows[row]
        req = state.req
        if req.cancelled:
            self._retire(row, notify=False, reason="cancelled")
            return
        if req.expired():
            self._deadline_timeouts += 1
            serve_metrics.DEADLINE_TIMEOUTS.inc()
            self._retire(row, notify=False, reason="timeout")
            self._deliver(req, "timeout", DeadlineExceeded(
                "inflight", "request deadline expired during prefill"))
            return
        faults.check("decode.prefill_chunk")
        size = state.chunks[state.chunk_idx]
        start = state.prefilled
        rng = jax.random.fold_in(self._rng, self._dispatch)
        self._dispatch += 1
        sp = (req.trace.span("prefill_chunk", parent=state.sp_prefill,
                             size=size, start=start)
              if req.trace is not None else None)
        # state.history is the effective prompt (the full pre-preemption
        # history for a resumed row, req.prompt otherwise) and is static
        # for the whole PREFILLING phase — tokens only append post-prefill.
        with model_mod.decode_priority(), \
                tracing.span("penroz/sched_prefill_chunk"):
            tok, self._kv = self._model.decode_prefill_chunk(
                self._kv, row, state.history[start:start + size], start, rng,
                self.temperature, self.top_k, lora=self._lora_pack,
                adapter_slot=int(self._row_adapter[row]))
        if req.trace is not None:
            req.trace.end(sp)
        state.prefilled += size
        state.chunk_idx += 1
        self._prefill_chunks += 1
        serve_metrics.PREFILL_CHUNKS.inc()
        self._lengths[row] = state.prefilled  # re-park (see _begin_prefill)
        if state.chunk_idx >= len(state.chunks):
            self._finish_prefill(row, state, tok)

    def _finish_prefill(self, row: int, state: _Row, first: int):
        """Final chunk done: its sampled token IS the request's first token
        (same logits position and program family as one-shot prefill).

        On a disaggregated prefill replica this is the hand-off seam: the
        finished row's KV pages ship to a decode replica and the row frees
        without emitting — the first token travels inside the hand-off and
        is emitted after the import, exactly once.  Rows that cannot hand
        off (single-token requests, resumed rows, export failure with no
        reachable decode replica) fall through and decode locally."""
        req = state.req
        if (self.role == "prefill" and self._handoff_sink is not None
                and req.handoff is None and req.max_new_tokens > 1
                and not state.resumed and not req.cancelled
                and isinstance(self._kv, KV.PagedKVState)):
            if self._export_handoff(row, state, first):
                return
        self._finish_prefill_local(row, state, first)

    def _finish_prefill_local(self, row: int, state: _Row, first: int):
        """Emit the first token and join the decode batch on THIS replica —
        the non-disaggregated tail of ``_finish_prefill``, also the last
        resort when a hand-off cannot leave the engine (export failed with
        no reachable decode replica, or a refused d2d hand-off whose host
        re-stage failed too)."""
        state.prefilling = False
        self._lengths[row] = state.prefilled  # == len(effective prompt)
        self._last_tok[row] = first
        ttft_ms = (time.monotonic() - state.req.enqueue_t) * 1000.0
        if not state.resumed:
            # A resumed row's first token shipped before the preempt —
            # re-observing here would double-count its TTFT.
            self._h_ttft.observe(ttft_ms)
            self._h_ttft_cls[state.req.priority].observe(ttft_ms)
            serve_metrics.TTFT_MS.observe(ttft_ms)
            serve_metrics.TTFT_BY_CLASS.observe(
                ttft_ms, priority=state.req.priority)
            if state.session_wake:
                # Hibernated-session wake: the same TTFT also lands in the
                # resume histogram so the warm-vs-cold comparison reads
                # straight off /metrics.
                self._h_resume_ttft.observe(ttft_ms)
                serve_metrics.SESSION_RESUME_TTFT_MS.observe(ttft_ms)
        trace = state.req.trace
        if trace is not None:
            trace.end(state.sp_prefill)
            state.sp_prefill = None
            state.sp_decode = trace.span("decode", ttft_ms=round(ttft_ms, 3))
        self._register_prefix(row, state)
        self._emit_token(row, state, first)

    def _register_prefix(self, row: int, state: _Row):
        """Copy the finished prompt's full pages into the reserved cache
        region and hang them on the radix tree — the next request sharing
        this prefix aliases them instead of recomputing.  Aliased blocks
        already live in the cache region (their nodes exist), so only the
        freshly prefilled suffix pages are copied."""
        if self._prefix_cache is None:
            return
        created = self._prefix_cache.insert(
            state.req.prompt, namespace=self._prefix_ns(state.req))
        if created:
            S = self._kv.pages_per_seq
            self._kv = self._kv.copy_pages(
                [row * S + b for b, _ in created],
                [page for _, page in created])

    # -- disaggregated prefill (export / hand-off / import) ------------------

    def _free_handoff_row(self, row: int, state: _Row):
        """Release a row whose request left this engine through the hand-off
        seam (export shipped, or requeued for monolithic prefill elsewhere).
        Mirrors ``_preempt_row``'s release — no terminal event is emitted;
        the request's stream stays open and finishes on the target replica."""
        self._rows[row] = None
        self._lengths[row] = 0
        self._last_tok[row] = 0
        self._row_adapter[row] = self._max_live
        self._release_prefix(row, state)
        self._kv = self._kv.reset_row(row)

    def _export_handoff(self, row: int, state: _Row, first: int) -> bool:
        """Prefill replica: ship the finished row's KV pages to a decode
        replica via ``_handoff_sink`` — device arrays over the d2d
        transport by default, the host-staged shm page blob otherwise (and
        as the in-flight fallback whenever d2d fails).  Returns True when
        the row left this engine (shipped, parked awaiting the importer's
        ack, or requeued remotely); False means the caller finishes it
        locally.

        Ordering is crash-shaped: the fault site and all export work happen
        BEFORE any engine mutation, so a failure there leaves the row
        intact and either requeues it for monolithic prefill on a decode
        replica (greedy-identical replay) or falls back to decoding right
        here."""
        t0 = time.monotonic()
        try:
            # disagg.handoff ordinal 1 = mid-export crash (chaos matrix) —
            # the hand-off seam itself, upstream of the transport choice.
            faults.check("disagg.handoff")
        except Exception as e:
            self._disagg_handoff_failures += 1
            serve_metrics.DISAGG_HANDOFFS.inc(
                outcome="export_failed", transport=_disagg_transport())
            state.req.handoff = None
            log.warning("engine %s[%d]: hand-off export failed (%s); "
                        "falling back to monolithic prefill",
                        self.model_id, self.replica, e)
            if self._requeue_monolithic(row, state):
                return True
            return False
        if _disagg_transport() == "d2d":
            if self._export_handoff_d2d(row, state, first, t0):
                return True
            # d2d failed before anything shipped: the row is intact, so the
            # SAME hand-off re-stages through the host blob codec (the
            # crash-safe fallback transport) — still greedy-identical.
        return self._export_handoff_host(row, state, first, t0)

    def _export_handoff_host(self, row: int, state: _Row, first: int,
                             t0: float) -> bool:
        """Host-staged transport: serialize the row's pages as a CRC-checked
        shm page blob and hand the blob id to a decode replica.  The row
        frees as soon as the sink accepts — the staged blob IS the
        crash-safe copy, so there is nothing to ack."""
        req = state.req
        blob_id = (f"{self.model_id}-{self.replica}-{id(req):x}"
                   f"-{self._dispatch}")
        try:
            if self._has_ssm:
                # ssm.handoff ordinal: mid-export crash with a recurrent
                # state plane in the blob (chaos matrix).
                faults.check("ssm.handoff")
            kv_len = int(state.prefilled)
            blob = self._kv.export_row_pages(row, kv_len)
            blob["first_token"] = int(first)
            checkpoint.save_page_blob(blob_id, blob)
        except Exception as e:
            self._disagg_handoff_failures += 1
            serve_metrics.DISAGG_HANDOFFS.inc(outcome="export_failed",
                                              transport="host")
            checkpoint.delete_page_blob(blob_id)
            req.handoff = None
            log.warning("engine %s[%d]: hand-off export failed (%s); "
                        "falling back to monolithic prefill",
                        self.model_id, self.replica, e)
            if self._requeue_monolithic(row, state):
                return True
            return False
        # Local prefix registration first: the exported prompt's pages feed
        # THIS replica's radix tree, so a repeat of the prompt prefills warm
        # here regardless of where it decodes.
        self._register_prefix(row, state)
        req.handoff = {"transport": "host", "blob_id": blob_id,
                       "kv_len": kv_len, "first_token": int(first),
                       "t0": t0}
        try:
            self._handoff_sink(req)
        except Exception as e:
            checkpoint.delete_page_blob(blob_id)
            req.handoff = None
            self._disagg_handoff_failures += 1
            serve_metrics.DISAGG_HANDOFFS.inc(outcome="export_failed",
                                              transport="host")
            log.warning("engine %s[%d]: hand-off placement failed (%s); "
                        "decoding locally", self.model_id, self.replica, e)
            return False
        self._disagg_exports += 1
        serve_metrics.DISAGG_HANDOFF_BYTES.observe(
            checkpoint.page_blob_nbytes(blob))
        trace = req.trace
        if trace is not None:
            trace.end(state.sp_prefill)
            state.sp_prefill = None
            trace.event("handoff_export", blob_id=blob_id, kv_len=kv_len,
                        replica=self.replica, transport="host")
        self._free_handoff_row(row, state)
        self._ledger.audit("disagg.export")
        return True

    def _export_handoff_d2d(self, row: int, state: _Row, first: int,
                            t0: float) -> bool:
        """d2d transport: gather the row's page planes as DEVICE arrays and
        hand them to the importer in-process — no host serialize, no CRC,
        no shm staging on the fast path.  On success the row does NOT free:
        it parks with its pages under the ledger's ``transit`` state until
        the importer acks (free-after-ack) — the source copy is the retry
        capital, so a refused import re-stages the same hand-off host-side,
        still greedy-identical because nothing was emitted.  Returns False
        with the row untouched when the transport fails before the sink."""
        req = state.req
        try:
            # disagg.d2d exporter-side ordinal (one per d2d hand-off; the
            # importer-side check in _admit_handoff is the other).
            faults.check("disagg.d2d")
            if self._has_ssm:
                faults.check("ssm.handoff")
            kv_len = int(state.prefilled)
            blob = self._kv.export_row_pages(row, kv_len, device=True)
            blob["first_token"] = int(first)
        except Exception as e:
            self._disagg_handoff_failures += 1
            serve_metrics.DISAGG_HANDOFFS.inc(outcome="export_failed",
                                              transport="d2d")
            log.warning("engine %s[%d]: d2d hand-off export failed (%s); "
                        "re-staging through the host blob codec",
                        self.model_id, self.replica, e)
            return False
        self._register_prefix(row, state)
        req.handoff = {"transport": "d2d", "planes": blob, "kv_len": kv_len,
                       "first_token": int(first), "t0": t0,
                       "ack": self._make_ack(row)}
        try:
            self._handoff_sink(req)
        except Exception as e:
            req.handoff = None
            self._disagg_handoff_failures += 1
            serve_metrics.DISAGG_HANDOFFS.inc(outcome="export_failed",
                                              transport="d2d")
            log.warning("engine %s[%d]: d2d hand-off placement failed "
                        "(%s); re-staging through the host blob codec",
                        self.model_id, self.replica, e)
            return False
        self._disagg_exports += 1
        serve_metrics.DISAGG_HANDOFF_BYTES.observe(
            checkpoint.page_blob_nbytes(blob))
        trace = req.trace
        if trace is not None:
            trace.end(state.sp_prefill)
            state.sp_prefill = None
            trace.event("handoff_export", kv_len=kv_len,
                        replica=self.replica, transport="d2d")
        # Free-after-ack: the pages stay owned (ledger state ``transit``)
        # until the importer confirms the scatter landed.
        with self._cond:
            state.transit = True
            self._transit_rows[row] = {"state": state, "first": int(first),
                                       "t0": t0, "t": time.monotonic()}
        return True

    def _make_ack(self, row: int):
        """Importer-side callback for a d2d hand-off: records the verdict
        and wakes this (exporting) engine's worker, which frees the parked
        source row (ok) or re-stages the hand-off host-side (refused) at
        its next loop boundary.  Called from the importing engine's worker
        thread; takes only this engine's lock, briefly."""
        def ack(ok: bool):
            with self._cond:
                self._acks.append((row, bool(ok)))
                self._cond.notify_all()
        return ack

    def _ack_overdue(self) -> bool:
        deadline = _ack_timeout_s()
        now = time.monotonic()
        return any(now - e["t"] > deadline
                   for e in self._transit_rows.values())

    def _drain_acks(self):
        """Exporter side of the d2d free-after-ack protocol, run at loop
        boundaries (the only thread that may mutate rows): an acked row
        frees; a refused one re-stages the SAME hand-off through the host
        blob codec from the intact source pages (greedy parity — nothing
        was emitted); an overdue one frees without touching the stream,
        because the importer owns the request by then and has already
        terminated it one way or the other."""
        if not self._transit_rows and not self._acks:
            return
        with self._cond:
            acks, self._acks = self._acks, []
        for row, ok in acks:
            entry = self._transit_rows.pop(row, None)
            if entry is None or self._rows[row] is not entry["state"]:
                continue
            state = entry["state"]
            state.transit = False
            if ok:
                self._free_handoff_row(row, state)
                self._ledger.audit("disagg.export")
                continue
            # Failure already counted importer-side (import_failed/d2d);
            # this side just re-sends from the intact source row.
            log.warning("engine %s[%d]: d2d import refused for row %d; "
                        "re-staging through the host blob codec",
                        self.model_id, self.replica, row)
            if not self._export_handoff_host(row, state, entry["first"],
                                             entry["t0"]):
                # No decode replica reachable: decode it right here.
                self._finish_prefill_local(row, state, entry["first"])
        deadline = _ack_timeout_s()
        now = time.monotonic()
        for row in [r for r, e in self._transit_rows.items()
                    if now - e["t"] > deadline]:
            entry = self._transit_rows.pop(row)
            state = entry["state"]
            if self._rows[row] is not state:
                continue
            state.transit = False
            self._disagg_handoff_failures += 1
            serve_metrics.DISAGG_HANDOFFS.inc(outcome="ack_timeout",
                                              transport="d2d")
            log.warning("engine %s[%d]: d2d hand-off ack overdue for row "
                        "%d; releasing the parked source pages",
                        self.model_id, self.replica, row)
            self._free_handoff_row(row, state)
            self._ledger.audit("disagg.export")

    def request_role(self, role: str):
        """Ask the worker to flip this replica's disaggregation role at its
        next drain boundary (elastic rebalancing, serve/router.py).  The
        flip waits for in-flight d2d exports to be acked; queued and
        in-flight requests are untouched — only where FUTURE finished
        prefills go changes, so a flipping prefill replica finishes its
        rows locally and a flipping decode replica keeps decoding."""
        if role not in ("prefill", "decode"):
            raise ValueError(f"unknown disaggregation role {role!r}")
        with self._cond:
            if role == self.role:
                self._requested_role = None
                return
            self._requested_role = role
            self._cond.notify_all()

    def _maybe_flip_role(self):
        """Apply a pending elastic role flip at a drain boundary: every
        in-flight d2d export acked first, fault site BEFORE the mutation so
        an injected ``disagg.rebalance`` crash cancels cleanly (role
        registry consistent, strict ledger audit green) and the flip
        retries at the next boundary."""
        target = self._requested_role
        if target is None:
            return
        if target == self.role:
            self._requested_role = None
            return
        if self._transit_rows:
            return
        faults.check("disagg.rebalance")
        with self._cond:
            self.role = target
            self._requested_role = None
        self._disagg_role_changes += 1
        serve_metrics.DISAGG_ROLE_CHANGES.inc()
        self._ledger.audit("disagg.rebalance")
        log.info("engine %s[%d]: role -> %s (elastic rebalance)",
                 self.model_id, self.replica, target)

    def _requeue_monolithic(self, row: int, state: _Row) -> bool:
        """Export failed before anything shipped: push the request back
        through the router so a decode replica runs monolithic prefill from
        scratch (greedy-identical — nothing was emitted).  Returns True when
        the requeue landed; False keeps the row local."""
        sink = self._handoff_sink
        req = state.req
        req.handoff = None
        if sink is None:
            return False
        try:
            sink(req)
        except Exception:
            return False
        trace = req.trace
        if trace is not None:
            trace.end(state.sp_prefill)
            state.sp_prefill = None
            trace.event("handoff_fallback", replica=self.replica)
        self._free_handoff_row(row, state)
        self._ledger.audit("disagg.fallback")
        return True

    def _abandon_import_row(self, row: int) -> None:
        """Return a half-imported hand-off row to the pool (import failed
        before anything was emitted)."""
        self._rows[row] = None
        self._lengths[row] = 0
        self._last_tok[row] = 0
        self._row_adapter[row] = self._max_live
        self._kv = self._kv.reset_row(row)

    def _admit_handoff(self, row: int, req: Request, slot: int | None):
        """Decode replica: admit a hand-off arrival directly in the DECODE
        phase — import the staged page blob into the row's block table, emit
        the first token the prefill replica sampled, and join the shared
        decode step.  Import failure falls back to monolithic prefill on
        THIS replica (nothing was emitted yet, so greedy output is
        unchanged).  While the import is in flight the row is marked
        ``transit`` so memledger snapshots attribute its pages honestly."""
        h = req.handoff
        req.handoff = None
        transport = h.get("transport", "host")
        state = _Row(req)
        state.transit = True
        state.prefilling = False
        self._row_adapter[row] = (slot if slot is not None
                                  else self._max_live)
        trace = req.trace
        if trace is not None:
            sp = trace.span("queue", t0=req.enqueue_t)
            trace.end(sp)
        self._rows[row] = state
        self._lengths[row] = 0
        try:
            # disagg.handoff ordinal 2 = mid-import crash (chaos matrix).
            faults.check("disagg.handoff")
            if not isinstance(self._kv, KV.PagedKVState):
                raise RuntimeError("hand-off import needs a paged KV pool")
            kv_len = int(h["kv_len"])
            # lengths first: a concurrent ledger snapshot between here and
            # the import's completion sees the pages under ``transit``.
            self._lengths[row] = kv_len
            state.prefilled = kv_len
            if transport == "d2d":
                try:
                    # disagg.d2d importer-side ordinal: transport failure
                    # mid-device_put refuses the hand-off back to the
                    # exporter, which re-stages through the host codec —
                    # generic disagg.handoff failures (the outer except)
                    # fall back to monolithic prefill instead.
                    faults.check("disagg.d2d")
                    self._kv = self._kv.import_row_pages(row, h["planes"])
                except Exception as e:
                    self._disagg_handoff_failures += 1
                    serve_metrics.DISAGG_HANDOFFS.inc(
                        outcome="import_failed", transport="d2d")
                    self._abandon_import_row(row)
                    if trace is not None:
                        trace.event("handoff_import_failed", reason=str(e),
                                    transport="d2d")
                    self._ledger.audit("disagg.import_failed")
                    log.warning("engine %s[%d]: d2d hand-off import failed "
                                "(%s); refusing back to the exporter",
                                self.model_id, self.replica, e)
                    if h.get("ack") is not None:
                        # Exporter still holds the source pages (free-
                        # after-ack): the refusal makes it re-send host-
                        # staged — greedy parity, nothing was emitted here.
                        h["ack"](False)
                    return
            else:
                blob = checkpoint.load_page_blob(h["blob_id"])
                self._kv = self._kv.import_row_pages(row, blob)
            first = int(h["first_token"])
        except Exception as e:
            self._disagg_handoff_failures += 1
            serve_metrics.DISAGG_HANDOFFS.inc(outcome="import_failed",
                                              transport=transport)
            if transport == "host":
                checkpoint.delete_page_blob(h["blob_id"])
            self._abandon_import_row(row)
            if trace is not None:
                trace.event("handoff_import_failed", reason=str(e),
                            transport=transport)
            self._ledger.audit("disagg.import_failed")
            if transport == "d2d" and h.get("ack") is not None:
                # This replica keeps the request (monolithic re-prefill
                # below), so the exporter's parked source pages are dead
                # weight — ack success to release them.
                h["ack"](True)
            log.warning("engine %s[%d]: hand-off import failed (%s); "
                        "re-prefilling monolithically",
                        self.model_id, self.replica, e)
            self._begin_prefill(row, req, slot)
            return
        if transport == "host":
            checkpoint.delete_page_blob(h["blob_id"])
        state.transit = False
        self._last_tok[row] = first
        self._disagg_imports += 1
        self._admissions += 1
        self._class_admissions[req.priority] += 1
        serve_metrics.CLASS_ADMISSIONS.inc(priority=req.priority)
        # No quota charge here: the prefill replica admitted and charged the
        # prompt; decode tokens bill per-token in _emit_token as usual.
        wait_ms = (time.monotonic() - req.enqueue_t) * 1000.0
        self._h_queue_wait.observe(wait_ms)
        self._h_queue_wait_cls[req.priority].observe(wait_ms)
        serve_metrics.QUEUE_WAIT_MS.observe(wait_ms)
        serve_metrics.QUEUE_WAIT_BY_CLASS.observe(wait_ms,
                                                  priority=req.priority)
        # TTFT anchored at the ORIGINAL enqueue — the hand-off latency is
        # part of the first token's wait, so it is not hidden.
        ttft_ms = (time.monotonic() - req.enqueue_t) * 1000.0
        self._h_ttft.observe(ttft_ms)
        self._h_ttft_cls[req.priority].observe(ttft_ms)
        serve_metrics.TTFT_MS.observe(ttft_ms)
        serve_metrics.TTFT_BY_CLASS.observe(ttft_ms, priority=req.priority)
        handoff_ms = (time.monotonic() - h["t0"]) * 1000.0
        self._h_handoff.observe(handoff_ms)
        serve_metrics.DISAGG_HANDOFF_MS.observe(handoff_ms)
        serve_metrics.DISAGG_HANDOFFS.inc(outcome="ok", transport=transport)
        if transport == "d2d" and h.get("ack") is not None:
            # Scatter landed: release the exporter's parked source pages.
            h["ack"](True)
        if trace is not None:
            trace.event("handoff_import", kv_len=int(h["kv_len"]),
                        handoff_ms=round(handoff_ms, 3),
                        transport=transport)
            state.sp_decode = trace.span("decode", ttft_ms=round(ttft_ms, 3))
        # The imported prompt's pages feed this replica's radix tree — the
        # router's fingerprint ledger points here now, so make it true.
        self._register_prefix(row, state)
        self._emit_token(row, state, first)
        self._ledger.audit("disagg.import")

    def _step(self):
        """One decode tick: a multi-token verify step for every row whose
        drafter proposed candidates (spec decode), then ONE shared batched
        step for the rest.  Counts as a single decode step either way —
        ``tokens_per_decode_step`` is the speculation win.  Returns the
        tick composition ``(verify_rows, shared_rows, emitted)`` for the
        tick timeline."""
        faults.check("decode.step")
        t0 = time.monotonic()
        self._max_chunks_between_steps = max(
            self._max_chunks_between_steps, self._chunks_between_steps)
        self._chunks_between_steps = 0
        active = self._decoding_rows()
        emitted = 0
        plan = self._plan_drafts(active)
        for row, draft in plan:
            emitted += self._verify_row(row, draft)
        drafted = {row for row, _ in plan}
        # Rows without a draft (or with spec off) run the plain shared
        # step; verified rows ride along parked — their discarded write
        # lands at their next write position and is always overwritten.
        normal = [i for i in self._decoding_rows() if i not in drafted]
        if normal:
            emitted += self._shared_step(normal)
        now = time.monotonic()
        self._decode_steps += 1
        self._decode_tokens += emitted
        serve_metrics.DECODE_TOKENS.inc(emitted)
        self._decode_time_s += now - t0
        self._occupancy_sum += len(active) / self.capacity
        self._token_window.append((now, emitted))
        while (self._token_window
               and now - self._token_window[0][0] > _TPS_WINDOW_S):
            self._token_window.popleft()
        return len(plan), len(normal), emitted

    def _shared_step(self, rows: list[int]) -> int:
        """The pre-speculation hot loop: one batched decode+sample step
        across every row, emitting for ``rows``.  Returns tokens emitted.

        The sampler key advance (``fold_in(rng, dispatch)``) happens
        INSIDE the jitted step — the host passes the unchanged base key
        plus the dispatch ordinal instead of launching a fold dispatch
        per token (bit-identical key, so seeded non-greedy output is
        unchanged — tested)."""
        if self._has_ssm:
            faults.check("ssm.scan")
        dispatch = self._dispatch
        self._dispatch += 1
        t0 = time.monotonic()
        with model_mod.decode_priority(), tracing.span("penroz/sched_step"):
            toks, self._kv = self._model.decode_step_batched(
                self._kv, self._last_tok[:, None], self._lengths, self._rng,
                self.temperature, self.top_k, lora=self._lora_pack,
                row_adapter=self._row_adapter, dispatch=dispatch)
            arr = np.asarray(toks)
        t1 = time.monotonic()
        emitted = 0
        for i in rows:
            state = self._rows[i]
            if state.req.trace is not None:
                sp = state.req.trace.span("decode_step",
                                          t0=t0, parent=state.sp_decode)
                state.req.trace.end(sp, t1=t1)
            self._lengths[i] += 1
            tok = int(arr[i])
            self._last_tok[i] = tok
            emitted += 1
            self._emit_token(i, state, tok)
        self._record_dispatch(emitted)
        return emitted

    # -- compiled multi-step decode (PENROZ_SCHED_SUPERSTEP) -----------------

    def _record_dispatch(self, emitted: int):
        """One decode-path device round trip (shared step / verify step /
        fused superstep) and the tokens it emitted."""
        self._dispatches += 1
        self._h_tokens_per_dispatch.observe(float(emitted))
        serve_metrics.DISPATCHES.inc()
        serve_metrics.TOKENS_PER_DISPATCH.observe(float(emitted))

    def _plan_superstep(self) -> int:
        """Fused decode steps for this tick's dispatch.

        Superstep > 1 only when the host provably has nothing to do at the
        intermediate step boundaries it would skip: no prefilling rows
        (chunk interleaving is a per-boundary stall contract), no queued
        admissions (a newcomer must not wait N tokens for a free slot it
        could take now), and no spec-decode drafts (verify is a per-row
        multi-token program with its own dispatch and rollback).  Any of
        those fall back to the legacy n=1 tick, so PR 2/4 interleaving
        semantics are preserved verbatim.  Deadlines/cancellation do NOT
        force n=1 — they are observed at the superstep boundary, up to N
        tokens late (the documented PENROZ_SCHED_SUPERSTEP granularity
        trade).  The env value is clamped to the largest per-row token
        need and bucketed down to a power of two, so the compiled program
        set stays {2^k ≤ PENROZ_SCHED_SUPERSTEP}."""
        n = _superstep_max()
        if n <= 1:
            return 1
        if self._next_prefill_row() is not None:
            return 1
        with self._cond:
            if self._pending:
                return 1
        rows = self._decoding_rows()
        if self._spec_on() and self._plan_drafts(rows):
            return 1
        need = 1
        for i in rows:
            state = self._rows[i]
            need = max(need,
                       min(state.req.max_new_tokens - state.produced,
                           self.block_size - int(self._lengths[i])))
        return bucketing.clamp_pow2_floor(need, hi=n)

    def _superstep(self, n: int) -> tuple[int, int]:
        """Dispatch ONE fused n-step decode program
        (``NeuralNetworkModel.decode_superstep``) and replay its token
        block through the normal per-token retirement path at the
        boundary.

        On-device, each fused step samples per row, folds the RNG key,
        advances only active rows' lengths, and drops rows from the
        active mask on stop-token / budget / cache-full — finished rows
        compute-but-discard, exactly like parked padded rows.  The host
        syncs ONCE per block: it replays ``(toks, emit)`` step-major
        through ``_emit_token``, whose stop/max bookkeeping retires each
        row on exactly the token the device mask stopped at (host and
        device run the same update rule on the same inputs).  Host-only
        terminal conditions — deadline expiry, client cancellation — are
        observed here at the boundary, so a row can overshoot its
        deadline by up to n tokens of device work (never by delivered
        tokens: ``_emit_token`` retires on the first replayed token once
        expired).  Counts as n decode steps (``tokens_per_decode_step``
        keeps measuring speculation, not fusing) and ONE dispatch
        (``tokens_per_dispatch`` ≈ n is this feature's win).  Returns
        ``(rows_in_step, tokens_emitted)``.
        """
        faults.check("decode.step")
        t0 = time.monotonic()
        self._max_chunks_between_steps = max(
            self._max_chunks_between_steps, self._chunks_between_steps)
        self._chunks_between_steps = 0
        rows = self._decoding_rows()
        states = {i: self._rows[i] for i in rows}
        active = np.zeros(self.capacity, bool)
        stop = np.full(self.capacity, -1, np.int32)
        remaining = np.zeros(self.capacity, np.int32)
        for i in rows:
            req = states[i].req
            active[i] = True
            stop[i] = -1 if req.stop_token is None else int(req.stop_token)
            remaining[i] = req.max_new_tokens - states[i].produced
        dispatch = self._dispatch
        # n dispatch ordinals, one per fused step: the key sequence is
        # identical to n single-step dispatches, so greedy AND seeded
        # non-greedy outputs are invariant under the superstep size.
        self._dispatch += n
        with model_mod.decode_priority(), \
                tracing.span("penroz/sched_superstep"):
            toks, emit, lens, self._kv = self._model.decode_superstep(
                self._kv, self._last_tok[:, None], self._lengths, active,
                stop, remaining, self._rng, dispatch, n,
                self.temperature, self.top_k, lora=self._lora_pack,
                row_adapter=self._row_adapter)
            toks = np.asarray(toks)
            emit = np.asarray(emit)
        t1 = time.monotonic()
        for i in rows:
            state = states[i]
            if state.req.trace is not None:
                sp = state.req.trace.span("decode_step", t0=t0,
                                          parent=state.sp_decode,
                                          superstep=n)
                state.req.trace.end(sp, t1=t1)
        emitted = 0
        for s in range(n):
            for i in rows:
                # A row the host retired mid-replay (stop/max on an earlier
                # token, deadline, cancel) is skipped for the rest of the
                # block — `is not states[i]` covers retirement AND slot
                # recycling.
                if not emit[s, i] or self._rows[i] is not states[i]:
                    continue
                self._lengths[i] += 1
                tok = int(toks[s, i])
                self._last_tok[i] = tok
                emitted += 1
                self._emit_token(i, states[i], tok)
        # Surviving rows' host lengths must agree with the device scan's —
        # drift here means the emit mask and KV write positions diverged.
        lens = np.asarray(lens)
        for i in rows:
            if self._rows[i] is states[i]:
                assert int(self._lengths[i]) == int(lens[i]), (
                    f"superstep length drift on row {i}: host "
                    f"{int(self._lengths[i])} != device {int(lens[i])}")
        now = time.monotonic()
        self._decode_steps += n
        self._decode_tokens += emitted
        serve_metrics.DECODE_TOKENS.inc(emitted)
        self._decode_time_s += now - t0
        self._occupancy_sum += n * len(rows) / self.capacity
        self._token_window.append((now, emitted))
        while (self._token_window
               and now - self._token_window[0][0] > _TPS_WINDOW_S):
            self._token_window.popleft()
        self._record_dispatch(emitted)
        return len(rows), emitted

    # -- speculative decoding (PENROZ_SPEC_DECODE=1) -------------------------

    def _spec_on(self) -> bool:
        """Speculative decoding applies to greedy engines everywhere, and
        to SAMPLING engines on the unified ragged path: its non-greedy
        sampler draws with positional keys (one deterministic draw per
        (row, position) — models/model.py::_sample_packed), so verifying
        a point-mass prompt-lookup draft by longest matching prefix IS
        exact rejection sampling (serve/spec_decode.py) and the emitted
        stream is token-identical to spec-off.  The legacy phased path
        still samples per-dispatch and keeps the greedy-only bypass."""
        return spec_decode.enabled() and (self.greedy or self._unified())

    def _plan_drafts(self, rows: list[int]) -> list[tuple[int, list[int]]]:
        """(row, draft) pairs for this tick's verify steps.  The per-row
        draft is capped so the verify step can neither write KV past
        block_size nor draft beyond the request's remaining budget (a
        draft longer than remaining-1 buys nothing: the bonus token
        already covers the last position)."""
        if not rows or not self._spec_on():
            return []
        k, n = spec_decode.draft_k(), spec_decode.ngram()
        plan = []
        for i in rows:
            state = self._rows[i]
            cap = min(k,
                      state.req.max_new_tokens - state.produced - 1,
                      self.block_size - 1 - int(self._lengths[i]))
            if cap < 1:
                continue
            draft = spec_decode.propose(state.history, cap, n)
            if draft:
                plan.append((i, draft))
        return plan

    def _verify_row(self, row: int, draft: list[int]) -> int:
        """Multi-token verify step for one row: one forward over the K+1
        candidate positions (last token + K drafted), emit the longest
        greedy-matching prefix plus the model's bonus token, and roll the
        row's KV back past the rejected positions.  Returns tokens
        emitted (1..K+1; a fully rejected draft still yields the bonus
        token, so a verify step never emits less than a plain step)."""
        faults.check("decode.verify")
        state = self._rows[row]
        start = int(self._lengths[row])
        tokens = [int(self._last_tok[row])] + [int(t) for t in draft]
        rng = jax.random.fold_in(self._rng, self._dispatch)
        self._dispatch += 1
        sp = (state.req.trace.span("verify", parent=state.sp_decode,
                                   drafted=len(draft))
              if state.req.trace is not None else None)
        with model_mod.decode_priority(), \
                tracing.span("penroz/sched_verify"):
            out, self._kv = self._model.decode_verify_row(
                self._kv, row, tokens, start, rng, self.temperature,
                self.top_k, lora=self._lora_pack,
                adapter_slot=int(self._row_adapter[row]))
        accepted = spec_decode.accept_length(draft, out)
        if state.req.trace is not None:
            state.req.trace.end(sp, accepted=accepted,
                                rollback_to=start + accepted + 1)
        self._spec_verify_steps += 1
        self._spec_drafted_tokens += len(draft)
        self._spec_accepted_tokens += accepted
        serve_metrics.SPEC_DRAFTED.inc(len(draft))
        serve_metrics.SPEC_ACCEPTED.inc(accepted)
        # The verify wrote K+1 fresh KV positions, but only the first
        # accepted+1 were fed the tokens greedy decoding would feed —
        # rewind past the rest (the bonus token's own KV is written by
        # the NEXT step that feeds it, exactly like the plain path).
        new_len = start + accepted + 1
        self._kv = self._kv.rollback_row(row, new_len)
        self._lengths[row] = new_len
        emitted = 0
        for tok in out[:accepted + 1]:
            self._last_tok[row] = tok
            emitted += 1
            self._emit_token(row, state, tok)
            if self._rows[row] is not state:
                break   # retired mid-acceptance (stop token / budget /
                # deadline / cancel): the remaining accepted tokens are
                # discarded, matching the plain path's stop exactly.
        self._record_dispatch(emitted)
        return emitted

    def _emit_token(self, row: int, state: _Row, tok: int):
        state.produced += 1
        state.history.append(tok)
        now = time.monotonic()
        if state.last_emit_t is not None:
            itl_ms = (now - state.last_emit_t) * 1000.0
            self._h_itl.observe(itl_ms)
            serve_metrics.ITL_MS.observe(itl_ms)
        state.last_emit_t = now
        if state.req.adapter is not None:
            aid = state.req.adapter.adapter_id
            self._adapter_tokens[aid] = self._adapter_tokens.get(aid, 0) + 1
            serve_metrics.LORA_TOKENS.inc(adapter_id=aid)
        tenant = state.req.tenant
        self._tenant_tokens[tenant] = self._tenant_tokens.get(tenant, 0) + 1
        serve_metrics.TENANT_TOKENS.inc(tenant=tenant)
        qos.QUOTAS.charge(tenant, 1)
        self._deliver(state.req, "token", tok)
        req = state.req
        if req.cancelled:
            self._retire(row, notify=False, reason="cancelled")
            return
        if req.stop_token is not None and tok == req.stop_token:
            self._retire(row, reason="stop_token")
            return
        if state.produced >= req.max_new_tokens:
            self._retire(row, reason="max_new_tokens")
            return
        if req.expired():
            # Deadline passed mid-generation: retire at this step boundary
            # and end the stream with a timeout event (tokens so far were
            # already delivered).
            self._deadline_timeouts += 1
            serve_metrics.DEADLINE_TIMEOUTS.inc()
            self._retire(row, notify=False, reason="timeout")
            self._deliver(req, "timeout", DeadlineExceeded(
                "inflight", f"request deadline expired after "
                f"{state.produced} generated token(s)"))
            return
        if self._lengths[row] >= self.block_size:
            # Defensive: eligibility admits only prompt+max_new <= block,
            # so this is a real pool-capacity truncation — count it.
            dropped = req.max_new_tokens - state.produced
            KV.record_pool_drop(
                dropped,
                context=f"scheduler row hit block_size={self.block_size}")
            self._ledger.note_pool_drop(dropped)
            if req.trace is not None:
                req.trace.event("capacity_pressure", reason="pool_capacity",
                                dropped_tokens=dropped)
            self._retire(row, reason="pool_capacity")

    # -- session hibernation (KV tiering, serve/tierstore.py) ---------------

    _HIBERNATE_REASONS = ("stop_token", "max_new_tokens", "pool_capacity")

    def _maybe_hibernate(self, row: int, state, reason: str):
        """At retirement, park a session-tagged request's full prompt+
        generated KV in the radix cache and register it with the tier
        store.  The pages stay pinned under ``_hib_holds`` until the
        worker-loop demotion pass exports them to the host tier — the
        retire hot path never serializes KV.  Mirrors ``_preempt_row``:
        insert + copy_pages + chain + pin, all while the row's pool pages
        are still live."""
        if state is None:
            return
        req = state.req
        sid = req.session_id
        if (sid is None or reason not in self._HIBERNATE_REASONS
                or req.adapter is not None
                or self._prefix_cache is None
                or not isinstance(self._kv, KV.PagedKVState)):
            return
        P = self._prefix_cache.page_size
        pages = int(self._lengths[row]) // P
        if pages <= 0:
            return
        kv_len = pages * P
        created = self._prefix_cache.insert(state.history, limit=kv_len,
                                            namespace=None)
        if created:
            S = self._kv.pages_per_seq
            self._kv = self._kv.copy_pages(
                [row * S + b for b, _ in created],
                [page for _, page in created])
        nodes = self._prefix_cache.chain(state.history, limit=kv_len,
                                         namespace=None)
        if len(nodes) * P < kv_len:
            # Radix allocation exhausted mid-insert: a partial blob cannot
            # resume correctly, so skip hibernation (the cached prefix
            # remains a plain radix entry).
            return
        ok = tierstore.TIERS.register(
            sid, tenant=req.tenant, model_id=self.model_id,
            model_stamp=self._ckpt_stamp_v,
            tokens=tuple(state.history[:kv_len]), kv_len=kv_len,
            page_size=P,
            quantized=bool(getattr(self._kv, "quantized", False)),
            nbytes=kv_len * self._kv._row_bytes(),
            owner=id(self), replica=self.replica)
        if not ok:
            # Tenant tier quota refused the session — nothing was pinned
            # on its behalf, the radix entry just ages out by LRU.
            return
        # A re-registered session id replaces the old record; tierstore
        # drops it, and the demotion pass below releases any stale hold.
        old = self._hib_holds.pop(sid, None)
        if old is not None:
            self._prefix_cache.unpin(old["nodes"])
        self._prefix_cache.pin(nodes)
        self._hib_holds[sid] = {"nodes": nodes, "kv_len": kv_len}
        self._hib_pending.append(sid)
        self._sessions_hibernated += 1
        if req.trace is not None:
            req.trace.event("session_hibernate", session_id=sid,
                            kv_len=kv_len, pages=pages)
        with self._cond:
            self._cond.notify_all()

    def _process_demotions(self):
        """Worker-loop tail: spill one pending hibernated session per tick
        from HBM to the host tier (export happens here, off the admission/
        decode hot path).  The radix copy stays resident and evictable —
        an early resume is an HBM-fast wake; LRU pressure reclaims it
        naturally once unpinned.  Crash-safe: ``tier.demote`` fires before
        any mutation and a crash fails the tick → ``_alloc_state`` clears
        holds and drops this engine's hbm-tier records."""
        if not self._hib_pending:
            return
        sid = self._hib_pending.popleft()
        hold = self._hib_holds.pop(sid, None)
        if hold is None:
            return
        rec = tierstore.TIERS.get(sid)
        if rec is None or rec.tier != "hbm" or rec.owner != id(self):
            # Deleted via the API (or replaced) while awaiting demotion:
            # just release the pin, the pages age out of the radix cache.
            self._prefix_cache.unpin(hold["nodes"])
            return
        faults.check("tier.demote")
        blob = self._kv.export_pages([n.page for n in hold["nodes"]],
                                     hold["kv_len"])
        tierstore.TIERS.demote_to_host(sid, blob)
        self._prefix_cache.unpin(hold["nodes"])
        # Demotion hands pages from a pinned hold back to plain cache
        # residency while a host copy appears — prove the books balanced.
        if memledger.strict():
            self._ledger.audit("tier.demote")

    def _drop_hib_holds(self):
        """Release every pending hibernation pin (reload/shutdown): the
        prefix cache is about to be cleared or abandoned, so no hold may
        outlive it.  HBM-tier records die with their owner."""
        if self._prefix_cache is not None:
            for hold in self._hib_holds.values():
                try:
                    self._prefix_cache.unpin(hold["nodes"])
                except Exception:  # noqa: BLE001 — teardown must not throw
                    log.exception("Failed to unpin hibernation hold")
        self._hib_holds = {}
        self._hib_pending.clear()

    def _retire(self, row: int, notify: bool = True,
                reason: str = "completed"):
        state = self._rows[row]
        if state is not None:
            self._maybe_hibernate(row, state, reason)
        self._rows[row] = None
        self._lengths[row] = 0
        self._last_tok[row] = 0
        self._row_adapter[row] = self._max_live
        self._release_prefix(row, state)
        self._kv = self._kv.reset_row(row)
        self._completed += 1
        if state is not None and state.req.trace is not None:
            trace = state.req.trace
            trace.end(state.sp_prefill)
            trace.end(state.sp_decode, produced=state.produced)
            trace.finish(reason)
        serve_metrics.REQUESTS.inc(
            outcome=("completed" if reason in ("stop_token",
                                               "max_new_tokens",
                                               "pool_capacity")
                     else reason))
        if notify and state is not None:
            # A successfully completed request is the engine-health signal:
            # it zeroes the consecutive-crash count and closes an open
            # breaker (this is exactly the probe request succeeding — while
            # open, nothing else is admitted).
            with self._cond:
                self._crashes = 0
                self._probe_inflight = False
                if self._breaker_open:
                    self._breaker_open = False
                    log.info("Decode engine %s: circuit breaker closed "
                             "(probe request completed)", self.model_id)
            self._deliver(state.req, "done", None)
        # Leak-sanitizer seam: retirement is where every page-ownership
        # transfer (unpin, reset_row, table restore) must have balanced.
        # AFTER _deliver so a strict audit failure crashes the tick (→
        # recovery) instead of hanging the retired request's consumer.
        if memledger.strict():
            self._ledger.audit("retire")

    def _release_prefix(self, row: int, state):
        """Unpin the row's aliased radix pages and restore its static block
        table — the slot's next occupant must not write through the shared
        entries (its parked position-0 write would corrupt every reader)."""
        if state is None or not state.prefix_nodes:
            return
        self._prefix_cache.unpin(state.prefix_nodes)
        state.prefix_nodes = []
        self._kv = self._kv.restore_row_table(row)

    def _deliver(self, req: Request, kind: str, value):
        try:
            req.on_event(kind, value)
        except Exception:  # noqa: BLE001 — a dead consumer must not kill the batch
            log.exception("Decode scheduler consumer callback failed")
            req.cancelled = True

    def _fail_all(self, exc: Exception, crashed: bool = False):
        """Fail every in-flight and queued request.  Returns the affected
        rows' traces; with ``crashed=True`` they carry an ``engine_crash``
        event and are left UNFINISHED so the caller can attach the
        recovery span before closing them (otherwise finished here)."""
        open_traces: list = []
        for i, state in enumerate(self._rows):
            if state is not None:
                # A row parked awaiting a d2d import ack handed its request
                # to the importing replica — release the source copy here
                # WITHOUT touching the stream (the importer owns every
                # terminal path for it now).
                handed_off = (i in self._transit_rows
                              and self._transit_rows[i]["state"] is state)
                self._rows[i] = None
                self._lengths[i] = 0
                self._last_tok[i] = 0
                self._row_adapter[i] = self._max_live
                try:
                    self._release_prefix(i, state)
                except Exception:  # noqa: BLE001 — the device state may be
                    # the failing thing; admission re-bases the row's table
                    # anyway (_begin_prefill), so only log.
                    log.exception("Failed to restore row %d block table", i)
                if handed_off:
                    continue
                serve_metrics.REQUESTS.inc(outcome="error")
                trace = state.req.trace
                if trace is not None:
                    trace.end(state.sp_prefill)
                    trace.end(state.sp_decode, produced=state.produced)
                    if crashed:
                        trace.event("engine_crash", error=str(exc))
                        open_traces.append(trace)
                    else:
                        trace.finish("error")
                self._deliver(state.req, "error", exc)
        with self._cond:
            self._transit_rows.clear()
            self._acks.clear()
            pending = self._pending.drain()
            if self._probe_inflight:
                # The probe died with everything else: stay open and re-arm
                # the cooldown so the next probe waits its turn.
                self._probe_inflight = False
                self._breaker_open_t = time.monotonic()
        for req in pending:
            self._release_resume(req)
            serve_metrics.REQUESTS.inc(outcome="error")
            self._finish_trace(req, "error")
            self._deliver(req, "error", exc)
        return open_traces

    # -- model staleness ----------------------------------------------------

    def _ckpt_stamp(self):
        try:
            return os.path.getmtime(checkpoint._source_path(self.model_id))
        except OSError:
            return None

    def _maybe_reload(self):
        """With zero rows in flight, pick up a newer checkpoint (a /train/
        that finished since the engine loaded) — serving stays at most one
        idle gap behind training, matching the legacy per-request
        deserialize semantics closely enough for a cached engine."""
        stamp = self._ckpt_stamp()
        if stamp == self._ckpt_stamp_v:
            return
        try:
            self._model = NeuralNetworkModel.deserialize(self.model_id)
            self._ckpt_stamp_v = stamp
            if self._prefix_cache is not None:
                # Cached prefix K/V was computed with the OLD weights; a hit
                # against the new ones would silently mix models.  Zero rows
                # are in flight here, so nothing is pinned — except pending
                # hibernation holds, whose HBM pages are about to vanish:
                # release them and drop this engine's hbm-tier records
                # (demoted host/disk copies stay, but their stale model
                # stamp makes every future match drop them).
                self._drop_hib_holds()
                tierstore.TIERS.drop_owner(id(self), "model_reload")
                self._prefix_cache.clear()
            # Same contract for adapters (the prefix-cache-flush mirror):
            # the live slots and the host registry cache hold factors
            # whose base just changed under them — drop both so the next
            # adapter request re-resolves against fresh state (a reloaded
            # entry gets a new uid, which also retires its old prefix
            # namespace).
            self._slot_entries = [None] * self._max_live
            self._lora_pack = None
            adapters_mod.REGISTRY.invalidate_model(self.model_id)
            log.info("Decode engine reloaded model %s (checkpoint changed; "
                     "prefix cache + adapter slots flushed)", self.model_id)
        except KeyError:
            # model deleted mid-flight: keep serving the cached weights;
            # the registry entry dies with the next reset/eviction.
            log.warning("Decode engine %s: checkpoint vanished; serving "
                        "cached weights", self.model_id)


# ---------------------------------------------------------------------------
# Engine registry
# ---------------------------------------------------------------------------

_ENGINES: dict = {}
_REG_LOCK = threading.Lock()
_DRAINING = False


def _engine_key(model_id, block_size, temperature, top_k):
    greedy = temperature is None or float(temperature) == 0.0
    return (model_id, int(block_size), 0.0 if greedy else float(temperature),
            int(top_k) if top_k is not None else None)


def get_engine(model_id, block_size, temperature, top_k):
    """Blocking engine lookup/creation (deserializes the model on a miss —
    call off the event loop).  Returns None when the registry is at
    capacity and nothing is evictable, or while the server is draining
    (shutdown must not spawn fresh engines); callers fall back to the
    legacy per-request path.  Raises KeyError for an unknown model
    (HTTP 404)."""
    if _DRAINING:
        return None
    if _replicas() > 1:
        # Data-parallel replica group: the router owns engine creation and
        # per-request placement; it quacks like an engine (submit) so the
        # HTTP layer is unchanged.  Lazy import — router imports this
        # module at its top.
        from penroz_tpu.serve import router as router_mod
        return router_mod.get_router(model_id, block_size, temperature,
                                     top_k)
    key = _engine_key(model_id, block_size, temperature, top_k)
    with _REG_LOCK:
        engine = _ENGINES.get(key)
        if engine is not None and not engine._shutdown:
            return engine
        if engine is not None:
            del _ENGINES[key]
        if len(_ENGINES) >= _max_engines():
            # Router-owned replicas are never eviction victims: their
            # lifecycle belongs to their router, and silently shutting one
            # down would strand the group's affinity index.
            victim = next((k for k, e in _ENGINES.items()
                           if e.idle() and not e._router_owned), None)
            if victim is None:
                log.warning("Decode engine registry full (%d) with no idle "
                            "engine; request falls back to the per-request "
                            "path", len(_ENGINES))
                return None
            _ENGINES.pop(victim).shutdown(timeout=5.0)
        engine = DecodeEngine(model_id, block_size, temperature, top_k)
        _ENGINES[key] = engine
        return engine


def reset():
    """Shut every engine down and clear the registry (tests, reloads)."""
    global _DRAINING
    from penroz_tpu.serve import router as router_mod
    router_mod.clear()
    with _REG_LOCK:
        engines = list(_ENGINES.values())
        _ENGINES.clear()
    _DRAINING = False
    for engine in engines:
        engine.shutdown(timeout=5.0)


def draining() -> bool:
    return _DRAINING


def breaker_open_engines() -> list[str]:
    """model_ids the scheduler path cannot currently serve — the /readyz
    not-ready signal.  A standalone engine with an open breaker reports
    its model, exactly as before; a router-owned replica GROUP reports
    only when EVERY replica's breaker is open — one healthy replica keeps
    the model ready because the router routes around the open ones."""
    with _REG_LOCK:
        live = [e for e in _ENGINES.values() if not e._shutdown]
    out = set()
    groups: dict = {}
    for e in live:
        if e._router_owned:
            groups.setdefault(e.model_id, []).append(e._breaker_open)
        elif e._breaker_open:
            out.add(e.model_id)
    out.update(m for m, opens in groups.items() if all(opens))
    return sorted(out)


def stuck_engines() -> list[str]:
    """model_ids whose worker is wedged inside a tick dispatch longer than
    ``PENROZ_TICK_WATCHDOG_MS`` — the watchdog readiness signal (and the
    ``penroz_engine_stuck`` gauge).  Same group-aware rule as
    ``breaker_open_engines``: a standalone stuck engine names its model;
    a router-owned replica group reports only when EVERY replica is stuck,
    because one live replica keeps the model serving."""
    with _REG_LOCK:
        live = [e for e in _ENGINES.values() if not e._shutdown]
    out = set()
    groups: dict = {}
    for e in live:
        if e._router_owned:
            groups.setdefault(e.model_id, []).append(e.stuck())
        elif e.stuck():
            out.add(e.model_id)
    out.update(m for m, vals in groups.items() if all(vals))
    return sorted(out)


def drain_and_shutdown(drain_s: float | None = None) -> bool:
    """Graceful server shutdown: mark the registry draining (readyz flips
    not-ready, engines stop admitting), give in-flight rows up to
    ``drain_s`` (default PENROZ_DRAIN_S) to finish, then join every worker
    thread.  Returns True iff every thread joined."""
    global _DRAINING
    _DRAINING = True
    if drain_s is None:
        drain_s = _drain_s()
    from penroz_tpu.serve import router as router_mod
    router_mod.clear()
    with _REG_LOCK:
        engines = list(_ENGINES.values())
        _ENGINES.clear()
    ok = True
    try:
        for engine in engines:
            ok = engine.shutdown(timeout=10.0, drain_s=drain_s) and ok
    finally:
        # Drain complete: the registry is empty and this app instance is
        # gone.  Clearing the flag keeps a later create_app() in the same
        # process (tests, embedded servers) serviceable.
        _DRAINING = False
    return ok


def _merged_q(per: list[dict], name: str, q: float):
    """Quantile over the merged per-engine histogram snapshots — the
    cross-engine aggregation path (all reads went through
    ``DecodeEngine.stats()``; nothing here touches engine internals)."""
    v = metrics_util.quantile_of(metrics_util.merge_snapshots(
        [p["histograms"][name] for p in per]), q)
    return round(v, 3) if v is not None else None


def _pipe_bubble_agg(per: list[dict]):
    """Stage-tick-weighted bubble fraction across every piped engine
    (None until any pipeline group ticks): each engine's lifetime
    fraction weighted by its pipe_ticks × stages denominator, so a busy
    group dominates an idle one instead of averaging them 50/50."""
    num = den = 0.0
    for p in per:
        ticks, frac = p["pipe_ticks"], p["pipe_bubble_fraction"]
        if ticks and frac is not None:
            w = ticks * p["pipe_stages"]
            num += frac * w
            den += w
    return round(num / den, 4) if den else None


def serving_stats() -> dict:
    """Aggregate scheduler observability — the /serving_stats/ payload.

    Every per-engine read goes through the one locked accessor
    ``DecodeEngine.stats()``; percentiles aggregate by merging the
    engines' histogram bucket snapshots (identical layouts), never by
    re-reading raw samples."""
    from penroz_tpu.serve import router as router_mod
    router = router_mod.stats_totals()
    router_lookups = router["affinity_hits"] + router["affinity_misses"]
    tiers = tierstore.TIERS.stats()
    with _REG_LOCK:
        engines = [e for e in _ENGINES.values() if not e._shutdown]
    per = [e.stats() for e in engines]
    capacity = sum(p["capacity"] for p in per)
    active = sum(p["active_rows"] for p in per)
    stall_p99 = _merged_q(per, "chunk_stall_ms", 0.99)
    pc = [p["prefix_cache"] for p in per if p["prefix_cache"] is not None]
    pc_lookups = sum(c["hits"] + c["misses"] for c in pc)
    queue_wait_p99 = _merged_q(per, "queue_wait_ms", 0.99)
    timeline = sorted((t for p in per for t in p["tick_timeline"]),
                      key=lambda e: e["age_s"])[:_TIMELINE_SERVE]
    spec_drafted = sum(p["spec_drafted_tokens"] for p in per)
    spec_accepted = sum(p["spec_accepted_tokens"] for p in per)
    decode_steps = sum(p["decode_steps"] for p in per)
    decode_tokens = sum(p["decode_tokens"] for p in per)
    tpd = metrics_util.merge_snapshots(
        [p["histograms"]["tokens_per_dispatch"] for p in per])
    adapter_tokens: dict = {}
    for p in per:
        for aid, n in p["lora_adapter_tokens"].items():
            adapter_tokens[aid] = adapter_tokens.get(aid, 0) + n
    tenant_tokens: dict = {}
    for p in per:
        for tid, n in p["tenant_tokens"].items():
            tenant_tokens[tid] = tenant_tokens.get(tid, 0) + n
    qdepth_by_class = {c: sum(p["queue_depth_by_class"][c] for p in per)
                       for c in qos.PRIORITIES}

    def _cls_q(name: str, cls: str, q: float):
        v = metrics_util.quantile_of(metrics_util.merge_snapshots(
            [p["histograms"][name][cls] for p in per]), q)
        return round(v, 3) if v is not None else None

    return {
        "continuous_batching_enabled": enabled(),
        "engines": per,
        "capacity": capacity,
        "active_rows": active,
        "queue_depth": sum(p["queue_depth"] for p in per),
        "queue_rejections": sum(p["queue_rejections"] for p in per),
        "deadline_timeouts": sum(p["deadline_timeouts"] for p in per),
        "quota_rejections": sum(p["quota_rejections"] for p in per),
        "preemptions_total": sum(p["preemptions"] for p in per),
        "preempted_resume_cached_tokens": sum(
            p["preempted_resume_cached_tokens"] for p in per),
        "queue_depth_by_class": qdepth_by_class,
        "tenant_tokens": tenant_tokens,
        "ttft_ms_p99_by_class": {
            c: _cls_q("ttft_ms_by_class", c, 0.99) for c in qos.PRIORITIES},
        "queue_wait_ms_p99_by_class": {
            c: _cls_q("queue_wait_ms_by_class", c, 0.99)
            for c in qos.PRIORITIES},
        "queue_wait_ms_p99": queue_wait_p99,
        "breaker_open": any(p["breaker_open"] for p in per),
        "crashes_total": sum(p["crashes_total"] for p in per),
        "engine_resets": sum(p["engine_resets"] for p in per),
        "draining": _DRAINING,
        "batch_occupancy": (active / capacity) if capacity else 0.0,
        "decode_tokens_per_sec": round(
            sum(p["decode_tokens_per_sec"] for p in per), 2),
        "admission_latency_ms_p50": _merged_q(per, "ttft_ms", 0.5),
        "ttft_ms_p99": _merged_q(per, "ttft_ms", 0.99),
        "itl_ms_p50": _merged_q(per, "itl_ms", 0.5),
        "itl_ms_p99": _merged_q(per, "itl_ms", 0.99),
        "tick_ms_p50": _merged_q(per, "tick_ms", 0.5),
        "tick_ms_p99": _merged_q(per, "tick_ms", 0.99),
        "tick_timeline": timeline,
        "prefill_chunk_stall_ms_p99": stall_p99,
        "prefix_cache_hit_rate": (
            sum(c["hits"] for c in pc) / pc_lookups if pc_lookups else None),
        "prefix_cache_evicted_pages": sum(c["evicted_pages"] for c in pc),
        "lora_active_adapters": sum(p["lora_active_adapters"] for p in per),
        "lora_rows": sum(p["lora_rows"] for p in per),
        "lora_adapter_tokens": adapter_tokens,
        "ssm_rows": sum(p["ssm_rows"] for p in per),
        "ssm_state_bytes": sum(p["ssm_state_bytes"] for p in per),
        "spec_decode_enabled": spec_decode.enabled(),
        "spec_drafted_tokens": spec_drafted,
        "spec_accepted_tokens": spec_accepted,
        "spec_accept_rate": stats_util.rate(spec_accepted, spec_drafted),
        "tokens_per_decode_step": round(
            stats_util.rate(decode_tokens, decode_steps) or 0.0, 3),
        "dispatches_total": sum(p["dispatches_total"] for p in per),
        "tokens_per_dispatch_avg": (round(tpd["sum"] / tpd["count"], 3)
                                    if tpd["count"] else None),
        "tokens_per_dispatch_p50": _merged_q(per, "tokens_per_dispatch",
                                             0.5),
        # Process-wide module totals, kept byte-compatible with the
        # /metrics counters; the per-engine attribution lives in each
        # engine's ledger-backed stats() fields of the same names.
        "kv_pool_capacity_drops": KV.pool_drop_count(),
        "unpin_underflows": KV.unpin_underflow_count(),
        # Replica router (serve/router.py): 0 replicas = no router live
        # (PENROZ_SCHED_REPLICAS=1, today's single-engine registry).
        "router_replicas": router["replicas"],
        "router_affinity_hits": router["affinity_hits"],
        "router_affinity_misses": router["affinity_misses"],
        "router_affinity_hit_rate": stats_util.rate(
            router["affinity_hits"], router_lookups),
        "router_failovers": router["failovers"],
        "disagg_prefill_replicas": router["prefill_replicas"],
        "disagg_exports": sum(p["disagg_exports"] for p in per),
        "disagg_imports": sum(p["disagg_imports"] for p in per),
        "disagg_handoff_failures": sum(
            p["disagg_handoff_failures"] for p in per),
        "disagg_handoff_ms_p50": _merged_q(per, "handoff_ms", 0.5),
        "disagg_handoff_ms_p99": _merged_q(per, "handoff_ms", 0.99),
        "disagg_transport": _disagg_transport(),
        "disagg_role_changes": sum(p["disagg_role_changes"] for p in per),
        # Pipeline-parallel serving (PENROZ_SERVE_PIPE_STAGES >= 2): the
        # router sees each stage group as ONE replica, so the aggregate is
        # over groups — widest group, total schedule ticks, and the
        # tick-weighted idle share across every piped engine.
        "pipe_stages": max((p["pipe_stages"] for p in per), default=1),
        "pipe_ticks": sum(p["pipe_ticks"] for p in per),
        "pipe_bubble_fraction": _pipe_bubble_agg(per),
        "pipe_handoffs": sum(p["pipe_handoffs"] for p in per),
        "pipe_handoff_host_fallbacks": sum(
            p["pipe_handoff_host_fallbacks"] for p in per),
        # KV tiering / session hibernation (serve/tierstore.py): the
        # store is process-wide (shared across engines and replicas), so
        # residency/tier fields come from it directly; the counters below
        # it are per-engine sums like everything else here.
        "sessions_resident": tiers["sessions_resident"],
        "sessions_by_tier": tiers["sessions_by_tier"],
        "tier_bytes": tiers["tier_bytes"],
        "tier_promotions": tiers["tier_promotions"],
        "tier_demotions": tiers["tier_demotions"],
        "tier_corrupt_blobs": tiers["tier_corrupt_blobs"],
        "sessions_hibernated": sum(p["sessions_hibernated"] for p in per),
        "session_promotions": sum(p["session_promotions"] for p in per),
        "session_resume_ttft_ms_p50": _merged_q(per, "session_resume_ttft_ms",
                                                0.5),
        "session_resume_ttft_ms_p99": _merged_q(per, "session_resume_ttft_ms",
                                                0.99),
        # Crash durability (serve/journal.py, serve/streams.py): the
        # write-ahead journal's counters, the last restart-recovery
        # summary (tierstore.recover()), the resumable-stream registry,
        # and the tick-watchdog verdict.
        "journal": journal.JOURNAL.stats(),
        "restart_recovery": tiers["restart_recovery"],
        "streams": streams.STREAMS.stats(),
        "engines_stuck": len(stuck_engines()),
    }


# ---------------------------------------------------------------------------
# Async request surface (serve/app.py)
# ---------------------------------------------------------------------------

def eligible(prompt: list[int], block_size: int, max_new_tokens: int) -> bool:
    """A request the scheduler can serve losslessly: non-empty prompt that
    fits the fixed-capacity row with all its new tokens (the scheduler has
    no overflow crop/re-prefill; oversized requests keep the legacy
    single-sequence path and its re-prefill loop)."""
    return (len(prompt) >= 1 and max_new_tokens >= 1
            and len(prompt) + max_new_tokens <= block_size)


async def acquire_engine(model_id, block_size, temperature, top_k):
    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(None, get_engine, model_id,
                                      block_size, temperature, top_k)


def _async_request(prompt, max_new_tokens, stop_token, timeout_ms=None,
                   adapter=None, request_id=None, trace=None,
                   priority=None, tenant=None, session_id=None):
    loop = asyncio.get_running_loop()
    queue: asyncio.Queue = asyncio.Queue()

    def on_event(kind, value):
        loop.call_soon_threadsafe(queue.put_nowait, (kind, value))

    return (Request(prompt, max_new_tokens, stop_token, on_event,
                    timeout_ms=timeout_ms, adapter=adapter,
                    request_id=request_id, trace=trace,
                    priority=priority, tenant=tenant,
                    session_id=session_id), queue)


async def run_request(engine: DecodeEngine, prompt, max_new_tokens,
                      stop_token, timeout_ms=None, adapter=None,
                      request_id=None, trace=None, priority=None,
                      tenant=None, session_id=None) -> list[int]:
    """Submit one request and await the full sequence (prompt + generated,
    the ``generate_tokens`` contract).  Raises DeadlineExceeded /
    QueueFullError / CircuitOpenError on the shed paths; an aiohttp client
    disconnect cancels the awaiting handler task, which propagates to
    ``req.cancelled`` so the row and its prefix pins free at the next
    boundary.  ``adapter`` (serve.adapters.AdapterEntry) routes the row
    through that adapter's live slot; the CALLER holds the registry pin.
    ``request_id``/``trace`` thread per-request observability through the
    scheduler (utils/tracing.py); the scheduler finishes the trace at
    retirement, the caller finishes it on shed paths.
    ``priority``/``tenant`` are the QoS routing fields (WFQ class +
    quota bucket).  ``session_id`` tags the request for KV hibernation at
    retirement (serve/tierstore.py)."""
    req, queue = _async_request(prompt, max_new_tokens, stop_token,
                                timeout_ms, adapter, request_id, trace,
                                priority, tenant, session_id)
    engine.submit(req)
    tokens = list(req.prompt)
    try:
        while True:
            kind, value = await queue.get()
            if kind == "token":
                tokens.append(value)
            elif kind == "done":
                return tokens
            else:  # "error" or "timeout": value is the exception
                raise value
    except asyncio.CancelledError:
        req.cancelled = True
        raise


def start_stream(engine: DecodeEngine, prompt, max_new_tokens, stop_token,
                 timeout_ms=None, adapter=None, request_id=None,
                 trace=None, priority=None, tenant=None, session_id=None):
    """Submit a streaming request; returns ``(req, queue, stream)`` so the
    HTTP layer can consume events AND flip ``req.cancelled`` itself when
    the client goes away mid-stream (a write failure is invisible to an
    async generator until its GC-time close — the explicit handle is the
    disconnect wiring).

    Events route through a :class:`serve.streams.StreamSession` replay
    ring, so the queue carries ``(seq, kind, value)`` triples and a
    dropped client can reattach at ``GET /generate/{id}/stream?from_seq=N``
    (serve/streams.py).  ``stream`` is the session handle: the HTTP layer
    calls ``stream.try_detach()`` on disconnect (grace window instead of
    cancel when ``PENROZ_STREAM_DETACH_MS`` > 0) and ``stream.release()``
    when it finishes reading."""
    req, queue = _async_request(prompt, max_new_tokens, stop_token,
                                timeout_ms, adapter, request_id, trace,
                                priority, tenant, session_id)
    rid = req.request_id or f"req-{id(req):x}"
    stream = streams.STREAMS.register(rid, req)
    stream.attach_initial(asyncio.get_running_loop(), queue)
    req.on_event = stream.publish
    engine.submit(req)
    return req, queue, stream
