"""Pydantic request models for the REST API (reference: main.py:38-282)."""

from __future__ import annotations

from typing import Optional

from pydantic import BaseModel, Field


class ModelRequest(BaseModel):
    model_id: str = Field(..., description="The unique identifier for the model.")


class ModelOnDeviceRequest(ModelRequest):
    device: str = Field("cpu", description="Device to place the model on "
                        "('cpu' or 'tpu'; 'cuda'/'gpu' map to the accelerator).")


class CreateModelRequest(ModelRequest):
    layers: list[dict] = Field(..., description="Layer DSL: list of "
                               "{algo: args} dicts, with optional init entries.")
    optimizer: dict = Field(..., description="{optimizer_name: args} dict.")


class DatasetRequest(BaseModel):
    dataset_id: str = Field(..., description="The unique identifier for the dataset")


class TokenizerRequest(BaseModel):
    encoding: str = Field(..., description="Tiktoken encoding (prefix "
                          "'tiktoken/') or HuggingFace tokenizer name")


class DownloadDatasetRequest(DatasetRequest, TokenizerRequest):
    path: str = Field(..., description="HuggingFace dataset path")
    name: Optional[str] = Field(None, description="HuggingFace dataset config name")
    split: str = Field(..., description="Dataset split to download")
    shard_size: int = Field(..., description="Number of tokens per shard")


class AdapterTrainConfig(BaseModel):
    """LoRA fine-tune selector on PUT /train/: the base model is frozen,
    only the adapter's low-rank factors train, and the checkpoint written
    is adapter-only (models/lora.py, servable via /adapters/)."""
    adapter_id: str = Field(..., description="Adapter to train (created on "
                            "first train if absent)")
    rank: int = Field(8, description="Low-rank dimension r; capped by "
                      "PENROZ_LORA_MAX_RANK")
    alpha: Optional[float] = Field(None, description="Scale numerator "
                                   "(delta = alpha/r · B·A·x); default 2r")
    targets: Optional[list[str]] = Field(
        None, description="Substring matchers over Linear param prefixes "
        "(e.g. ['layers.2']); null targets every Linear projection")


class TrainingRequest(ModelOnDeviceRequest, DatasetRequest):
    shard: int = Field(..., description="Dataset shard to begin training from")
    epochs: int = Field(..., description="Number of training epochs")
    batch_size: int = Field(..., description="Batch size sampled each epoch")
    block_size: int = Field(..., description="Sequence length per sample")
    step_size: int | float = Field(
        ..., gt=0, description="batch_size / step_size micro-steps of "
        "batch_size x block_size tokens accumulate into one optimizer step; "
        "a fraction (0.25 with batch_size 1) asks for more micro-steps than "
        "the batch has rows")
    adapter: Optional[AdapterTrainConfig] = Field(
        None, description="Train a LoRA adapter instead of the base "
        "weights (base frozen; adapter-only checkpoint)")


class EvaluateRequest(TrainingRequest):
    target_dataset_id: Optional[str] = Field(None, description="Separate "
                                             "target dataset (optional)")


class TokenizeTextRequest(TokenizerRequest):
    text: str = Field(..., description="Text to tokenize")


class OutputRequest(ModelRequest):
    input: list = Field(..., description="The input context")
    target: Optional[list | int] = Field(None, description="Expected target")


class GenerateRequest(ModelRequest):
    input: list = Field(..., description="The initial token context")
    block_size: int = Field(..., description="Max context length")
    max_new_tokens: int = Field(..., description="Max tokens to generate")
    temperature: float = Field(1.0, description="Logits temperature")
    top_k: Optional[int] = Field(None, description="Top-K sampling")
    stop_token: Optional[int] = Field(None, description="Early-stop token id")
    stream: bool = Field(False, description="Stream tokens as produced")
    timeout_ms: Optional[int] = Field(
        None, description="Request deadline in ms (scheduler path), capped "
        "by PENROZ_REQ_TIMEOUT_MS server-side: 504 while queued, retired "
        "at the next step boundary (stream ends with a 'timeout' line) in "
        "flight")
    adapter_id: Optional[str] = Field(
        None, description="Serve through this LoRA adapter (POST "
        "/adapters/ or a /train/ adapter run creates one). Unknown "
        "adapter → 400 naming it; still loading → 409. Mixed adapters "
        "share one decode batch under PENROZ_CONTINUOUS_BATCHING=1")
    priority: Optional[str] = Field(
        None, description="SLO class: 'interactive' | 'standard' | "
        "'batch' (default 'standard'). Classes drain by deficit-weighted "
        "round robin (PENROZ_QOS_WEIGHTS); an interactive arrival may "
        "preempt a lower-class decode row (PENROZ_QOS_PREEMPT)")
    tenant: Optional[str] = Field(
        None, description="Tenant id for fair queuing + token quotas "
        "(default: adapter_id, else 'default'). An exhausted tenant "
        "token bucket 429s new admissions with a refill-derived "
        "Retry-After (PENROZ_QOS_TENANT_TOKENS_PER_S / PUT "
        "/tenants/{id}/quota)")
    session_id: Optional[str] = Field(
        None, pattern=r"^[A-Za-z0-9._-]{1,120}$",
        description="Session handle for KV hibernation: at retirement the "
        "full prompt+generated KV demotes HBM → host RAM → disk "
        "(PENROZ_TIER_HOST_MB / PENROZ_TIER_DISK_MB) instead of being "
        "freed, and a later request whose prompt extends the session's "
        "history resumes from the hibernated pages (promote-on-match) — "
        "on any replica, and across engine restarts from the disk tier. "
        "Scheduler path only (base model, no adapter); GET/DELETE "
        "/sessions/ manage residency")


class GenerateBatchRequest(ModelRequest):
    inputs: list[list[int]] = Field(
        ..., description="N prompt token lists (different lengths allowed — "
        "ragged batched decode shares one forward per step). Capped at "
        "PENROZ_MAX_GENERATE_BATCH (default 64) server-side; exceeding "
        "it is a 400.")
    block_size: int = Field(..., description="Max context length; must fit "
                            "max prompt + max_new_tokens")
    max_new_tokens: int = Field(..., description="Max tokens per sequence")
    temperature: float = Field(1.0, description="Logits temperature")
    top_k: Optional[int] = Field(None, description="Top-K sampling")
    stop_token: Optional[int] = Field(None, description="Per-row early-stop "
                                      "token id")
    timeout_ms: Optional[int] = Field(
        None, description="Per-row deadline in ms (scheduler path), capped "
        "by PENROZ_REQ_TIMEOUT_MS; any shed row sheds the whole batch "
        "(all-or-nothing contract)")
    adapter_id: Optional[str] = Field(
        None, description="LoRA adapter applied to EVERY row (overridden "
        "per-row by adapter_ids)")
    adapter_ids: Optional[list[Optional[str]]] = Field(
        None, description="Per-row LoRA adapter ids (null entries = base "
        "model); length must equal inputs. Rows with different adapters "
        "share one decode batch; unknown adapters 400 naming the rows, "
        "still-loading adapters 409")
    priority: Optional[str] = Field(
        None, description="SLO class applied to every row: 'interactive' "
        "| 'standard' | 'batch' (default 'standard')")
    tenant: Optional[str] = Field(
        None, description="Tenant id applied to every row for fair "
        "queuing + token quotas (default: the row's adapter id, else "
        "'default')")
    session_ids: Optional[list[Optional[str]]] = Field(
        None, description="Per-row session handles for KV hibernation "
        "(null entries = no session; see GenerateRequest.session_id); "
        "length must equal inputs")


class TenantQuotaRequest(BaseModel):
    """PUT /tenants/{tenant_id}/quota — per-tenant token-rate override
    of PENROZ_QOS_TENANT_TOKENS_PER_S (serve/qos.py token bucket over
    emitted + prefilled tokens).  Null restores the env default."""
    tokens_per_s: Optional[float] = Field(
        ..., description="Sustained token budget per second (burst = 1s "
        "of rate, min 1 token); 0 blocks all new admissions for the "
        "tenant; null clears the override back to the env default")
    tier_mb: Optional[float] = Field(
        None, description="Hibernated-session KV residency cap for the "
        "tenant in MB across the host+disk tiers (overrides "
        "PENROZ_QOS_TENANT_TIER_MB). A hibernation over cap evicts the "
        "tenant's LRU sessions; one that cannot fit at all is refused "
        "(the KV is simply freed). 0 = unlimited; null clears the "
        "override. Omit to leave the tier quota unchanged")


class CreateAdapterRequest(ModelRequest):
    """POST /adapters/ — register a fresh LoRA adapter for a model.
    B is zero-initialized, so an untrained adapter serves exactly the
    base model; ``init='random'`` randomizes B too (benchmarks/tests)."""
    adapter_id: str = Field(..., description="Unique adapter id")
    rank: int = Field(8, description="Low-rank dimension r (1..PENROZ_"
                      "LORA_MAX_RANK)")
    alpha: Optional[float] = Field(None, description="Scale numerator; "
                                   "default 2r")
    targets: Optional[list[str]] = Field(
        None, description="Substring matchers over Linear param prefixes; "
        "null targets every Linear projection")
    seed: int = Field(0, description="Init seed")
    init: str = Field("zeros", description="'zeros' (identity until "
                      "trained) or 'random' (non-trivial delta without "
                      "training)")


class DecodeTokensRequest(TokenizerRequest):
    tokens: list[int] = Field(..., description="Token ids to decode")


class ImportModelRequest(BaseModel):
    hf_repo_id: str = Field(..., description="HuggingFace repo id (GPT-2 or "
                            "Gemma families)")
    model_id: str = Field(..., description="Internal model id to save under")
    revision: Optional[str] = Field(None, description="HF revision/branch/tag")
    device: str = Field("cpu", description="Device to load the model on")


class PrefixCacheStats(BaseModel):
    """Radix prefix-KV cache snapshot (PENROZ_PREFIX_CACHE=1 over the paged
    pool; ops/kv_cache.py RadixPrefixCache)."""
    capacity_pages: int = Field(..., description="Reserved pool pages "
                                "(PENROZ_PREFIX_CACHE_PAGES)")
    cached_pages: int
    hits: int = Field(..., description="Admissions matching ≥1 cached page")
    misses: int
    hit_rate: Optional[float] = Field(None, description="hits / lookups "
                                      "(null before any lookup)")
    hit_tokens: int = Field(..., description="Prompt tokens whose prefill "
                            "was skipped via aliased pages")
    inserted_pages: int
    evicted_pages: int = Field(..., description="LRU-evicted pages "
                               "(unpinned leaves only)")


class TickRecord(BaseModel):
    """One scheduler tick in the telemetry timeline: what the loop did
    between dispatches — phase composition (prefill chunks / spec-decode
    verify rows / shared-step rows), batch occupancy, and dispatch wall
    time.  Ring-buffered per engine (PENROZ_TICK_TIMELINE entries); the
    dashboard renders the tail as the occupancy/latency strip."""
    age_s: float = Field(..., description="Seconds before the stats "
                         "snapshot this tick ran (newest ≈ 0)")
    dispatch_ms: float = Field(..., description="Tick dispatch wall time "
                               "(prefill chunks + decode step)")
    occupancy: float = Field(..., description="active_rows / capacity "
                             "after the tick")
    prefill_chunks: int = Field(0, description="Prefill chunks run at "
                                "this step boundary")
    verify_rows: int = Field(0, description="Rows that ran a spec-decode "
                             "multi-token verify step")
    shared_rows: int = Field(0, description="Rows in the plain shared "
                             "batched step")
    emitted: int = Field(0, description="Tokens emitted this tick")
    superstep: int = Field(0, description="Decode steps fused into this "
                           "tick's dispatch (PENROZ_SCHED_SUPERSTEP path; "
                           "1 = legacy single step, 0 = no decode dispatch "
                           "ran this tick)")
    unified: bool = Field(False, description="True when the tick ran as "
                          "ONE ragged mixed dispatch (paged KV + "
                          "PENROZ_RAGGED_ATTENTION) carrying prefill "
                          "chunks, decode steps, and verify rows in a "
                          "single descriptor grid; False on the legacy "
                          "phased path")
    prefill_rows: int = Field(0, description="Rows still chunk-prefilling "
                              "at tick start (mixed-composition view)")
    decode_rows: int = Field(0, description="Rows in the decode/verify "
                             "phase at tick start (mixed-composition view)")
    pipe_ticks: int = Field(0, description="Pipeline schedule ticks this "
                            "scheduler tick ran (stage-unit rounds; 0 off "
                            "the pipeline path)")
    pipe_bubbles: int = Field(0, description="Idle stage-ticks during "
                              "this tick's pipeline schedule (fill/drain "
                              "or too few micro-blocks); bubble fraction "
                              "= pipe_bubbles / (pipe_ticks × stages)")


class StagePoolEntry(BaseModel):
    """One pipeline stage's slice of a group's paged KV pool
    (PENROZ_SERVE_PIPE_STAGES): the stage holds the SAME logical page
    partition over its own attention layers only, so per-device pool HBM
    drops ~1/S while the page states stay group-wide."""
    stage: int = Field(..., description="Stage index (0-based, in layer "
                       "order)")
    kv_layers: int = Field(..., description="Attention layers whose K/V "
                           "pools live on this stage's mesh")
    pool_pages: int = Field(..., description="Logical pool pages visible "
                            "to this stage (= pool_pages_total; audited "
                            "per stage in strict mode)")
    kv_pool_bytes: int = Field(..., description="Pool bytes resident on "
                               "this stage's devices (values + int8 "
                               "scales); stages sum to the group's "
                               "kv_values + kv_scales")


class EngineMemory(BaseModel):
    """One engine's capacity-ledger snapshot (serve/memledger.py): every
    paged-pool page attributed to exactly one owner state, plus byte
    accounting for the non-paged components.  The page states PARTITION
    the pool — their sum equals pool_pages_total (audited in strict
    mode)."""
    paged: bool = Field(..., description="True when the engine runs the "
                        "paged pool (page states populated); contiguous "
                        "engines report bytes only")
    page_size: int = Field(0, description="Tokens per pool page "
                           "(PENROZ_KV_PAGE_SIZE; 0 when not paged)")
    pool_pages_total: int = Field(0, description="Total pages in the "
                                  "engine's paged pool (row partition + "
                                  "reserved prefix-cache region)")
    pool_pages: dict[str, int] = Field(
        default_factory=dict, description="Pages per owner state: free | "
        "row (live-row KV) | prefix_pinned (radix pages aliased by a live "
        "row) | prefix_evictable (cached, unpinned) | preempted (pinned "
        "by a queued preempted session's resume hold) | reserved (radix "
        "free list) | transit (disaggregated-prefill hand-off import in "
        "flight) | hibernating (pinned by a hibernated session's hold "
        "awaiting tier demotion).  States sum to pool_pages_total")
    tenant_pages: dict[str, int] = Field(
        default_factory=dict, description="Row-owned pages per tenant id "
        "(page-granular HBM attribution)")
    adapter_pages: dict[str, int] = Field(
        default_factory=dict, description="Row-owned pages per LoRA "
        "adapter id (adapter-bound rows only)")
    stage_pools: list[StagePoolEntry] = Field(
        default_factory=list, description="Per-pipeline-stage pool "
        "attribution (PENROZ_SERVE_PIPE_STAGES >= 2 groups only; empty "
        "for unpiped engines)")
    hbm_bytes: dict[str, int] = Field(
        default_factory=dict, description="Bytes per component: "
        "kv_values / kv_scales (int8 variants) / kv_block_table / "
        "lora_pack / params.  The aggregate adds adapter_host_cache "
        "plus host_tier / disk_tier (hibernated-session blobs outside "
        "HBM, serve/tierstore.py)")
    high_water_pages: dict[str, int] = Field(
        default_factory=dict, description="Peak pages per state since "
        "engine start ('used' = total minus free)")
    time_to_exhaustion_s: Optional[float] = Field(
        None, description="Free-pool runway at the recent token burn "
        "rate, seconds (null when idle or not paged — unknown is not "
        "exhausted)")
    kv_pool_capacity_drops: int = Field(
        0, description="THIS engine's pool-capacity truncations "
        "(engine-scoped; /serving_stats/ top level keeps the "
        "process-wide total)")
    unpin_underflows: int = Field(
        0, description="THIS engine's prefix-cache refcount underflows, "
        "carried across crash-recovery cache reallocations — any nonzero "
        "value is a pin/unpin pairing bug")
    pressure_events: int = Field(
        0, description="Capacity-pressure events: pool-capacity "
        "truncations + QoS preemptions")
    audit_failures: int = Field(
        0, description="Ledger audits that found leaked/orphaned pages "
        "(raises in PENROZ_MEMLEDGER_STRICT=1, counts always)")


class EngineStats(BaseModel):
    """Per-engine snapshot inside ServingStatsResponse (one continuous-
    batching engine per (model, block_size, sampling config))."""
    model_id: str
    block_size: int
    temperature: float
    top_k: Optional[int] = None
    capacity: int = Field(..., description="Decode batch rows "
                          "(PENROZ_SCHED_MAX_ROWS)")
    replica: int = Field(0, description="Data-parallel replica index "
                         "within this model's router group "
                         "(PENROZ_SCHED_REPLICAS; 0 for standalone "
                         "engines)")
    mesh_devices: int = Field(1, description="Devices in this engine's "
                              "serving mesh (PENROZ_SERVE_MESH / "
                              "PENROZ_SERVE_MESH_MODEL; 1 = unmeshed "
                              "single-device engine)")
    role: str = Field("decode", description="Disaggregated-prefill role "
                      "(PENROZ_DISAGG_PREFILL=1): 'prefill' replicas run "
                      "chunked prefill and export KV page blobs; 'decode' "
                      "replicas import them and run the token loop — "
                      "'decode' for every replica when disaggregation "
                      "is off")
    disagg_exports: int = Field(0, description="Finished prefills exported "
                                "as page blobs and handed to a decode "
                                "replica (prefill replicas)")
    disagg_imports: int = Field(0, description="Hand-off page blobs "
                                "imported and admitted directly in the "
                                "DECODE phase (decode replicas)")
    disagg_handoff_failures: int = Field(
        0, description="Hand-offs that fell back to monolithic prefill "
        "(export or import failure; the request still completes)")
    disagg_handoff_ms_p50: Optional[float] = Field(
        None, description="Median prefill-complete → decode-replica first "
        "token per hand-off (export + blob staging + placement + import)")
    disagg_handoff_ms_p99: Optional[float] = Field(
        None, description="p99 hand-off latency")
    disagg_transport: str = Field(
        "d2d", description="Hand-off transport in effect "
        "(PENROZ_DISAGG_TRANSPORT): 'd2d' hands device arrays across "
        "meshes via jax.device_put, 'host' stages a CRC-checked shm "
        "page blob; d2d falls back to host per hand-off on failure")
    disagg_role_changes: int = Field(
        0, description="Elastic role flips this engine applied at drain "
        "boundaries (PENROZ_DISAGG_ELASTIC=1)")
    pipe_stages: int = Field(1, description="Pipeline stages in this "
                             "engine's serving group "
                             "(PENROZ_SERVE_PIPE_STAGES; 1 = unpiped)")
    pipe_microblocks: int = Field(0, description="Micro-blocks the mixed "
                                  "batch splits into per pipeline tick "
                                  "(PENROZ_SERVE_PIPE_BLOCKS, >= stages; "
                                  "0 = unpiped)")
    pipe_ticks: int = Field(0, description="Pipeline schedule ticks over "
                            "the engine lifetime (stage-unit rounds)")
    pipe_bubble_fraction: Optional[float] = Field(
        None, description="Lifetime idle share of stage-ticks: "
        "bubble_ticks / (pipe_ticks × stages).  Null before the first "
        "pipeline tick or when unpiped")
    pipe_stage_busy: dict[str, int] = Field(
        default_factory=dict, description="Stage-unit dispatches per "
        "stage index (balanced stages decode in lockstep; a skewed "
        "count means a stage is starving)")
    pipe_handoffs: int = Field(0, description="Stage-to-stage activation "
                               "hand-offs (device-array transfers, PR 16 "
                               "d2d style)")
    pipe_handoff_host_fallbacks: int = Field(
        0, description="Hand-offs re-staged through the host after a "
        "pipe.handoff fault mid-transfer (contained; numerics "
        "identical)")
    sessions_hibernated: int = Field(
        0, description="Session-tagged retirements whose KV this engine "
        "parked in the radix cache for tier demotion instead of freeing "
        "(serve/tierstore.py)")
    session_promotions: int = Field(
        0, description="Admissions this engine woke from a hibernated "
        "blob (host/disk tier import through the prefix cache) — "
        "HBM-fast wakes ride the normal radix hit and count only in the "
        "store's tier_promotions")
    session_resume_ttft_ms_p50: Optional[float] = Field(
        None, description="Median enqueue → first token for session-"
        "resume admissions (any wake tier)")
    session_resume_ttft_ms_p99: Optional[float] = Field(
        None, description="p99 session-resume TTFT")
    active_rows: int
    queue_depth: int
    occupancy: float = Field(..., description="active_rows / capacity now")
    occupancy_avg: float = Field(..., description="Mean occupancy over all "
                                 "decode steps")
    decode_steps: int
    decode_tokens: int
    decode_tokens_per_sec: float = Field(..., description="Over a 30s "
                                         "sliding window")
    admissions: int
    completed: int
    admission_latency_ms_p50: Optional[float] = Field(
        None, description="Enqueue → prefill-complete latency median")
    prefill_chunks: int = Field(0, description="Chunked-prefill dispatches "
                                "(PENROZ_PREFILL_CHUNK-sized + pow-2 tail)")
    prefill_chunk_stall_ms_p99: Optional[float] = Field(
        None, description="p99 decode-batch stall injected per step "
        "boundary by interleaved prefill chunks")
    prefill_max_chunks_between_steps: int = Field(
        0, description="Max chunks ever run between two decode steps "
        "(1 unless PENROZ_SCHED_MAX_STALL_MS budgets more)")
    prefix_cache: Optional[PrefixCacheStats] = Field(
        None, description="null unless PENROZ_PREFIX_CACHE=1 with the "
        "paged pool")
    kv_pool_capacity_drops: int = Field(
        0, description="Pool-capacity truncations attributed to THIS "
        "engine by its ledger (the process-wide total stays on "
        "/serving_stats/ and /metrics)")
    unpin_underflows: int = Field(
        0, description="Prefix-cache refcount underflows attributed to "
        "THIS engine, surviving crash-recovery cache swaps")
    memory: EngineMemory = Field(..., description="Capacity-ledger "
                                 "snapshot: per-page ownership, byte "
                                 "components, high-water marks, "
                                 "time-to-exhaustion")
    queue_rejections: int = Field(0, description="Requests shed 429 at a "
                                  "full admission queue "
                                  "(PENROZ_SCHED_MAX_QUEUE / per-class "
                                  "PENROZ_QOS_MAX_QUEUE_*)")
    deadline_timeouts: int = Field(0, description="Requests shed 504 "
                                   "(queued) or retired mid-flight on an "
                                   "expired deadline")
    breaker_rejections: int = Field(0, description="Submits refused 503 "
                                    "while the circuit breaker was open")
    quota_rejections: int = Field(0, description="Admissions shed 429 by "
                                  "an exhausted tenant token bucket "
                                  "(PENROZ_QOS_TENANT_TOKENS_PER_S / PUT "
                                  "/tenants/{id}/quota)")
    preemptions: int = Field(0, description="Decode rows evicted mid-"
                             "generation for a queued interactive "
                             "admission (PENROZ_QOS_PREEMPT)")
    preempted_resume_cached_tokens: int = Field(
        0, description="Prompt+generated tokens restored from the prefix "
        "cache — zero recompute — when preempted requests resumed")
    queue_depth_by_class: dict[str, int] = Field(
        default_factory=dict, description="Waiting requests per SLO class "
        "(interactive/standard/batch)")
    admissions_by_class: dict[str, int] = Field(
        default_factory=dict, description="Rows admitted per SLO class "
        "over the engine lifetime")
    tenant_tokens: dict[str, int] = Field(
        default_factory=dict, description="Tokens emitted per tenant id "
        "(quota accounting view; tenant = explicit field > adapter id > "
        "'default')")
    ttft_ms_p99_by_class: dict[str, Optional[float]] = Field(
        default_factory=dict, description="p99 enqueue → first token per "
        "SLO class (null before any admission of that class)")
    queue_wait_ms_p99_by_class: dict[str, Optional[float]] = Field(
        default_factory=dict, description="p99 enqueue → admission wait "
        "per SLO class")
    queue_wait_ms_p99: Optional[float] = Field(
        None, description="p99 enqueue → admission (prefill start) wait")
    breaker_open: bool = Field(False, description="Circuit breaker state "
                               "(PENROZ_ENGINE_MAX_CRASHES consecutive "
                               "crashes open it; a successful probe "
                               "closes it)")
    stuck: bool = Field(False, description="Worker-tick watchdog verdict: "
                        "the worker has been inside ONE tick dispatch "
                        "longer than PENROZ_TICK_WATCHDOG_MS (0/unset = "
                        "watchdog off; computed at read time — /readyz "
                        "503s only when a model has NO unstuck replica)")
    consecutive_crashes: int = Field(0, description="Tick crashes since "
                                     "the last successfully completed "
                                     "request")
    crashes_total: int = Field(0, description="Tick crashes over the "
                               "engine lifetime")
    engine_resets: int = Field(0, description="Full KV/prefix-state "
                               "reallocations after crashes")
    lora_active_adapters: int = Field(0, description="LoRA adapters "
                                      "occupying live slots of this "
                                      "engine's stacked pack "
                                      "(PENROZ_LORA_MAX_LIVE cap)")
    lora_rows: int = Field(0, description="In-flight rows bound to an "
                           "adapter (the rest decode the base model)")
    lora_adapter_tokens: dict[str, int] = Field(
        default_factory=dict, description="Tokens emitted per adapter id "
        "over the engine lifetime (multi-tenant accounting)")
    ssm_rows: int = Field(0, description="In-flight rows carrying O(1) "
                          "recurrent (SSM) state — nonzero only when the "
                          "served arch has ssm blocks")
    ssm_state_bytes: int = Field(0, description="HBM bytes of the engine's "
                                 "recurrent-state planes (states + rollback "
                                 "checkpoint ring); constant w.r.t. "
                                 "generated length by construction")
    spec_decode: bool = Field(False, description="Speculative decoding "
                              "active on this engine (PENROZ_SPEC_DECODE=1; "
                              "greedy engines verify by argmax match, "
                              "non-greedy unified engines by rejection "
                              "sampling against the positional keys)")
    spec_verify_steps: int = Field(0, description="Multi-token verify "
                                   "dispatches (one per drafted row per "
                                   "decode tick)")
    spec_drafted_tokens: int = Field(0, description="Prompt-lookup draft "
                                     "tokens proposed (PENROZ_SPEC_K cap)")
    spec_accepted_tokens: int = Field(0, description="Draft tokens the "
                                      "verify step accepted (greedy-"
                                      "matching prefix)")
    spec_accept_rate: Optional[float] = Field(
        None, description="spec_accepted_tokens / spec_drafted_tokens "
        "(null before any draft)")
    tokens_per_decode_step: float = Field(
        0.0, description="decode_tokens / decode_steps — >1 per active "
        "row means speculation is paying (a plain step emits exactly one "
        "token per decoding row; a fused superstep counts as N steps, so "
        "this stays a speculation metric)")
    superstep: int = Field(1, description="Configured "
                           "PENROZ_SCHED_SUPERSTEP — max decode steps "
                           "fused per dispatch (1 = legacy per-token "
                           "dispatch loop)")
    dispatches_total: int = Field(0, description="Decode-path device "
                                  "round trips (shared steps + verify "
                                  "steps + fused supersteps) — what the "
                                  "compiled multi-step decode path "
                                  "shrinks per token")
    tokens_per_dispatch_avg: Optional[float] = Field(
        None, description="Mean tokens emitted per decode dispatch "
        "(histogram-backed; ≈ superstep for unconstrained fused decode, "
        "1.0 on the legacy path — distinct from tokens_per_decode_step, "
        "which measures speculation not fusing)")
    tokens_per_dispatch_p50: Optional[float] = Field(
        None, description="Median tokens emitted per decode dispatch")
    ttft_ms_p99: Optional[float] = Field(
        None, description="p99 enqueue → first token (histogram-derived, "
        "like every percentile here — never a truncated-sample p99)")
    itl_ms_p50: Optional[float] = Field(
        None, description="Median inter-token latency per decoding row")
    itl_ms_p99: Optional[float] = Field(
        None, description="p99 inter-token latency per decoding row")
    tick_ms_p50: Optional[float] = Field(
        None, description="Median scheduler-tick dispatch wall time")
    tick_ms_p99: Optional[float] = Field(
        None, description="p99 scheduler-tick dispatch wall time")
    tick_timeline: list[TickRecord] = Field(
        default_factory=list, description="Recent ticks (newest-first cap "
        "120 of the PENROZ_TICK_TIMELINE ring): phase composition, "
        "occupancy, dispatch wall time")


class ServingStatsResponse(BaseModel):
    """GET /serving_stats/ — continuous-batching scheduler observability
    (serve/decode_scheduler.py)."""
    continuous_batching_enabled: bool
    engines: list[EngineStats]
    capacity: int
    active_rows: int
    queue_depth: int
    queue_rejections: int = Field(0, description="Aggregate 429 queue-full "
                                  "sheds")
    deadline_timeouts: int = Field(0, description="Aggregate deadline "
                                   "expiries (queued + in flight)")
    quota_rejections: int = Field(0, description="Aggregate 429 tenant-"
                                  "quota sheds")
    preemptions_total: int = Field(0, description="Aggregate mid-"
                                   "generation row evictions for "
                                   "interactive admissions")
    preempted_resume_cached_tokens: int = Field(
        0, description="Aggregate tokens restored from the prefix cache "
        "(zero recompute) when preempted requests resumed")
    queue_depth_by_class: dict[str, int] = Field(
        default_factory=dict, description="Aggregate waiting requests per "
        "SLO class")
    tenant_tokens: dict[str, int] = Field(
        default_factory=dict, description="Aggregate tokens emitted per "
        "tenant id")
    ttft_ms_p99_by_class: dict[str, Optional[float]] = Field(
        default_factory=dict, description="p99 enqueue → first token per "
        "SLO class across engines (merged histogram buckets)")
    queue_wait_ms_p99_by_class: dict[str, Optional[float]] = Field(
        default_factory=dict, description="p99 enqueue → admission wait "
        "per SLO class across engines")
    queue_wait_ms_p99: Optional[float] = Field(
        None, description="p99 enqueue → admission wait across engines")
    breaker_open: bool = Field(False, description="True if ANY engine's "
                               "circuit breaker is open (/readyz mirrors "
                               "this)")
    crashes_total: int = Field(0, description="Aggregate engine tick "
                               "crashes")
    engine_resets: int = Field(0, description="Aggregate post-crash engine "
                               "resets")
    draining: bool = Field(False, description="Graceful shutdown in "
                           "progress (admission stopped)")
    batch_occupancy: float
    decode_tokens_per_sec: float
    admission_latency_ms_p50: Optional[float] = None
    ttft_ms_p99: Optional[float] = Field(
        None, description="p99 enqueue → first token across engines "
        "(merged histogram buckets, not truncated samples)")
    itl_ms_p50: Optional[float] = Field(
        None, description="Median inter-token latency across engines")
    itl_ms_p99: Optional[float] = Field(
        None, description="p99 inter-token latency across engines")
    tick_ms_p50: Optional[float] = Field(
        None, description="Median scheduler-tick dispatch wall time "
        "across engines")
    tick_ms_p99: Optional[float] = Field(
        None, description="p99 scheduler-tick dispatch wall time across "
        "engines")
    tick_timeline: list[TickRecord] = Field(
        default_factory=list, description="Merged recent ticks across "
        "engines (newest-first, cap 120) — the dashboard "
        "occupancy/latency strip")
    prefill_chunk_stall_ms_p99: Optional[float] = Field(
        None, description="p99 prefill-chunk stall across engines")
    prefix_cache_hit_rate: Optional[float] = Field(
        None, description="Aggregate radix prefix-cache hit rate (null "
        "when no engine runs a prefix cache)")
    prefix_cache_evicted_pages: int = Field(
        0, description="Aggregate LRU-evicted prefix-cache pages")
    lora_active_adapters: int = Field(0, description="Aggregate live "
                                      "adapter slots across engines")
    lora_rows: int = Field(0, description="Aggregate in-flight adapter-"
                           "bound rows")
    lora_adapter_tokens: dict[str, int] = Field(
        default_factory=dict, description="Aggregate tokens emitted per "
        "adapter id")
    ssm_rows: int = Field(0, description="Aggregate in-flight rows carrying "
                          "O(1) recurrent (SSM) state")
    ssm_state_bytes: int = Field(0, description="Aggregate HBM bytes of "
                                 "recurrent-state planes across engines")
    spec_decode_enabled: bool = Field(False, description="PENROZ_SPEC_DECODE"
                                      "=1 (greedy engines draft via prompt "
                                      "lookup + multi-token verify steps)")
    spec_drafted_tokens: int = Field(0, description="Aggregate draft "
                                     "tokens proposed")
    spec_accepted_tokens: int = Field(0, description="Aggregate draft "
                                      "tokens accepted")
    spec_accept_rate: Optional[float] = Field(
        None, description="Aggregate accepted/drafted (null before any "
        "draft)")
    tokens_per_decode_step: float = Field(
        0.0, description="Aggregate decode_tokens / decode_steps across "
        "engines")
    dispatches_total: int = Field(0, description="Aggregate decode-path "
                                  "device round trips (shared + verify + "
                                  "superstep dispatches)")
    tokens_per_dispatch_avg: Optional[float] = Field(
        None, description="Mean tokens per decode dispatch across engines "
        "(merged histogram; ≈ PENROZ_SCHED_SUPERSTEP for unconstrained "
        "fused decode)")
    tokens_per_dispatch_p50: Optional[float] = Field(
        None, description="Median tokens per decode dispatch across "
        "engines")
    kv_pool_capacity_drops: int = Field(..., description="KV writes dropped "
                                        "at pool capacity (process-wide; "
                                        "ops/kv_cache.py record_pool_drop)")
    unpin_underflows: int = Field(0, description="Prefix-cache refcount "
                                  "underflows (process-wide module "
                                  "counter, byte-compatible with the "
                                  "/metrics gauge; per-engine attribution "
                                  "lives on each engine's ledger)")
    router_replicas: int = Field(
        0, description="Live data-parallel engine replicas owned by "
        "routers (serve/router.py; 0 = no router, "
        "PENROZ_SCHED_REPLICAS=1 single-engine registry)")
    router_affinity_hits: int = Field(
        0, description="Fingerprinted admissions steered to the replica "
        "whose radix prefix cache holds the prompt's pages")
    router_affinity_misses: int = Field(
        0, description="Fingerprinted admissions placed anywhere else "
        "(cold prefix, affinity off, or target replica refused)")
    router_affinity_hit_rate: Optional[float] = Field(
        None, description="hits / (hits + misses); null before any "
        "fingerprinted admission")
    router_failovers: int = Field(
        0, description="Admissions rerouted past a refusing replica "
        "(breaker open, queue full, draining) to a live sibling — the "
        "no-503-while-one-replica-is-healthy counter")
    disagg_prefill_replicas: int = Field(
        0, description="Live prefill-only replicas across routers "
        "(PENROZ_DISAGG_PREFILL=1 + PENROZ_DISAGG_PREFILL_REPLICAS; "
        "0 = disaggregation off, every replica co-locates both phases)")
    disagg_exports: int = Field(0, description="Aggregate KV page-blob "
                                "exports by prefill replicas")
    disagg_imports: int = Field(0, description="Aggregate hand-off "
                                "imports admitted by decode replicas")
    disagg_handoff_failures: int = Field(
        0, description="Aggregate hand-offs that fell back to monolithic "
        "prefill")
    disagg_handoff_ms_p50: Optional[float] = Field(
        None, description="Median hand-off latency across engines "
        "(merged histogram buckets)")
    disagg_handoff_ms_p99: Optional[float] = Field(
        None, description="p99 hand-off latency across engines")
    disagg_transport: str = Field(
        "d2d", description="Hand-off transport in effect "
        "(PENROZ_DISAGG_TRANSPORT): 'd2d' device-array hand-over, "
        "'host' staged shm page blob")
    disagg_role_changes: int = Field(
        0, description="Aggregate elastic role flips applied across "
        "engines (PENROZ_DISAGG_ELASTIC=1)")
    pipe_stages: int = Field(
        1, description="Widest pipeline group across engines "
        "(PENROZ_SERVE_PIPE_STAGES; 1 = no piped engine)")
    pipe_ticks: int = Field(
        0, description="Aggregate pipeline schedule ticks across piped "
        "engines")
    pipe_bubble_fraction: Optional[float] = Field(
        None, description="Stage-tick-weighted idle share across every "
        "piped engine (null until any pipeline group ticks)")
    pipe_handoffs: int = Field(
        0, description="Aggregate stage-to-stage activation hand-offs")
    pipe_handoff_host_fallbacks: int = Field(
        0, description="Aggregate hand-offs re-staged through the host "
        "after a pipe.handoff fault")
    sessions_resident: int = Field(
        0, description="Hibernated sessions currently resident in any "
        "tier (process-wide tier store, serve/tierstore.py; "
        "penroz_sessions_resident)")
    sessions_by_tier: dict[str, int] = Field(
        default_factory=dict, description="Resident hibernated sessions "
        "per tier: hbm (pinned radix pages awaiting demotion) | host "
        "(pinned host-RAM blob) | disk (CRC-checked blob under "
        "PENROZ_TIER_DISK_PATH)")
    tier_bytes: dict[str, int] = Field(
        default_factory=dict, description="Hibernated-session bytes per "
        "lower tier (host_tier / disk_tier) — the /memory/ aggregate "
        "reports the same values inside hbm_bytes")
    tier_promotions: dict[str, int] = Field(
        default_factory=dict, description="Session wake attempts by "
        "outcome: ok | partial (radix alloc exhausted mid-import) | "
        "stale (model reloaded since hibernation) | corrupt (disk blob "
        "failed CRC — recomputed, never served) | miss (blob vanished). "
        "penroz_tier_promotions_total{tier,outcome} keeps the per-tier "
        "split")
    tier_demotions: dict[str, int] = Field(
        default_factory=dict, description="Background demotions per "
        "destination tier (host = HBM export, disk = host-cap spill; "
        "penroz_tier_demotions_total{tier})")
    tier_corrupt_blobs: int = Field(
        0, description="Disk-tier blobs that failed CRC/container "
        "validation and were treated as misses "
        "(penroz_tier_corrupt_blobs_total)")
    sessions_hibernated: int = Field(
        0, description="Aggregate session-tagged retirements parked for "
        "tiering across engines (penroz_sessions_hibernated_total)")
    session_promotions: int = Field(
        0, description="Aggregate blob-import session wakes across "
        "engines")
    session_resume_ttft_ms_p50: Optional[float] = Field(
        None, description="Median session-resume TTFT across engines "
        "(merged histogram buckets; penroz_session_resume_ttft_ms)")
    session_resume_ttft_ms_p99: Optional[float] = Field(
        None, description="p99 session-resume TTFT across engines")
    journal: dict = Field(
        default_factory=dict, description="Write-ahead journal counters "
        "(serve/journal.py): enabled, fsync policy, records in the "
        "current log, lifetime appends/append_errors, bad_records + "
        "truncated_bytes dropped by torn-tail replay truncation, "
        "compactions, last replay_ms")
    restart_recovery: dict = Field(
        default_factory=dict, description="Summary of the last "
        "tierstore.recover() (runs at create_app, before the socket "
        "binds): records_replayed, sessions_recovered/volatile/stale/"
        "blob_missing/blob_corrupt, quota_overrides_replayed, "
        "blobs_swept + temp_files_swept, replay_ms — empty before any "
        "recovery ran")
    streams: dict = Field(
        default_factory=dict, description="Resumable-stream registry "
        "(serve/streams.py): active/detached rings, lifetime detaches/"
        "resumes/expired, PENROZ_STREAM_REPLAY ring capacity and "
        "PENROZ_STREAM_DETACH_MS grace in effect")
    engines_stuck: int = Field(
        0, description="Engines currently failing the worker-tick "
        "watchdog, group-aware (penroz_engine_stuck gauge; names appear "
        "in /readyz stuck_engines)")


class SessionInfo(BaseModel):
    """One hibernated session's residency record (GET /sessions/)."""
    session_id: str
    tenant: str = Field(..., description="Tenant charged for the "
                        "session's tier residency (tier quota)")
    model_id: str
    tier: str = Field(..., description="DEEPEST copy: 'hbm' (pinned "
                      "radix pages awaiting demotion) | 'host' | 'disk'")
    tokens: int = Field(..., description="Whole-page KV tokens resident "
                        "(prompt + generated, floor to page size)")
    pages: int = Field(..., description="KV pool pages the session spans")
    nbytes: int = Field(..., description="Bytes the resident copy holds "
                        "in its tier")
    replica: int = Field(0, description="Replica that hibernated the "
                         "session (wake may land anywhere — the match "
                         "is content-addressed)")
    age_s: float = Field(..., description="Seconds since hibernation "
                         "registration")
    idle_s: float = Field(..., description="Seconds since last "
                          "hibernate/match touch (LRU age)")


class SessionsResponse(BaseModel):
    """GET /sessions/ — hibernated-session residency across every tier
    (process-wide; one listing covers all engines and replicas)."""
    sessions: list[SessionInfo] = Field(
        default_factory=list, description="LRU order, oldest first")
    sessions_resident: int = Field(0, description="len(sessions)")
    sessions_by_tier: dict[str, int] = Field(
        default_factory=dict, description="Resident count per tier "
        "(hbm/host/disk)")
    tier_bytes: dict[str, int] = Field(
        default_factory=dict, description="Bytes per lower tier "
        "(host_tier/disk_tier)")


class DeleteSessionResponse(BaseModel):
    """DELETE /sessions/{session_id} — evict one hibernated session from
    every tier (the disk blob is unlinked; a pinned hbm-tier hold is
    released by its engine at the next loop boundary)."""
    session_id: str
    deleted: bool = Field(..., description="False when the session was "
                          "not resident (still 200 — deletion is "
                          "idempotent)")


class MemoryEngineEntry(EngineMemory):
    """Per-engine entry of MemoryResponse: the ledger snapshot plus the
    engine identity it belongs to."""
    model_id: str
    block_size: int
    capacity: int = Field(..., description="Decode batch rows "
                          "(PENROZ_SCHED_MAX_ROWS)")
    replica: int = Field(0, description="Data-parallel replica index "
                         "within the model's router group (0 for "
                         "standalone engines) — the partition invariant "
                         "holds per replica")
    role: str = Field("decode", description="Disaggregated-prefill role "
                      "of this replica ('prefill' | 'decode'; 'decode' "
                      "when disaggregation is off)")
    disagg_transport: str = Field(
        "d2d", description="Hand-off transport in effect for this "
        "replica (PENROZ_DISAGG_TRANSPORT: 'd2d' | 'host')")


class MemoryResponse(BaseModel):
    """GET /memory/ — the HBM capacity ledger (serve/memledger.py):
    who owns every page of serving memory right now, across engines."""
    memledger_enabled: bool = Field(..., description="False only with "
                                    "PENROZ_MEMLEDGER=0 (page walks "
                                    "skipped; snapshots empty)")
    engines: list[MemoryEngineEntry]
    pool_pages: dict[str, int] = Field(
        default_factory=dict, description="Aggregate pages per owner "
        "state across engines (penroz_pool_pages{state} mirrors this)")
    tenant_pages: dict[str, int] = Field(
        default_factory=dict, description="Aggregate row-owned pages per "
        "tenant (penroz_tenant_kv_pages{tenant})")
    hbm_bytes: dict[str, int] = Field(
        default_factory=dict, description="Aggregate bytes per component "
        "incl. adapter_host_cache and the off-HBM KV tiers host_tier / "
        "disk_tier (penroz_hbm_bytes{component})")
    high_water_pages: dict[str, int] = Field(
        default_factory=dict, description="Aggregate per-state peaks "
        "(sum of engine peaks — engines peak independently)")
    time_to_exhaustion_s: Optional[float] = Field(
        None, description="MOST-PRESSED engine's free-pool runway at its "
        "current burn rate (null when no engine has a recent rate)")
    kv_pool_capacity_drops: int = Field(
        0, description="Process-wide pool-capacity truncations "
        "(ops/kv_cache.py counter — byte-compatible with /metrics)")
    unpin_underflows: int = Field(
        0, description="Process-wide prefix-cache refcount underflows")
    pressure_events: int = Field(
        0, description="Aggregate capacity-pressure events")
    audit_failures: int = Field(
        0, description="Aggregate ledger-audit failures (leaks/orphans)")
    flight_records: int = Field(
        0, description="Crash snapshots captured into the flight-recorder "
        "ring (GET /debug/dump)")


class DebugDumpResponse(BaseModel):
    """GET /debug/dump — the engine flight recorder: bounded ring of
    pre-crash snapshots (ledger + tick timeline + queue depths + recent
    trace ids) captured at every engine_crash / circuit_open /
    reset_failed, BEFORE recovery throws the evidence away."""
    capacity: int = Field(..., description="Ring size "
                          "(PENROZ_DEBUG_DUMP_RING, default 8)")
    recorded: int = Field(..., description="Snapshots captured over the "
                          "process lifetime (ring keeps the newest)")
    entries: list[dict] = Field(
        default_factory=list, description="Oldest-first ring contents; "
        "each entry: unix_ts, reason (engine_crash|circuit_open|"
        "reset_failed), error, model_id, block_size, crashes_total, "
        "engine_resets, active_rows, queue_depth, ledger (EngineMemory "
        "shape), tick_timeline (last PENROZ_DEBUG_DUMP_TICKS TickRecords), "
        "queue_depth_by_class, queue_depth_by_tenant, recent_traces "
        "{completed, live}")
    restart_recovery: dict = Field(
        default_factory=dict, description="The last tierstore.recover() "
        "summary (journal replay + disk-tier cross-check + orphan "
        "sweeps); empty before any recovery ran this process")


class ProfileRequest(BaseModel):
    action: str = Field(..., description="'start' or 'stop' a jax.profiler "
                        "trace capture.")
    log_dir: str = Field("profiles", description="Directory for the captured "
                         "trace (start only); view with TensorBoard/Perfetto.")
