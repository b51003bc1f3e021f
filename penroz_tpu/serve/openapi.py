"""OpenAPI 3.1 spec + interactive docs for the REST API.

The reference gets ``/docs`` and ``openapi.json`` for free from FastAPI,
including a full GPT-2-124M layer DSL as the ``/model/`` request example
(reference: main.py:53-93).  The aiohttp service generates the equivalent
here from the same pydantic request models (serve/schemas.py):

- :func:`build_spec` — OpenAPI document with component schemas from
  ``pydantic.json_schema.models_json_schema`` and a per-route table below.
- ``/docs`` — self-contained HTML that fetches ``/openapi.json`` and renders
  it client-side (no CDN dependency, works in an egress-less sandbox).
"""

from __future__ import annotations

import json
from typing import Optional

from pydantic.json_schema import models_json_schema

from penroz_tpu.serve import schemas


def gpt2_124m_example() -> dict:
    """The ``/model/`` example request: a GPT-2-124M layer DSL (mirrors the
    reference's OpenAPI example, main.py:53-93, expressed through the same
    DSL this framework trains/imports)."""
    vocab, d, heads, block, depth = 50257, 768, 12, 1024, 12
    attn_block = {"sequential": [
        {"layernorm": {"normalized_shape": d}},
        {"linear": {"in_features": d, "out_features": 3 * d},
         "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
        {"attention": {"num_heads": heads, "dropout": 0.1}},
        {"linear": {"in_features": d, "out_features": d},
         "normal": {"mean": 0.0, "std": 0.02 / (2 * depth) ** 0.5},
         "zeros": {}},
        {"dropout": {"p": 0.1}}]}
    mlp_block = {"sequential": [
        {"layernorm": {"normalized_shape": d}},
        {"linear": {"in_features": d, "out_features": 4 * d},
         "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
        {"gelu": {"approximate": "tanh"}},
        {"linear": {"in_features": 4 * d, "out_features": d},
         "normal": {"mean": 0.0, "std": 0.02 / (2 * depth) ** 0.5},
         "zeros": {}},
        {"dropout": {"p": 0.1}}]}
    layers = ([{"summation": [
                  {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
                   "normal": {"mean": 0.0, "std": 0.02}},
                  {"position": {"num_embeddings": block, "embedding_dim": d},
                   "normal": {"mean": 0.0, "std": 0.02}}]},
               {"dropout": {"p": 0.1}}]
              + [{"residual": [attn_block, mlp_block]} for _ in range(depth)]
              + [{"layernorm": {"normalized_shape": d}},
                 {"linear": {"in_features": d, "out_features": vocab,
                             "bias": False},
                  "normal": {"mean": 0.0, "std": 0.02}},
                 {"softmaxlast": {"dim": -1}}])
    return {
        "model_id": "gpt2-124M",
        "layers": layers,
        "optimizer": {"adamw": {"lr": 6e-4, "betas": [0.9, 0.95],
                                "eps": 1e-8, "weight_decay": 0.1}},
    }


def _query_params(*names: str) -> list[dict]:
    return [{"name": n, "in": "query", "required": True,
             "schema": {"type": "string"}} for n in names]


def _body(model_name: str, example: Optional[dict] = None) -> dict:
    media: dict = {"schema": {"$ref": f"#/components/schemas/{model_name}"}}
    if example is not None:
        media["example"] = example
    return {"required": True, "content": {"application/json": media}}


def _resp(status: int, description: str) -> tuple[str, dict]:
    return str(status), {"description": description}


# (method, path, summary, request model or query params, responses, extra)
def _routes() -> list[dict]:
    ok = _resp(200, "Success")
    return [
        dict(method="get", path="/dashboard", summary="Training dashboard",
             responses=dict([_resp(200, "HTML dashboard")])),
        dict(method="get", path="/healthz",
             summary="Liveness probe (always 200 while the loop answers)",
             responses=dict([_resp(200, "Alive")])),
        dict(method="get", path="/readyz",
             summary="Readiness probe: 503 while any engine circuit "
                     "breaker is open or shutdown is draining",
             responses=dict([_resp(200, "Ready to serve"),
                             _resp(503, "Breaker open or draining")])),
        dict(method="get", path="/metrics",
             summary="Prometheus text exposition (format 0.0.4, "
                     "dependency-free): request/token/shed/crash "
                     "counters, engine gauges, and fixed-bucket TTFT / "
                     "ITL / queue-wait / chunk-stall / tick-duration "
                     "histograms, and penroz_train_span_ms{span}: every "
                     "span of /train/ jobs by name",
             responses=dict([_resp(200, "text/plain exposition")])),
        dict(method="get", path="/trace/",
             summary="Recent per-request trace summaries (completed ring "
                     "of PENROZ_TRACE_BUFFER + in-flight), sampled via "
                     "PENROZ_TRACE_SAMPLE; training jobs are listed too "
                     "(route '/train/', model_id, end status)",
             responses=dict([_resp(200, "Trace summaries")])),
        dict(method="get", path="/trace/{request_id}",
             summary="One request's lifecycle span tree: queue wait, "
                     "prefix-cache match, prefill chunks, decode/verify "
                     "steps, crash-recovery events, retirement reason "
                     "(request ids come from the X-Request-Id response "
                     "header).  For a PUT /train/ job, live and after: "
                     "penroz/train_setup, load_batch, train_epoch "
                     "(train_dispatch + train_wait), train_stats, "
                     "ckpt_save (ckpt_d2h, ckpt_encode, ckpt_write, "
                     "ckpt_flush), compile; the newest subtrees, "
                     "dropped_spans and per-name totals.  "
                     "?format=chrome returns the same tree as "
                     "Chrome trace-event JSON loadable in Perfetto / "
                     "chrome://tracing",
             params=[{"name": "format", "in": "query", "required": False,
                      "schema": {"type": "string",
                                 "enum": ["json", "chrome"],
                                 "default": "json"}}],
             responses=dict([_resp(200, "Span tree (or Chrome "
                                        "trace-event JSON)"),
                             _resp(404, "Unknown/evicted request id"),
                             _resp(422, "Unknown format")])),
        dict(method="get", path="/memory/",
             summary="HBM capacity ledger: every paged-pool page "
                     "attributed to an owner (free / active row / "
                     "prefix-cache pinned vs evictable / preempted "
                     "session / reserved tail), per-tenant and "
                     "per-adapter page counts, byte accounting per HBM "
                     "component (KV values/scales/block tables, LoRA "
                     "pack, params), high-water marks, and a token-burn "
                     "time-to-exhaustion estimate "
                     "(PENROZ_MEMLEDGER gates the ledger; "
                     "PENROZ_MEMLEDGER_STRICT turns audit failures into "
                     "crashes)",
             responses={"200": {
                 "description": "Memory ledger",
                 "content": {"application/json": {"schema": {
                     "$ref": "#/components/schemas/MemoryResponse"}}},
             }}),
        dict(method="get", path="/debug/dump",
             summary="Crash flight recorder: the last "
                     "PENROZ_DEBUG_DUMP_RING engine_crash / circuit_open "
                     "snapshots, each carrying the pre-crash memory "
                     "ledger, the last PENROZ_DEBUG_DUMP_TICKS tick "
                     "records, per-class/per-tenant queue depths, and "
                     "recent trace ids",
             responses={"200": {
                 "description": "Flight-recorder dump",
                 "content": {"application/json": {"schema": {
                     "$ref": "#/components/schemas/DebugDumpResponse"}}},
             }}),
        dict(method="post", path="/model/",
             summary="Create a model from the layer/optimizer DSL",
             body=_body("CreateModelRequest", gpt2_124m_example()),
             responses=dict([ok, _resp(400, "Invalid DSL"),
                             _resp(422, "Validation error")])),
        dict(method="post", path="/import/",
             summary="Import GPT-2/Gemma weights from HuggingFace",
             body=_body("ImportModelRequest"),
             responses=dict([ok, _resp(409, "Import already in progress")])),
        dict(method="get", path="/dataset/", summary="List dataset shards",
             params=_query_params("dataset_id"),
             responses=dict([ok, _resp(404, "Unknown dataset")])),
        dict(method="post", path="/dataset/",
             summary="Download + tokenize + shard a HuggingFace dataset",
             body=_body("DownloadDatasetRequest"),
             responses=dict([_resp(202, "Download started"),
                             _resp(409, "Download already in progress")])),
        dict(method="delete", path="/dataset/", summary="Delete all shards",
             params=_query_params("dataset_id"),
             responses=dict([_resp(204, "Deleted")])),
        dict(method="post", path="/tokenize/", summary="Tokenize text",
             body=_body("TokenizeTextRequest"), responses=dict([ok])),
        dict(method="post", path="/output/",
             summary="Raw forward pass (+ optional cost)",
             body=_body("OutputRequest"),
             responses=dict([ok, _resp(404, "Unknown model")])),
        dict(method="post", path="/evaluate/", summary="Evaluate model cost",
             body=_body("EvaluateRequest"),
             responses=dict([ok, _resp(404, "Unknown model")])),
        dict(method="post", path="/generate/",
             summary="Generate tokens (set stream:true for one per line)",
             body=_body("GenerateRequest"),
             responses=dict([ok, _resp(404, "Unknown model"),
                             _resp(429, "Admission queue full "
                                        "(PENROZ_SCHED_MAX_QUEUE / "
                                        "per-class PENROZ_QOS_MAX_QUEUE_*) "
                                        "or tenant token quota exhausted "
                                        "(PENROZ_QOS_TENANT_TOKENS_PER_S) "
                                        "— retry after the load-aware "
                                        "Retry-After seconds"),
                             _resp(503, "Engine circuit breaker open "
                                        "(PENROZ_ENGINE_MAX_CRASHES "
                                        "consecutive crashes)"),
                             _resp(504, "Request deadline exceeded "
                                        "(timeout_ms / "
                                        "PENROZ_REQ_TIMEOUT_MS)")])),
        dict(method="post", path="/generate_batch/",
             summary="Ragged batched generation: N prompts of different "
                     "lengths share one forward per step",
             body=_body("GenerateBatchRequest"),
             responses=dict([ok, _resp(404, "Unknown model"),
                             _resp(400, "Prompt + max_new_tokens exceeds "
                                        "block_size, or an empty prompt"),
                             _resp(429, "Admission queue full or tenant "
                                        "quota exhausted (any shed row "
                                        "sheds the batch)"),
                             _resp(503, "Engine circuit breaker open"),
                             _resp(504, "Row deadline exceeded")])),
        dict(method="post", path="/decode/", summary="Decode token ids",
             body=_body("DecodeTokensRequest"), responses=dict([ok])),
        dict(method="put", path="/train/",
             summary="Train asynchronously (poll /progress/; with an "
                     "'adapter' config, fine-tune a LoRA adapter against "
                     "the frozen base and poll GET /adapters/).  The "
                     "202's X-Request-Id names the job's trace: GET "
                     "/trace/{id} is its timeline while it runs and after",
             body=_body("TrainingRequest"),
             responses=dict([_resp(202, "Training started; X-Request-Id "
                                        "is the job's trace id"),
                             _resp(404, "Unknown model"),
                             _resp(400, "Invalid device or adapter config"),
                             _resp(409, "Training already in progress")])),
        dict(method="post", path="/adapters/",
             summary="Register a LoRA adapter for a model (zero-init B: "
                     "serves as the base model until trained)",
             body=_body("CreateAdapterRequest"),
             responses=dict([ok, _resp(404, "Unknown model"),
                             _resp(400, "Invalid rank/targets "
                                        "(PENROZ_LORA_MAX_RANK caps rank)"),
                             _resp(409, "Adapter already exists")])),
        dict(method="get", path="/adapters/",
             summary="List adapters (or one adapter's detail + training "
                     "progress with ?adapter_id=)",
             responses=dict([ok, _resp(404, "Unknown adapter")])),
        dict(method="delete", path="/adapters/",
             summary="Delete an adapter (checkpoint + registry cache; "
                     "in-flight rows finish on their copied factors)",
             params=_query_params("adapter_id"),
             responses=dict([_resp(204, "Deleted"),
                             _resp(404, "Unknown adapter")])),
        dict(method="post", path="/profile/",
             summary="Start/stop a jax.profiler trace capture",
             body=_body("ProfileRequest"),
             responses=dict([ok, _resp(409, "Capture state conflict")])),
        dict(method="post", path="/profiler/trace/",
             summary="Alias of /profile/: start/stop a jax.profiler "
                     "capture whose timeline carries the framework's "
                     "penroz/sched_* span annotations",
             body=_body("ProfileRequest"),
             responses=dict([ok, _resp(409, "Capture state conflict")])),
        dict(method="get", path="/progress/",
             summary="Training progress, average cost history, status",
             params=_query_params("model_id"),
             responses=dict([ok, _resp(404, "Unknown model")])),
        dict(method="get", path="/stats/",
             summary="Activation/gradient/weight histograms",
             params=_query_params("model_id"),
             responses=dict([ok, _resp(404, "Unknown model")])),
        dict(method="get", path="/serving_stats/",
             summary="Continuous-batching scheduler stats: queue depth, "
                     "batch occupancy, decode tokens/sec, "
                     "histogram-derived TTFT/ITL/queue-wait/chunk-stall/"
                     "tick percentiles, the tick telemetry timeline, "
                     "prefix-cache hit rate/evictions, "
                     "speculative-decoding accept rate + tokens per "
                     "decode step, LoRA live adapters/rows + per-adapter "
                     "token counts, KV pool-drop counter",
             responses={"200": {
                 "description": "Serving statistics",
                 "content": {"application/json": {"schema": {
                     "$ref": "#/components/schemas/ServingStatsResponse"}}},
             }}),
        dict(method="get", path="/tenants/",
             summary="Tenant quota state: per-tenant rate overrides, "
                     "tokens charged, and quota-shed counts "
                     "(serve/qos.py token buckets)",
             responses=dict([ok])),
        dict(method="put", path="/tenants/{tenant_id}/quota",
             summary="Set (or clear with null) a tenant's token-rate "
                     "override of PENROZ_QOS_TENANT_TOKENS_PER_S; an "
                     "exhausted bucket 429s that tenant's new admissions "
                     "with a refill-derived Retry-After while in-flight "
                     "rows finish",
             body=_body("TenantQuotaRequest"),
             responses=dict([ok, _resp(400, "Negative tokens_per_s or "
                                            "tier_mb"),
                             _resp(422, "Validation error")])),
        dict(method="get", path="/sessions/",
             summary="Hibernated-session residency across the KV tiers "
                     "(HBM radix / host RAM / disk, serve/tierstore.py): "
                     "tier, size, and LRU age per session — a request "
                     "whose prompt extends a resident session's history "
                     "resumes from its pages instead of re-prefilling",
             responses={"200": {
                 "description": "Resident hibernated sessions",
                 "content": {"application/json": {"schema": {
                     "$ref": "#/components/schemas/SessionsResponse"}}},
             }}),
        dict(method="delete", path="/sessions/{session_id}",
             summary="Evict one hibernated session from every tier "
                     "(idempotent; deleted=false when not resident)",
             responses={"200": {
                 "description": "Eviction result",
                 "content": {"application/json": {"schema": {
                     "$ref": "#/components/schemas/DeleteSessionResponse"
                 }}}}}),
        dict(method="delete", path="/model/", summary="Delete a model",
             params=_query_params("model_id"),
             responses=dict([_resp(204, "Deleted")])),
    ]


def build_spec() -> dict:
    models = [
        schemas.CreateModelRequest, schemas.ImportModelRequest,
        schemas.DownloadDatasetRequest, schemas.TokenizeTextRequest,
        schemas.OutputRequest, schemas.EvaluateRequest,
        schemas.GenerateRequest, schemas.GenerateBatchRequest,
        schemas.DecodeTokensRequest,
        schemas.TrainingRequest, schemas.ProfileRequest,
        schemas.CreateAdapterRequest, schemas.TenantQuotaRequest,
        schemas.ServingStatsResponse, schemas.MemoryResponse,
        schemas.DebugDumpResponse, schemas.SessionsResponse,
        schemas.DeleteSessionResponse,
    ]
    _, defs = models_json_schema(
        [(m, "validation") for m in models],
        ref_template="#/components/schemas/{model}")
    paths: dict = {}
    for route in _routes():
        op: dict = {"summary": route["summary"],
                    "responses": route["responses"]}
        if "body" in route:
            op["requestBody"] = route["body"]
        if "params" in route:
            op["parameters"] = route["params"]
        paths.setdefault(route["path"], {})[route["method"]] = op
    return {
        "openapi": "3.1.0",
        "info": {
            "title": "penroz_tpu",
            "version": "1.0.0",
            "description": "TPU-native neural-network service: model "
                           "lifecycle, datasets, training, generation "
                           "(same surface as the reference API).",
        },
        "paths": paths,
        "components": {"schemas": defs.get("$defs", {})},
    }


_DOCS_HTML = """<!DOCTYPE html>
<html><head><meta charset="utf-8"><title>penroz_tpu API docs</title>
<style>
body{font-family:system-ui,sans-serif;margin:2em auto;max-width:960px;color:#222}
h1{font-size:1.5em} .op{border:1px solid #ddd;border-radius:6px;margin:.8em 0}
.hd{display:flex;gap:.8em;align-items:center;padding:.5em .8em;cursor:pointer;background:#fafafa}
.m{font-weight:700;text-transform:uppercase;min-width:4.5em;text-align:center;
   border-radius:4px;padding:.15em .4em;color:#fff;font-size:.85em}
.get{background:#2b7de9}.post{background:#2fa44f}.put{background:#c77d0a}.delete{background:#c0392b}
.body{display:none;padding:.8em;border-top:1px solid #eee}
.op.open .body{display:block}
pre{background:#f6f8fa;padding:.8em;border-radius:6px;overflow:auto;font-size:.85em}
code{background:#f2f2f2;padding:.1em .3em;border-radius:3px}
.resp{margin:.15em 0}
</style></head><body>
<h1>penroz_tpu API</h1>
<p>Spec: <a href="/openapi.json">openapi.json</a></p>
<div id="ops">loading…</div>
<script>
fetch('/openapi.json').then(r=>r.json()).then(spec=>{
  const root=document.getElementById('ops'); root.textContent='';
  for(const [path,methods] of Object.entries(spec.paths)){
    for(const [method,op] of Object.entries(methods)){
      const div=document.createElement('div'); div.className='op';
      const hd=document.createElement('div'); hd.className='hd';
      hd.innerHTML=`<span class="m ${method}">${method}</span>`+
        `<code>${path}</code><span>${op.summary||''}</span>`;
      hd.onclick=()=>div.classList.toggle('open');
      const body=document.createElement('div'); body.className='body';
      let html='';
      if(op.parameters) html+='<p>Query: '+op.parameters.map(p=>
        `<code>${p.name}</code>`).join(' ')+'</p>';
      const ex=op.requestBody?.content?.['application/json']?.example;
      const ref=op.requestBody?.content?.['application/json']?.schema?.$ref;
      if(ref) html+=`<p>Body schema: <code>${ref.split('/').pop()}</code></p>`;
      if(ex) html+='<p>Example:</p><pre>'+
        JSON.stringify(ex,null,1).slice(0,4000)+'</pre>';
      html+='<p>Responses:</p>'+Object.entries(op.responses).map(([c,r])=>
        `<div class="resp"><code>${c}</code> ${r.description||''}</div>`).join('');
      body.innerHTML=html; div.append(hd,body); root.append(div);
    }
  }
});
</script></body></html>"""


def docs_html() -> str:
    return _DOCS_HTML


def spec_json() -> str:
    return json.dumps(build_spec())
