"""REST API tests (aiohttp TestClient — mirrors the reference's FastAPI
TestClient coverage in test_main.py: route behavior, lock 409s, gzip,
streaming, error mapping)."""

import asyncio
import gzip
import json

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from penroz_tpu.serve import app as app_mod

# CI tier: heavier compiles (see pyproject markers / ci.yml shards).
pytestmark = pytest.mark.runtime

TOY_LAYERS = [
    {"embedding": {"num_embeddings": 32, "embedding_dim": 8}},
    {"linear": {"in_features": 8, "out_features": 32}},
    {"softmaxlast": {"dim": -1}},
]
SGD = {"sgd": {"lr": 0.1}}


@pytest.fixture
def client(workdir, event_loop=None):
    app_mod.model_locks.clear()
    app_mod.dataset_locks.clear()
    loop = asyncio.new_event_loop()
    client = TestClient(TestServer(app_mod.create_app()), loop=loop)
    loop.run_until_complete(client.start_server())
    yield _SyncClient(client, loop)
    loop.run_until_complete(client.close())
    loop.close()


class _SyncClient:
    """Synchronous facade over the async TestClient."""

    def __init__(self, client, loop):
        self._client = client
        self._loop = loop

    def request(self, method, path, **kw):
        async def go():
            resp = await self._client.request(method, path, **kw)
            body = await resp.read()
            return resp, body
        return self._loop.run_until_complete(go())

    def json(self, method, path, **kw):
        resp, body = self.request(method, path, **kw)
        return resp.status, (json.loads(body) if body else None)


def _create_model(client, model_id="m1", layers=None, optimizer=None):
    status, body = client.json("POST", "/model/", json={
        "model_id": model_id,
        "layers": layers or TOY_LAYERS,
        "optimizer": optimizer or SGD,
    })
    assert status == 200, body
    return body


def _make_shards(workdir, dataset_id="ds", vocab=32):
    (workdir / "data").mkdir(exist_ok=True)
    rng = np.random.default_rng(0)
    np.save(workdir / "data" / f"{dataset_id}_000000",
            rng.integers(0, vocab, 4000).astype(np.uint16))


def test_create_model(client):
    body = _create_model(client)
    assert "created and saved successfully" in body["message"]


def test_root_redirects_to_dashboard(client):
    resp, body = client.request("GET", "/")
    assert resp.status == 200
    assert b"dashboard" in body


def test_output_route(client):
    _create_model(client)
    status, body = client.json("POST", "/output/", json={
        "model_id": "m1", "input": [[1, 2]], "target": [[2, 3]]})
    assert status == 200
    assert len(body["output"][0]) == 32
    assert body["cost"] > 0


def test_generate_route(client):
    _create_model(client)
    status, body = client.json("POST", "/generate/", json={
        "model_id": "m1", "input": [[1, 2]], "block_size": 8,
        "max_new_tokens": 3, "temperature": 0.0})
    assert status == 200
    assert len(body["tokens"]) == 5


def test_generate_batch_route(client):
    """/generate_batch/: ragged prompts, per-row greedy outputs equal the
    single-sequence route."""
    _create_model(client)
    status, body = client.json("POST", "/generate_batch/", json={
        "model_id": "m1", "inputs": [[1, 2, 3], [5]], "block_size": 8,
        "max_new_tokens": 3, "temperature": 0.0})
    assert status == 200
    assert len(body["sequences"]) == 2
    assert body["sequences"][0][:3] == [1, 2, 3]
    assert body["sequences"][1][:1] == [5]
    for row in body["sequences"]:
        _, single = client.json("POST", "/generate/", json={
            "model_id": "m1", "input": [row[:len(row) - 3]], "block_size": 8,
            "max_new_tokens": 3, "temperature": 0.0})
        assert single["tokens"] == row
    # oversized request → 400
    status, _ = client.json("POST", "/generate_batch/", json={
        "model_id": "m1", "inputs": [[1] * 7], "block_size": 8,
        "max_new_tokens": 3, "temperature": 0.0})
    assert status == 400


def test_generate_streaming(client):
    _create_model(client)
    resp, body = client.request("POST", "/generate/", json={
        "model_id": "m1", "input": [[1]], "block_size": 8,
        "max_new_tokens": 4, "stream": True})
    assert resp.status == 200
    assert resp.headers["Content-Type"].startswith("text/plain")
    lines = body.decode().strip().split("\n")
    assert len(lines) == 4
    assert all(line.isdigit() for line in lines)


def test_train_route_202_and_progress(client, workdir):
    _create_model(client)
    _make_shards(workdir)
    status, body = client.json("PUT", "/train/", json={
        "model_id": "m1", "device": "cpu", "dataset_id": "ds", "shard": 0,
        "epochs": 2, "batch_size": 2, "block_size": 8, "step_size": 1})
    assert status == 202
    assert "asynchronously" in body["message"]
    import time
    for _ in range(300):
        status, body = client.json("GET", "/progress/?model_id=m1")
        if body["status"]["code"] in ("Trained", "Error"):
            break
        time.sleep(0.2)
    assert body["status"]["code"] == "Trained", body["status"]
    assert len(body["progress"]) == 2
    assert body["average_cost"] is not None
    status, stats = client.json("GET", "/stats/?model_id=m1")
    assert status == 200
    assert len(stats["layers"]) >= 2


def test_generate_while_training(client, workdir):
    """Serving-under-training policy: a /generate/ arriving mid-/train/ is
    served from the latest checkpoint (it never shares the training
    thread's in-memory params) while the epoch loop owns the device; it
    must return 200 with valid tokens, and training must still complete.
    What the device contention costs in latency has no benchmark cell yet
    (not measured); see README "Serving while training"."""
    import time
    _create_model(client)
    _make_shards(workdir)
    status, _ = client.json("PUT", "/train/", json={
        "model_id": "m1", "device": "cpu", "dataset_id": "ds", "shard": 0,
        "epochs": 400, "batch_size": 2, "block_size": 8, "step_size": 1})
    assert status == 202
    served_during = 0
    code = None
    for _ in range(600):
        _, body = client.json("GET", "/progress/?model_id=m1")
        code = body["status"]["code"]
        if code == "Training":
            gs, gb = client.json("POST", "/generate/", json={
                "model_id": "m1", "input": [[1, 2]], "block_size": 8,
                "max_new_tokens": 2, "temperature": 0.0})
            assert gs == 200, gb
            assert len(gb["tokens"]) == 4
            served_during += 1
        if code in ("Trained", "Error"):
            break
        time.sleep(0.05)
    assert code == "Trained", code
    assert served_during > 0, "training finished before any mid-run generate"


def test_train_unknown_model_404(client):
    status, body = client.json("PUT", "/train/", json={
        "model_id": "nope", "device": "cpu", "dataset_id": "ds", "shard": 0,
        "epochs": 1, "batch_size": 2, "block_size": 8, "step_size": 1})
    assert status == 404


def test_train_conflict_409(client, workdir):
    _create_model(client)
    lock = app_mod.model_locks.setdefault("m1", asyncio.Lock())
    client._loop.run_until_complete(lock.acquire())
    try:
        status, body = client.json("PUT", "/train/", json={
            "model_id": "m1", "device": "cpu", "dataset_id": "ds", "shard": 0,
            "epochs": 1, "batch_size": 2, "block_size": 8, "step_size": 1})
        assert status == 409
        assert "already in progress" in body["detail"]
    finally:
        lock.release()


def test_dataset_download_409_and_list(client, workdir):
    lock = app_mod.dataset_locks.setdefault("dl", asyncio.Lock())
    client._loop.run_until_complete(lock.acquire())
    try:
        status, body = client.json("POST", "/dataset/", json={
            "dataset_id": "dl", "encoding": "byte", "path": "p",
            "name": "n", "split": "train", "shard_size": 100})
        assert status == 409
    finally:
        lock.release()
    _make_shards(workdir, "listme")
    status, body = client.json("GET", "/dataset/?dataset_id=listme")
    assert body["files"] == ["listme_000000.npy"]


def test_dataset_delete_204(client, workdir):
    _make_shards(workdir, "deadds")
    resp, _ = client.request("DELETE", "/dataset/?dataset_id=deadds")
    assert resp.status == 204
    status, body = client.json("GET", "/dataset/?dataset_id=deadds")
    assert body["files"] == []


def test_tokenize_and_decode(client):
    status, body = client.json("POST", "/tokenize/", json={
        "encoding": "byte", "text": "ab"})
    assert status == 200
    assert body["tokens"] == [97, 98, 256]
    status, body = client.json("POST", "/decode/", json={
        "encoding": "byte", "tokens": [97, 98]})
    assert body["text"] == "ab"


def test_evaluate_route(client, workdir):
    _create_model(client)
    _make_shards(workdir)
    status, body = client.json("POST", "/evaluate/", json={
        "model_id": "m1", "device": "cpu", "dataset_id": "ds", "shard": 0,
        "epochs": 1, "batch_size": 2, "block_size": 8, "step_size": 1})
    assert status == 200
    assert body["cost"] > 0


def test_gzip_request_body(client):
    payload = gzip.compress(json.dumps(
        {"encoding": "byte", "text": "zip"}).encode())
    resp, body = client.request(
        "POST", "/tokenize/", data=payload,
        headers={"Content-Type": "application/json",
                 "Content-Encoding": "gzip"})
    assert resp.status == 200
    assert json.loads(body)["tokens"] == [122, 105, 112, 256]


def test_error_mapping(client):
    # 404: unknown model
    status, body = client.json("GET", "/progress/?model_id=ghost")
    assert status == 404
    assert "Not found" in body["detail"]
    # 422: validation error
    status, body = client.json("POST", "/generate/", json={"model_id": "x"})
    assert status == 422
    # 422: missing query param
    status, body = client.json("GET", "/progress/")
    assert status == 422
    # 400: bad layer DSL (ValueError)
    status, body = client.json("POST", "/model/", json={
        "model_id": "bad", "layers": [{"nonsense": {}}], "optimizer": SGD})
    assert status == 400
    assert "Value error" in body["detail"]


def test_delete_model_204_then_404(client):
    _create_model(client, "gone")
    resp, _ = client.request("DELETE", "/model/?model_id=gone")
    assert resp.status == 204
    status, _ = client.json("GET", "/progress/?model_id=gone")
    assert status == 404


def test_model_locks_shared_between_train_and_import(client):
    """/import/ and /train/ share the per-model lock namespace."""
    lock = app_mod.model_locks.setdefault("shared", asyncio.Lock())
    client._loop.run_until_complete(lock.acquire())
    try:
        status, _ = client.json("POST", "/import/", json={
            "hf_repo_id": "openai-community/gpt2", "model_id": "shared"})
        assert status == 409
    finally:
        lock.release()


def test_ops_files_present_and_valid():
    """run scripts, log config, CI workflow (parity: reference test_run_sh)."""
    import json, os, stat
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for script in ("run.sh", "run-in-vm.sh"):
        path = os.path.join(root, script)
        assert os.path.exists(path)
        assert os.stat(path).st_mode & stat.S_IXUSR
        with open(path) as f:
            content = f.read()
        assert content.startswith("#!/bin/bash")
        assert "penroz_tpu.serve.app" in content
    with open(os.path.join(root, "log_config.json")) as f:
        cfg = json.load(f)
    assert cfg["version"] == 1
    assert "aiohttp.access" in cfg["loggers"]
    import logging.config
    logging.config.dictConfig(cfg)  # must be a valid dictConfig
    assert os.path.exists(os.path.join(root, ".github", "workflows",
                                       "ci.yml"))


def test_profile_start_stop_roundtrip(client, tmp_path):
    """POST /profile/ start → trace capture → stop writes trace files."""
    log_dir = str(tmp_path / "prof")
    status, _ = client.json("POST", "/profile/",
                            json={"action": "start", "log_dir": log_dir})
    assert status == 200
    # a second start while capturing → 409
    status, _ = client.json("POST", "/profile/",
                            json={"action": "start", "log_dir": log_dir})
    assert status == 409
    import jax.numpy as jnp
    (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
    status, _ = client.json("POST", "/profile/", json={"action": "stop"})
    assert status == 200
    import os
    found = [f for _, _, fs in os.walk(log_dir) for f in fs]
    assert found, "trace capture produced no files"
    # stop when idle → 409
    status, _ = client.json("POST", "/profile/", json={"action": "stop"})
    assert status == 409


def test_profile_unknown_action(client):
    status, _ = client.json("POST", "/profile/", json={"action": "bogus"})
    assert status == 400


def test_configure_logging_all_paths(monkeypatch, tmp_path, capsys):
    """Regression: the basicConfig fallback crashed with UnboundLocalError
    when PENROZ_LOG_CONFIG was unset (branch-local `import logging.config`
    shadowed the module-level `logging` name)."""
    monkeypatch.delenv("PENROZ_LOG_CONFIG", raising=False)
    app_mod._configure_logging()  # must not raise
    monkeypatch.setenv("PENROZ_LOG_CONFIG", str(tmp_path / "missing.json"))
    app_mod._configure_logging()
    assert "does not exist" in capsys.readouterr().err
    config = tmp_path / "log.json"
    config.write_text(json.dumps({
        "version": 1, "disable_existing_loggers": False,
        "handlers": {"default": {"class": "logging.StreamHandler"}},
        "root": {"handlers": ["default"]}}))
    monkeypatch.setenv("PENROZ_LOG_CONFIG", str(config))
    app_mod._configure_logging()


def test_openapi_spec(client):
    """OpenAPI parity with the reference's FastAPI docs surface: the spec
    covers every route and /model/ carries the GPT-2-124M example
    (reference: main.py:53-93)."""
    status, spec = client.json("GET", "/openapi.json")
    assert status == 200
    assert spec["openapi"].startswith("3.")
    for path in ["/model/", "/import/", "/dataset/", "/tokenize/",
                 "/output/", "/evaluate/", "/generate/", "/decode/",
                 "/train/", "/progress/", "/stats/", "/serving_stats/",
                 "/profile/", "/profiler/trace/", "/metrics", "/trace/",
                 "/trace/{request_id}", "/dashboard", "/healthz",
                 "/readyz"]:
        assert path in spec["paths"], path
    assert set(spec["paths"]["/dataset/"]) == {"get", "post", "delete"}
    assert "CreateModelRequest" in spec["components"]["schemas"]
    example = (spec["paths"]["/model/"]["post"]["requestBody"]["content"]
               ["application/json"]["example"])
    assert example["model_id"] == "gpt2-124M"
    embed = example["layers"][0]["summation"][0]["embedding"]
    assert embed == {"num_embeddings": 50257, "embedding_dim": 768}
    blocks = [l for l in example["layers"] if "residual" in l]
    assert len(blocks) == 12
    assert "adamw" in example["optimizer"]


def test_docs_page(client):
    resp, body = client.request("GET", "/docs")
    assert resp.status == 200
    assert "text/html" in resp.headers["Content-Type"]
    assert b"openapi.json" in body


@pytest.mark.parametrize("device", ["tpuu", "tpu", "cuda", "accelerator"])
def test_train_bad_device_400s_before_202(client, device):
    """A device typo — and an accelerator this (CPU-only) process does not
    have — must 400 synchronously: not 202 then silently no-op in the
    background task, and never a run on the CPU that reports Trained."""
    _create_model(client, "devcheck")
    status, body = client.json("PUT", "/train/", json={
        "model_id": "devcheck", "dataset_id": "nope", "shard": 0,
        "epochs": 1, "batch_size": 1, "block_size": 4, "step_size": 1,
        "device": device})
    assert status == 400
    assert device in body["detail"]


def test_orphaned_training_swept_at_startup(workdir):
    """A checkpoint stuck in 'Training' (server killed mid-run) must read
    Error after a restart — training runs in the server process, so no run
    can survive one.  Other statuses pass through untouched."""
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    from penroz_tpu.utils import checkpoint

    for mid, code in (("orph", "Training"), ("done", "Trained")):
        m = NeuralNetworkModel(mid, Mapper(TOY_LAYERS, SGD))
        m.status = {"code": code, "message": None}
        m.serialize(sync_flush=True)

    app_mod._sweep_orphaned_training()

    swept = checkpoint.peek_tree("orph")["status"]
    assert swept["code"] == "Error"
    assert "restart" in swept["message"]
    assert checkpoint.peek_tree("done")["status"]["code"] == "Trained"
    # weights survive the metadata rewrite
    restored = NeuralNetworkModel.deserialize("orph")
    assert restored.params


def test_sweep_runs_at_create_app_and_tolerates_corrupt_checkpoints(workdir):
    """create_app() itself runs the orphan sweep synchronously (a client
    retrying /train/ right after restart must not race it), a healthy
    checkpoint is left alone, and an unreadable/corrupt checkpoint file in
    the models dir must not block startup."""
    import os
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    from penroz_tpu.utils import checkpoint

    for mid, code in (("stale", "Training"), ("healthy", "Trained")):
        m = NeuralNetworkModel(mid, Mapper(TOY_LAYERS, SGD))
        m.status = {"code": code, "message": None}
        m.serialize(sync_flush=True)
    # garbage that list_model_ids will pick up but peek_tree cannot parse
    os.makedirs("models", exist_ok=True)
    with open("models/model_corrupt.ckpt", "wb") as f:
        f.write(b"\x00garbage, not a container")

    app_mod.create_app()  # must not raise despite the corrupt file

    assert checkpoint.peek_tree("stale")["status"]["code"] == "Error"
    assert "restart" in checkpoint.peek_tree("stale")["status"]["message"]
    assert checkpoint.peek_tree("healthy")["status"]["code"] == "Trained"


@pytest.fixture
def fake_datasets(monkeypatch):
    """A stub HuggingFace `datasets` module: download exercises the REAL
    tokenize/shard pipeline, only the network fetch is faked."""
    import sys
    import types
    mod = types.SimpleNamespace(
        load_dataset=lambda path, name, split: {"text": ["hello world"] * 4})
    monkeypatch.setitem(sys.modules, "datasets", mod)
    return mod


def _poll_download(client, dataset_id, timeout_s=30):
    import time
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        status, body = client.json("GET", f"/dataset/?dataset_id={dataset_id}")
        assert status == 200
        dl = body.get("download")
        if dl and dl["state"] in ("complete", "failed"):
            return body
        time.sleep(0.05)
    raise AssertionError(f"download for {dataset_id} never settled")


def test_download_retries_through_injected_fault(client, workdir,
                                                 fake_datasets, monkeypatch):
    """A transient download failure (injected at the data.download site) is
    retried with backoff and succeeds on attempt 2 — shards exist and the
    dataset status reports the attempt count."""
    from penroz_tpu.utils import faults
    monkeypatch.setenv(faults.ENV, "data.download:raise@1")
    monkeypatch.setenv("PENROZ_DOWNLOAD_RETRIES", "3")
    monkeypatch.setenv("PENROZ_DOWNLOAD_BACKOFF_S", "0.01")
    faults.reset()
    status, _ = client.json("POST", "/dataset/", json={
        "dataset_id": "retryds", "encoding": "byte", "path": "p",
        "name": None, "split": "train", "shard_size": 64})
    assert status == 202
    body = _poll_download(client, "retryds")
    assert body["download"]["state"] == "complete"
    assert body["download"]["attempts"] == 2
    assert body["download"]["error"] is None
    assert body["files"], body
    faults.reset()


def test_download_terminal_failure_surfaced_to_clients(client, workdir,
                                                       fake_datasets,
                                                       monkeypatch):
    """Exhausted retries surface as state=failed with the error text in the
    dataset listing — clients see the terminal failure instead of a
    silently-logged fire-and-forget task."""
    from penroz_tpu.utils import faults
    monkeypatch.setenv(faults.ENV, "data.download:raise@1+")
    monkeypatch.setenv("PENROZ_DOWNLOAD_RETRIES", "2")
    monkeypatch.setenv("PENROZ_DOWNLOAD_BACKOFF_S", "0.01")
    faults.reset()
    status, _ = client.json("POST", "/dataset/", json={
        "dataset_id": "deadds2", "encoding": "byte", "path": "p",
        "name": None, "split": "train", "shard_size": 64})
    assert status == 202
    body = _poll_download(client, "deadds2")
    assert body["download"]["state"] == "failed"
    assert body["download"]["attempts"] == 2
    assert "InjectedFault" in body["download"]["error"]
    assert body["files"] == []
    faults.reset()


def test_stats_exposes_moe_router_fractions(client, workdir):
    """A trained MoE model's /stats/ carries per-expert routing fractions
    (additive key; expert collapse must be observable from the API)."""
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel

    d, vocab = 8, 32
    layers = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d}},
        {"moe": {"in_features": d, "intermediate_size": 2 * d,
                 "num_experts": 4, "top_k": 2}},
        {"linear": {"in_features": d, "out_features": vocab}},
        {"softmaxlast": {"dim": -1}}]
    import os as _os
    _os.makedirs("data", exist_ok=True)
    np.save("data/moestats_000000",
            np.random.randint(0, vocab, 4096).astype(np.uint16))
    model = NeuralNetworkModel("moest", Mapper(layers, SGD))
    model.train_model("moestats", shard=0, epochs=1, batch_size=2,
                      block_size=8, step_size=1)

    status, body = client.json("GET", "/stats/?model_id=moest")
    assert status == 200
    routing = body["moe_router_fractions"]
    (fractions,) = routing.values()
    assert len(fractions) == 4
    assert abs(sum(fractions) - 1.0) < 1e-5



def test_train_pipe_over_http(client, workdir, monkeypatch):
    """API-driven GPipe training: PUT /train/ with PENROZ_MESH_PIPE=2
    reaches Trained and the checkpoint serves /generate/ afterwards."""
    import time
    monkeypatch.setenv("PENROZ_MESH_PIPE", "2")
    d, heads, vocab, block = 32, 4, 64, 16
    layers = ([{"summation": [
                  {"embedding": {"num_embeddings": vocab,
                                 "embedding_dim": d},
                   "normal": {"mean": 0.0, "std": 0.02}},
                  {"position": {"num_embeddings": block,
                                "embedding_dim": d},
                   "normal": {"mean": 0.0, "std": 0.02}}]}]
              + [{"residual": [
                  {"sequential": [
                      {"layernorm": {"normalized_shape": d}},
                      {"linear": {"in_features": d, "out_features": 3 * d},
                       "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                      {"attention": {"num_heads": heads, "dropout": 0.0}},
                      {"linear": {"in_features": d, "out_features": d}}]}]}
                 for _ in range(2)]
              + [{"layernorm": {"normalized_shape": d}},
                 {"linear": {"in_features": d, "out_features": vocab,
                             "bias": False}},
                 {"softmax": {"dim": -1}}])
    status, _ = client.json("POST", "/model/", json={
        "model_id": "ppapi", "layers": layers,
        "optimizer": {"sgd": {"lr": 0.1}}})
    assert status == 200
    data_dir = workdir / "data"
    data_dir.mkdir(exist_ok=True)
    rng = np.random.default_rng(0)
    np.save(data_dir / "ppds_000000",
            rng.integers(0, vocab, 4000).astype(np.uint16))
    status, body = client.json("PUT", "/train/", json={
        "model_id": "ppapi", "device": "cpu", "dataset_id": "ppds",
        "shard": 0, "epochs": 2, "batch_size": 8, "block_size": 16,
        "step_size": 8})
    assert status == 202
    for _ in range(600):
        status, body = client.json("GET", "/progress/?model_id=ppapi")
        if body["status"]["code"] in ("Trained", "Error"):
            break
        time.sleep(0.2)
    assert body["status"]["code"] == "Trained", body["status"]
    status, gen = client.json("POST", "/generate/", json={
        "model_id": "ppapi", "input": [1, 2, 3], "block_size": 16,
        "max_new_tokens": 4, "temperature": 0.0})
    assert status == 200 and len(gen["tokens"]) == 7
