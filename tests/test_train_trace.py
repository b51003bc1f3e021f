"""A ``PUT /train/`` job as a first-class trace (utils/tracing.py): the
202's ``X-Request-Id`` resolves on ``GET /trace/{id}`` while the job runs
and after; epoch, save anatomy and recompiles are one span tree; a long
job keeps its newest subtrees and per-name totals; sampling it out
changes nothing it computes.  A toy model through the app, on the CPU."""

import asyncio
import json
import os
import threading
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from penroz_tpu.serve import app as app_mod
from penroz_tpu.serve import metrics as serve_metrics
from penroz_tpu.utils import checkpoint, tracing

pytestmark = pytest.mark.runtime

LAYERS = [
    {"embedding": {"num_embeddings": 32, "embedding_dim": 8}},
    {"linear": {"in_features": 8, "out_features": 32}},
    {"softmaxlast": {"dim": -1}},
]
BATCH, BLOCK, EPOCHS = 4, 8, 6
MICRO_STEPS = BATCH          # step_size 1: one micro-step per row


class App:
    """The app on a loop of its own, in a temp cwd with its own shm dir."""

    def __init__(self, root):
        self.root = root
        self._mp = pytest.MonkeyPatch()
        self._mp.chdir(root)
        (root / "shm").mkdir()
        (root / "data").mkdir()
        self._mp.setattr(checkpoint, "SHM_PATH", str(root / "shm"))
        np.save(root / "data" / "ds_000000", np.random.default_rng(0)
                .integers(0, 32, 4000).astype(np.uint16))
        app_mod.model_locks.clear()
        tracing.reset()
        serve_metrics.reset()
        self._loop = asyncio.new_event_loop()
        self._client = TestClient(TestServer(app_mod.create_app()),
                                  loop=self._loop)
        self._loop.run_until_complete(self._client.start_server())

    def close(self):
        checkpoint.join_flushes()
        self._loop.run_until_complete(self._client.close())
        self._loop.close()
        self._mp.undo()

    def call(self, method, path, **kw):
        async def go():
            resp = await self._client.request(method, path, **kw)
            return resp, await resp.read()
        resp, body = self._loop.run_until_complete(go())
        try:
            return resp, json.loads(body)
        except ValueError:
            return resp, body.decode()

    def create(self, model_id, layers=LAYERS):
        resp, body = self.call("POST", "/model/", json={
            "model_id": model_id, "layers": layers,
            "optimizer": {"sgd": {"lr": 0.1}}})
        assert resp.status == 200, body

    def train(self, model_id, epochs=EPOCHS, dataset="ds", batch=BATCH):
        resp, body = self.call("PUT", "/train/", json={
            "model_id": model_id, "device": "cpu", "dataset_id": dataset,
            "shard": 0, "epochs": epochs, "batch_size": batch,
            "block_size": BLOCK, "step_size": 1})
        assert resp.status == 202, body
        return resp.headers["X-Request-Id"]

    def wait(self, model_id, timeout=120):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            _, body = self.call("GET", f"/progress/?model_id={model_id}")
            if body["status"]["code"] in ("Trained", "Error"):
                lock = app_mod.model_locks[model_id]
                while lock.locked() and time.monotonic() < deadline:
                    time.sleep(0.02)
                checkpoint.join_flushes()
                return body
            time.sleep(0.05)
        raise AssertionError(f"training of {model_id} did not end")


@pytest.fixture
def app(tmp_path):
    a = App(tmp_path)
    yield a
    a.close()


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """One traced job, looked at from every side by the cases below."""
    a = App(tmp_path_factory.mktemp("job"))
    try:
        a.create("m1")
        rid = a.train("m1")
        live_resp, live = a.call("GET", f"/trace/{rid}")
        progress = a.wait("m1")
        _, done = a.call("GET", f"/trace/{rid}")
        _, listing = a.call("GET", "/trace/")
        _, chrome = a.call("GET", f"/trace/{rid}?format=chrome")
        _, scrape = a.call("GET", "/metrics")
        size = os.path.getsize(a.root / "models" / "model_m1.ckpt")
        yield {"rid": rid, "live_status": live_resp.status, "live": live,
               "done": done, "listing": listing, "chrome": chrome,
               "metrics": scrape, "progress": progress, "file_bytes": size}
    finally:
        a.close()


def walk(node, parent=None):
    yield node, parent
    for child in node.get("children", []):
        yield from walk(child, node)


def named(tree, name):
    return [n for n, _ in walk(tree["root"]) if n["name"] == name]


def _resolves_live_and_after(job):
    assert job["live_status"] == 200
    assert job["live"]["finished"] is False
    assert job["live"]["meta"]["route"] == "/train/"
    assert job["live"]["root"]["t1_ms"] is None
    done = job["done"]
    assert done["request_id"] == job["rid"] and done["finished"] is True
    assert done["meta"] == {"route": "/train/", "model_id": "m1",
                            "status": "Trained",
                            "retire_reason": "completed"}
    assert job["progress"]["status"]["code"] == "Trained"
    (summary,) = [t for t in job["listing"]["traces"]
                  if t["request_id"] == job["rid"]]
    assert summary["route"] == "/train/" and summary["finished"] is True


def _children_lie_inside_parents(job):
    checked = 0
    for node, parent in walk(job["done"]["root"]):
        if parent is None:
            continue
        assert node["t1_ms"] is not None, node["name"]
        assert node["t0_ms"] >= parent["t0_ms"], node["name"]
        if node["name"] != "penroz/ckpt_flush":
            # a flush outlives the save that spawned it; nothing else does
            assert node["t1_ms"] <= parent["t1_ms"], node["name"]
        checked += 1
    assert checked > 4 * EPOCHS


def _top_level_is_setup_then_epochs_then_the_last_save(job):
    top = [c["name"] for c in job["done"]["root"]["children"]]
    assert top == (["penroz/train_setup"]
                   + ["penroz/load_batch", "penroz/train_epoch"] * EPOCHS
                   + ["penroz/train_stats", "penroz/ckpt_save"])
    setup = job["done"]["root"]["children"][0]
    first_batch = job["done"]["root"]["children"][1]
    assert setup["t1_ms"] <= first_batch["t0_ms"]
    # the save at train start (status "Training") is part of set-up
    (start_save,) = [c for c in setup["children"]
                     if c["name"] == "penroz/ckpt_save"]
    assert start_save["meta"]["periodic"] is False
    assert start_save["meta"]["tag"] is None


def _wait_lies_inside_its_epoch_after_the_dispatch(job):
    epochs = named(job["done"], "penroz/train_epoch")
    assert [e["meta"]["epoch"] for e in epochs] == list(range(1, EPOCHS + 1))
    for e in epochs:
        kids = [c for c in e["children"] if c["name"] != "penroz/compile"]
        assert [c["name"] for c in kids] == ["penroz/train_dispatch",
                                             "penroz/train_wait"]
        dispatch, wait = kids
        assert e["t0_ms"] <= dispatch["t0_ms"] <= dispatch["t1_ms"] \
            <= wait["t0_ms"] <= wait["t1_ms"] <= e["t1_ms"]


def _epoch_counters(job):
    tokens = MICRO_STEPS * BATCH * BLOCK
    for e in named(job["done"], "penroz/train_epoch"):
        assert e["meta"]["tokens"] == tokens
        assert e["meta"]["microstepped"] is False
        assert e["meta"]["sampled"] is True     # epochs < 100: every one
    assert [b["meta"]["tokens"] for b in
            named(job["done"], "penroz/load_batch")] == [tokens] * EPOCHS
    (stats,) = named(job["done"], "penroz/train_stats")
    assert stats["meta"]["refreshed"] is True


def _save_anatomy_and_bytes(job):
    saves = named(job["done"], "penroz/ckpt_save")
    assert len(saves) == 2          # train start, train end
    last = saves[-1]
    assert last["meta"]["tag"] == EPOCHS and last["meta"]["periodic"] is True
    for save in saves:
        kids = save["children"]
        assert [k["name"] for k in kids] == [
            "penroz/ckpt_d2h", "penroz/ckpt_encode", "penroz/ckpt_write",
            "penroz/ckpt_flush"]
        d2h, encode, write, flush = kids
        assert d2h["t1_ms"] <= encode["t0_ms"] <= encode["t1_ms"] \
            <= write["t0_ms"] <= write["t1_ms"] <= flush["t0_ms"]
        assert d2h["meta"]["arrays"] >= 3 and d2h["meta"]["bytes"] > 0
        assert save["meta"]["bytes"] == write["meta"]["bytes"] \
            == flush["meta"]["bytes"] > d2h["meta"]["bytes"]
    # the last save is the file on disk
    assert last["meta"]["bytes"] == job["file_bytes"]


def _compile_in_the_first_epoch_only(job):
    epochs = named(job["done"], "penroz/train_epoch")
    has_compile = [any(n["name"] == "penroz/compile"
                       for n, _ in walk(e)) for e in epochs]
    assert has_compile == [True] + [False] * (EPOCHS - 1)
    (dispatch,) = [c for c in epochs[0]["children"]
                   if c["name"] == "penroz/train_dispatch"]
    compiles = [c for c in dispatch.get("children", [])
                if c["name"] == "penroz/compile"]
    assert compiles and all(c["meta"]["seconds"] > 0 for c in compiles)
    assert sum(c["duration_ms"] for c in compiles) <= dispatch["duration_ms"]


def _totals_and_metrics(job):
    totals = job["done"]["totals"]
    assert totals["penroz/train_epoch"]["count"] == EPOCHS
    assert totals["penroz/load_batch"]["count"] == EPOCHS
    assert totals["penroz/ckpt_save"]["count"] == 2
    assert totals["penroz/ckpt_flush"]["count"] == 2
    epochs = named(job["done"], "penroz/train_epoch")
    assert totals["penroz/train_epoch"]["sum_ms"] == pytest.approx(
        sum(e["duration_ms"] for e in epochs), abs=0.01)
    scrape = job["metrics"]
    assert "# TYPE penroz_train_span_ms histogram" in scrape
    assert (f'penroz_train_span_ms_count{{span="penroz/train_epoch"}} '
            f'{EPOCHS}') in scrape
    assert 'penroz_train_span_ms_count{span="penroz/ckpt_write"} 2' in scrape
    assert 'penroz_train_span_ms_bucket{span="penroz/ckpt_d2h",le=' in scrape


def _chrome_export_is_valid_trace_event_json(job):
    chrome = job["chrome"]
    events = chrome["traceEvents"]
    assert chrome["displayTimeUnit"] == "ms"
    assert len(events) == sum(1 for _ in walk(job["done"]["root"]))
    assert all(e["ph"] == "X" and e["ts"] >= 0 and e["dur"] >= 0
               and e["pid"] == job["rid"] and isinstance(e["tid"], int)
               for e in events)
    assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)
    (root,) = [e for e in events if e["tid"] == 0]
    assert root["args"]["route"] == "/train/"
    by_name = {e["name"] for e in events}
    assert {"penroz/train_wait", "penroz/ckpt_flush",
            "penroz/compile"} <= by_name
    json.dumps(chrome)


HOST_KEYS = {"cpu_ms", "sys_ms", "major_faults", "minor_faults", "waits",
             "preempted"}


def _every_span_carries_its_threads_account(job):
    """``host`` beside ``meta`` on every span the job opened through
    ``tracing.span``; the root and the after-the-fact ``penroz/compile``
    have none.  The account is the thread's own, so it fits in the span."""
    seen = set()
    for node, parent in walk(job["done"]["root"]):
        if parent is None or node["name"] == "penroz/compile":
            assert "host" not in node, node["name"]
            continue
        host = node["host"]
        assert set(host) == HOST_KEYS, node["name"]
        assert host["sys_ms"] >= 0, node
        assert 0 <= host["cpu_ms"] <= node["duration_ms"] + 1, node
        assert all(isinstance(host[k], int) and host[k] >= 0
                   for k in HOST_KEYS - {"cpu_ms", "sys_ms"}), node
        assert not HOST_KEYS & set(node.get("meta", {})), node["name"]
        seen.add(node["name"])
    assert {"penroz/train_setup", "penroz/load_batch", "penroz/train_epoch",
            "penroz/train_dispatch", "penroz/train_wait",
            "penroz/train_stats", "penroz/ckpt_save", "penroz/ckpt_d2h",
            "penroz/ckpt_encode", "penroz/ckpt_write",
            "penroz/ckpt_flush"} <= seen


def _load_batch_says_where_its_time_went(job):
    batches = named(job["done"], "penroz/load_batch")
    assert len(batches) == EPOCHS
    for b in batches:
        scan, gather = b["meta"]["scan_ms"], b["meta"]["gather_ms"]
        assert scan > 0 and gather > 0
        assert scan + gather <= b["duration_ms"] + 0.002   # three roundings


def _chrome_export_carries_host_under_args(job):
    by_name = {}
    for e in job["chrome"]["traceEvents"]:
        by_name.setdefault(e["name"], []).append(e)
    for name in ("penroz/load_batch", "penroz/train_wait",
                 "penroz/ckpt_write", "penroz/ckpt_flush"):
        assert all(set(e["args"]["host"]) == HOST_KEYS
                   for e in by_name[name]), name
    assert all("host" not in e.get("args", {})
               for e in by_name["penroz/compile"] + by_name["request"])
    (batch,) = by_name["penroz/load_batch"][:1]
    assert batch["args"]["tokens"] == MICRO_STEPS * BATCH * BLOCK
    assert json.loads(json.dumps(job["chrome"])) == job["chrome"]


CASES = [_resolves_live_and_after, _children_lie_inside_parents,
         _top_level_is_setup_then_epochs_then_the_last_save,
         _wait_lies_inside_its_epoch_after_the_dispatch, _epoch_counters,
         _save_anatomy_and_bytes, _compile_in_the_first_epoch_only,
         _totals_and_metrics, _chrome_export_is_valid_trace_event_json,
         _every_span_carries_its_threads_account,
         _load_batch_says_where_its_time_went,
         _chrome_export_carries_host_under_args]


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__.lstrip("_"))
def test_train_trace(job, case):
    case(job)


def test_a_looped_models_job_carries_its_plan_and_its_exits(app):
    """``POST /model/`` → ``PUT /train/`` → ``/progress/`` → ``POST
    /generate/`` of a small looped model on the normal path: the job's
    trace has ``penroz/loop_plan`` under the compiling epoch's dispatch and
    the eight exit counters on every epoch; ``/progress/`` rows and
    ``/metrics`` carry the same."""
    from penroz_tpu.models import presets
    steps, depth, epochs = 4, 2, 3
    app.create("loop", presets.ouro_custom(
        d=16, heads=2, head_dim=8, intermediate=24, depth=depth, steps=steps,
        vocab=32))
    rid = app.train("loop", epochs=epochs, batch=2)
    progress = app.wait("loop")
    assert progress["status"]["code"] == "Trained", progress["status"]
    _, tree = app.call("GET", f"/trace/{rid}")
    first, *later = named(tree, "penroz/train_epoch")
    (dispatch,) = [c for c in first["children"]
                   if c["name"] == "penroz/train_dispatch"]
    plans = [n for n, _ in walk(dispatch) if n["name"] == "penroz/loop_plan"]
    assert plans and all(p["meta"] == {
        "steps": steps, "layers": depth, "applications": steps * depth,
        "recomputed_applications": steps * depth,
        "cache_slots": steps * depth,
        "kept_outputs": "penroz_flash_out,penroz_flash_lse,penroz_ce_lse"}
        for p in plans)
    assert not any(n["name"] == "penroz/loop_plan"
                   for e in later for n, _ in walk(e))
    # the stats passes at the job's end run the loop too, recomputing nothing
    # (the last such span: where the first epoch's compile outlasts the save
    # cadence's 10 s, a periodic save and its unrefreshed stats come first)
    *_, stats = named(tree, "penroz/train_stats")
    assert {(n["meta"]["recomputed_applications"], n["meta"]["kept_outputs"])
            for n, _ in walk(stats)
            if n["name"] == "penroz/loop_plan"} == {(0, "")}
    counters = [f"{name}_{t}" for name in ("pass_loss", "exit_mass")
                for t in range(1, steps + 1)]
    for epoch, row in zip([first, *later], progress["progress"]):
        meta = epoch["meta"]
        assert set(counters) <= set(meta)
        assert sum(meta[f"exit_mass_{t}"] for t in range(1, steps + 1)) \
            == pytest.approx(1.0, abs=1e-5)
        assert all(0.0 < meta[f"pass_loss_{t}"] < 10.0
                   for t in range(1, steps + 1))
        assert row["pass_loss"] == [meta[f"pass_loss_{t}"]
                                    for t in range(1, steps + 1)]
        assert row["exit_mass"] == [meta[f"exit_mass_{t}"]
                                    for t in range(1, steps + 1)]
    _, scrape = app.call("GET", "/metrics")
    for t in range(1, steps + 1):
        assert f'penroz_train_pass_loss{{pass="{t}"}} ' in scrape
        assert f'penroz_train_exit_mass{{pass="{t}"}} ' in scrape
    resp, body = app.call("POST", "/generate/", json={
        "model_id": "loop", "input": [[1, 2, 3]], "block_size": BLOCK,
        "max_new_tokens": 4, "temperature": 0.0})
    assert resp.status == 200, body


def test_ring_keeps_the_newest_subtrees_and_counts_the_dropped(
        app, monkeypatch):
    monkeypatch.setattr(tracing, "JOB_HEAD", 2)
    monkeypatch.setattr(tracing, "JOB_RING", 6)
    epochs = 20
    app.create("long")
    rid = app.train("long", epochs=epochs)
    app.wait("long")
    _, tree = app.call("GET", f"/trace/{rid}")
    top = tree["root"]["children"]
    assert [c["name"] for c in top] == [
        "penroz/train_setup", "penroz/load_batch",          # the head
        "penroz/load_batch", "penroz/train_epoch",          # the newest six
        "penroz/load_batch", "penroz/train_epoch",
        "penroz/train_stats", "penroz/ckpt_save"]
    assert [c["meta"]["epoch"] for c in top
            if c["name"] == "penroz/train_epoch"] == [epochs - 1, epochs]
    # whole subtrees left: 18 epochs of (epoch, dispatch, wait) and 17
    # load_batch spans (and the first epoch's compiles, where it compiled)
    assert tree["dropped_spans"] >= 18 * 3 + 17
    held = sum(1 for _ in walk(tree["root"]))
    assert held < 40
    # what the ring forgot, the totals did not
    assert tree["totals"]["penroz/train_epoch"]["count"] == epochs
    assert tree["totals"]["penroz/train_wait"]["count"] == epochs


def test_sampled_out_records_nothing_and_trains_the_same(app, monkeypatch):
    def losses(model_id):
        app.create(model_id)
        rid = app.train(model_id)
        body = app.wait(model_id)
        assert body["status"]["code"] == "Trained"
        return rid, [p["cost"] for p in body["progress"]]

    rid_on, on = losses("traced")
    monkeypatch.setenv(tracing.TRACE_SAMPLE_ENV, "0")
    rid_off, off = losses("untraced")
    assert len(on) == EPOCHS and on == off
    assert app.call("GET", f"/trace/{rid_on}")[0].status == 200
    assert app.call("GET", f"/trace/{rid_off}")[0].status == 404
    assert [t.request_id for t in tracing.completed()] == [rid_on]
    assert tracing.live() == []
    _, scrape = app.call("GET", "/metrics")
    assert (f'penroz_train_span_ms_count{{span="penroz/train_epoch"}} '
            f'{EPOCHS}') in scrape


def test_a_failed_job_finishes_its_trace_with_error(app):
    app.create("bad")
    rid = app.train("bad", dataset="no_such_dataset")
    body = app.wait("bad")
    assert body["status"]["code"] == "Error"
    _, tree = app.call("GET", f"/trace/{rid}")
    assert tree["finished"] is True
    assert tree["meta"]["retire_reason"] == "error"
    assert tree["meta"]["status"] == "Error"
    assert "no_such_dataset" in tree["meta"]["error"]
    # set-up was cut short and closed; the error status was saved
    top = [c["name"] for c in tree["root"]["children"]]
    assert top[0] == "penroz/train_setup" and "penroz/train_epoch" not in top
    assert all(n["t1_ms"] is not None for n, p in walk(tree["root"]) if p)
    assert named(tree, "penroz/ckpt_save")


def test_worker_process_job_records_nothing_and_says_so(monkeypatch):
    from penroz_tpu.models.model import NeuralNetworkModel

    class Ended:
        status = {"code": "Trained"}

    monkeypatch.setenv("PENROZ_TRAIN_WORKER", "1")
    monkeypatch.setattr(NeuralNetworkModel, "_train_in_worker_process",
                        classmethod(lambda cls, *a, **kw: Ended()))
    tracing.reset()
    trace = tracing.maybe_trace("w1", job=True, route="/train/",
                                model_id="w")
    NeuralNetworkModel.train_model_on_device(
        "w", "cpu", "ds", 0, 1, 2, 8, 1, None, trace)
    tree = tracing.get("w1").to_dict()
    assert tree["finished"] is True and "children" not in tree["root"]
    assert tree["meta"]["recorded"] is False
    assert "PENROZ_TRAIN_WORKER" in tree["meta"]["note"]
    assert tree["meta"]["retire_reason"] == "completed"


def test_span_without_a_trace_is_the_annotation_and_nothing_more(
        monkeypatch):
    import jax
    opened = []

    class Annotation:
        def __init__(self, *args, **kwargs):
            opened.append((args, kwargs))

        def __enter__(self):
            opened.append("enter")

        def __exit__(self, *exc):
            opened.append("exit")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tracing.reset()
    with tracing.span("penroz/sched_tick"):
        pass
    with tracing.span("penroz/ckpt_write", bytes=7) as sp:
        sp.set(more=1)
        assert tracing.capture() is None
    # exactly the name, no metadata: the trace reduction matches by equality
    assert opened == [(("penroz/sched_tick",), {}), "enter", "exit",
                      (("penroz/ckpt_write",), {}), "enter", "exit"]
    assert tracing.live() == [] and tracing.completed() == []
    # and with one current, the same call records too, twin included
    trace = tracing.Trace("t", job=True)
    with tracing.use(trace), tracing.span("penroz/x", k=1) as sp:
        sp.set(bytes=2)
    (x,) = trace.to_dict()["root"]["children"]
    assert x["name"] == "penroz/x" and x["meta"] == {"k": 1, "bytes": 2}
    assert opened[-3:] == [(("penroz/x",), {}), "enter", "exit"]


def test_threads_share_one_job_trace_without_losing_a_span(monkeypatch):
    """More threads than cores, a short switch interval, a small ring:
    every span opened is either held or counted as dropped, and the totals
    saw every one."""
    import sys
    monkeypatch.setattr(tracing, "JOB_HEAD", 2)
    monkeypatch.setattr(tracing, "JOB_RING", 16)
    trace = tracing.Trace("stress", job=True)
    workers, rounds = 4 * (os.cpu_count() or 4), 200
    with tracing.use(trace):
        binding = tracing.capture()

    def work():
        with tracing.use(binding):
            for _ in range(rounds):
                with tracing.span("outer"):
                    with tracing.span("inner"):
                        pass

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(prev)
    tree = trace.to_dict()
    held = sum(1 for _ in walk(tree["root"])) - 1
    assert len(tree["root"]["children"]) == 18
    assert held + tree["dropped_spans"] == 2 * workers * rounds
    assert tree["totals"]["outer"]["count"] == workers * rounds
    assert tree["totals"]["inner"]["count"] == workers * rounds


def _burn(seconds):
    """Spin until the calling thread has used ``seconds`` of CPU."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def _one_span(body, name="penroz/x"):
    trace = tracing.Trace("t", job=True)
    with tracing.use(trace), tracing.span(name):
        body()
    (sp,) = trace.to_dict()["root"]["children"]
    return sp


@pytest.mark.parametrize("body, low, high", [
    (lambda: time.sleep(0.05), 0.0, 10.0),
    (lambda: _burn(0.05), 40.0, None),
], ids=["a_span_that_sleeps_waited", "a_span_that_spins_ran"])
def test_the_account_tells_running_from_waiting(body, low, high):
    """Two spans of 50 ms and more on the wall clock: ``cpu_ms`` says which
    computed; ``duration_ms - cpu_ms`` is what the other waited."""
    sp = _one_span(body)
    assert sp["duration_ms"] >= 50.0
    cpu = sp["host"]["cpu_ms"]
    assert low <= cpu <= (high if high is not None
                          else sp["duration_ms"] + 1), sp
    if high is not None:
        assert sp["host"]["waits"] >= 1     # the sleep gave the CPU up


def test_a_thread_beside_a_span_keeps_an_account_of_its_own():
    """The flush thread's ``penroz/ckpt_flush`` burns a core while the
    thread that spawned it waits inside its own span: each span reads its
    own thread (were the account the process's, the waiting span would
    read the other's 50 ms)."""
    trace = tracing.Trace("t", job=True)

    def flush(binding):
        with tracing.use(binding), tracing.span("penroz/ckpt_flush"):
            _burn(0.05)

    with tracing.use(trace), tracing.span("penroz/ckpt_save"):
        thread = threading.Thread(target=flush, args=(tracing.capture(),))
        with tracing.span("penroz/ckpt_write"):
            thread.start()
            thread.join(60)
            assert not thread.is_alive()
    (save,) = trace.to_dict()["root"]["children"]
    write, flushed = save["children"]
    assert (write["name"], flushed["name"]) == ("penroz/ckpt_write",
                                                "penroz/ckpt_flush")
    assert flushed["host"]["cpu_ms"] > 40.0
    assert write["duration_ms"] >= 50.0 > 10.0 > write["host"]["cpu_ms"]
    assert save["host"]["cpu_ms"] < 10.0


class _CountedUsage:
    """``resource.getrusage`` with its calls counted."""

    def __init__(self, monkeypatch):
        self.calls = 0
        real = tracing.resource.getrusage

        def getrusage(who):
            self.calls += 1
            return real(who)

        monkeypatch.setattr(tracing.resource, "getrusage", getrusage)


@pytest.mark.parametrize("trace", [
    lambda: None, lambda: tracing.Trace("r", route="/generate/")],
    ids=["no_trace_current", "a_requests_trace"])
def test_outside_a_job_trace_no_getrusage_call_is_made(monkeypatch, trace):
    """Serving spans, request traces and a job sampled out pay nothing for
    the account: not one read of it.  A job's span pays two."""
    counted = _CountedUsage(monkeypatch)
    current = trace()
    with tracing.use(current), tracing.span("penroz/decode_step_batched",
                                            rows=3) as sp:
        with tracing.span("penroz/prefill"):
            sp.set(more=1)
    assert counted.calls == 0
    if current is not None:
        (step,) = current.to_dict()["root"]["children"]
        assert step["meta"] == {"rows": 3, "more": 1}
        assert "host" not in step and "host" not in step["children"][0]
    assert "host" in _one_span(lambda: None) and counted.calls == 2


def test_without_rusage_thread_the_job_runs_and_carries_no_account(
        app, monkeypatch):
    """A platform that keeps no per-thread account: nothing raises, no read
    is attempted, and the spans are what they were before the field."""
    monkeypatch.delattr(tracing.resource, "RUSAGE_THREAD")
    counted = _CountedUsage(monkeypatch)
    app.create("plain")
    rid = app.train("plain")
    assert app.wait("plain")["status"]["code"] == "Trained"
    _, tree = app.call("GET", f"/trace/{rid}")
    nodes = [n for n, _ in walk(tree["root"])]
    assert len(nodes) > 4 * EPOCHS and not any("host" in n for n in nodes)
    assert counted.calls == 0
    _, chrome = app.call("GET", f"/trace/{rid}?format=chrome")
    assert not any("host" in e.get("args", {})
                   for e in chrome["traceEvents"])


def test_a_laguna_share_trains_on_the_normal_path_and_counts_its_routing(app):
    """``presets.laguna_custom`` told a share (4 of 16 experts, 2 or 3 query
    heads on one K/V head) → ``POST /model/`` → ``PUT /train/``: the job's
    trace has ``penroz/moe_plan`` under the compiling epoch's dispatch, every
    ``penroz/train_epoch`` the four routing counters summed over the sparse
    layers and the micro-steps, ``/progress/`` rows and ``/metrics`` the
    same; nothing dropped; ``/generate/`` serves it."""
    from penroz_tpu.models import presets
    epochs, batch, top_k, sparse = 3, 2, 4, 2
    app.create("share", presets.laguna_custom(
        d=16, head_dim=8, layer_types=["full_attention", "sliding_attention",
                                       "full_attention"],
        heads_per_layer=[2, 3, 2], kv_heads=1,
        mlp_layer_types=["dense", "sparse", "sparse"], intermediate=32,
        num_experts=16, experts_held=4, first_expert=4, top_k=top_k,
        moe_intermediate=8, shared_intermediate=8, vocab=32, window=4,
        rope={"full_attention": {
                  "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                  "original_max_position_embeddings": 8192, "beta_slow": 1,
                  "beta_fast": 32, "partial_rotary_factor": 0.5},
              "sliding_attention": {"rope_type": "default",
                                    "rope_theta": 10000,
                                    "partial_rotary_factor": 1}},
        routed_scale=2.5))
    rid = app.train("share", epochs=epochs, batch=batch)
    progress = app.wait("share")
    assert progress["status"]["code"] == "Trained", progress["status"]
    _, tree = app.call("GET", f"/trace/{rid}")
    first, *later = named(tree, "penroz/train_epoch")
    (dispatch,) = [c for c in first["children"]
                   if c["name"] == "penroz/train_dispatch"]
    plans = [n["meta"] for n, _ in walk(dispatch)
             if n["name"] == "penroz/moe_plan"]
    # a micro-step is batch x BLOCK = 16 tokens: a buffer of one tile, and
    # 16 · min(4, 4) + 4 · 127 rows in whole tiles at the bound
    assert plans and all(p == {
        "experts": 16, "held": 4, "first": 4, "top_k": top_k, "rows": 128,
        "row_tile": 128, "dispatch": "dropless", "rows_bound": 640,
        "rounds_bound": 5, "places": 4, "combine": "take", "latent": 0,
        "activation": "silu"}
        for p in plans), plans[0]
    # an epoch's pairs, held or not: ``batch`` micro-steps of batch x BLOCK
    pairs = batch * batch * BLOCK * top_k * sparse
    for epoch, row in zip([first, *later], progress["progress"]):
        meta = epoch["meta"]
        assert 0 < meta["moe_rows"] < pairs
        assert meta["moe_rows"] <= meta["moe_rows_padded"] \
            < meta["moe_rows"] + 128 * 4 * sparse * batch
        assert 0 < meta["moe_load_max"] <= meta["moe_rows"]
        assert meta["moe_dropped"] == 0
        assert all(row[k] == meta[k] for k in (
            "moe_rows", "moe_rows_padded", "moe_load_max", "moe_dropped"))
    _, scrape = app.call("GET", "/metrics")
    for name in ("moe_rows", "moe_rows_padded", "moe_load_max"):
        assert (f'penroz_train_moe{{counter="{name}"}} '
                f'{later[-1]["meta"][name]}\n') in scrape, name
    resp, body = app.call("POST", "/generate/", json={
        "model_id": "share", "input": [[1, 2, 3]], "block_size": BLOCK,
        "max_new_tokens": 4, "temperature": 0.0})
    assert resp.status == 200, body
