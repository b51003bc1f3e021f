#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the service still starts on the chip.

Runs the REST service the way ``penroz_tpu/serve/app.py::main`` does
(compile cache → ``dist.initialize`` → ``create_app``, real HTTP on a
loopback port) **inside the one process that holds the chip**, and drives
it as a client at the full width of GPT-2 124M (d=768, 12 heads × 12
blocks, block 1024, vocab 50304; random weights from a seed, a fabricated
token shard — no network, no tokenizer).

    python chip_smoke.py            one chip: kernels vs their jnp oracles,
                                    /train/ → /evaluate/ → /stats/, /generate/
                                    on the default path and on the continuous-
                                    batching + paged + ragged scheduler path
    python chip_smoke.py --chips 4  four chips, ONLY what exists across chips:
                                    data=4 training mesh vs one chip, TP-4
                                    serving mesh vs unmeshed, 4 router replicas
    ... --tiny                      a rehearsal at toy widths (guide
                                    on-chip-measurement §2.1).  On a CPU it
                                    says ``platform: cpu`` and ends
                                    ``"ok": false``: a rehearsal is never a
                                    chip run.

Every request that takes a ``device`` says ``"tpu"``.  Earlier stdout lines
are one JSON object each (versions, compile seconds, steps/s, tokens/s, peak
HBM); the LAST line is ``{"ok": …, "device": {"platform", "kind", "count"}}``
as JAX reports the device.  Exit code 0 only when every phase passed on a
TPU.  With no TPU (and no ``--tiny``) it exits non-zero before any phase and
prints no result line.
"""

from __future__ import annotations

import argparse
import asyncio
import concurrent.futures
import json
import math
import os
import shutil
import socket
import statistics
import sys
import tempfile
import threading
import time
import traceback
import urllib.error
import urllib.request

MODEL = "smoke"
DATASET = "smoketoks"
PROBE_LEN = 40   # the repeated prompt: more than two pages at either size


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, message: str):
    if not cond:
        raise SmokeFailure(message)


def emit(**fields):
    """One JSON object per stdout line (the driver reads only the last)."""
    print(json.dumps(fields, default=str), flush=True)


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

def sizes(tiny: bool) -> dict:
    if tiny:
        return dict(d=64, heads=4, depth=2, vocab=512, block=64, batch=4,
                    epochs=40, prompt_lens=[3, 9, 40, 5, 17, 50], new_tokens=8,
                    page=8, kernel_T=128, kernel_rows=4, kernel_vocab=2048,
                    cell_rows=2)
    return dict(d=768, heads=12, depth=12, vocab=50304, block=1024, batch=8,
                epochs=100, prompt_lens=[5, 40, 300, 17, 129, 600],
                new_tokens=24, page=16, kernel_T=1024, kernel_rows=8,
                kernel_vocab=50304, cell_rows=12)


# ---------------------------------------------------------------------------
# the service, in this process, and a client for it
# ---------------------------------------------------------------------------

class Service:
    """``serve/app.py::main`` minus ``web.run_app``'s blocking loop: the
    aiohttp app runs on its own event-loop thread so the main thread can be
    the client — one process, one owner of the chip."""

    def __init__(self):
        from aiohttp import web
        from penroz_tpu.parallel import dist
        from penroz_tpu.serve import app as app_mod
        from penroz_tpu.utils import profiling
        app_mod._configure_logging()
        app_mod._configure_compile_cache()
        dist.initialize()
        profiling.maybe_start_server()
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self._loop = asyncio.new_event_loop()
        self._runner = web.AppRunner(app_mod.create_app())
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self._loop)
            self._loop.run_until_complete(self._runner.setup())
            self._loop.run_until_complete(
                web.TCPSite(self._runner, "127.0.0.1", self.port).start())
            started.set()
            self._loop.run_forever()

        self._thread = threading.Thread(target=run, name="smoke-server",
                                        daemon=True)
        self._thread.start()
        check(started.wait(60), "server did not start within 60 s")
        self.base = f"http://127.0.0.1:{self.port}"

    def stop(self):
        from penroz_tpu.serve import decode_scheduler
        decode_scheduler.reset()
        fut = asyncio.run_coroutine_threadsafe(self._runner.cleanup(),
                                               self._loop)
        fut.result(timeout=60)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=30)

    # -- client -------------------------------------------------------------

    def call(self, method: str, path: str, body=None, timeout: float = 900):
        """(status, parsed JSON | text)."""
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path, data=data, method=method,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                status, raw = resp.status, resp.read()
        except urllib.error.HTTPError as e:
            status, raw = e.code, e.read()
        text = raw.decode(errors="replace")
        try:
            return status, json.loads(text)
        except ValueError:
            return status, text

    def ok(self, method: str, path: str, body=None, expect=200, **kw):
        status, payload = self.call(method, path, body, **kw)
        check(status == expect, f"{method} {path} → {status} (wanted "
              f"{expect}): {str(payload)[:500]}")
        return payload

    def generate(self, prompt, new_tokens, block, stream=False, **extra):
        body = {"model_id": MODEL, "input": [prompt], "block_size": block,
                "max_new_tokens": new_tokens, "temperature": 0.0,
                "stream": stream, **extra}
        if not stream:
            return self.ok("POST", "/generate/", body)["tokens"]
        status, text = self.call("POST", "/generate/", body)
        check(status == 200, f"streaming /generate/ → {status}: "
              f"{str(text)[:300]}")
        lines = str(text).split()
        check(all(t.lstrip("-").isdigit() for t in lines),
              f"stream carried a non-token line: {lines[-3:]}")
        return list(prompt) + [int(t) for t in lines]


# ---------------------------------------------------------------------------
# live objects of this process (the point of running the server in-process)
# ---------------------------------------------------------------------------

def devices_of(tree) -> set:
    """Every device any array leaf of ``tree`` lives on."""
    import jax
    out = set()
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            out |= leaf.sharding.device_set
    return out


def platforms(tree) -> set:
    return {d.platform for d in devices_of(tree)}


def device_ids(tree) -> set:
    return {d.id for d in devices_of(tree)}


class Spy:
    """Where the service's own objects put their arrays while it serves a
    request: a few of its entry points are wrapped for the duration of a
    phase (what a test's monkeypatch does; the server runs in this process
    precisely so that this can be seen) and record the placement of what
    passes through them.  Nothing is changed."""

    def __init__(self):
        self.seen = {"params": set(), "opt_state": set(), "kv": set(),
                     "param_devices": 0, "batch_devices": 0,
                     "batch_shard_shapes": set()}
        self._undo = []

    def _wrap(self, owner, name, after):
        original = getattr(owner, name)

        def wrapper(*args, **kwargs):
            try:
                result = original(*args, **kwargs)
            except BaseException:
                after(args, None)
                raise
            after(args, result)
            return result

        setattr(owner, name, wrapper)
        self._undo.append((owner, name, original))

    def __enter__(self):
        from penroz_tpu.models.model import NeuralNetworkModel
        from penroz_tpu.ops import kv_cache as KV
        from penroz_tpu.parallel import sharding as sharding_lib
        seen = self.seen

        def model_state(args, _):
            model = args[0]
            seen["params"] |= platforms(model.params)
            seen["opt_state"] |= platforms(model.opt_state)
            seen["param_devices"] = max(seen["param_devices"],
                                        len(device_ids(model.params)))

        def kv_state(_, kv):
            if kv is not None:
                seen["kv"] |= platforms(kv)

        def batch(_, arr):
            if arr is not None:
                seen["batch_devices"] = max(seen["batch_devices"],
                                            len(arr.sharding.device_set))
                seen["batch_shard_shapes"] |= {
                    tuple(s.data.shape) for s in arr.addressable_shards}

        # after train_model the params/optimizer state are the outputs of
        # the last epoch program: they sit where it ran
        self._wrap(NeuralNetworkModel, "train_model", model_state)
        self._wrap(NeuralNetworkModel, "_generate_iter", model_state)
        self._wrap(KV, "create_kv_state", kv_state)
        self._wrap(sharding_lib, "global_batch", batch)
        return self

    def __exit__(self, *exc):
        for owner, name, original in reversed(self._undo):
            setattr(owner, name, original)

    def summary(self) -> dict:
        return {k: (sorted(v) if isinstance(v, set) else v)
                for k, v in self.seen.items()}


# ---------------------------------------------------------------------------
# phases shared by both modes
# ---------------------------------------------------------------------------

def fabricate_shard(seed: int, vocab: int, tokens: int):
    """A learnable token stream: a fixed 64-token cycle of distinct ids, so
    the next token is a function of the current one."""
    import numpy as np
    rng = np.random.default_rng(seed)
    cycle = rng.choice(min(vocab, 50304), size=64, replace=False)
    os.makedirs("data", exist_ok=True)
    np.save(f"data/{DATASET}_000000",
            np.tile(cycle, tokens // 64 + 1)[:tokens].astype(np.uint16))
    return [int(t) for t in cycle]


def create_model(svc: Service, sz: dict, model_id: str = MODEL):
    from __graft_entry__ import OPTIMIZER
    from penroz_tpu.models import presets
    layers = presets.gpt2_custom(d=sz["d"], heads=sz["heads"],
                                 depth=sz["depth"], vocab=sz["vocab"],
                                 block=sz["block"])
    svc.ok("POST", "/model/", {"model_id": model_id, "layers": layers,
                               "optimizer": OPTIMIZER})


def train(svc: Service, sz: dict, device: str, model_id: str = MODEL,
          epochs: int | None = None) -> dict:
    """PUT /train/ → poll /progress/ to a terminal status.  One epoch is one
    optimizer step over a ``batch × block`` buffer (``step_size = batch``)."""
    epochs = epochs or sz["epochs"]
    body = {"model_id": model_id, "device": device, "dataset_id": DATASET,
            "shard": 0, "epochs": epochs, "batch_size": sz["batch"],
            "block_size": sz["block"], "step_size": sz["batch"]}
    t0 = time.monotonic()
    with Spy() as spy:
        svc.ok("PUT", "/train/", body, expect=202)
        deadline = t0 + 900
        while True:
            time.sleep(2.0)
            prog = svc.ok("GET", f"/progress/?model_id={model_id}")
            code = prog["status"]["code"]
            if code in ("Trained", "Error"):
                break
            check(time.monotonic() < deadline, f"training still {code} "
                  f"after 900 s")
    seen = spy.summary()
    check(code == "Trained", f"training ended {prog['status']}")
    costs = [p["cost"] for p in prog["progress"]]
    check(len(costs) == epochs, f"{len(costs)} progress records for "
          f"{epochs} epochs")
    check(all(math.isfinite(c) for c in costs), f"non-finite cost: {costs}")
    steady = [p["durationInSecs"] for p in prog["progress"][1:]]
    return {"costs": costs, "seen": seen, "body": body,
            "wall_s": round(time.monotonic() - t0, 2),
            "first_epoch_s": prog["progress"][0]["durationInSecs"],
            "steady_epoch_s": statistics.median(steady) if steady else None}


def lowered_has_kernel(fn, *args) -> bool:
    """Whether the program ``fn`` lowers to for ``args`` carries a Pallas
    (Mosaic) custom call — the proof a kernel gate did not pick jnp."""
    return "tpu_custom_call" in fn.lower(*args).as_text()


def train_program_has_kernels(sz: dict, model_id: str = MODEL) -> bool:
    """Re-lower the epoch program /train/ just ran (same arch cache entry,
    same shapes, same placement) and look for the kernels in it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from penroz_tpu.models.model import CompiledArch, NeuralNetworkModel
    model = NeuralNetworkModel.deserialize(model_id)
    arch = CompiledArch.get(model.layers_dsl)
    keys = [k for k in arch._jit_cache
            if isinstance(k, tuple) and k and k[0] == "epoch"]
    check(keys, "no train epoch program in the arch's jit cache")
    xs = jnp.asarray(np.zeros((1, sz["batch"], sz["block"]), np.int32))
    return all(lowered_has_kernel(arch._jit_cache[k], model.params,
                                  model.opt_state, model.buffers, xs, xs,
                                  jax.random.key(0)) for k in keys)


SCHED_ENV = {"PENROZ_CONTINUOUS_BATCHING": "1", "PAGED_KV_CACHE": "1",
             "PENROZ_PREFIX_CACHE": "1", "PENROZ_SCHED_SUPERSTEP": "8",
             "PENROZ_SCHED_MAX_ROWS": "8"}


def launch_env(extra: dict):
    """Switch the launch configuration of the in-process server: the env
    knobs a user would export before starting it (read at call time), with
    every engine of the previous configuration shut down first."""
    from penroz_tpu.serve import decode_scheduler
    decode_scheduler.reset()
    for key in list(os.environ):
        if key in launch_env.owned:
            del os.environ[key]
    os.environ.update(extra)
    launch_env.owned = set(extra)


launch_env.owned = set()


def follows_cycle(tokens: list, prompt_len: int, cycle: list) -> int:
    """How many generated tokens are the training cycle's successor of the
    token before them — what a model that learned the shard emits."""
    succ = dict(zip(cycle, cycle[1:] + cycle[:1]))
    return sum(succ.get(a) == b for a, b in
               zip(tokens[prompt_len - 1:], tokens[prompt_len:]))


def prompts_for(cycle: list, lens: list) -> list:
    """Prompts of the given lengths that walk the training cycle from
    different starting points."""
    return [[cycle[(i * 7 + j) % len(cycle)] for j in range(n)]
            for i, n in enumerate(lens)]


def concurrent_generate(svc: Service, prompts: list, sz: dict) -> list:
    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        futs = [pool.submit(svc.generate, p, sz["new_tokens"], sz["block"])
                for p in prompts]
        return [f.result(timeout=1200) for f in futs]


def engines():
    from penroz_tpu.serve import decode_scheduler
    with decode_scheduler._REG_LOCK:
        return [e for e in decode_scheduler._ENGINES.values()
                if not e._shutdown]


def check_serving_health(stats: dict, served: int):
    check(stats["crashes_total"] == 0 and stats["engine_resets"] == 0,
          f"engine crashed: crashes_total={stats['crashes_total']} "
          f"engine_resets={stats['engine_resets']}")
    check(not stats["breaker_open"]
          and all(e["breaker_rejections"] == 0 for e in stats["engines"]),
          "a circuit breaker tripped")
    completed = sum(e["completed"] for e in stats["engines"])
    check(completed == served, f"scheduler completed {completed} of {served} "
          f"requests — the rest fell back to the legacy path")


def check_memory_partition(svc: Service, replicas: int = 1):
    mem = svc.ok("GET", "/memory/")
    check(len(mem["engines"]) == replicas,
          f"/memory/ lists {len(mem['engines'])} engines, wanted {replicas}")
    for e in mem["engines"]:
        check(sum(e["pool_pages"].values()) == e["pool_pages_total"] > 0,
              f"/memory/ partition {e['pool_pages']} does not sum to the "
              f"pool ({e['pool_pages_total']} pages)")
    check(mem["audit_failures"] == 0, "memory-ledger audit failed")
    return mem


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_native():
    """Build the native loader/BPE from native/*.cpp in THIS run: the
    ``_native/*.so`` on disk are git-ignored leftovers a checkout does not
    have, and a stale one must not stand in for the source."""
    import glob
    from penroz_tpu.data import bpe, loaders
    native_dir = os.path.join(os.path.dirname(loaders.__file__), "_native")
    for stale in glob.glob(os.path.join(native_dir, "*.so")):
        os.remove(stale)
    loader = loaders._native_loader_module()
    tokenizer = bpe._load_native()
    emit(phase="native", loader="native" if loader else "python",
         bpe="native" if tokenizer else "python",
         toolchain=shutil.which("g++") or "g++ not found")
    check(loader is not None and tokenizer is not None,
          "native loader/BPE did not build (see the warning above); "
          "training would be served by the Python loader")


def normalized_error(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-12))


def median_ms(fn, *args, calls: int = 20) -> float:
    """Median wall time of ``calls`` calls of a jitted ``fn``, each waited
    for, after two that warm it up."""
    import jax
    import statistics
    times = []
    for i in range(calls + 2):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times[2:])


def phase_kernels(sz: dict, on_tpu: bool):
    """Each main-path kernel, compiled on the chip through the dispatchers
    the model uses, against its jnp oracle on the same inputs.  Error is
    max|got − want| / max|want| per output; tolerances 4e-2 where inputs
    or outputs are bf16 (ε = 7.8e-3, several roundings deep in the flash
    backward) and 1e-2 for fp32 — a smoke test's: a wrong mask, layout or
    page walk is an O(1) error.  The measured errors are printed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from penroz_tpu.ops import attention as A
    from penroz_tpu.ops import losses, ssm

    hint = "tpu" if on_tpu else None   # "cpu" = the jnp path, same device
    rng = np.random.default_rng(0)
    H, D, T, B = sz["heads"], 64, sz["kernel_T"], sz["kernel_rows"]
    page = sz["page"]
    BF16, F32 = 4e-2, 1e-2

    def rand(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32), dtype)

    report = {}

    def compare(name, kernel_fn, oracle_fn, args, tol):
        jitted = jax.jit(kernel_fn)
        if on_tpu:
            check(lowered_has_kernel(jitted, *args),
                  f"{name}: the gate chose the jnp path on the chip")
        with jax.default_matmul_precision("float32"):  # fp32 oracles
            want = jax.jit(oracle_fn)(*args)
        got = jitted(*args)
        err = max(normalized_error(g, w) for g, w in
                  zip(jax.tree.leaves(got), jax.tree.leaves(want)))
        report[name] = round(err, 6)
        check(err <= tol and all(np.isfinite(np.asarray(g, np.float32)).all()
                                 for g in jax.tree.leaves(got)),
              f"{name}: error {err:.3g} over tolerance {tol}")

    # flash forward + backward
    def flash_loss(attend, q, k, v):
        out = attend(q, k, v)
        return (out.astype(jnp.float32) ** 2).sum(), out

    q, k, v = (rand(2, H, T, D) for _ in range(3))
    compare("flash_fwd_bwd",
            jax.value_and_grad(lambda q, k, v: flash_loss(
                lambda *a: A.causal_attention(*a, platform=hint), q, k, v),
                argnums=(0, 1, 2), has_aux=True),
            jax.value_and_grad(lambda q, k, v: flash_loss(
                A.causal_attention_reference, q, k, v),
                argnums=(0, 1, 2), has_aux=True),
            (q, k, v), BF16)

    # the same at the benchmark cell's micro-batch (gpt2s-train-1chip:
    # 12 x 12 x 1024 x 64), and timed: the before/after of a kernel change
    qc, kc, vc = (rand(sz["cell_rows"], H, T, D) for _ in range(3))
    flash_fwd = jax.jit(lambda q, k, v: A.causal_attention(
        q, k, v, platform=hint))
    flash_fwd_bwd = jax.value_and_grad(lambda q, k, v: flash_loss(
        lambda *a: A.causal_attention(*a, platform=hint), q, k, v),
        argnums=(0, 1, 2), has_aux=True)
    compare("flash_fwd_bwd_cell", flash_fwd_bwd,
            jax.value_and_grad(lambda q, k, v: flash_loss(
                A.causal_attention_reference, q, k, v),
                argnums=(0, 1, 2), has_aux=True),
            (qc, kc, vc), BF16)
    fwd_ms = median_ms(flash_fwd, qc, kc, vc)
    timings = {"flash_fwd_ms": round(fwd_ms, 4),
               "flash_bwd_ms": round(median_ms(jax.jit(flash_fwd_bwd),
                                               qc, kc, vc) - fwd_ms, 4)}

    # the same attention in the model's own layout, (B, T, lanes), whose
    # entry is the kernels' alone (off the chip there is nothing to compare)
    if on_tpu:
        def heads_first(x, heads):
            return x.reshape(*x.shape[:2], heads, -1).transpose(0, 2, 1, 3)

        def in_btd(attend, heads, kv_heads):
            """``attend`` on (B, H, T, D) operands as a function of
            (B, T, H·D) ones: the relayouts the module made before PR 32."""
            def fn(q, k, v):
                out = attend(heads_first(q, heads), heads_first(k, kv_heads),
                             heads_first(v, kv_heads))
                return out.transpose(0, 2, 1, 3).reshape(q.shape)
            return fn

        def value_and_grads(attend):
            return jax.value_and_grad(
                lambda *a: flash_loss(attend, *a),
                argnums=(0, 1, 2), has_aux=True)

        # as the training phases below run it: the fused (B, T, 3·H·D)
        # projection read in place, two D = 64 heads to a lane block
        def fused(attend):
            def loss(qkv):
                out = attend(qkv)
                return (out.astype(jnp.float32) ** 2).sum(), out
            return jax.value_and_grad(loss, has_aux=True)

        w = H * D
        qkv_cell = rand(sz["cell_rows"], T, 3 * w)
        flash_btd = fused(lambda qkv: A.causal_attention_btd(
            qkv, heads=H, kv_heads=H, platform=hint))
        compare("flash_btd_fwd_bwd_cell", flash_btd,
                fused(lambda qkv: in_btd(A.causal_attention_reference, H, H)(
                    qkv[..., :w], qkv[..., w:2 * w], qkv[..., 2 * w:])),
                (qkv_cell,), BF16)
        timings["flash_btd_fwd_bwd_ms"] = round(
            median_ms(jax.jit(flash_btd), qkv_cell), 4)

        # three arrays (what RoPE / qk-norm models hand over), D = 128,
        # grouped-query: 8 query heads on 2 K/V heads, one head a lane block
        hq, hkv, d = 8, 2, 128
        qg = rand(4, T, hq * d)
        kg, vg = (rand(4, T, hkv * d) for _ in range(2))
        flash_gqa = value_and_grads(lambda q, k, v: A.causal_attention_btd(
            q, k, v, heads=hq, kv_heads=hkv, platform=hint))
        compare("flash_btd_gqa128_fwd_bwd", flash_gqa,
                value_and_grads(in_btd(A.causal_attention_reference,
                                       hq, hkv)), (qg, kg, vg), BF16)
        timings["flash_btd_gqa128_fwd_bwd_ms"] = round(
            median_ms(jax.jit(flash_gqa), qg, kg, vg), 4)
        timings["flash_bhtd_gqa128_fwd_bwd_ms"] = round(median_ms(
            jax.jit(value_and_grads(in_btd(
                lambda *a: A.causal_attention(*a, platform=hint),
                hq, hkv))), qg, kg, vg), 4)

        # the looped cell's attention (ouro-train-4k-loop4: micro-batch 2,
        # 16 heads of 128, T = 4096, three arrays after RoPE): the one-pass
        # backward under the VMEM limit its plan asks the compiler for
        hl, tl = 16, 4096
        ql, kl, vl = (rand(2, tl, hl * d) for _ in range(3))
        flash_loop = value_and_grads(lambda q, k, v: A.causal_attention_btd(
            q, k, v, heads=hl, kv_heads=hl, platform=hint))
        compare("flash_btd_loop4k_fwd_bwd", flash_loop,
                value_and_grads(in_btd(A.causal_attention_reference,
                                       hl, hl)), (ql, kl, vl), BF16)
        timings["flash_btd_loop4k_fwd_bwd_ms"] = round(
            median_ms(jax.jit(flash_loop), ql, kl, vl), 4)

    # contiguous decode, ragged lengths
    lengths = jnp.asarray(rng.integers(1, T + 1, B), jnp.int32)
    q1 = rand(B, H, 1, D)
    cache = [rand(B, H, T, D) for _ in range(2)]
    cache8 = [jnp.round(c.astype(jnp.float32) * 30).astype(jnp.int8)
              for c in cache]
    scale8 = [jnp.abs(rand(B, H, T, 1, dtype=jnp.float32)) / 30 + 1e-3
              for _ in range(2)]

    def decode(platform):
        return lambda q, k, v, n, *s: A.cached_attention(
            q, k, v, 0, n, platform=platform,
            **(dict(k_scale=s[0], v_scale=s[1]) if s else {}))

    compare("decode_bf16", decode(hint), decode("cpu"),
            (q1, *cache, lengths), BF16)
    compare("decode_int8", decode(hint), decode("cpu"),
            (q1, *cache8, lengths, *scale8), BF16)

    # paged pool, block table a permutation of the pages
    pages_per_seq = T // page
    table = jnp.asarray(rng.permutation(B * pages_per_seq)
                        .reshape(B, pages_per_seq).astype(np.int32))
    pool = [rand(H, B * T, D) for _ in range(2)]
    pool8 = [jnp.round(p.astype(jnp.float32) * 30).astype(jnp.int8)
             for p in pool]
    pscale = [jnp.abs(rand(H, B * T, 1, dtype=jnp.float32)) / 30 + 1e-3
              for _ in range(2)]

    def paged(platform):
        return lambda q, k, v, t, n, *s: A.paged_cached_attention(
            q, k, v, t, page, 0, n, platform=platform,
            **(dict(k_scale=s[0], v_scale=s[1]) if s else {}))

    compare("paged_bf16", paged(hint), paged("cpu"),
            (q1, *pool, table, lengths), BF16)
    compare("paged_int8", paged(hint), paged("cpu"),
            (q1, *pool8, table, lengths, *pscale), BF16)

    # ragged unified tick: B-1 decode rows + one row prefilling 3 blocks
    block_q = 8
    rows = [[r, int(lengths[r]) - 1, 1, int(lengths[r])]
            for r in range(B - 1)]
    chunk0 = 2 * block_q
    rows += [[B - 1, chunk0 + i * block_q, block_q if i < 2 else 3,
              chunk0 + 2 * block_q + 3] for i in range(3)]
    rows += [[-1, 0, 0, 0]] * (-len(rows) % 8)
    descs = jnp.asarray(rows, jnp.int32)
    qp = rand(1, H, len(rows) * block_q, D)

    def ragged(q, k, v, t, d, *s):
        return A.ragged_paged_cached_attention(
            q, k, v, t, page, d, platform=hint,
            **(dict(k_scale=s[0], v_scale=s[1]) if s else {}))

    def ragged_oracle(q, k, v, t, d, *s):
        return A.ragged_paged_attention_reference(
            q, k, v, t, page, d,
            **(dict(k_scale=s[0], v_scale=s[1]) if s else {}))

    compare("ragged_bf16", ragged, ragged_oracle,
            (qp, *pool, table, descs), BF16)
    compare("ragged_int8", ragged, ragged_oracle,
            (qp, *pool8, table, descs, *pscale), BF16)
    qf = qp.astype(jnp.float32)
    compare("ragged_f32", ragged, ragged_oracle,
            (qf, *(p.astype(jnp.float32) for p in pool), table, descs), F32)

    # fused cross-entropy forward + backward at the full vocabulary
    logits = rand(2, T, sz["kernel_vocab"]) * 3
    targets = jnp.asarray(rng.integers(0, sz["kernel_vocab"], (2, T)),
                          jnp.int32)

    def ce(platform):
        return jax.value_and_grad(
            lambda x, y: losses.fused_cross_entropy_mean(x, y, 512, platform))

    compare("fused_ce_fwd_bwd", ce(hint), ce("cpu"), (logits, targets), BF16)

    # gated-SSM chunked scan (presets.hybrid_custom widths)
    sq, sk, sv = (rand(2, T, H, D, dtype=jnp.float32) * 0.3
                  for _ in range(3))
    sg = jnp.asarray(rng.uniform(0.8, 0.999, (2, T, H)), jnp.float32)
    compare("ssm_scan", lambda *a: ssm.gla_full(*a, platform=hint),
            ssm.gla_full_reference, (sq, sk, sv, sg), F32)

    emit(phase="kernels", normalized_max_error=report,
         tolerance={"bf16": BF16, "f32": F32}, **timings)


def phase_train_one_chip(svc: Service, sz: dict, device: str, on_tpu: bool):
    create_model(svc, sz)
    result = train(svc, sz, device)
    costs = result["costs"]
    uniform = math.log(sz["vocab"])
    check(costs[-1] < uniform - 1.0,
          f"cost {costs[-1]:.3f} after {len(costs)} steps is not clearly "
          f"under ln(vocab) = {uniform:.2f} on a learnable shard: {costs}")
    want = {"tpu"} if on_tpu else {"cpu"}
    seen = result["seen"]
    check(set(seen["params"]) == want and set(seen["opt_state"]) == want,
          f"training state seen on {seen}, wanted {sorted(want)}")
    evaluated = svc.ok("POST", "/evaluate/", dict(result["body"], epochs=1))
    check(math.isfinite(evaluated["cost"])
          and evaluated["cost"] < uniform - 1.0,
          f"/evaluate/ cost {evaluated['cost']}")
    stats = svc.ok("GET", f"/stats/?model_id={MODEL}")
    check(isinstance(stats, dict) and stats.get("layers")
          and stats.get("weights"), "/stats/ has no histograms")
    tokens = sz["batch"] * sz["block"]
    emit(phase="train", device=device, costs=[round(c, 4) for c in costs],
         evaluate_cost=round(evaluated["cost"], 4),
         first_epoch_s_incl_compile=round(result["first_epoch_s"], 2),
         steady_step_s=result["steady_epoch_s"],
         steady_tokens_per_s=(round(tokens / result["steady_epoch_s"], 1)
                              if result["steady_epoch_s"] else None),
         wall_s=result["wall_s"], state_platforms=seen)
    has = train_program_has_kernels(sz)
    emit(phase="train_program", pallas_custom_calls=has)
    check(has or not on_tpu, "the train epoch program carries no Pallas "
          "custom call: the kernel gates chose the jnp path")
    return has


def phase_generate_default(svc: Service, sz: dict, cycle: list, on_tpu: bool):
    """Default settings: contiguous KV, the legacy per-request path."""
    launch_env({})
    prompt = prompts_for(cycle, [PROBE_LEN])[0]
    t0 = time.monotonic()
    first = svc.generate(prompt, sz["new_tokens"], sz["block"])
    cold_s = time.monotonic() - t0
    t0 = time.monotonic()
    second = svc.generate(prompt, sz["new_tokens"], sz["block"])
    warm_s = time.monotonic() - t0
    check(first == second, f"temperature 0 is not deterministic: {first} "
          f"vs {second}")
    check(len(first) == len(prompt) + sz["new_tokens"],
          f"{len(first)} tokens for a {len(prompt)}-token prompt + "
          f"{sz['new_tokens']} new")
    with Spy() as spy:
        streamed = svc.generate(prompt, sz["new_tokens"], sz["block"],
                                stream=True)
    seen = spy.summary()
    check(streamed == first, f"stream {streamed} != non-stream {first}")
    want = {"tpu"} if on_tpu else {"cpu"}
    check(set(seen["params"]) == want and set(seen["kv"]) == want,
          f"/generate/ state seen on {seen}, wanted {sorted(want)}")
    learned = follows_cycle(first, len(prompt), cycle)
    emit(phase="generate_default", tokens=first[len(prompt):],
         follows_training_cycle=f"{learned}/{sz['new_tokens']}",
         cold_request_s=round(cold_s, 2), warm_request_s=round(warm_s, 2),
         warm_tokens_per_s_incl_checkpoint_load=round(
             sz["new_tokens"] / warm_s, 1), state_platforms=seen)
    return first, learned


def mixed_program_has_kernels(engine) -> bool:
    """Re-lower the engine's unified-tick programs at the shapes it ran."""
    import jax
    import numpy as np
    arch = engine._model.arch
    # the arch cache is shared by every engine of this DSL; an engine's own
    # programs are the ones keyed by its placement hint
    keys = [k for k in arch._jit_cache
            if isinstance(k, tuple) and k and k[0] == "mixed_step"
            and engine._model._placement in k]
    check(keys, "the engine never ran a unified (mixed_step) program")
    model, found = engine._model, []
    for key in keys:
        n, nb, tp = key[1:4]
        i32 = lambda *shape: np.zeros(shape, np.int32)
        found.append(lowered_has_kernel(
            arch._jit_cache[key], model.params, model.buffers, engine._kv,
            i32(n, nb, 4), i32(n, tp), i32(n, tp), i32(n, tp),
            i32(n, engine.capacity), i32(n, tp), i32(n, tp),
            i32(engine.capacity), jax.random.key(0), i32(), np.float32(1.0),
            None))
    return all(found)


def phase_generate_scheduler(svc: Service, sz: dict, cycle: list,
                             on_tpu: bool, default: tuple):
    """Continuous batching + paged pool + prefix cache + superstep 8 +
    ragged unified attention (the verify skill's Round-10 launch)."""
    launch_env(dict(SCHED_ENV, PENROZ_KV_PAGE_SIZE=str(sz["page"])))
    prompts = prompts_for(cycle, sz["prompt_lens"])
    t0 = time.monotonic()
    outs = concurrent_generate(svc, prompts, sz)
    burst_s = time.monotonic() - t0
    for p, out in zip(prompts, outs):
        check(out[:len(p)] == p and len(out) == len(p) + sz["new_tokens"],
              f"scheduler returned {len(out)} tokens for a {len(p)}-token "
              f"prompt")
    served = len(prompts)
    stats = svc.ok("GET", "/serving_stats/")
    ticks = stats["tick_timeline"]
    check(any(t["unified"] and t["prefill_rows"] > 0 and t["decode_rows"] > 0
              for t in ticks),
          "no unified tick carried prefill and decode rows together: "
          f"{[(t['unified'], t['prefill_rows'], t['decode_rows']) for t in ticks][:20]}")
    check(any(t["unified"] and t["superstep"] > 1 for t in ticks),
          "no unified tick fused more than one step")
    # parity with the default path, determinism, stream == non-stream; the
    # first request compiles this prompt length's bucket, the second is warm
    # and finds its first pages in the prefix cache
    prompt = prompts_for(cycle, [PROBE_LEN])[0]
    again = svc.generate(prompt, sz["new_tokens"], sz["block"])
    t0 = time.monotonic()
    repeat = svc.generate(prompt, sz["new_tokens"], sz["block"])
    warm_s = time.monotonic() - t0
    streamed = svc.generate(prompt, sz["new_tokens"], sz["block"],
                            stream=True)
    served += 3
    check(again == repeat, "scheduler path is not deterministic at "
          "temperature 0")
    default_tokens, default_learned = default or (None, 0)
    learned = follows_cycle(again, len(prompt), cycle)
    check(streamed == again, "scheduler stream != non-stream")
    stats = svc.ok("GET", "/serving_stats/")
    check_serving_health(stats, served)
    check(stats["prefix_cache_hit_rate"], "a repeated prompt of "
          f"{PROBE_LEN} tokens never hit the prefix cache")
    check_memory_partition(svc)
    (engine,) = engines()
    want = {"tpu"} if on_tpu else {"cpu"}
    where = {"params": platforms(engine._model.params),
             "kv": platforms(engine._kv)}
    check(where["params"] == want and where["kv"] == want,
          f"engine state on {where}, wanted {sorted(want)}")
    has = mixed_program_has_kernels(engine)
    emit(phase="generate_scheduler", requests=served,
         burst_of_6_s_incl_compile=round(burst_s, 2),
         warm_request_s=round(warm_s, 2),
         warm_tokens_per_s=round(sz["new_tokens"] / warm_s, 1),
         tokens_equal_default_path=again == default_tokens,
         follows_training_cycle=f"{learned}/{sz['new_tokens']}",
         unified_ticks=sum(t["unified"] for t in ticks),
         mixed_ticks=sum(bool(t["unified"] and t["prefill_rows"]
                              and t["decode_rows"]) for t in ticks),
         tick_ms_p50=stats["tick_ms_p50"], itl_ms_p50=stats["itl_ms_p50"],
         tokens_per_dispatch_avg=stats["tokens_per_dispatch_avg"],
         prefix_cache_hit_rate=stats["prefix_cache_hit_rate"],
         crashes_total=stats["crashes_total"],
         mixed_step_pallas_custom_calls=has,
         state_platforms={k: sorted(v) for k, v in where.items()})
    check(has or not on_tpu, "the mixed decode program carries no Pallas "
          "custom call: the kernel gate chose the jnp path")
    # Two different kernels (contiguous decode vs ragged paged) round
    # differently, so a near-tie may flip a greedy token; what both paths
    # must do alike is continue the cycle the model was trained on.
    check(learned >= default_learned - 2,
          f"scheduler path continues the training cycle on {learned}/"
          f"{sz['new_tokens']} tokens, the default path on "
          f"{default_learned}")


def run_one_chip(sz: dict, seed: int, device: str, on_tpu: bool, phase):
    phase("native", phase_native)
    phase("kernels", lambda: phase_kernels(sz, on_tpu))
    cycle = fabricate_shard(seed, sz["vocab"], 40 * sz["batch"] * sz["block"])
    svc = Service()
    try:
        state = {}
        phase("train", lambda: phase_train_one_chip(svc, sz, device, on_tpu))
        phase("generate_default", lambda: state.update(
            default=phase_generate_default(svc, sz, cycle, on_tpu)))
        phase("generate_scheduler", lambda: phase_generate_scheduler(
            svc, sz, cycle, on_tpu, state.get("default")))
    finally:
        svc.call("DELETE", "/model/?model_id=" + MODEL)
        svc.stop()


# ---------------------------------------------------------------------------
# four chips: only what exists across chips
# ---------------------------------------------------------------------------

def phase_train_mesh(svc: Service, sz: dict, device: str, on_tpu: bool):
    """The same /train/ on the automatic data=4 mesh and, with
    PENROZ_TRAIN_MESH=0, on one chip: same init (seed 0), same shard."""
    import jax
    import numpy as np
    from penroz_tpu.models.model import NeuralNetworkModel
    from penroz_tpu.parallel import mesh as mesh_lib
    epochs = 40
    create_model(svc, sz, MODEL)           # trains on the mesh, then serves
    create_model(svc, sz, "smoke-one")     # same init, one chip
    launch_env({})
    probe = NeuralNetworkModel.deserialize(MODEL)
    probe.to_device(device)
    mesh = probe._training_mesh(sz["batch"], sz["block"])
    check(mesh is not None and mesh.shape[mesh_lib.DATA_AXIS] == 4,
          f"automatic training mesh is {mesh and dict(mesh.shape)}, wanted "
          f"data=4")
    mesh_devs = {d.id: d.platform for d in np.asarray(mesh.devices).flat}
    check(len(mesh_devs) == 4 and (not on_tpu or set(mesh_devs.values())
                                   == {"tpu"}),
          f"mesh devices {mesh_devs}")
    del probe
    meshed = train(svc, sz, device, MODEL, epochs)
    seen = meshed["seen"]
    shard = (1, sz["batch"] // 4, sz["block"])
    check(seen["param_devices"] == 4,
          f"meshed training kept its params on {seen['param_devices']} "
          f"device(s), wanted 4")
    check(seen["batch_devices"] == 4
          and seen["batch_shard_shapes"] == [shard],
          f"batch on {seen['batch_devices']} device(s) in shards "
          f"{seen['batch_shard_shapes']}, wanted 4 × {shard}")
    has = train_program_has_kernels(sz, MODEL)
    launch_env({"PENROZ_TRAIN_MESH": "0"})
    single = train(svc, sz, device, "smoke-one", epochs)
    check(single["seen"]["param_devices"] == 1,
          f"PENROZ_TRAIN_MESH=0 trained on "
          f"{single['seen']['param_devices']} devices")
    # Same weights at step 1, so only the forward's reduction order differs.
    # After that each shard's bf16 gradient is rounded before the all-reduce
    # (≈4e-3 relative per element) and AdamW without warm-up passes through
    # an unstable stretch that amplifies it, so the trajectories are held
    # to 10 % of the loss, and reported whole.
    gaps = [abs(a - b) for a, b in zip(meshed["costs"], single["costs"])]
    first_tol, rel_tol = 0.01, 0.10
    rel_gap = max(g / b for g, b in zip(gaps, single["costs"]))
    emit(phase="train_mesh", mesh=dict(mesh.shape),
         mesh_devices=sorted(mesh_devs),
         batch_device_set=seen["batch_devices"], batch_shard_shape=shard,
         param_device_set=seen["param_devices"],
         costs_mesh=[round(c, 4) for c in meshed["costs"]],
         costs_one_chip=[round(c, 4) for c in single["costs"]],
         first_step_gap=round(gaps[0], 6), first_step_tolerance=first_tol,
         max_relative_gap=round(rel_gap, 5), relative_tolerance=rel_tol,
         steady_step_s_mesh=meshed["steady_epoch_s"],
         steady_step_s_one_chip=single["steady_epoch_s"],
         mesh_program_pallas_custom_calls=has)
    check(gaps[0] <= first_tol and rel_gap <= rel_tol,
          f"loss on the data=4 mesh and on one chip differ by {gaps[0]:.4f} "
          f"at step 1 (> {first_tol}) or by {rel_gap:.3f} of the loss later "
          f"(> {rel_tol}): {meshed['costs']} vs {single['costs']}")
    check(has or not on_tpu, "the meshed train program carries no Pallas "
          "custom call")
    check(meshed["costs"][-1] < math.log(sz["vocab"]) - 0.5,
          f"meshed training did not learn: {meshed['costs']}")
    svc.ok("DELETE", "/model/?model_id=smoke-one", expect=204)


def phase_serve_mesh(svc: Service, sz: dict, cycle: list, on_tpu: bool):
    """Scheduler path on a 4-wide tensor-parallel serving mesh against the
    unmeshed engine: logits through the same forward, greedy tokens, and
    where the KV pool lives."""
    import numpy as np
    from penroz_tpu.parallel import mesh as mesh_lib
    sched = dict(SCHED_ENV, PENROZ_KV_PAGE_SIZE=str(sz["page"]))
    prompts = prompts_for(cycle, sz["prompt_lens"])
    probe = [prompts[1][:8]]

    launch_env(sched)
    base_tokens = concurrent_generate(svc, prompts, sz)
    (engine,) = engines()
    base_logits = np.asarray(engine._model.compute_output(probe)[0])
    check(len(device_ids(engine._kv)) == 1, "unmeshed engine KV spans "
          f"{len(device_ids(engine._kv))} devices")

    launch_env(dict(sched, PENROZ_SERVE_MESH="1",
                    PENROZ_SERVE_MESH_MODEL="4"))
    mesh_tokens = concurrent_generate(svc, prompts, sz)
    (engine,) = engines()
    pool = engine._kv.k[0]
    spec = tuple(pool.sharding.spec)
    check(len(pool.sharding.device_set) == 4
          and spec[:1] == (mesh_lib.MODEL_AXIS,),
          f"KV pool sharded {spec} over {len(pool.sharding.device_set)} "
          f"device(s), wanted heads over model=4")
    check(len(device_ids(engine._model.params)) == 4,
          "meshed engine params are not on 4 devices")
    mesh_logits = np.asarray(engine._model.compute_output(probe)[0])
    err = normalized_error(mesh_logits, base_logits)
    # fp32 params, but the TPU's default fp32 matmul multiplies in bf16
    # passes, and TP splits each contraction four ways
    tol = 1e-2
    stats = svc.ok("GET", "/serving_stats/")
    check_serving_health(stats, len(prompts))
    check_memory_partition(svc)
    check(stats["engines"][0]["mesh_devices"] == 4,
          f"/serving_stats/ mesh_devices = "
          f"{stats['engines'][0]['mesh_devices']}")
    agree = sum(a == b for a, b in zip(sum(base_tokens, []),
                                       sum(mesh_tokens, [])))
    total = sum(map(len, base_tokens))
    has = mixed_program_has_kernels(engine)
    emit(phase="serve_mesh", mesh_devices=4, kv_pool_spec=[str(s) for s in spec],
         kv_pool_device_set=len(pool.sharding.device_set),
         kv_pool_shard_shape=list(pool.addressable_shards[0].data.shape),
         output_probabilities_normalized_max_error=round(err, 7),
         tolerance=tol, greedy_tokens_equal=f"{agree}/{total}",
         mixed_step_pallas_custom_calls=has,
         tick_ms_p50=stats["tick_ms_p50"])
    check(err <= tol, f"TP-4 output differs from one chip by {err:.3g} "
          f"(> {tol})")
    # a near-tie may flip under another reduction order and the streams
    # then part ways; the logits above are the tolerance that counts
    check(agree >= 0.5 * total, f"greedy tokens agree on {agree}/{total}")
    check(has or not on_tpu, "the meshed mixed-step program carries no "
          "Pallas custom call")
    return base_tokens


def phase_replicas(svc: Service, sz: dict, cycle: list, base_tokens: list):
    """PENROZ_SCHED_REPLICAS=4 at mesh width 1: one engine per chip."""
    launch_env(dict(SCHED_ENV, PENROZ_KV_PAGE_SIZE=str(sz["page"]),
                    PENROZ_PREFIX_CACHE="0", PENROZ_SCHED_REPLICAS="4",
                    PENROZ_SERVE_MESH="1", PENROZ_SERVE_MESH_MODEL="1"))
    prompt = prompts_for(cycle, sz["prompt_lens"])[1]
    outs = concurrent_generate(svc, [prompt] * 8, sz)
    check(all(o == outs[0] for o in outs),
          "replicas gave different greedy answers to one prompt")
    check(base_tokens is None or outs[0] == base_tokens[1],
          "replica answer differs from the single unmeshed engine's")
    stats = svc.ok("GET", "/serving_stats/")
    check_serving_health(stats, 8)
    check(stats["router_replicas"] == 4, f"router_replicas = "
          f"{stats['router_replicas']}")
    by_replica = {e["replica"]: e["completed"] for e in stats["engines"]}
    check(sorted(by_replica) == [0, 1, 2, 3]
          and all(n >= 1 for n in by_replica.values()),
          f"completed per replica {by_replica}: a replica served nothing")
    check_memory_partition(svc, replicas=4)
    where = {e.replica: (sorted(device_ids(e._model.params)),
                         sorted(device_ids(e._kv))) for e in engines()}
    check(len({tuple(p) for p, _ in where.values()}) == 4
          and all(p == kv and len(p) == 1 for p, kv in where.values()),
          f"replica placement {where}: wanted one device each, all "
          f"distinct, KV with its params")
    emit(phase="replicas", router_replicas=4, completed_by_replica=by_replica,
         replica_devices={r: p for r, (p, _) in sorted(where.items())},
         same_answer_from_every_replica=True,
         tick_ms_p50=stats["tick_ms_p50"])


def run_four_chips(sz: dict, seed: int, device: str, on_tpu: bool, phase):
    cycle = fabricate_shard(seed, sz["vocab"], 40 * sz["batch"] * sz["block"])
    svc = Service()
    try:
        state = {}
        phase("train_mesh", lambda: phase_train_mesh(svc, sz, device, on_tpu))
        phase("serve_mesh", lambda: state.update(
            base=phase_serve_mesh(svc, sz, cycle, on_tpu)))
        phase("replicas", lambda: phase_replicas(svc, sz, cycle,
                                                 state.get("base")))
    finally:
        svc.call("DELETE", "/model/?model_id=" + MODEL)
        svc.stop()


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the cross-chip phases")
    parser.add_argument("--tiny", action="store_true",
                        help="toy widths: a rehearsal, never a chip run")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jaxlib
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: no TPU (JAX reports platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, repo)
    import penroz_tpu  # noqa: F401 — fails here, before any phase, if absent
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    emit(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu_version, python=sys.version.split()[0],
         platform=dev.platform, device_kind=dev.device_kind,
         device_count=len(devices), tiny=args.tiny, chips=args.chips,
         seed=args.seed,
         compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR",
                                      "<checkout>/.jax_cache"))

    sz = sizes(args.tiny)
    device = "tpu" if on_tpu else "cpu"
    failed = []

    def phase(name, fn):
        t0 = time.monotonic()
        try:
            fn()
        except Exception as exc:  # noqa: BLE001 — a phase boundary: record
            traceback.print_exc()
            failed.append(name)
            emit(phase=name, ok=False, error=f"{type(exc).__name__}: {exc}"[:2000])
        finally:
            emit(phase=name, seconds=round(time.monotonic() - t0, 1))

    workdir = tempfile.mkdtemp(prefix="penroz_smoke_")
    prev_cwd = os.getcwd()
    os.chdir(workdir)   # models/ and data/ are relative to the server's cwd
    t0 = time.monotonic()
    try:
        (run_four_chips if args.chips == 4 else run_one_chip)(
            sz, args.seed, device, on_tpu, phase)
    except Exception as exc:  # noqa: BLE001 — set-up/tear-down outside a phase
        traceback.print_exc()
        failed.append(f"harness: {type(exc).__name__}: {exc}"[:500])
    finally:
        os.chdir(prev_cwd)
        shutil.rmtree(workdir, ignore_errors=True)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    emit(phase="end", wall_s=round(time.monotonic() - t0, 1),
         failed_phases=failed,
         peak_hbm_bytes=peaks if any(p is not None for p in peaks)
         else "not reported by this backend")
    ok = on_tpu and not args.tiny and not failed
    print(json.dumps({"ok": ok, "device": {"platform": dev.platform,
                                           "kind": dev.device_kind,
                                           "count": len(devices)}}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
