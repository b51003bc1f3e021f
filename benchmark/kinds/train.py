"""Traffic of kind ``train``: one ``PUT /train/`` that outlasts the window.

The service runs in this process and is observed from inside
(``lib/spy.py``): no REST call is made between the PUT and the end of the
window, because ``GET /progress/`` deserializes the whole checkpoint in the
server's threads and would compete with the save it is measuring.  Warm-up
lasts until the end of the first periodic save (compilation, the save at
train start and the first save's cold costs are then behind); the window is
the whole save cycles that complete within ``--seconds`` (``lib/cycles.py``).

``train_tokens_per_s`` is the tokens of those cycles over their wall time
*less the time inside the periodic saves*: every step of the whole cycles
and all the time between their saves.  Two readers split it
(``lib/cycles.py``): the median step that ran back to back
(``train_step_ms``, which repeats to the fourth digit) and what a cycle's
steps and bookkeeping took beyond that pace (``save_edge_ms``: mostly one
step a cycle that waits for the flush thread of the save before it, 0 to
0.74 s by the host of the run, which is the whole of the rate's spread;
PERF.md sections 2 and 5).  The whole-cycle rate, stall included, is printed
beside it (``whole_cycle_tokens_per_s``) and is what a user feels, but a
step more or less to a cycle and a save 2.3 s longer than the other move it
by 6 %, which two or three cycles to a window cannot average out; the stall
is the per-layer ``ckpt_stall_pct`` until it repeats.
"""

from __future__ import annotations

import math
import os
import shutil
import time
from statistics import median

import numpy as np

from benchmark.lib import cycles, kernel_costs, program, tracing
from benchmark.lib.service import Service
from benchmark.lib.spy import TrainSpy

MODEL = "bench"
DATASET = "benchtoks"


def token_stream(seed: int, vocab: int, tokens: int) -> np.ndarray:
    """A learnable stream made from the seed: a fixed 64-token cycle of
    distinct ids (as ``chip_smoke.py::fabricate_shard``), so that a falling
    loss means "it learns" and nothing about quality."""
    rng = np.random.default_rng([int(seed), 7])
    cycle = rng.choice(vocab, size=64, replace=False)
    return np.tile(cycle, tokens // 64 + 1)[:tokens]


def fabricate_shard(seed: int, vocab: int, tokens: int):
    os.makedirs("data", exist_ok=True)
    np.save(f"data/{DATASET}_000000",
            token_stream(seed, vocab, tokens).astype(np.uint16))


def _wait_until(spy, predicate, timeout: float, what: str):
    deadline = time.monotonic() + timeout
    while not predicate():
        if time.monotonic() > deadline:
            raise TimeoutError(f"{what}: not within {timeout:.0f} s")
        spy.wait(0.25)


def _training_over(spy, model_id: str) -> bool:
    """The job took the model's lock before its first save, so once the spy
    has seen a save, a free lock means the job has ended (not: has yet to
    start)."""
    from penroz_tpu.serve import app as app_mod
    lock = app_mod.model_locks.get(model_id)
    return bool(spy.saves) and (lock is None or not lock.locked())


def compare_with_reference(ctx, spy) -> dict:
    """Loss and gradient of the first optimizer step — the program's, as it
    trained (bf16 compute, its kernels), against the plain float32
    reference on the same sequences from the same seeded weights — and
    whether the run's losses are finite and fell."""
    import jax
    import jax.numpy as jnp
    cfg = ctx["cfg"]
    ref = program.reference_for(cfg)
    job, limits = cfg["train"], cfg["correct"]
    d = ref.dims(cfg)
    xs, ys = spy.first_batch
    xs = jnp.asarray(xs.reshape(-1, xs.shape[-1]))
    ys = jnp.asarray(ys.reshape(-1, ys.shape[-1]))
    weights = ref.init_params(cfg, ctx["args"].seed)
    loss, grads = ref.mean_loss_and_grad(
        weights, xs, ys, heads=d["heads"], rows=job["reference_rows"])
    want = {k: np.asarray(v) for k, v in
            ref.as_gpt2_custom(grads, d["depth"]).items()}
    del grads, weights
    grad_err = ref.tree_rel_error(spy.first_grad, want)
    loss_err = abs(spy.costs[0] - loss) / abs(loss)
    costs = spy.costs
    finite = all(math.isfinite(c) for c in costs)
    fell = costs[0] - min(costs[-3:])
    checks = {
        "loss_rel_err": {"value": loss_err,
                         "limit": limits["loss_rel_err"]},
        "grad_rel_err": {"value": grad_err,
                         "limit": limits["grad_rel_err"]},
        "loss_fall": {"value": fell, "at_least": limits["loss_fall_min"]},
    }
    correct = (finite and loss_err <= limits["loss_rel_err"]
               and grad_err <= limits["grad_rel_err"]
               and fell >= limits["loss_fall_min"])
    ctx["say"](phase="correct", correct=correct, finite=finite,
               first_loss=costs[0], reference_first_loss=loss,
               last_loss=costs[-1], optimizer_steps=len(costs), **checks)
    jax.clear_caches()
    return {"correct": correct, "checks": checks}


def run(ctx) -> dict:
    cfg, traffic, args, say = (ctx["cfg"], ctx["traffic"], ctx["args"],
                               ctx["say"])
    ref = program.reference_for(cfg)
    d = ref.dims(cfg)
    job = cfg["train"]
    device = "cpu" if ctx["rehearse"] else "tpu"
    fabricate_shard(args.seed, d["vocab"], int(traffic["dataset_tokens"]))
    svc = Service()
    trace_info = None
    try:
        t = time.monotonic()
        program.create_model(cfg, args.seed, MODEL)
        say(phase="create_model", seconds=time.monotonic() - t)
        body = {"model_id": MODEL, "device": device, "dataset_id": DATASET,
                "shard": 0, "epochs": int(traffic["epochs"]),
                "batch_size": job["batch_size"],
                "block_size": job["block_size"],
                "step_size": job["step_size"]}
        with TrainSpy() as spy:
            svc.ok("PUT", "/train/", body, expect=202)
            # warm-up: to the end of the first periodic save
            _wait_until(spy, lambda: len(spy.periodic_save_ends()) >= 1
                        or _training_over(spy, MODEL),
                        float(traffic["warmup_timeout_s"]),
                        "first periodic save")
            if not spy.periodic_save_ends():
                raise RuntimeError("training ended before its first "
                                   "periodic save (see the server log)")
            t_open = spy.periodic_save_ends()[0]
            setup_s = t_open - ctx["t_start"]
            memory = [program.device_memory(ctx["devices"])]
            say(phase="warm", setup_s=setup_s, memory=memory[0],
                first_epoch_s=(spy.epochs[0][0] - spy.saves[0][1]
                               if spy.saves else None),
                epochs_in_warmup=len(spy.epochs))
            _wait_until(spy, lambda: cycles.closed(
                spy.periodic_save_ends(), args.seconds, time.monotonic())
                or _training_over(spy, MODEL),
                args.seconds + float(traffic["cycle_timeout_s"]),
                "a whole save cycle")
            memory.append(program.device_memory(ctx["devices"]))
            window = cycles.whole_cycles(spy.periodic_save_ends(),
                                         args.seconds)
            if window is None:
                raise RuntimeError("training ended before one whole save "
                                   "cycle (see the server log)")
            if window.overran:
                say(phase="window", note="no save cycle completed within "
                    f"--seconds {args.seconds}: ran on to the end of the "
                    f"first, {window.t1 - window.t0:.1f} s")
            if args.trace and not ctx["rehearse"]:
                trace_info = _trace_steady_epochs(ctx, spy, traffic)
            spy.stop_training()
            _wait_until(spy, lambda: _training_over(spy, MODEL), 300,
                        "the training job to end")
        # one REST call after the window: what a user polling would read
        prog = svc.ok("GET", f"/progress/?model_id={MODEL}")
        recorded = [p["cost"] for p in prog["progress"]]
        epochs = list(spy.epochs)
        saves = list(spy.saves)
        tokens = cycles.tokens_in(epochs, window.t0, window.t1)
        seconds = window.t1 - window.t0
        periodic = [(a, b) for a, b, is_periodic in saves if is_periodic]
        stall = cycles.stall_seconds(periodic, window.t0, window.t1)
        steady = cycles.steady_steps([t for t, _ in epochs], periodic,
                                     window.t0, window.t1)
        say(phase="window", cycles=window.cycles, seconds=seconds,
            memory=memory[-1],
            tokens=tokens, overran=window.overran, stall_seconds=stall,
            whole_cycle_tokens_per_s=tokens / seconds,
            steady_steps=len(steady),
            steady_tokens_per_s=(epochs[0][1] / median(steady)
                                 if steady else None),
            epochs_in_window=sum(window.t0 < t <= window.t1
                                 for t, _ in epochs),
            save_seconds=[round(b - a, 3) for a, b in periodic],
            progress_status=prog["status"].get("code"),
            progress_first_cost=recorded[0] if recorded else None,
            spy_first_cost=spy.costs[0])
        # every event the arithmetic above read, seconds from the opening:
        # tools/cycle_table.py recomputes a cycle's parts from a kept line
        say(phase="timeline", opened_at_s=window.t0 - ctx["t_start"],
            epoch_ends=[round(t - window.t0, 4) for t, _ in epochs],
            saves=[[round(a - window.t0, 4), round(b - window.t0, 4),
                    is_periodic] for a, b, is_periodic in saves])
        verdict = compare_with_reference(ctx, spy)
    finally:
        program.delete_model(svc, MODEL)
        svc.stop()
        shutil.rmtree("data", ignore_errors=True)
    return {
        "kind": "train", "cfg": cfg, "traffic": traffic,
        "peaks": ctx["peaks"], "device": ctx["device"],
        "correct": verdict["correct"], "checks": verdict["checks"],
        "attempted": len(epochs), "failed": 0,
        "end_to_end": {"train_tokens_per_s": tokens / (seconds - stall),
                       "setup_s": setup_s},
        "window": window, "epochs": epochs, "saves": periodic,
        "flops_per_token": kernel_costs.model_flops_per_token(
            kernel_costs.gpt2_matmul_params(d["d"], d["depth"], d["vocab"]),
            d["depth"], d["d"], job["block_size"]),
        "micro_steps_per_epoch": max(1, job["batch_size"]
                                     // job["step_size"]),
        "dims": d, "job": job, "trace": trace_info,
        "memory_samples": memory,
    }


def _trace_steady_epochs(ctx, spy, traffic) -> dict:
    """After the window: wait for the next periodic save to end, then trace
    the steady epochs that follow it (between saves: the name's window is
    *training epochs*, the save cycle's stall is ``ckpt_stall_pct``'s)."""
    from benchmark.lib import trace_reduce
    n_before = len(spy.periodic_save_ends())
    _wait_until(spy, lambda: len(spy.periodic_save_ends()) > n_before
                or _training_over(spy, MODEL),
                float(traffic["cycle_timeout_s"]), "the save before the trace")
    want = int(traffic["trace_epochs"])
    trace = tracing.Trace(os.path.join(ctx["work"], "trace")).start()
    n0 = len(spy.epochs)
    # one epoch more than reduced: the first is cut by the trace's start
    _wait_until(spy, lambda: len(spy.epochs) >= n0 + want + 1
                or _training_over(spy, MODEL), 120, "the traced epochs")
    trace.stop()
    keep = ctx["args"].keep_trace
    if keep:
        dest = os.path.join(ctx["root"], keep)
        os.makedirs(dest, exist_ok=True)
        shutil.copy(trace.path, dest)
    reduced = trace_reduce.reduce(trace.path,
                                  crop_to_spans="penroz/train_epoch")
    shutil.rmtree(trace.log_dir, ignore_errors=True)
    return reduced
