"""Traffic of kind ``serve_open``: an open loop of streamed ``/generate/``
requests at a rate fixed in the traffic file.

Set-up: weights from the seed, the engine's first start, then the file's
warm-up waves (each built to make the scheduler compile one shape of its
mixed-step program; what they reached is printed), then a lead-in of the
same traffic so that the window opens on a system already in its steady
state.  The window is ``--seconds`` long; requests due inside it are the
samples, tokens that reach the client inside it are the throughput, and
nothing is offered after it closes.
"""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

from benchmark.lib import program, stats, tracing, traffic as traffic_lib
from benchmark.lib.loadgen import LoadGen
from benchmark.lib.service import Service

MODEL = "bench"


def _mixed_programs(layers) -> set:
    """(steps, descriptor blocks) of every mixed-step program the
    architecture has compiled so far — read as a counter, to see that the
    window compiled nothing."""
    from penroz_tpu.models.model import CompiledArch
    cache = getattr(CompiledArch.get(layers), "_jit_cache", {})
    return {(k[1], k[2]) for k in list(cache)
            if isinstance(k, tuple) and k and k[0] == "mixed_step"}


def _engine_stats() -> dict:
    from penroz_tpu.serve import decode_scheduler
    return decode_scheduler.serving_stats()


def _warm_up(ctx, gen, layers, vocab: int) -> dict:
    say, plan = ctx["say"], ctx["traffic"]["warmup"]
    rng = np.random.default_rng([int(ctx["args"].seed), 99])
    next_id, missed = int(rng.integers(0, vocab)), []
    for i, wave in enumerate(plan["waves"]):
        expect = {tuple(e) for e in wave.get("expect", [])}
        for attempt in range(int(plan.get("retries", 2)) + 1):
            first = traffic_lib.wave_requests(wave.get("first", []), rng,
                                              vocab, next_id)
            next_id += len(first)
            then = traffic_lib.wave_requests(wave.get("then", []), rng,
                                             vocab, next_id)
            next_id += len(then)
            t = time.monotonic()
            gen.wave(first, then, wave.get("wait", "first_token"))
            bad = [r for r in first + then if not r.ok]
            if bad:
                raise RuntimeError(f"warm-up wave {i} failed: "
                                   f"{bad[0].status} {bad[0].error}")
            have = _mixed_programs(layers)
            if expect <= have:
                break
        else:
            missed.append(sorted(expect - have))
        if ctx["args"].trace or i == 0:
            say(phase="warmup_wave", wave=i, tries=attempt + 1,
                seconds=time.monotonic() - t)
    programs = sorted(_mixed_programs(layers))
    say(phase="warm", mixed_step_programs=programs, missed=missed)
    return {"programs": programs, "missed": missed}


def _window(ctx, gen, layers, d, rate=None, trace_dir=None) -> dict:
    """The lead-in and one measured window; returns its artefacts."""
    args, params = ctx["args"], ctx["traffic"]
    reqs = traffic_lib.schedule(params, args.seed, args.seconds, d["vocab"],
                                d["block"], rate=rate)
    lead = float(params.get("lead_in_s", 0.0))
    t0 = time.monotonic() + lead + 0.25
    done = gen.start(reqs, t0)
    time.sleep(max(0.0, t0 - time.monotonic()))
    before, programs_before = _engine_stats(), _mixed_programs(layers)
    memory = [program.device_memory(ctx["devices"])]
    trace = None
    if trace_dir:
        time.sleep(float(params["trace_after_s"]))
        trace = tracing.Trace(trace_dir).start()
        time.sleep(float(params["trace_seconds"]))
        trace.stop()
    time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
    after, programs_after = _engine_stats(), _mixed_programs(layers)
    memory.append(program.device_memory(ctx["devices"]))
    backlog = sum(1 for r in reqs if r.sent_at and not r.ok
                  and not r.error)
    try:
        done.result(timeout=float(params["drain_timeout_s"]))
    except Exception as exc:  # noqa: BLE001 — unfinished requests are failures
        ctx["say"](phase="drain", error=f"{type(exc).__name__}: {exc}")
    t_end = time.monotonic()
    return {"requests": reqs, "t0": t0, "t1": t0 + args.seconds,
            "t_end": t_end,
            "opened": {"rows_busy": before["active_rows"],
                       "rows": before["capacity"],
                       "queue_depth": before["queue_depth"]},
            "ticks": _ticks(after, args.seconds),
            "stats_before": before, "stats_after": after,
            "new_programs": sorted(programs_after - programs_before),
            "unfinished_at_close": backlog, "trace_obj": trace,
            "memory": memory}


def _ticks(engine_stats: dict, seconds: float) -> dict:
    """The anatomy of the window's ticks from the engines' tick timelines
    (the program's own record, read as a counter at the window's close; no
    metric, a line for PERF.md §5): every tick as [wall ms, prefill chunks
    it carried, tokens it emitted], oldest first, and the same summed by
    whether a tick carried a prefill."""
    ticks = sorted((t for e in engine_stats["engines"]
                    for t in e["tick_timeline"] if t["age_s"] <= seconds),
                   key=lambda t: -t["age_s"])
    out = {"each": [[round(t["dispatch_ms"]), t["prefill_chunks"],
                     t["emitted"]] for t in ticks],
           "supersteps": sorted({t["superstep"] for t in ticks})}
    for name, group in (("decode_only", [t for t in ticks
                                         if not t["prefill_chunks"]]),
                        ("with_prefill", [t for t in ticks
                                          if t["prefill_chunks"]])):
        out[name] = {"ticks": len(group),
                     "ms_p50": stats.quantile([t["dispatch_ms"]
                                               for t in group], 0.5),
                     "ms_sum": sum(t["dispatch_ms"] for t in group),
                     "emitted": sum(t["emitted"] for t in group)}
    return out


def measure(win: dict) -> dict:
    """End-to-end numbers of one window, by the definitions of PERF.md §2."""
    t0, t1, reqs = win["t0"], win["t1"], win["requests"]
    tokens = sum(1 for r in reqs for t in r.token_at if t0 <= t < t1)
    gaps = [1000.0 * (b - a) for r in reqs
            for a, b in zip(r.token_at, r.token_at[1:]) if t0 <= b < t1]
    counted = [r for r in reqs if r.counted]
    worst = win["t_end"]
    ttft = [1000.0 * ((r.token_at[0] if r.ok else worst) - r.due_at)
            for r in counted]
    late = [1000.0 * (r.sent_at - r.due_at) for r in counted if r.sent_at]
    prefilled = [len(r.prompt) for r in reqs
                 if r.token_at and t0 <= r.token_at[0] < t1]
    return {"tokens": tokens, "seconds": t1 - t0, "gaps": len(gaps),
            "prefills": len(prefilled), "prefill_tokens": sum(prefilled),
            "serve_tokens_per_s": tokens / (t1 - t0),
            "itl_ms.p90": stats.quantile(gaps, 0.90),
            "itl_ms.p50": stats.quantile(gaps, 0.50),
            "ttft_ms.p50": stats.quantile(ttft, 0.50),
            "ttft_ms.p90": stats.quantile(ttft, 0.90),
            "gen_late_ms.p90": stats.quantile(late, 0.90),
            "attempted": len(counted),
            "failed": sum(1 for r in counted if not r.ok)}


def sample_requests(requests: list, seed: int, k: int) -> list:
    """The seeded sample of requests whose tokens are scored, the one with
    the longest reply (the first such) in it."""
    k = min(int(k), len(requests))
    if k <= 0:
        return []
    longest = max(range(len(requests)),
                  key=lambda i: (requests[i].max_new, -i))
    rest = [i for i in range(len(requests)) if i != longest]
    rng = np.random.default_rng([int(seed), 5])
    picks = rng.choice(len(rest), k - 1, replace=False) if k > 1 else []
    return [requests[longest]] + [requests[rest[i]] for i in picks]


def regret_numbers(regrets: list) -> dict:
    """The numbers compared, from each sampled request's
    ``reference.greedy_regret``: the mean over every scored token and the
    widest single gap."""
    flat = np.concatenate(regrets) if regrets else np.zeros(0)
    if not flat.size:
        return {"greedy_regret_mean": float("inf"),
                "greedy_regret_max": float("inf"), "scored_tokens": 0,
                "off_argmax_share": None}
    return {"greedy_regret_mean": float(flat.mean()),
            "greedy_regret_max": float(flat.max()),
            "scored_tokens": int(flat.size),
            "off_argmax_share": float((flat > 0).mean())}


def compare_with_reference(ctx, win, d) -> dict:
    """Greedy tokens that came through the served path (chunked prefill,
    paged cache, ragged kernel, fused supersteps) against the plain float32
    reference: for a seeded sample of the requests that finished in the
    window, the longest among them, every generated token's distance from
    the reference's own greedy choice (``reference.greedy_regret``); each
    number that the configuration gives a limit is held to it."""
    cfg, say = ctx["cfg"], ctx["say"]
    ref = program.reference_for(cfg)
    limits = cfg["correct"]
    # every request finished in the window or its drain, the lead-in's
    # among them: above the knee most of what is due in a window is still
    # queued at its close
    ok = [r for r in win["requests"] if r.ok and r.token_at[-1] >= win["t0"]]
    in_range = all(0 <= t < d["vocab"] for r in ok for t in r.tokens)
    sample = sample_requests(ok, ctx["args"].seed, limits["sample_requests"])
    weights = ref.init_params(cfg, ctx["args"].seed)
    got = regret_numbers([ref.greedy_regret(weights, r.prompt, r.tokens,
                                            heads=d["heads"],
                                            block=d["block"])
                          for r in sample])
    del weights
    checks = {name: {"value": got[name], "limit": limits[name]}
              for name in ("greedy_regret_mean", "greedy_regret_max")
              if name in limits}
    correct = bool(in_range and got["scored_tokens"] and checks and all(
        c["value"] <= c["limit"] for c in checks.values()))
    say(phase="correct", correct=correct, tokens_in_range=in_range,
        sampled_requests=len(sample), **{**got, **checks})
    return {"correct": correct, "checks": checks}


def _setup(ctx):
    cfg, say, args = ctx["cfg"], ctx["say"], ctx["args"]
    d = program.reference_for(cfg).dims(cfg)
    svc = Service()
    t = time.monotonic()
    made = program.create_model(cfg, args.seed, MODEL)
    say(phase="create_model", seconds=time.monotonic() - t,
        n_params=made["n_params"],
        memory=program.device_memory(ctx["devices"]))
    gen = LoadGen(svc.base, MODEL, d["block"])
    t = time.monotonic()
    try:
        warm = _warm_up(ctx, gen, made["layers"], d["vocab"])
    except BaseException:
        _teardown(svc, gen)
        raise
    say(phase="warmup", seconds=time.monotonic() - t,
        memory=program.device_memory(ctx["devices"]))
    return svc, gen, made["layers"], d, warm


def _teardown(svc, gen):
    try:
        gen.close()
    finally:
        program.delete_model(svc, MODEL)
        svc.stop()


def run(ctx) -> dict:
    args, say = ctx["args"], ctx["say"]
    svc, gen, layers, d, warm = _setup(ctx)
    try:
        trace_dir = (os.path.join(ctx["work"], "trace")
                     if args.trace and not ctx["rehearse"] else None)
        win = _window(ctx, gen, layers, d, trace_dir=trace_dir)
        setup_s = win["t0"] - ctx["t_start"]
        m = measure(win)
        say(phase="window", setup_s=setup_s, memory=win["memory"][-1],
            compiles_in_window=len(win["new_programs"]),
            new_programs=win["new_programs"], opened=win["opened"],
            unfinished_at_close=win["unfinished_at_close"],
            drain_s=win["t_end"] - win["t1"], ticks=win["ticks"], **m)
        trace_info = None
        if win["trace_obj"] is not None:
            from benchmark.lib import trace_reduce
            trace = win["trace_obj"]
            if args.keep_trace:
                dest = os.path.join(ctx["root"], args.keep_trace)
                os.makedirs(dest, exist_ok=True)
                shutil.copy(trace.path, dest)
            trace_info = trace_reduce.reduce(trace.path)
            shutil.rmtree(trace.log_dir, ignore_errors=True)
        # free the engine (weights, pool) before the reference takes the chip
        from penroz_tpu.serve import decode_scheduler
        t = time.monotonic()
        decode_scheduler.reset()
        verdict = compare_with_reference(ctx, win, d)
        say(phase="reference", seconds=time.monotonic() - t)
    finally:
        t = time.monotonic()
        _teardown(svc, gen)
        say(phase="teardown", seconds=time.monotonic() - t,
            since_start_s=time.monotonic() - ctx["t_start"])
    return {
        "kind": "serve_open", "cfg": ctx["cfg"], "traffic": ctx["traffic"],
        "peaks": ctx["peaks"], "device": ctx["device"], "dims": d,
        "correct": verdict["correct"], "checks": verdict["checks"],
        "attempted": m["attempted"], "failed": m["failed"],
        "end_to_end": {"serve_tokens_per_s": m["serve_tokens_per_s"],
                       "itl_ms.p90": m["itl_ms.p90"],
                       "ttft_ms.p50": m["ttft_ms.p50"],
                       "setup_s": setup_s},
        "measured": m, "window": win, "warm": warm, "trace": trace_info,
        "memory_samples": win["memory"],
    }


def sweep(ctx, rates: list):
    """One set-up, one window per rate: where the knee is.  Prints a line
    per rate; not a measurement run (no result line)."""
    svc, gen, layers, d, _ = _setup(ctx)
    try:
        for rate in rates:
            win = _window(ctx, gen, layers, d, rate=rate)
            m = measure(win)
            ticks = stats.hist_delta(
                win["stats_after"]["engines"][0]["histograms"]["tick_ms"],
                win["stats_before"]["engines"][0]["histograms"]["tick_ms"])
            ctx["say"](phase="sweep", rate_per_s=rate, opened=win["opened"],
                       ticks=win["ticks"],
                       offered_tokens_per_s=sum(
                           r.max_new for r in win["requests"] if r.counted)
                       / m["seconds"],
                       unfinished_at_close=win["unfinished_at_close"],
                       drain_s=win["t_end"] - win["t1"],
                       tick_ms_p50=stats.hist_quantile(ticks, 0.5),
                       new_programs=win["new_programs"], **m)
    finally:
        _teardown(svc, gen)
