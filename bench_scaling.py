"""Scaling-efficiency harness: train tokens/sec/device at 1/2/4/8-device
mesh sizes (BASELINE.md target "Scaling efficiency — measure 1→64 chips").

On a pod this runs against real chips; on a development host it re-execs
itself per mesh size under ``XLA_FLAGS=--xla_force_host_platform_device_
count=N JAX_PLATFORMS=cpu`` so the same data-parallel program (global batch
sharded over the mesh's data axis, gradient psum inserted by XLA) is
exercised end-to-end on a virtual mesh.  Weak scaling: per-device batch is
fixed, so ideal scaling keeps tokens/sec/device flat and efficiency(N) =
tps(N) / (N · tps(1)).

Prints ONE JSON line:
  {"metric": "scaling efficiency", "value": eff@max, "unit": ...,
   "points": [{"devices": N, "tokens_per_sec": ..., "per_device": ...}]}
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

MESH_SIZES = (1, 2, 4, 8)
PER_DEVICE_BATCH = 4
BLOCK = 256
DEPTH = 4
D_MODEL = 256
STEPS = 3
TIMED = 4


def _child(n_devices: int) -> None:
    """Measure tokens/sec for one mesh size; prints a JSON line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import CompiledArch
    from penroz_tpu.parallel import mesh as mesh_lib
    from penroz_tpu.parallel import sharding as sharding_lib
    from __graft_entry__ import OPTIMIZER

    devices = jax.devices()[:n_devices]
    if len(devices) != n_devices:
        raise SystemExit(f"requested {n_devices} devices but only "
                         f"{len(devices)} available — refusing to report "
                         f"a mislabeled scaling point")
    # BENCH_SCALING_MODEL=gpt2-xl runs a real ladder size (BASELINE.md's
    # "gpt2-xl multi-host /train/" scaling config — for pods; the default
    # shrunken stack keeps the virtual CPU mesh tractable).
    preset = os.environ.get("BENCH_SCALING_MODEL")
    from penroz_tpu.models import presets
    if preset:
        layers = presets.gpt2(preset, block=BLOCK)
    else:
        layers = presets.gpt2_custom(d=D_MODEL, heads=4, depth=DEPTH,
                                     vocab=2048, block=BLOCK)
    mapper = Mapper(layers, OPTIMIZER)
    arch = CompiledArch.get(mapper.layers)
    params, _ = mapper.init_params(arch.mods, seed=0)
    opt_state = mapper.to_optimizer().init(params)

    mesh = mesh_lib.make_mesh(devices)
    params = sharding_lib.shard_params(params, mesh)
    opt_state = jax.device_put(opt_state, mesh_lib.replicated(mesh))

    batch = PER_DEVICE_BATCH * n_devices
    rng = np.random.default_rng(0)
    x = rng.integers(0, 2048, (STEPS, batch, BLOCK), dtype=np.int32)
    y = rng.integers(0, 2048, (STEPS, batch, BLOCK), dtype=np.int32)
    xs = sharding_lib.shard_batch(x, mesh, leading_steps=True)
    ys = sharding_lib.shard_batch(y, mesh, leading_steps=True)

    epoch_fn = arch.train_epoch_fn(mapper.optimizer, STEPS)
    key = jax.random.key(0)
    buffers = {}
    for _ in range(2):  # compile + warm
        params, opt_state, buffers, cost, _ = epoch_fn(params, opt_state,
                                                       buffers, xs, ys, key)
    float(cost)
    # Best-of-3 timed windows: the virtual-device points run on one
    # contended CPU, and a single window is hostage to whatever else the
    # host is doing (r03's retention read 645/166/122 tok/s/dev at 2/4/8
    # with the 4-point below the 8-point).  Min-elapsed is the standard
    # contended-environment estimator; the artifact stays labeled a
    # contention-bound proxy either way.
    elapsed = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(TIMED):
            params, opt_state, buffers, cost, _ = epoch_fn(
                params, opt_state, buffers, xs, ys, key)
        float(cost)
        elapsed = min(elapsed, time.perf_counter() - t0)
    tokens = TIMED * STEPS * batch * BLOCK
    rec = {"devices": n_devices, "tokens_per_sec": tokens / elapsed,
           "timing": "best_of_3_windows"}

    # Mesh-aware /evaluate/ throughput: the forward-only cost program over
    # the same data-sharded batch (evaluate_model routes through
    # _eval_mesh + eval_cost_fn; pre-round-4 it used one device per
    # process regardless of host capacity).
    ex, ey = xs[0], ys[0]
    float(arch.eval_cost_fn(params, buffers, ex, ey))  # compile + warm
    eval_elapsed = float("inf")
    for _ in range(3):  # best-of-3, same contention rationale as above
        t0 = time.perf_counter()
        for _ in range(TIMED):
            float(arch.eval_cost_fn(params, buffers, ex, ey))
        eval_elapsed = min(eval_elapsed, time.perf_counter() - t0)
    rec["eval_tokens_per_sec"] = TIMED * batch * BLOCK / eval_elapsed

    if os.environ.get("BENCH_SCALING_ZERO") == "1" and n_devices > 1:
        # ZeRO ladder memory: bytes of params + optimizer state resident on
        # device 0 under the replicated/TP layout vs FSDP+WUS
        # (PENROZ_FSDP=1).  The training-math equivalence is test-asserted
        # (tests/test_parallel.py); this records the memory win.
        def dev0_bytes(tree):
            total = 0
            for leaf in jax.tree.leaves(tree):
                for s in getattr(leaf, "addressable_shards", []):
                    if s.device == devices[0] and s.data is not None:
                        total += s.data.size * s.data.dtype.itemsize
            return total

        repl = dev0_bytes(params) + dev0_bytes(opt_state)
        f_params = jax.device_put(
            params, sharding_lib.param_shardings(params, mesh, fsdp=True))
        f_opt = jax.device_put(opt_state, sharding_lib.opt_state_sharding_tree(
            opt_state, f_params, mesh, wus=True))
        jax.block_until_ready((f_params, f_opt))
        rec["state_bytes_per_device"] = repl
        rec["zero_state_bytes_per_device"] = dev0_bytes(f_params) \
            + dev0_bytes(f_opt)
    print(json.dumps(rec))


_COLLECTIVE_RE = None


def _collective_stats(hlo_text: str) -> dict:
    """Per-collective op counts and payload bytes from compiled HLO.

    Parses lines shaped ``%x = bf16[2048,256]{...} all-reduce(...)`` (and
    tuple-result variants) for the XLA collectives GSPMD inserted; the sum
    is the per-step communication volume the strategy costs — measurable
    without hardware, unlike ICI bandwidth."""
    import re
    global _COLLECTIVE_RE
    if _COLLECTIVE_RE is None:
        _COLLECTIVE_RE = re.compile(
            r"=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?)\s*"
            r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
            r"all-to-all)(-start)?\(")
    itemsize = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "s64": 8, "u64": 8, "s8": 1, "u8": 1,
                "pred": 1, "s16": 2, "u16": 2}
    shape_re = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    ops: dict = {}
    op_bytes: dict = {}
    total = 0
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shapes, op, started = m.group(1), m.group(2), m.group(3)
        nbytes = 0
        for dtype, dims in shape_re.findall(shapes):
            if dtype not in itemsize:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * itemsize[dtype]
        if started:
            # Async ``-start`` results are (aliased input, output) tuples:
            # halving removes the double count (exact for all-reduce; a
            # small under/over-estimate for all-gather/reduce-scatter whose
            # halves differ by the 1/shards factor).  The sync forms the
            # CPU backend emits need no correction.
            nbytes //= 2
        ops[op] = ops.get(op, 0) + 1
        op_bytes[op] = op_bytes.get(op, 0) + nbytes
        total += nbytes
    return {"ops": ops, "bytes": total, "bytes_per_op": op_bytes}


def _comm_child() -> None:
    """Per-strategy collective volume + step time on the 8-device mesh.

    One JSON line: for each of DP/TP/SP/EP/FSDP/PP, the collectives GSPMD
    scheduled per training step (op counts + payload bytes from the
    compiled HLO) and the measured step time.  Bytes are exact compiler
    output; times on a VIRTUAL mesh are contention-bound and only useful
    relative to each other."""
    import jax
    import numpy as np

    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import CompiledArch, NeuralNetworkModel
    from penroz_tpu.models import presets
    from penroz_tpu.parallel import mesh as mesh_lib
    from penroz_tpu.parallel import sharding as sharding_lib
    from __graft_entry__ import OPTIMIZER

    devices = jax.devices()[:8]
    assert len(devices) == 8, "comm breakdown wants 8 devices"
    vocab = 2048
    batch = 8

    def dense_layers():
        return presets.gpt2_custom(d=D_MODEL, heads=4, depth=DEPTH,
                                   vocab=vocab, block=BLOCK)

    def moe_layers():
        # capacity dispatch: the EP-scalable mode — tokens route to their
        # expert's owning device via all_to_all (ops/modules.
        # _apply_capacity_ep) instead of every device computing its
        # experts for every token and psum-combining (the r04 census
        # pathology: 34 all-reduces, 11.1s step, zero all-to-all).
        layers = dense_layers()
        moe_mlp = {"sequential": [
            {"layernorm": {"normalized_shape": D_MODEL}},
            {"moe": {"in_features": D_MODEL,
                     "intermediate_size": 2 * D_MODEL,
                     "num_experts": 4, "top_k": 2,
                     "dispatch": "capacity"}}]}
        for i in range(2, 2 + DEPTH):
            layers[i]["residual"][1] = moe_mlp
        return layers

    def measure(epoch_fn, params, opt_state, buffers, xs, ys, key):
        """(collective stats, step ms) for one compiled epoch program."""
        compiled = epoch_fn.lower(params, opt_state, buffers, xs, ys,
                                  key).compile()
        stats = _collective_stats(compiled.as_text())
        for _ in range(2):
            params, opt_state, buffers, cost, _ = epoch_fn(
                params, opt_state, buffers, xs, ys, key)
        float(cost)
        best = float("inf")
        for _ in range(3):  # best-of-3: see the retention-point comment
            t0 = time.perf_counter()
            for _ in range(TIMED):
                params, opt_state, buffers, cost, _ = epoch_fn(
                    params, opt_state, buffers, xs, ys, key)
            float(cost)
            best = min(best, time.perf_counter() - t0)
        step_ms = best * 1000 / (TIMED * STEPS)
        return stats, step_ms

    configs = [
        ("dp", {}, dense_layers, False, False),
        ("tp", {"model": 4}, dense_layers, False, False),
        ("sp", {"sequence": 4}, dense_layers, True, False),
        # moe_dp: the SAME MoE model on pure data parallelism — the fair
        # step-time denominator for the ep row (the dense `dp` row runs a
        # smaller model; capacity-MoE carries ~2.5x its MLP FLOPs).
        ("moe_dp", {}, moe_layers, False, False),
        ("ep", {"expert": 4}, moe_layers, False, False),
        ("fsdp", {}, dense_layers, False, True),
    ]
    out = []
    for name, axes, layer_fn, use_sp, fsdp in configs:
        use_ep = "expert" in axes
        mapper = Mapper(layer_fn(), OPTIMIZER)
        arch = CompiledArch.get(mapper.layers)
        params, buffers = mapper.init_params(arch.mods, seed=0)
        opt_state = mapper.to_optimizer().init(params)
        mesh = mesh_lib.make_mesh(devices, **axes)
        out_shardings = None
        if fsdp:
            params = sharding_lib.shard_params(params, mesh, fsdp=True)
            out_shardings = (
                sharding_lib.param_shardings(params, mesh, fsdp=True),
                sharding_lib.opt_state_sharding_tree(opt_state, params,
                                                     mesh, wus=True))
            opt_state = sharding_lib.place_tree(opt_state, out_shardings[1])
        else:
            params = sharding_lib.shard_params(params, mesh)
            opt_state = jax.device_put(opt_state, mesh_lib.replicated(mesh))
        rng = np.random.default_rng(0)
        x = rng.integers(0, vocab, (STEPS, batch, BLOCK), dtype=np.int32)
        y = rng.integers(0, vocab, (STEPS, batch, BLOCK), dtype=np.int32)
        xs = sharding_lib.shard_batch(x, mesh, leading_steps=True,
                                      shard_sequence=use_sp)
        ys = sharding_lib.shard_batch(y, mesh, leading_steps=True,
                                      shard_sequence=use_sp)
        epoch_fn = arch.train_epoch_fn(
            mapper.optimizer, STEPS, sp_mesh=mesh if use_sp else None,
            out_shardings=out_shardings,
            ep_mesh=mesh if use_ep else None)
        stats, step_ms = measure(epoch_fn, params, opt_state, buffers,
                                 xs, ys, jax.random.key(0))
        out.append({"strategy": name, "mesh": dict(mesh.shape),
                    "collective_ops": stats["ops"],
                    "collective_bytes_per_op": stats["bytes_per_op"],
                    "collective_bytes_per_epoch": stats["bytes"],
                    "step_time_ms": round(step_ms, 2)})

    # PP goes through the product path (stacked layout + GPipe epoch fn)
    os.environ["PENROZ_MESH_PIPE"] = "2"
    try:
        model = NeuralNetworkModel("comm-pp", Mapper(dense_layers(),
                                                     OPTIMIZER))
        mesh = model._training_mesh(batch, BLOCK)
        pipe_cfg, out_shardings = model._enter_pipe_layout(mesh, batch)
        # pipe_remat pinned so recorded step times don't silently shift if
        # the training default changes: 'block' is what /train/ ships.
        epoch_fn = model.arch.train_epoch_fn(
            OPTIMIZER, STEPS, out_shardings=out_shardings,
            pipe_cfg=pipe_cfg, pipe_remat="block")
        rng = np.random.default_rng(0)
        import jax.numpy as jnp  # noqa: F401
        x = rng.integers(0, vocab, (STEPS, batch, BLOCK), dtype=np.int32)
        y = rng.integers(0, vocab, (STEPS, batch, BLOCK), dtype=np.int32)
        xs = sharding_lib.shard_batch(x, mesh, leading_steps=True)
        ys = sharding_lib.shard_batch(y, mesh, leading_steps=True)
        stats, step_ms = measure(epoch_fn, model.params, model.opt_state,
                                 model.buffers, xs, ys, jax.random.key(0))
        out.append({"strategy": "pp", "mesh": dict(mesh.shape),
                    "collective_ops": stats["ops"],
                    "collective_bytes_per_op": stats["bytes_per_op"],
                    "collective_bytes_per_epoch": stats["bytes"],
                    "step_time_ms": round(step_ms, 2)})
    finally:
        os.environ.pop("PENROZ_MESH_PIPE", None)
    print(json.dumps(out))


def _mh_child() -> None:
    """One process of the 2-process × 4-device multi-host point."""
    import jax
    import numpy as np

    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import CompiledArch
    from penroz_tpu.models import presets
    from penroz_tpu.parallel import dist, mesh as mesh_lib
    from penroz_tpu.parallel import sharding as sharding_lib
    from __graft_entry__ import OPTIMIZER

    assert dist.initialize(), "multi-host env not picked up"
    vocab = 2048
    layers = presets.gpt2_custom(d=D_MODEL, heads=4, depth=DEPTH,
                                 vocab=vocab, block=BLOCK)
    mapper = Mapper(layers, OPTIMIZER)
    arch = CompiledArch.get(mapper.layers)
    params, buffers = mapper.init_params(arch.mods, seed=0)
    opt_state = mapper.to_optimizer().init(params)
    mesh = mesh_lib.make_mesh(jax.devices())  # 8 global over 2 processes
    params = sharding_lib.shard_params(params, mesh)
    opt_state = jax.device_put(opt_state, mesh_lib.replicated(mesh))
    n_global = len(jax.devices())
    local_batch = PER_DEVICE_BATCH * len(jax.local_devices())
    rng = np.random.default_rng(dist.process_index())
    x = rng.integers(0, vocab, (STEPS, local_batch, BLOCK), dtype=np.int32)
    y = rng.integers(0, vocab, (STEPS, local_batch, BLOCK), dtype=np.int32)
    xs = sharding_lib.global_batch(x, mesh, leading_steps=True)
    ys = sharding_lib.global_batch(y, mesh, leading_steps=True)
    epoch_fn = arch.train_epoch_fn(mapper.optimizer, STEPS)
    key = jax.random.key(0)
    for _ in range(2):
        params, opt_state, buffers, cost, _ = epoch_fn(params, opt_state,
                                                       buffers, xs, ys, key)
    float(cost)
    t0 = time.perf_counter()
    for _ in range(TIMED):
        params, opt_state, buffers, cost, _ = epoch_fn(params, opt_state,
                                                       buffers, xs, ys, key)
    float(cost)
    elapsed = time.perf_counter() - t0
    tokens = TIMED * STEPS * PER_DEVICE_BATCH * n_global * BLOCK
    if dist.master_proc():
        print(json.dumps({"devices": n_global,
                          "processes": dist.process_count(),
                          "tokens_per_sec": tokens / elapsed}))


def _multihost_point():
    """Launch the 2-process × 4-device point; None on any failure (the
    single-host artifact stays useful without it)."""
    import socket
    procs = []
    try:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        for pid in range(2):
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
                "JAX_NUM_PROCESSES": "2",
                "JAX_PROCESS_ID": str(pid),
                "PYTHONPATH": os.path.dirname(os.path.abspath(__file__)),
            })
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--mh-child"],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=1200)
            outs.append(out)
        if any(p.returncode != 0 for p in procs):
            for i, (p, out) in enumerate(zip(procs, outs)):
                if p.returncode != 0:
                    print(f"multi-host worker {i} rc={p.returncode}:\n"
                          f"{out[-1500:]}", file=sys.stderr)
            return None
        for out in outs:
            for line in out.splitlines():
                if line.startswith("{"):
                    return json.loads(line)
        return None
    except Exception as exc:  # noqa: BLE001
        print(f"multi-host point failed: {exc}", file=sys.stderr)
        for p in procs:
            if p.poll() is None:
                p.kill()
        return None


def main() -> None:
    # This parent never imports jax (the children do, lazily, inside their
    # own bodies): a chip belongs to one process, so a parent that had
    # touched JAX would hold it and every BENCH_SCALING_PLATFORM=tpu child
    # after it would fail or hang.  Children run one at a time.
    from penroz_tpu.utils import compile_cache
    os.environ.setdefault(compile_cache.ENV, compile_cache.cache_dir())
    points = []
    for n in MESH_SIZES:
        env = dict(os.environ)
        if n == MESH_SIZES[-1]:
            env["BENCH_SCALING_ZERO"] = "1"
        env["JAX_PLATFORMS"] = env.get("BENCH_SCALING_PLATFORM", "cpu")
        if env["JAX_PLATFORMS"] == "cpu":
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count={n}"
                                ).strip()
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", str(n)],
            env=env, capture_output=True, text=True, timeout=1200)
        if out.returncode != 0:
            print(out.stderr[-2000:], file=sys.stderr)
            raise SystemExit(f"child failed for {n} devices")
        line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
        rec = json.loads(line)
        rec["per_device"] = rec["tokens_per_sec"] / rec["devices"]
        points.append(rec)

    base = points[0]["tokens_per_sec"]
    top = points[-1]
    virtual = os.environ.get("BENCH_SCALING_PLATFORM", "cpu") == "cpu"
    if virtual:
        # All "devices" share one host CPU, so per-device weak scaling is
        # physically impossible — the meaningful number is how much total
        # throughput the sharded program retains versus single-device
        # (collective/partitioning overhead).  Real chips report true
        # per-device efficiency below.
        metric = (f"virtual-mesh total-throughput retention "
                  f"@{top['devices']} devices")
        value = top["tokens_per_sec"] / base
    else:
        metric = f"train scaling efficiency @{top['devices']} devices"
        value = top["tokens_per_sec"] / (top["devices"] * base)

    comm = None
    if os.environ.get("BENCH_SCALING_COMM", "1") == "1":
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
        env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__))
        try:
            out_c = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--comm"],
                env=env, capture_output=True, text=True, timeout=1800)
            if out_c.returncode == 0:
                lines = [l for l in out_c.stdout.splitlines()
                         if l.startswith("[")]
                comm = json.loads(lines[-1]) if lines else None
            else:
                print(out_c.stderr[-1500:], file=sys.stderr)
        except (subprocess.TimeoutExpired, json.JSONDecodeError) as exc:
            # optional enrichment: never lose the collected scaling points
            print(f"comm breakdown skipped: {exc}", file=sys.stderr)

    mh = None
    if os.environ.get("BENCH_SCALING_MULTIHOST", "1") == "1":
        mh = _multihost_point()

    out = {
        "metric": metric,
        "value": round(value, 4),
        "unit": "fraction of linear",
        "vs_baseline": round(value, 4),  # linear scaling = 1.0
        "virtual_mesh": virtual,
        # An honest label: on the virtual mesh all devices contend for one
        # host CPU, so the retention number bounds partitioning overhead
        # from above — it is NOT an ICI scaling-efficiency measurement.
        "contention_bound_proxy": virtual,
        "points": [{k: (round(v, 1) if isinstance(v, float) else v)
                    for k, v in p.items()} for p in points],
    }
    if "zero_state_bytes_per_device" in top:
        out["zero_memory_reduction"] = round(
            top["state_bytes_per_device"]
            / max(top["zero_state_bytes_per_device"], 1), 2)
    if comm is not None:
        # Exact compiler-scheduled communication per strategy: op counts +
        # payload bytes from the compiled HLO (hardware-independent).
        out["comm_breakdown"] = comm
    if mh is not None:
        mh["per_device"] = round(mh["tokens_per_sec"] / mh["devices"], 1)
        mh["tokens_per_sec"] = round(mh["tokens_per_sec"], 1)
        out["multihost_point"] = mh
    print(json.dumps(out))


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--child":
        _child(int(sys.argv[2]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--comm":
        _comm_child()
    elif len(sys.argv) > 1 and sys.argv[1] == "--mh-child":
        _mh_child()
    else:
        main()
