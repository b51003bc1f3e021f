#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run: load, warm up, measure for ``--seconds``, check the
outputs against the plain reference, remove what the run wrote, print one
JSON object as the last line of stdout, exit 0.  Everything that belongs to
one cell is data: the cell is an entry of ``workloads`` in ``BENCHMARK.json``
naming ``benchmark/configs/<config>.json`` and ``benchmark/traffic/<mix>.json``;
the traffic file's ``kind`` picks the driver in ``benchmark/kinds/``; each
per-layer metric is read by ``benchmark/metrics/<name>.py``.

With no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result.  ``--rehearse`` (the benchmark's own, for its CPU tests)
runs the configuration's tiny ``rehearse`` sizes on whatever JAX finds and
prints every metric as null: a rehearsal is never a measurement.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse      # noqa: E402
import hashlib       # noqa: E402
import importlib     # noqa: E402
import importlib.util  # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(**fields):
    """One JSON object per stdout line; the driver reads only the last."""
    print(json.dumps(fields, default=str), flush=True)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def deep_update(base: dict, extra: dict) -> dict:
    out = dict(base)
    for k, v in extra.items():
        out[k] = (deep_update(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def load_cell(name: str, rehearse: bool) -> dict:
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json "
                         f"(have: {', '.join(sorted(cells))})")
    cell = cells[name]
    (config_entry,) = [c for c in manifest["configs"]
                       if c["name"] == cell["config"]]
    cfg = load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    if rehearse:
        cfg = deep_update(cfg, cfg.get("rehearse", {}))
        traffic = deep_update(traffic, traffic.get("rehearse", {}))
    return {"manifest": manifest, "cell": cell, "cfg": cfg,
            "traffic": traffic}


def metric_reader(name: str):
    """``benchmark/metrics/<name>.py::read`` (names carry dots, so the file
    is loaded by path)."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def applies(metric: dict, cell_name: str, reported: set) -> bool:
    """Whether ``metric`` is this cell's to report: it lists the cell, or it
    lists none and (per layer) the cell reports the end-to-end metric it
    moves."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def shm_dir() -> str:
    """A RAM-backed directory of this checkout's own for the program's
    checkpoint write-through (``PENROZ_SHM_PATH``): what a deployment gets
    from /dev/shm, without two checkouts meeting at one fixed path."""
    base = "/dev/shm" if (os.path.isdir("/dev/shm")
                          and os.access("/dev/shm", os.W_OK)) \
        else os.environ.get("TMPDIR", "/tmp")
    tag = hashlib.sha1(ROOT.encode()).hexdigest()[:12]
    return os.path.join(base, f"penroz_bench_{tag}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on any backend; prints no numbers")
    parser.add_argument("--keep-trace", default=None, metavar="DIR",
                        help="copy the raw .xplane.pb there (for a look by "
                             "hand; relative to the checkout)")
    parser.add_argument("--sweep", default=None, metavar="RATES",
                        help="serve cells: comma-separated rates, one window "
                             "each in this one process, no result line")
    args = parser.parse_args(argv)

    spec = load_cell(args.workload, args.rehearse)
    cell, cfg, traffic = spec["cell"], spec["cfg"], spec["traffic"]
    manifest = spec["manifest"]

    # The launch environment, as a user would export it before starting the
    # server; read by the program at import or at call time.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.update({k: str(v) for k, v in
                       cfg.get("launch_env", {}).items()})
    shm = shm_dir()
    os.environ["PENROZ_SHM_PATH"] = shm
    work = os.path.join(ROOT, ".bench_work", cell["name"])
    for stale in (shm, work):
        shutil.rmtree(stale, ignore_errors=True)
    os.makedirs(work)
    sys.path.insert(0, ROOT)

    import jax
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if not args.rehearse:
        if dev.platform != "tpu":
            print(f"run.py: no TPU (JAX reports platform "
                  f"{dev.platform!r}); nothing was run", file=sys.stderr)
            return 2
        if len(devices) < cell["chips"]:
            print(f"run.py: {cell['name']} needs {cell['chips']} chip(s), "
                  f"JAX reports {len(devices)}", file=sys.stderr)
            return 2
    import penroz_tpu  # noqa: F401 — absent program: fail here, no result

    from benchmark.lib import peaks as peaks_lib
    peaks = None if args.rehearse else peaks_lib.peaks_for(dev.device_kind)
    kind = importlib.import_module(f"benchmark.kinds.{traffic['kind']}")
    ctx = {"args": args, "cell": cell, "cfg": cfg, "traffic": traffic,
           "device": device, "devices": devices[:cell["chips"]],
           "peaks": peaks, "work": work, "root": ROOT, "say": say,
           "t_start": T_PROCESS_START, "rehearse": args.rehearse}
    prev_cwd = os.getcwd()
    os.chdir(work)      # the program's models/ and data/ are cwd-relative
    try:
        if args.sweep:
            kind.sweep(ctx, [float(r) for r in args.sweep.split(",")])
            return 0
        art = kind.run(ctx)
    finally:
        os.chdir(prev_cwd)
        for path in (shm, work):
            shutil.rmtree(path, ignore_errors=True)

    from benchmark.lib import program
    samples = art.get("memory_samples", []) + [
        program.device_memory(ctx["devices"])]
    art["memory_peak_bytes"] = program.memory_peak_bytes(samples)
    device["memory_peak_bytes"] = art["memory_peak_bytes"]

    end_to_end = {m["name"]: m for m in manifest["end_to_end"]
                  if applies(m, cell["name"], set())}
    if args.trace:
        wanted = [m for m in manifest["per_layer"]
                  if applies(m, cell["name"], set(art["end_to_end"]))]
        metrics = {}
        for m in wanted:
            value = metric_reader(m["name"])(art)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        trace = art.get("trace") or {}
        device["busy_s"] = trace.get("busy_s")
        device["window_s"] = trace.get("window_s")
    else:
        metrics = {name: {"value": art["end_to_end"][name],
                          "unit": m["unit"]}
                   for name, m in end_to_end.items()
                   if name in art["end_to_end"]}
    if args.rehearse:
        metrics = {k: {"value": None, "unit": v["unit"]}
                   for k, v in metrics.items()}
        device.update(rehearsal=True, busy_s=None, window_s=None)
    result = {"correct": bool(art["correct"]),
              "attempted": art["attempted"], "failed": art["failed"],
              "metrics": metrics, "device": device}
    if args.trace and art.get("trace") and not args.rehearse:
        result["breakdown"] = {
            "device_ops": art["trace"]["device_ops"][:10],
            "idle_gaps": art["trace"]["idle_gaps"][:10]}
    # every number compared beside its limit: last in the result line, and
    # as the last lines of stderr
    result["checks"] = art.get("checks", {})
    print(json.dumps(result), flush=True)
    for name, check in result["checks"].items():
        bound = ("limit", "<=") if "limit" in check else ("at_least", ">=")
        print(f"run.py: correct={result['correct']} {name} "
              f"{check['value']!r} {bound[1]} {check[bound[0]]!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
