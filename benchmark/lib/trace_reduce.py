"""From a profiler trace (``.xplane.pb``) to busy/idle share, time per device
operation, kernel time, and the idle gaps named by what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else.  What the trace of
a TPU run holds (looked at by hand, PR 24, JAX 0.9.0): one plane per chip
named ``/device:TPU:<i>``.  Its line ``XLA Ops`` carries one event per
executed HLO operation, named by the operation's whole HLO text
(``%fusion.12 = bf16[...] fusion(...)``); control flow nests — a ``%while``
event encloses the events of its body — so durations are summed as *self*
time, and busy time is the union.  A Pallas kernel is a ``custom-call(``
event whose name is the JAX name stack it was traced under (``%jvp__.105``:
the program gives its kernels no names yet), told apart by its result
shapes.  ``XLA Modules`` has one event per executed program; ``Async XLA
Ops`` (copies in flight) overlaps compute and is left out.  The plane
``/host:CPU`` has a line per thread with the host's TraceMe events, among
them the program's ``penroz/*`` ``TraceAnnotation`` spans.  Host and device
events share one clock (nanoseconds from the trace's start).
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "penroz/"
NO_SPAN = "no_penroz_span"


def op_group(name: str) -> str:
    """``%fusion.123 = bf16[...] fusion(...)`` and ``fusion.7`` are both
    ``fusion``: operations grouped by what they are, not by their serial
    number or their operands."""
    name = name.split(" = ", 1)[0].strip().lstrip("%")
    name = re.sub(r"(\.\d+)+$", "", name)
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", name)[:64] or "unnamed"


def self_times(events: list) -> list:
    """``[(name, start, end, self seconds)]`` for ``[(name, start, end)]``
    of one line: an event's duration less what the events nested inside it
    cover (a ``while`` holds its body's operations)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    selfs = [e[2] - e[1] for e in events]
    stack = []
    for i in order:
        _, a, b = events[i]
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][2]:
            selfs[stack[-1]] -= b - a
        stack.append(i)
    return [(e[0], e[1], e[2], max(s, 0.0)) for e, s in zip(events, selfs)]


def union_length(intervals: list) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        elif b > cur_b:
            cur_b = b
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def gaps_of(intervals: list, w0: float, w1: float) -> list:
    """The parts of ``[w0, w1]`` that ``intervals`` leave uncovered."""
    out, cursor = [], w0
    for a, b in sorted(intervals):
        if a > cursor:
            out.append((cursor, min(a, w1)))
        cursor = max(cursor, b)
        if cursor >= w1:
            break
    if cursor < w1:
        out.append((cursor, w1))
    return [(a, b) for a, b in out if b > a]


def clip(intervals: list, w0: float, w1: float) -> list:
    return [(max(a, w0), min(b, w1)) for a, b in intervals
            if b > w0 and a < w1]


def read_planes(path: str) -> dict:
    """``{"devices": {i: {"ops": [(name, start, end)]}},
    "spans": [(name, start, end)]}`` in seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"ops": []})
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    a = e.start_ns * 1e-9
                    dev["ops"].append((e.name, a, a + e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        a = e.start_ns * 1e-9
                        spans.append((e.name, a, a + e.duration_ns * 1e-9))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def kernel_time(planes: dict, w0: float, w1: float, spec: dict) -> dict:
    """Device seconds and calls of one kernel inside ``[w0, w1]``: the
    ``custom-call(`` events of ``XLA Ops`` whose operation name matches
    ``spec["name"]`` and whose result types (the HLO text between `` = ``
    and `` custom-call(``) match ``spec["result"]``, summed over chips."""
    rx_name, rx_result = re.compile(spec["name"]), re.compile(spec["result"])
    seconds, calls = 0.0, 0
    for dev in planes["devices"].values():
        for name, a, b in dev["ops"]:
            if a < w0 or b > w1:
                continue
            head, call, _ = name.partition(" custom-call(")
            op, _, result = head.partition(" = ")
            if call and rx_name.search(op) and rx_result.search(result):
                seconds += b - a
                calls += 1
    return {"seconds": seconds, "calls": calls}


def reduce_planes(planes: dict, crop_to_spans: str | None = None) -> dict:
    """The reduction proper, on :func:`read_planes`' output (so that a test
    can hand it a synthetic trace).

    ``crop_to_spans``: take as the window the stretch from the start of the
    first to the end of the last host span of that name, instead of the
    whole trace.
    """
    devices, spans = planes["devices"], planes["spans"]
    if not devices:
        raise ValueError("the trace holds no /device:TPU:<i> plane")
    starts = [a for d in devices.values() for _, a, _ in d["ops"]]
    ends = [b for d in devices.values() for _, _, b in d["ops"]]
    if not starts:
        raise ValueError("no operation ran on the device in the trace")
    w0 = min(starts + [a for _, a, _ in spans])
    w1 = max(ends + [b for _, _, b in spans])
    if crop_to_spans:
        named = [(a, b) for n, a, b in spans if n == crop_to_spans]
        if len(named) < 1:
            raise ValueError(f"no complete {crop_to_spans!r} span in the "
                             f"trace to crop to")
        w0, w1 = named[0][0], named[-1][1]
    busy, by_op, idle = [], {}, {}
    for dev in devices.values():
        ivals = clip([(a, b) for _, a, b in dev["ops"]], w0, w1)
        busy.append(union_length(ivals))
        for name, a, b, own in self_times(dev["ops"]):
            if a < w0 or b > w1:
                continue        # cut by the window's edge: left out whole
            group = op_group(name)
            by_op[group] = by_op.get(group, 0.0) + own
        for a, b in gaps_of(ivals, w0, w1):
            mid = 0.5 * (a + b)
            inside = [n for n, sa, sb in spans if sa <= mid < sb]
            name = inside[-1] if inside else NO_SPAN   # innermost: latest start
            idle[name] = idle.get(name, 0.0) + (b - a)
    n = len(devices)
    top = lambda d: [[k, v / n] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])]
    return {"window_s": w1 - w0, "busy_s": sum(busy) / n, "w0": w0, "w1": w1,
            "device_ops": top(by_op)[:10], "idle_gaps": top(idle)[:10],
            "devices": n, "planes": planes}


def reduce(path: str, crop_to_spans: str | None = None) -> dict:
    return reduce_planes(read_planes(path), crop_to_spans)
