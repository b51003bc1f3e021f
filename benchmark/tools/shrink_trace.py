#!/usr/bin/env python3
"""Cut a slice out of a recorded trace and write it as a small ``.xplane.pb``
for the tests: the device's ``XLA Ops`` and ``XLA Modules`` events and the
host's ``penroz/*`` spans that lie in ``[t0, t1]`` seconds, names cut to
``--name-chars``.

    python3 benchmark/tools/shrink_trace.py in.xplane.pb out.xplane.pb 1.40 1.62
"""

import sys

from jax.profiler import ProfileData


def esc(text: str) -> str:
    return text.replace("\\", "\\\\").replace('"', '\\"')


def main(src, dst, t0, t1, name_chars=240):
    t0, t1, name_chars = float(t0), float(t1), int(name_chars)
    data = ProfileData.from_file(src)
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:TPU:")
        if not (device or plane.name.startswith("/host:CPU")):
            continue
        ids, lines = {}, []
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            events = []
            for e in line.events:
                a = e.start_ns * 1e-9
                b = a + e.duration_ns * 1e-9
                if not device and not e.name.startswith("penroz/"):
                    continue
                if b < t0 or a > t1:
                    continue
                a, b = max(a, t0), min(b, t1)       # spans: clipped
                mid = ids.setdefault(e.name[:name_chars], len(ids) + 1)
                events.append(
                    f"events {{ metadata_id: {mid} "
                    f"offset_ps: {int(round((a - t0) * 1e12))} "
                    f"duration_ps: {int(round((b - a) * 1e12))} }}")
            if events:
                lines.append(f'lines {{ name: "{esc(line.name)}" '
                             f"timestamp_ns: 0 " + " ".join(events) + " }")
        meta = " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} '
            f'name: "{esc(n)}" }} }}' for n, i in ids.items())
        out.append(f'planes {{ name: "{esc(plane.name)}" '
                   + " ".join(lines) + " " + meta + " }")
    blob = ProfileData.text_proto_to_serialized_xspace(" ".join(out))
    with open(dst, "wb") as f:
        f.write(blob)
    print(f"{dst}: {len(blob)} bytes")


if __name__ == "__main__":
    main(*sys.argv[1:])
