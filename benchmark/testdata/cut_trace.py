#!/usr/bin/env python3
"""Cuts a recorded ``.xplane.pb`` down to its first programs, so that a piece
of a real trace can be kept in the repository for ``check_trace.py``.

    JAX_PLATFORMS=cpu python3 benchmark/testdata/cut_trace.py <in.xplane.pb> <out.xplane.pb.gz> [programs]

Keeps, of the first device plane, the first ``programs`` events of ``XLA
Modules`` and every ``XLA Ops`` event that starts before the last of them
ends; names, start times and durations are kept to the picosecond the
reader exposes (nanoseconds x 1000).  Prints the busy and window seconds of
the piece by the raster method of ``check_trace.py``.
"""

import gzip
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import tracereduce as tr  # noqa: E402
from check_trace import raster_busy_ns  # noqa: E402


def esc(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"')


def main() -> int:
    from jax.profiler import ProfileData

    src, dst = sys.argv[1], sys.argv[2]
    n = int(sys.argv[3]) if len(sys.argv) > 3 else 12
    name, ops, mods = tr.device_lines(tr.load(src))[0]
    mods = sorted(mods, key=lambda m: m[1])[:n]
    t_end = max(e for _, _, e in mods)
    ops = [o for o in ops if o[1] < t_end and o[1] >= mods[0][1]]
    t0 = min(mods[0][1], min(o[1] for o in ops))
    meta: dict[str, int] = {}
    lines = []
    for line_id, (line_name, events) in enumerate(
            (("XLA Modules", mods), ("XLA Ops", ops)), start=1):
        body = []
        for ev_name, s, e in events:
            mid = meta.setdefault(ev_name, len(meta) + 1)
            body.append(f"events {{ metadata_id: {mid} offset_ps: "
                        f"{int(round((s - t0) * 1000))} duration_ps: "
                        f"{int(round((e - s) * 1000))} }}")
        lines.append(f'lines {{ id: {line_id} name: "{line_name}" '
                     f"timestamp_ns: {int(t0)} " + " ".join(body) + " }")
    metas = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{esc(k)}" }} }}'
        for k, i in meta.items())
    text = (f'planes {{ id: 1 name: "{name}" ' + " ".join(lines) + " "
            + metas + " }")
    blob = ProfileData.text_proto_to_serialized_xspace(text)
    with gzip.open(dst, "wb") as f:
        f.write(blob)
    pd = tr.load(dst)
    kept = [(s, e) for _, s, e in tr.device_lines(pd)[0][1]]
    print(f"{len(mods)} programs, {len(kept)} operations, "
          f"{os.path.getsize(dst)} bytes")
    print(f"RECORDED_BUSY_S = {raster_busy_ns(kept) / 1e9!r}")
    print(f"RECORDED_WINDOW_S = "
          f"{(max(e for _, e in kept) - min(s for s, _ in kept)) / 1e9!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
