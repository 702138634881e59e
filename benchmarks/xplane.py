"""From a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` gives planes -> lines -> events (name,
start_ns, duration_ns).  ``load`` flattens the DEVICE planes (name
``/device:TPU:<n>``; ``--rehearse`` on a CPU has none) to plain lists, and
``reduce`` works on those alone, so it can be checked on a hand-built
fixture (``tests/fixtures``):

- busy_s: union of the intervals in which an operation ran on a device
  (line ``XLA Ops``; where a plane has no such line, its ``XLA Modules``
  line), mean over the device planes that ran anything;
- modules: [(name, start_s, dur_s)] of every jitted program run, by the
  entry's name as the device plane shows it;
- device_ops: the operations that took most device time, by name;
- idle_gaps: the longest stretches with no operation on the device.
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAME_CHARS = 96     # an op's name is its HLO text: keep the head


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, device_prefix: str = "/device:TPU:") -> dict:
    """{plane name: {line name: [(event name, start_ns, dur_ns)]}} of
    the device planes, and the names of every plane seen."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes, names = {}, []
    for plane in data.planes:
        names.append(plane.name)
        if not plane.name.startswith(device_prefix):
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(ev.name, float(ev.start_ns),
                                 float(ev.duration_ns))
                                for ev in line.events]
        planes[plane.name] = lines
    return {"planes": planes, "plane_names": names}


def union_ns(intervals: list) -> tuple:
    """(total covered ns, merged [(start, end)]) of (start, dur) pairs."""
    merged = []
    for start, dur in sorted(intervals):
        end = start + dur
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), merged


def reduce(loaded: dict, window_s: float, top: int = 10) -> dict:
    busy, modules, op_time, gaps = [], [], {}, []
    for _pname, lines in sorted(loaded["planes"].items()):
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if not ops:
            continue
        total, merged = union_ns([(s, d) for _n, s, d in ops])
        busy.append(total * 1e-9)
        for name, _s, dur in ops:
            name = name[:NAME_CHARS]
            op_time[name] = op_time.get(name, 0.0) + dur * 1e-9
        for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
            gaps.append((s1 - e0) * 1e-9)
        for name, start, dur in lines.get(MODULES_LINE, []):
            modules.append((name, start * 1e-9, dur * 1e-9))
    modules.sort(key=lambda m: m[1])
    gaps.sort(reverse=True)
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": window_s,
        "modules": modules,
        "device_ops": [[n, t] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [["device idle (host work not attributed)", g]
                      for g in gaps[:top]],
        "chips_busy": len(busy),
    }

