"""From a profiler trace to numbers: the reduction every PR shares.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a small
plain record (JSON-able; ``tests/data`` keeps one recorded on the chip):

    {"device_ops": [[name, module, start_ns, dur_ns, device], ...],
     "modules":    [[name, start_ns, dur_ns, device], ...],
     "host_spans": [[name, start_ns, dur_ns], ...]}

with times on the profile's own clock. Device operations are the events of
the ``XLA Ops`` line of every ``/device:`` plane. A trace with no such line
yields no record (``LookupError``): nothing read from host events may go out
under ``device_trace``. Only a rehearsal, which reports no metric, takes the
host events that carry an ``hlo_op`` in their place, so the same code runs.
Host spans are the ``TraceAnnotation`` events the benchmark wrote
(``HOST_PREFIXES``): its own marks and the program's device-category tracer
spans. Everything else here is arithmetic on that record.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_PREFIXES = ("host:", "bench:")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNATTRIBUTED = "unattributed"


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def op_name(raw: str) -> str:
    """``%fusion.2 = u32[...] fusion(...)`` -> ``fusion.2``: the instruction's
    name without its text, which changes with every shape."""
    name = raw.split(" = ", 1)[0].strip()
    return name.lstrip("%") or raw


def extract(xplane_path: str, rehearsal: bool = False) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    device_ops, modules, host_spans, host_ops = [], [], [], []
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and line.name == OPS_LINE:
                for ev in line.events:
                    stats = dict(ev.stats)
                    device_ops.append([op_name(ev.name), str(stats.get("hlo_module", "")), ev.start_ns, ev.duration_ns, plane.name])
            elif on_device and line.name == MODULES_LINE:
                for ev in line.events:
                    modules.append([re.sub(r"\(\d+\)$", "", ev.name), ev.start_ns, ev.duration_ns, plane.name])
            elif not on_device:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host_spans.append([ev.name, ev.start_ns, ev.duration_ns])
                    elif ev.duration_ns > 0:
                        stats = dict(ev.stats)
                        if "hlo_op" in stats:
                            host_ops.append([op_name(ev.name), str(stats.get("hlo_module", "")), ev.start_ns, ev.duration_ns, plane.name])
    if not device_ops and not rehearsal:
        raise LookupError(f"{xplane_path} has no /device: plane with an {OPS_LINE!r} line: no device metric can be read")
    attribute_modules(device_ops, modules)
    return {
        "device_ops": device_ops if device_ops else host_ops,
        "modules": modules,
        "host_spans": host_spans,
        "from_device_plane": bool(device_ops),
    }


def attribute_modules(device_ops: List[list], modules: List[list]) -> None:
    """Name the program each device operation ran in, where the operation does
    not say: the module event on the same device whose interval holds its
    start. ``fusion.1`` of one program is not ``fusion.1`` of another."""
    by_device: Dict[str, List[list]] = {}
    for m in modules:
        by_device.setdefault(m[3], []).append(m)
    starts = {}
    for dev, mods in by_device.items():
        mods.sort(key=lambda m: m[1])
        starts[dev] = [m[1] for m in mods]
    for op in device_ops:
        if op[1] or op[4] not in by_device:
            continue
        mods = by_device[op[4]]
        i = bisect.bisect_right(starts[op[4]], op[2]) - 1
        if i >= 0 and op[2] < mods[i][1] + mods[i][2]:
            op[1] = mods[i][0]


def clip(events: Iterable[Sequence], lo: float, hi: float, start: int, dur: int) -> List[Tuple[float, float, Sequence]]:
    """(begin, end, event) of every event that overlaps [lo, hi], cut to it."""
    out = []
    for ev in events:
        a, b = max(ev[start], lo), min(ev[start] + ev[dur], hi)
        if b > a:
            out.append((a, b, ev))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, ascending, non-overlapping intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def devices_of(record: dict) -> List[str]:
    return sorted({ev[4] for ev in record["device_ops"]})


def busy_ns(record: dict, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which an operation ran on the device: the union of
    the operations' intervals (operations nest: a ``while`` holds its body's,
    so a sum of durations would count them twice), averaged over the devices
    that ran any. This is also all the device time the programs took."""
    devices = devices_of(record)
    if not devices:
        return 0.0
    total = 0.0
    for dev in devices:
        cut = clip((ev for ev in record["device_ops"] if ev[4] == dev), lo, hi, 2, 3)
        total += sum(b - a for a, b in union((a, b) for a, b, _ in cut))
    return total / len(devices)


def sums_by_name(record: dict, lo: float, hi: float, key: str = "device_ops") -> Dict[str, float]:
    """name -> summed nanoseconds in [lo, hi]; operations are named
    ``module/op`` where the trace says which program they belong to."""
    out: Dict[str, float] = {}
    if key == "device_ops":
        for a, b, ev in clip(record["device_ops"], lo, hi, 2, 3):
            name = f"{ev[1]}/{ev[0]}" if ev[1] else ev[0]
            out[name] = out.get(name, 0.0) + (b - a)
    else:
        for a, b, ev in clip(record[key], lo, hi, 1, 2):
            out[ev[0]] = out.get(ev[0], 0.0) + (b - a)
    return out


def idle_gaps(record: dict, lo: float, hi: float) -> Dict[str, float]:
    """The device's idle time in [lo, hi] by what the host was doing: every
    gap between operations (on the first device) goes to the host span that
    covers most of it, or to ``unattributed``; the benchmark's own marks
    (``bench:``) say nothing of what the host did. name -> summed nanoseconds."""
    devices = devices_of(record)
    if not devices:
        return {}
    cut = clip((ev for ev in record["device_ops"] if ev[4] == devices[0]), lo, hi, 2, 3)
    busy = union((a, b) for a, b, _ in cut)
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    spans = [ev for ev in record["host_spans"] if ev[0].startswith("host:")]
    out: Dict[str, float] = {}
    for a, b in gaps:
        cover: Dict[str, float] = {}
        for sa, sb, ev in clip(spans, a, b, 1, 2):
            cover[ev[0]] = cover.get(ev[0], 0.0) + (sb - sa)
        name = max(cover, key=cover.get) if cover else UNATTRIBUTED
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def top(sums: Dict[str, float], n: int = 10) -> List[List]:
    """[[name, seconds], ...], the ``n`` largest."""
    return [[k, v / 1e9] for k, v in sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def clock_offset_ns(record: dict, mark_name: str, mark_wall_ns: int) -> float:
    """profile clock = wall clock - offset, from the one host span the
    benchmark wrote at a wall-clock time it noted."""
    for name, start, _ in record["host_spans"]:
        if name == mark_name:
            return mark_wall_ns - start
    raise LookupError(f"the trace holds no {mark_name!r} span to set its clock by")
