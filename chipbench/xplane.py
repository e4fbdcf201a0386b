"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics
read: device busy time, time per executable, the operations that took
most time, and the idle gaps by what the host was doing in them.

Two steps, so that the arithmetic is tested on known intervals without
a chip (tests/chipbench, chipbench/testdata/trace_small.json):

``load_events(path)``  the file -> plain lists (needs jax's
                       ProfileData; runs in the engine child)
``reduce(events)``     lists -> numbers (pure)

What a trace of the v5e holds (my chip run, PR 23): one plane
``/device:TPU:0`` with the lines ``XLA Modules`` (one event per run of
an executable, named ``jit_<function>(<fingerprint>)``), ``XLA Ops`` (one
event per operation, named by its HLO text, a ``while`` holding its
body's operations), ``Async XLA Ops``, ``Scalar Unit`` and ``TC
Overlay`` (not read); and planes ``/host:CPU`` with one line per thread
(``python3`` with the interpreter's frames as ``$file.py:line func``,
``pjrt-tpu-tasks/...``, ``tfrt-...``).
"""

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

Interval = Tuple[float, float]          # start_s, end_s
SMALL_GAP_S = 50e-6


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_HLO = re.compile(r"%?([^ =]+) = \(?([a-z0-9]+\[[0-9,]*\])?")


def clean(name: str) -> str:
    """An event's name in the characters a ledger line keeps: no
    spaces, brackets or commas. A device operation is named by its HLO
    text (``%copy.109 = bf16[32,385,8,64,128]{...} copy(...)``): keep
    the operation's name and its first result shape
    (``copy.109_bf16_32_385_8_64_128_``)."""
    m = _HLO.match(name)
    if m:
        name = m.group(1) + ("_" + m.group(2) if m.group(2) else "")
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", name)[:96]


def self_seconds(events: List[List]) -> Dict[str, float]:
    """Seconds per name with nested events taken out of what contains
    them (a ``while`` holds the operations of its body on the same
    line): every device second is counted under the innermost
    operation that ran in it."""
    out: Dict[str, float] = {}
    stack: List[List] = []          # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out[done[0]] = out.get(done[0], 0.0) + done[2]
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([clean(name), start + dur, dur])
    for done in stack:
        out[done[0]] = out.get(done[0], 0.0) + done[2]
    return out


def load_events(path: str) -> Dict:
    """{"devices": {plane: {"ops": [[name, start_s, dur_s]],
    "modules": [...]}}, "host": [[name, start_s, dur_s]]}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict = {"devices": {}, "host": []}
    for plane in data.planes:
        is_device = (plane.name.startswith("/device:")
                     and "host" not in plane.name.lower())
        if is_device:
            dev = out["devices"].setdefault(plane.name,
                                            {"ops": [], "modules": []})
        for line in plane.lines:
            events = list(line.events)
            if is_device:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key] += [[ev.name, ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9] for ev in events]
            elif plane.name.startswith("/host:"):
                out["host"] += [[ev.name, ev.start_ns * 1e-9,
                                 ev.duration_ns * 1e-9] for ev in events]
    return out


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def attribute_gaps(gaps: List[Interval], host: List[List]
                   ) -> List[List]:
    """Idle seconds by the host event that covers each gap's middle
    (the shortest such event: the innermost call), ``unattributed``
    where none does; gaps under 50 us are one row."""
    events = sorted((s, s + d, n) for n, s, d in host)
    starts = [e[0] for e in events]
    by_name: Dict[str, float] = {}
    for a, b in gaps:
        if b - a < SMALL_GAP_S:
            name = "gaps_under_50us"
        else:
            mid, name, best = (a + b) / 2, "unattributed", None
            i = bisect.bisect_right(starts, mid)
            for s, e, n in events[max(0, i - 400):i]:
                if s <= mid < e and (best is None or e - s < best):
                    name, best = clean(n), e - s
        by_name[name] = by_name.get(name, 0.0) + (b - a)
    return sorted(([n, s] for n, s in by_name.items()),
                  key=lambda r: -r[1])


def op_base(name: str) -> str:
    """``%paged_decode_attention.12 = bf16[...]`` ->
    ``paged_decode_attention``: an operation's name without its number
    and shape, which is what a kernel is known by."""
    m = _HLO.match(name)
    return re.sub(r"\.[0-9]+$", "", m.group(1) if m else name)


def ops_by_module(dev: Dict) -> Dict[str, Dict[str, List[float]]]:
    """For each executable name, its operations by base name:
    [calls, seconds] summed over its runs. An operation belongs to the
    run whose interval holds its start (runs on one device do not
    overlap). Executables are told apart by what they run: the program
    gives its step functions no name yet (``jit__unknown``)."""
    runs = sorted((s, s + d, clean(n)) for n, s, d in dev["modules"])
    starts = [r[0] for r in runs]
    out: Dict[str, Dict[str, List[float]]] = {}
    for name, s, d in dev["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1]:
            continue
        row = out.setdefault(runs[i][2], {}).setdefault(op_base(name),
                                                        [0, 0.0])
        row[0] += 1
        row[1] += d
    return out


def reduce(events: Dict) -> Dict:
    """Numbers from the event lists. Times in seconds.

    ``window_s``: from the first device operation's start to the last
    one's end (what the profiler itself costs at either edge of the
    trace is left out). ``busy_s``: union of the device's operation
    intervals, averaged over the device planes that ran any.
    ``modules``: per executable name its runs, total and median device
    seconds, and its 24 longest operations by base name as [calls,
    seconds]. ``top_ops``: operations by their own device seconds
    (self_seconds) over all device planes. ``idle_gaps``: see
    attribute_gaps, on the first device plane that ran anything."""
    planes = [events["devices"][p] for p in sorted(events["devices"])
              if events["devices"][p]["ops"]]
    if not planes:
        raise ValueError("no operation ran on a device in the trace")
    t0 = min(s for dev in planes for _, s, _ in dev["ops"])
    t1 = max(s + d for dev in planes for _, s, d in dev["ops"])
    busy, ops, modules, by_module, gaps0 = [], {}, {}, {}, None
    for dev in planes:
        merged = union([(s, s + d) for _, s, d in dev["ops"]])
        busy.append(sum(b - a for a, b in merged))
        for name, sec in self_seconds(dev["ops"]).items():
            ops[name] = ops.get(name, 0.0) + sec
        for name, _, d in dev["modules"]:
            modules.setdefault(clean(name), []).append(d)
        for name, rows in ops_by_module(dev).items():
            mine = by_module.setdefault(name, {})
            for base, (calls, sec) in rows.items():
                row = mine.setdefault(base, [0, 0.0])
                row[0] += calls
                row[1] += sec
        if gaps0 is None:
            gaps0 = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]

    def med(xs):
        s = sorted(xs)
        return s[len(s) // 2]
    return {
        "window_s": t1 - t0,
        "busy_s": sum(busy) / len(busy),
        "device_planes": len(busy),
        "modules": {n: {"runs": len(d), "total_s": sum(d),
                        "median_s": med(d),
                        "ops": dict(sorted(by_module.get(n, {}).items(),
                                           key=lambda r: -r[1][1])[:24])}
                    for n, d in modules.items()},
        "top_ops": sorted(([n, s] for n, s in ops.items()),
                          key=lambda r: -r[1])[:10],
        "idle_gaps": attribute_gaps(gaps0, events["host"])[:10],
    }


def reduce_file(path: str) -> Dict:
    return reduce(load_events(path))
