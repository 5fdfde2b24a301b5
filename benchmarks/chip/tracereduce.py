"""From a profiler trace to the numbers the device metrics read.

:func:`load` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
a plain dictionary: for each device plane (``/device:TPU:<n>``) the
events of its ``XLA Ops`` and ``XLA Modules`` lines, and the host spans
whose name starts with ``bench.``. The rest works on that dictionary
alone, so a small recorded one can test it.

- busy: the union of a device's op intervals inside the window;
- idle share: 1 - busy / window, averaged over the devices;
- named time: the summed duration of a device's modules whose name
  holds a given text (e.g. ``scan_program``);
- idle gaps: the gaps between busy intervals, each put down to the
  innermost benchmark span that covers its middle (``bench.window``
  itself covers every gap that no finer span does).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def load(trace_dir: str) -> Dict[str, Any]:
    """The newest trace under ``trace_dir``, in seconds on the trace's
    clock. The window is the host span ``bench.window``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices: Dict[str, Dict[str, List[list]]] = {}
    host: List[list] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    lines[key].extend([e.name, e.start_ns * 1e-9,
                                       e.duration_ns * 1e-9]
                                      for e in line.events)
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns * 1e-9,
                             e.duration_ns * 1e-9]
                            for e in line.events
                            if e.name.startswith("bench."))
    spans = [(s, s + d) for name, s, d in host if name == "bench.window"]
    if not spans:
        raise ValueError("the trace holds no bench.window span")
    return {"window": list(spans[0]), "devices": devices, "host": host}


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_intervals(trace: Dict[str, Any], device: str) -> List[Interval]:
    lo, hi = trace["window"]
    ops = [(max(s, lo), min(s + d, hi))
           for _, s, d in trace["devices"][device]["ops"]]
    return union([(s, e) for s, e in ops if e > s])


def busy_seconds(trace: Dict[str, Any]) -> float:
    """Busy seconds averaged over the device planes."""
    devs = sorted(trace["devices"])
    if not devs:
        return 0.0
    return sum(sum(e - s for s, e in busy_intervals(trace, d))
               for d in devs) / len(devs)


def window_seconds(trace: Dict[str, Any]) -> float:
    lo, hi = trace["window"]
    return hi - lo


def idle_share(trace: Dict[str, Any]) -> float:
    return 1.0 - busy_seconds(trace) / window_seconds(trace)


def module_seconds(trace: Dict[str, Any], text: str) -> float:
    """Device seconds of modules whose name holds ``text``, averaged
    over the device planes."""
    devs = sorted(trace["devices"])
    if not devs:
        return 0.0
    return sum(d for dev in devs
               for name, _, d in trace["devices"][dev]["modules"]
               if text in name) / len(devs)


def top_ops(trace: Dict[str, Any], n: int = 10) -> List[list]:
    """The ``n`` device operations that took most time (all planes)."""
    tot: Dict[str, float] = {}
    for dev in trace["devices"].values():
        for name, _, d in dev["ops"]:
            tot[name] = tot.get(name, 0.0) + d
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:n]]


def idle_gaps(trace: Dict[str, Any], n: int = 10) -> List[list]:
    """Idle seconds of the first device, summed by the innermost host
    span covering each gap's middle ("no span" where none does)."""
    devs = sorted(trace["devices"])
    if not devs:
        return []
    lo, hi = trace["window"]
    busy = busy_intervals(trace, devs[0])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = sorted(((s, s + d, name) for name, s, d in trace["host"]),
                   key=lambda t: t[1] - t[0])
    tot: Dict[str, float] = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        name = next((nm for a, b, nm in spans if a <= mid <= b), "no span")
        tot[name] = tot.get(name, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:n]]
