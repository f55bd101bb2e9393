"""Reduction of a JAX profiler trace (an ``.xplane.pb``) to the intervals the
per-layer metrics read: each chip's device operations, the harness's own
host spans, and the traced window.

Times are in nanoseconds on the trace's common clock. Only this module
knows the trace's layout; the metric readers under ``bench/metrics`` work
on the ``Summary`` it returns.
"""
from __future__ import annotations

import gzip
import pathlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"      # one plane per chip: /device:TPU:0 ...
OPS_LINE = "XLA Ops"               # the line of a device plane that holds ops
SPAN_PREFIX = "bench."             # host spans the harness writes
WINDOW_SPAN = "bench.window"

Interval = Tuple[float, float, str]


@dataclass
class Summary:
    window: Tuple[float, float]
    devices: List[List[Interval]] = field(default_factory=list)
    spans: List[Interval] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def load(path):
    """ProfileData of a trace file; ``.gz`` files are read decompressed."""
    from jax.profiler import ProfileData
    path = pathlib.Path(path)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(gzip.decompress(
            path.read_bytes()))
    return ProfileData.from_file(str(path))


def find_trace(root) -> pathlib.Path:
    found = sorted(pathlib.Path(root).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def summarize(profile) -> Summary:
    """Device ops of every chip and the harness spans, clipped to the
    ``bench.window`` span (the whole trace when that span is absent)."""
    devices: Dict[int, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in profile.planes:
        name = plane.name
        if name.startswith(DEVICE_PLANE) and name[len(DEVICE_PLANE):].isdigit():
            lines = list(plane.lines)
            # the ops line; a trace that names it otherwise falls back to
            # every line that holds ops (never modules or steps)
            chosen = ([ln for ln in lines if ln.name == OPS_LINE]
                      or [ln for ln in lines if "Ops" in ln.name])
            ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for ln in chosen for e in ln.events]
            devices[int(name[len(DEVICE_PLANE):])] = sorted(ops)
        elif name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    spans.sort()
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if win:
        t0, t1 = win[0][0], win[0][1]
    else:
        ends = [t for ops in devices.values() for o in ops for t in o[:2]]
        t0, t1 = (min(ends), max(ends)) if ends else (0.0, 0.0)
    clipped = [[(max(a, t0), min(b, t1), n) for a, b, n in ops
                if b > t0 and a < t1] for _, ops in sorted(devices.items())]
    return Summary((t0, t1), clipped,
                   [s for s in spans if s[2] != WINDOW_SPAN])


def merged(ops: List[Interval]) -> List[Tuple[float, float]]:
    """The union of op intervals as disjoint sorted intervals."""
    out: List[List[float]] = []
    for a, b, _ in sorted(ops):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(s: Summary) -> float:
    """Union of device-op intervals, averaged over the chips traced."""
    if not s.devices:
        return 0.0
    return sum(sum(b - a for a, b in merged(ops)) for ops in s.devices
               ) / len(s.devices)


def idle_gaps(s: Summary, chip: int = 0) -> List[Tuple[float, float]]:
    """Intervals of the window in which no op ran on ``chip``."""
    gaps, t = [], s.window[0]
    for a, b in merged(s.devices[chip]) if s.devices else []:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < s.window[1]:
        gaps.append((t, s.window[1]))
    return gaps


def span_at(s: Summary, a: float, b: float) -> str:
    """The harness span that overlaps [a, b] the most ("none" if none)."""
    best, label = 0.0, "none"
    for x, y, name in s.spans:
        if x >= b:
            break
        ov = min(b, y) - max(a, x)
        if ov > best:
            best, label = ov, name
    return label


def op_totals(s: Summary, match: Optional[str] = None) -> Dict[str, float]:
    """Device seconds per op name, summed over events and averaged over
    chips; ``match`` keeps the names that contain it."""
    tot: Dict[str, float] = {}
    for ops in s.devices:
        for a, b, n in ops:
            if match is None or match in n:
                tot[n] = tot.get(n, 0.0) + (b - a) * 1e-9 / len(s.devices)
    return tot


def breakdown(s: Summary, top: int = 10) -> dict:
    """The device ops that took most time and the longest idle gaps, each
    gap named by the harness span the host was in."""
    ops = sorted(op_totals(s).items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(s), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[span_at(s, a, b), (b - a) * 1e-9]
                          for a, b in gaps]}
