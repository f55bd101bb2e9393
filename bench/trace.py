"""Reduction of a JAX profiler trace (an ``.xplane.pb``) to the intervals the
per-layer metrics read: each chip's device operations with the program's
named scope each ran under, the harness's own host spans, the program's
host spans, and the traced window.

Times are in nanoseconds on the trace's common clock. Only this module
knows the trace's layout; the metric readers under ``bench/metrics`` work
on the ``Summary`` it returns.

Scopes. The program marks its round with ``jax.named_scope``s
(``dwfl.grad``, ``dwfl.mix``, ``dwfl.telemetry``) and XLA keeps each op's
scope path in its ``op_name`` metadata. A device event of the trace
carries only the HLO instruction's name and text; the optimized modules
that ran, with their metadata, are ``Hlo Proto`` stats of the trace's
``/host:metadata`` plane, which ``jax.profiler.ProfileData`` does not
expose. So this module reads the file's protobuf itself (``_fields``) and
gives each device op the innermost ``dwfl.*`` scope on its path, or
``unscoped``. An op whose interval encloses another op of its chip (the
scan's ``while``) is a container, not a leaf: the device time per scope
and the breakdown's list of ops count leaves only, so they add up to the
summed leaf-op time.
"""
from __future__ import annotations

import bisect
import gzip
import pathlib
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"      # one plane per chip: /device:TPU:0 ...
OPS_LINE = "XLA Ops"               # the line of a device plane that holds ops
MODULES_LINE = "XLA Modules"       # ... and the modules the ops ran in
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
SPAN_PREFIX = "bench."             # host spans the harness writes
WINDOW_SPAN = "bench.window"
SCOPE_PREFIX = "dwfl."             # the program's scopes and host spans
UNSCOPED = "unscoped"
KERNEL_MATCH = "dp_mix_round"      # the dense mix kernel: its events' name

Interval = Tuple[float, float, str]


class Op(NamedTuple):
    """One device op: its interval, its event name (the HLO instruction's
    text), the innermost ``dwfl.*`` scope it ran under, and whether it is
    a leaf (encloses no other op of its chip)."""
    start: float
    end: float
    name: str
    scope: str = UNSCOPED
    leaf: bool = True


@dataclass
class Summary:
    window: Tuple[float, float]
    devices: List[List[Op]] = field(default_factory=list)
    spans: List[Interval] = field(default_factory=list)
    program: List[Interval] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _raw(path) -> bytes:
    """The serialized XSpace of a trace file; ``.gz`` files decompressed."""
    path = pathlib.Path(path)
    raw = path.read_bytes()
    return gzip.decompress(raw) if path.suffix == ".gz" else raw


def load(path):
    """ProfileData of a trace file; ``.gz`` files are read decompressed."""
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(_raw(path))


def read(path) -> Summary:
    """The Summary of a trace file, each device op with its scope."""
    from jax.profiler import ProfileData
    raw = _raw(path)
    return summarize(ProfileData.from_serialized_xspace(raw),
                     hlo_modules(raw))


def find_trace(root) -> pathlib.Path:
    found = sorted(pathlib.Path(root).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


# ----------------------------------------------------- protobuf, by hand

def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: varints as ints,
    length-delimited fields as bytes; fixed-width fields are skipped."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        out = shift = 0
        while True:
            b = buf[i]
            i += 1
            out |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return out
    while i < n:
        key = varint()
        kind = key & 7
        if kind == 0:
            yield key >> 3, varint()
        elif kind == 2:
            size = varint()
            yield key >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")


def _first(buf: bytes, number: int, default=b""):
    return next((v for f, v in _fields(buf) if f == number), default)


def _op_names(module: bytes) -> Dict[str, str]:
    """{instruction: op_name} of an ``xla.HloModuleProto`` (computations
    3 > instructions 2 > name 1, metadata 7 > op_name 2)."""
    out = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, ins in _fields(comp):
            if g == 2:
                meta = _first(ins, 7)
                out[_first(ins, 1).decode()] = (
                    _first(meta, 2).decode() if meta else "")
    return out


def hlo_modules(raw: bytes) -> Dict[str, Dict[str, str]]:
    """{module as the metadata plane names it, e.g. ``jit_program(12)``:
    {instruction: op_name}} of every HLO module in an ``XSpace``."""
    out = {}
    for f, plane in _fields(raw):
        if f != 1 or _first(plane, 2).decode() != METADATA_PLANE:
            continue
        stat_ids = {}
        for g, entry in _fields(plane):              # stat_metadata map
            if g == 5:
                meta = _first(entry, 2)
                stat_ids[_first(meta, 1, 0)] = _first(meta, 2).decode()
        for g, entry in _fields(plane):              # event_metadata map
            if g != 4:
                continue
            meta = _first(entry, 2)
            for h, stat in _fields(meta):
                if h == 5 and stat_ids.get(_first(stat, 1, 0)) == HLO_STAT:
                    # an xla.HloProto, whose field 1 is the module
                    out[_first(meta, 2).decode()] = _op_names(
                        _first(_first(stat, 6), 1))
    return out


# ------------------------------------------------------------ attribution

def scope_of(op_name: str) -> str:
    """The innermost ``dwfl.*`` component of an op_name path (the first
    of several names joined by ``;``), else ``unscoped``."""
    parts = op_name.split(";")[0].split("/")
    return next((p for p in reversed(parts) if p.startswith(SCOPE_PREFIX)),
                UNSCOPED)


def instruction(event_name: str) -> str:
    """The HLO instruction a device event is named after: the event name
    up to the first space, less a leading ``%`` (the chip names an event
    by the instruction's text, ``%fusion.3 = f32[...] fusion(...)``)."""
    return event_name.split(" ", 1)[0].lstrip("%")


def mark_containers(ops: List[Op]) -> List[Op]:
    """The ops of one chip in their order, each that encloses another op
    (the scan's ``while`` spans its body's ops) marked as no leaf."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    container = [False] * len(ops)
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]][1] <= ops[i][0]:
            stack.pop()
        if stack and ops[stack[-1]][1] >= ops[i][1]:
            container[stack[-1]] = True
        stack.append(i)
    return [o._replace(leaf=False) if c else o
            for o, c in zip(ops, container)]


def leaves(ops: List[Op]) -> List[Op]:
    """The leaf ops of one chip, as ``summarize`` marked them."""
    return [o for o in ops if o.leaf]


def in_scope(scope: str, prefix: str) -> bool:
    """``scope`` is ``prefix`` or one of its sub-scopes
    (``dwfl.grad.attn`` is under ``dwfl.grad``)."""
    return scope == prefix or scope.startswith(prefix + ".")


# ------------------------------------------------------------- reduction

def summarize(profile, modules: Optional[Dict[str, Dict[str, str]]] = None
              ) -> Summary:
    """Device ops of every chip, the harness spans and the program's
    ``dwfl.*`` host spans, clipped to the ``bench.window`` span (the whole
    trace when that span is absent). Each op gets its scope from
    ``modules`` (``hlo_modules`` of the same trace; without them every op
    is ``unscoped``) and its leaf mark."""
    modules = modules or {}
    devices: Dict[int, List[Interval]] = {}
    runs: Dict[int, List[Tuple[float, str]]] = {}
    spans: List[Interval] = []
    program: List[Interval] = []
    for plane in profile.planes:
        name = plane.name
        if name.startswith(DEVICE_PLANE) and name[len(DEVICE_PLANE):].isdigit():
            chip = int(name[len(DEVICE_PLANE):])
            lines = list(plane.lines)
            # the ops line; a trace that names it otherwise falls back to
            # every line that holds ops (never modules or steps)
            chosen = ([ln for ln in lines if ln.name == OPS_LINE]
                      or [ln for ln in lines if "Ops" in ln.name])
            ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for ln in chosen for e in ln.events]
            devices[chip] = sorted(ops)
            # each op runs inside an event of this line, named as the
            # metadata plane names the module: jit_program(<id>)
            runs[chip] = sorted((e.start_ns, e.name) for ln in lines
                                if ln.name == MODULES_LINE
                                for e in ln.events)
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
                    elif e.name.startswith(SCOPE_PREFIX):
                        # an annotation with attributes is "name#k=v#"
                        program.append((e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        e.name.split("#", 1)[0]))
    spans.sort()
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if win:
        t0, t1 = win[0][0], win[0][1]
    else:
        ends = [t for ops in devices.values() for o in ops for t in o[:2]]
        t0, t1 = (min(ends), max(ends)) if ends else (0.0, 0.0)
    chips = []
    for chip, ops in sorted(devices.items()):
        chip_runs = runs.get(chip, [])
        starts = [a for a, _ in chip_runs]

        def scope(a, name):
            k = bisect.bisect_right(starts, a) - 1
            module = modules.get(chip_runs[k][1], {}) if k >= 0 else {}
            return scope_of(module.get(instruction(name), ""))
        chips.append(mark_containers(
            [Op(max(a, t0), min(b, t1), n, scope(a, n)) for a, b, n in ops
             if b > t0 and a < t1]))
    return Summary((t0, t1), chips,
                   [s for s in spans if s[2] != WINDOW_SPAN], sorted(program))


def merged(ops: List[Op]) -> List[Tuple[float, float]]:
    """The union of op intervals as disjoint sorted intervals."""
    out: List[List[float]] = []
    for o in sorted(ops):
        a, b = o[0], o[1]
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


def op_totals(s: Summary, match: Optional[str] = None,
              leaf_only: bool = False,
              scope: Optional[str] = None) -> Dict[str, float]:
    """Device seconds per op name, summed over events and averaged over
    chips; ``match`` keeps the names that contain it, ``leaf_only`` leaves
    containers out, ``scope`` keeps the ops under that scope or its
    sub-scopes."""
    tot: Dict[str, float] = {}
    for ops in s.devices:
        for o in ops:
            a, b, n = o[0], o[1], o[2]
            if ((match is None or match in n) and (not leaf_only or o.leaf)
                    and (scope is None or in_scope(o.scope, scope))):
                tot[n] = tot.get(n, 0.0) + (b - a) * 1e-9 / len(s.devices)
    return tot


def scope_seconds(s: Summary, scope: str,
                  exclude: Optional[str] = None) -> float:
    """Leaf-op device seconds under ``scope`` and its sub-scopes, averaged
    over chips; ``exclude`` leaves out the ops whose instruction name
    starts with it (the kernel inside ``dwfl.mix``)."""
    return sum(t for n, t in op_totals(s, leaf_only=True,
                                       scope=scope).items()
               if not (exclude and instruction(n).startswith(exclude)))


def breakdown(s: Summary, top: int = 10) -> dict:
    """The leaf device ops that took most time and the longest idle gaps,
    each gap named by the harness span the host was in."""
    ops = sorted(op_totals(s, leaf_only=True).items(),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(s), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[span_at(s, a, b), (b - a) * 1e-9]
                          for a, b in gaps]}
