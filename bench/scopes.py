#!/usr/bin/env python3
"""Device time of the DWFL round per named scope, from a raw profiler trace.

    python3 bench/scopes.py TRACE.xplane.pb[.gz] --rounds N

The program marks its round with ``jax.named_scope``s (``dwfl.grad``: the
vmapped gradient pass and its clip; ``dwfl.mix``: the mixing plan, the pads
around the ``dp_mix_round`` kernel, the kernel and the slice back;
``dwfl.telemetry``: what the round computes only to report it), and its host
driver with spans (``dwfl.main``, ``dwfl.main.data``, ``dwfl.chunk.compile``,
``dwfl.chunk.dispatch``; ``repro.obs.spans``). XLA keeps each op's scope path
in its ``op_name`` metadata. A device event of the trace carries only the HLO
instruction's name and text; the optimized modules that ran, with their
metadata, are ``Hlo Proto`` stats of the trace's ``/host:metadata`` plane,
which ``jax.profiler.ProfileData`` does not expose. So this module reads the
file's protobuf itself (``_fields``) and attributes each device op to the
innermost ``dwfl.*`` scope on its path, or to ``unscoped``.

A container event, an op whose interval encloses another op of its chip
(the scan's ``while``), is not a leaf and is left out of every sum, so the
scopes add up to the summed leaf-op time. Each idle gap of the window is
labelled ``<harness span>><program span>`` where a program span overlaps it.

``bench/trace.py``'s ``Summary``, which the per-layer readers get, keeps
neither the scopes nor the program spans; this is the reduction a reader of
them would call.
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import json
import pathlib
import sys
from dataclasses import dataclass
from typing import Dict, Iterator, List, Tuple

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench import trace  # noqa: E402

SCOPE_PREFIX = "dwfl."
SCOPES = ("dwfl.grad", "dwfl.mix", "dwfl.telemetry")
KERNEL_MATCH = "dp_mix_round"       # the kernel's name=, as dp_mix_roofline
UNSCOPED = "unscoped"
METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
HLO_STAT = "Hlo Proto"

Op = Tuple[float, float, str, str]     # start ns, end ns, name, scope


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


def leaves(ops: List[Op]) -> List[Op]:
    """The ops that enclose no other op of the same chip: a container
    (the scan's ``while``) spans its body's ops and is not counted."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    container = [False] * len(ops)
    stack: List[int] = []
    for i, (a, b, _, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        if stack and ops[stack[-1]][1] >= b:
            container[stack[-1]] = True
        stack.append(i)
    return [o for o, c in zip(ops, container) if not c]


@dataclass
class Reduced:
    """A trace's window, each chip's device ops with their scopes, and the
    host spans of the harness (``bench.*``) and the program (``dwfl.*``);
    ops and harness spans as ``trace.summarize`` clips them."""
    window: Tuple[float, float]
    devices: List[List[Op]]
    harness: List[trace.Interval]
    program: List[trace.Interval]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def reduce(path) -> Reduced:
    path = pathlib.Path(path)
    raw = path.read_bytes()
    if path.suffix == ".gz":
        raw = gzip.decompress(raw)
    modules = hlo_modules(raw)
    profile = trace.load(path)
    summary = trace.summarize(profile)
    runs: Dict[int, List[Tuple[float, str]]] = {}
    program: List[trace.Interval] = []
    for plane in profile.planes:
        name = plane.name
        chip = name[len(trace.DEVICE_PLANE):]
        if name.startswith(trace.DEVICE_PLANE) and chip.isdigit():
            # each op runs inside an event of this line, named as the
            # metadata plane names the module: jit_program(<id>)
            runs[int(chip)] = sorted(
                (e.start_ns, e.name) for ln in plane.lines
                if ln.name == MODULES_LINE for e in ln.events)
        elif name.startswith("/host:"):
            # an annotation with attributes may be named "name#k=v#"
            program += [(e.start_ns, e.start_ns + e.duration_ns,
                         e.name.split("#", 1)[0])
                        for line in plane.lines for e in line.events
                        if e.name.startswith(SCOPE_PREFIX)]
    devices = []
    for chip_runs, ops in zip((runs[c] for c in sorted(runs)),
                              summary.devices):
        starts = [a for a, _ in chip_runs]

        def scope(a, name):
            k = bisect.bisect_right(starts, a) - 1
            module = modules.get(chip_runs[k][1], {}) if k >= 0 else {}
            return scope_of(module.get(instruction(name), ""))
        devices.append([(a, b, n, scope(a, n)) for a, b, n in ops])
    return Reduced(summary.window, devices, summary.spans, sorted(program))


def scope_seconds(r: Reduced) -> Dict[str, float]:
    """Leaf-op device seconds per scope, the kernel apart from the rest
    of ``dwfl.mix`` (under ``dp_mix_round``), averaged over the chips."""
    tot: Dict[str, float] = {}
    for ops in r.devices:
        for a, b, name, scope in leaves(ops):
            key = (KERNEL_MATCH if scope == "dwfl.mix"
                   and instruction(name).startswith(KERNEL_MATCH) else scope)
            tot[key] = tot.get(key, 0.0) + (b - a) * 1e-9 / len(r.devices)
    return tot


def top_ops(r: Reduced, scope: str, top: int = 5) -> List[List]:
    """The leaf ops of ``scope`` that took most device time."""
    tot: Dict[str, float] = {}
    for ops in r.devices:
        for a, b, name, s in leaves(ops):
            if s == scope:
                tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9 / len(
                    r.devices)
    return [[n[:120], t] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def gap_labels(r: Reduced, top: int = 10) -> List[List]:
    """The longest idle gaps of chip 0, each named by the harness span
    the host was in and, where one overlaps it, the program span:
    ``bench.readback``, ``bench.dispatch>dwfl.chunk.dispatch``."""
    s = trace.Summary(r.window, [[(a, b, n) for a, b, n, _ in ops]
                                 for ops in r.devices], r.harness)
    inner = trace.Summary(r.window, spans=r.program)
    gaps = sorted(trace.idle_gaps(s), key=lambda g: g[0] - g[1])[:top]
    out = []
    for a, b in gaps:
        label, sub = trace.span_at(s, a, b), trace.span_at(inner, a, b)
        out.append([label if sub == "none" else f"{label}>{sub}",
                    (b - a) * 1e-9])
    return out


def table(r: Reduced, rounds: int) -> dict:
    """Device ms per round of each scope, the kernel and the unscoped
    remainder, which add up to ``leaf_ms``; the unscoped share of it; the
    largest ops of each; the labelled idle gaps."""
    sec = scope_seconds(r)
    per = {k: 1e3 * v / rounds for k, v in sorted(sec.items())}
    leaf = sum(per.values())
    return {"rounds": rounds, "window_s": r.window_s, "ms_per_round": per,
            "leaf_ms": leaf,
            "unscoped_share": per.get(UNSCOPED, 0.0) / leaf if leaf else None,
            "top_ops": {k: top_ops(r, k) for k in (*SCOPES, UNSCOPED)},
            "idle_gaps": gap_labels(r)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--rounds", type=int, required=True,
                    help="rounds completed in the traced window (the "
                         "run's 'attempted')")
    args = ap.parse_args(argv)
    print(json.dumps(table(reduce(args.trace), args.rounds), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
