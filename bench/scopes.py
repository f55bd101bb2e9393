#!/usr/bin/env python3
"""Device time of the DWFL round per named scope, from a raw profiler trace.

    python3 bench/scopes.py TRACE.xplane.pb[.gz] --rounds N

The program marks its round with ``jax.named_scope``s (``dwfl.grad``: the
vmapped gradient pass and its clip; ``dwfl.mix``: the mixing plan, the pads
around the ``dp_mix_round`` kernel, the kernel and the slice back;
``dwfl.telemetry``: what the round computes only to report it), and its host
driver with spans (``dwfl.main``, ``dwfl.main.data``, ``dwfl.chunk.compile``,
``dwfl.chunk.dispatch``; ``repro.obs.spans``). ``bench/trace.py`` gives each
device op its innermost ``dwfl.*`` scope and marks the containers; this
table splits the leaf-op time by scope, the kernel apart from the rest of
``dwfl.mix``, so the parts add up to the summed leaf-op time. Each idle gap
of the window is labelled ``<harness span>><program span>`` where a
program span overlaps it. The per-layer readers ``grad_pass_ms``,
``mix_wrap_ms`` and ``telemetry_ms`` read the same split in every traced
run.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import Dict, List

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench import trace  # noqa: E402

SCOPES = ("dwfl.grad", "dwfl.mix", "dwfl.telemetry")
MIX = "dwfl.mix"                    # the scope the kernel runs under


def split(s: trace.Summary) -> Dict[str, float]:
    """Leaf-op device seconds per scope (``trace.scope_seconds``), the
    kernel apart from the rest of ``dwfl.mix``, averaged over the chips.
    A ``dwfl.*`` scope under none of ``SCOPES`` is a part of its own, so
    the parts add up to the leaf-op time."""
    found = {o.scope for ops in s.devices for o in trace.leaves(ops)}
    other = {sc for sc in found - {trace.UNSCOPED}
             if not any(trace.in_scope(sc, top) for top in SCOPES)}
    parts = [*SCOPES, *sorted(sc for sc in other if not any(
        trace.in_scope(sc, top) for top in other - {sc})), trace.UNSCOPED]
    out = {sc: trace.scope_seconds(
        s, sc, exclude=trace.KERNEL_MATCH if sc == MIX else None)
        for sc in parts}
    out[trace.KERNEL_MATCH] = trace.scope_seconds(s, MIX) - out[MIX]
    return {k: v for k, v in out.items() if v}


def top_ops(s: trace.Summary, scope: str, top: int = 5) -> List[List]:
    """The leaf ops under ``scope`` that took most device time."""
    tot = trace.op_totals(s, leaf_only=True, scope=scope)
    return [[n[:120], t] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def gap_labels(s: trace.Summary, top: int = 10) -> List[List]:
    """The longest idle gaps of chip 0, each named by the harness span
    the host was in and, where one overlaps it, the program span:
    ``bench.readback``, ``bench.dispatch>dwfl.chunk.dispatch``."""
    inner = trace.Summary(s.window, spans=s.program)
    gaps = sorted(trace.idle_gaps(s), key=lambda g: g[0] - g[1])[:top]
    out = []
    for a, b in gaps:
        label, sub = trace.span_at(s, a, b), trace.span_at(inner, a, b)
        out.append([label if sub == "none" else f"{label}>{sub}",
                    (b - a) * 1e-9])
    return out


def table(s: trace.Summary, rounds: int) -> dict:
    """Device ms per round of each scope, the kernel and the unscoped
    remainder, which add up to ``leaf_ms``; the unscoped share of it; the
    largest ops of each; the labelled idle gaps."""
    per = {k: 1e3 * v / rounds for k, v in sorted(split(s).items())}
    leaf = sum(per.values())
    return {"rounds": rounds, "window_s": s.window_s, "ms_per_round": per,
            "leaf_ms": leaf,
            "unscoped_share": (per.get(trace.UNSCOPED, 0.0) / leaf
                               if leaf else None),
            "top_ops": {k: top_ops(s, k) for k in (*SCOPES, trace.UNSCOPED)},
            "idle_gaps": gap_labels(s)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--rounds", type=int, required=True,
                    help="rounds completed in the traced window (the "
                         "run's 'attempted')")
    args = ap.parse_args(argv)
    print(json.dumps(table(trace.read(args.trace), args.rounds), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
