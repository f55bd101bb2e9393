#!/usr/bin/env python3
"""The readings each limit of ``bench/limits/<workload>.json`` is set from.

    python3 bench/calibrate.py --workload paper_n10.sweep --mode program \
        --seeds 101,102,...
    python3 bench/calibrate.py --workload paper_n10.sweep --mode control \
        --seeds 101,102,103

``program``: the benchmark's own set-up and check on each seed, with no
window: the numbers sound runs of the program read (the lower readings).
``--ref-matmul float32`` compares the same runs with a reference whose
matrix products keep float32 operands instead of the configuration's type.
``control``: the plain reference put in the program's place, computed one
precision step below the configuration (the control), and with each fault
planted (``FAULTS`` of the reference), each against the reference as
stated on its own noise stream: the upper readings. Both run at the
cell's own size, on the chip, in one process; one JSON line per seed and
variant on stdout. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import copy
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import run  # noqa: E402


def program_readings(cell, seed, ref_matmul=None):
    from bench import check
    live = run.setup(cell, seed)
    first = live.pop("first")
    live.clear()
    run.free_device()
    cfg = cell["cfg"]
    if ref_matmul:
        cfg = copy.deepcopy(cfg)
        cfg["model"]["matmul"] = ref_matmul
    ref = run.part(cell, "reference", cfg["reference"])
    return check.compare(first, ref.run(cfg, seed,
                                        cell["traffic"]["chunk_rounds"]))


def control_readings(cell, seed):
    """{variant: numbers} of the control and each planted fault."""
    from bench import check
    cfg = cell["cfg"]
    ref = run.part(cell, "reference", cfg["reference"])
    K = cell["traffic"]["chunk_rounds"]
    want = ref.run(cfg, seed, K)
    variants = [("stream", {}), ("control", {"control": True})] + [
        (f, {"fault": f}) for f in ref.FAULTS]
    return {name: check.compare(
                ref.run(cfg, seed, K, noise_stream=ref.NOISE_STREAM + 1, **kw),
                want)
            for name, kw in variants}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", choices=["program", "control"], required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--ref-matmul", default=None,
                    help="program mode: the reference's operand type")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.init_jax(True, cell["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.mode == "program":
            rows = {"program": program_readings(cell, seed, args.ref_matmul)}
        else:
            rows = control_readings(cell, seed)
        for variant, values in rows.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": variant, "values": values}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
